"""The device's time by unit, from the traced epochs: what the readers
``ssm_device_ms``, ``gdn_device_ms``, ``moe_device_ms``,
``attn_device_ms``, ``head_device_ms``, ``lrn_device_ms``,
``optimizer_device_ms`` and ``scoped_device_share`` read.

The program notes, where it compiles a step program, which unit,
sub-scope and direction each instruction belongs to
(``veles_tpu.runtime.program_scopes``), and joins a trace's seconds by
event name (``trace.seconds_by_op``) to those tables: a fusion goes
whole to the unit XLA names it by, a ``conditional`` or ``while`` is
charged its self time, an event two programs claim under different
units is ``ambiguous``, one that no noted program has is ``unmatched``.
This module calls that join once a run, prints its table once on
standard error, and picks rows by the units' classes, so no reader
knows a configuration's names.

A reading is in **ms of device self time a train step of the traced
whole epochs**, validation's share included as in PERF.md section 5:
seconds x 1000 / (``measured.steps`` / ``measured.epochs`` x
``trace.epochs_in_window``).  Without a trace, without a noted table (a
program that has no ``program_scopes``) or without a unit of the class
asked for, nothing is returned.
"""

import sys
import time

KEY = "unit_device"


def steps_traced(run):
    m, trace = run["measured"], run.get("trace") or {}
    if not m.get("epochs") or not m.get("steps") \
            or not trace.get("epochs_in_window"):
        return None
    return m["steps"] / m["epochs"] * trace["epochs_in_window"]


def joined(run):
    """The run's seconds by scope (``program_scopes.seconds_by_scope``),
    joined at the first call and kept in ``run``; None where there is
    nothing to join."""
    if KEY not in run:
        run[KEY] = None
        trace = run.get("trace")
        if trace and trace.get("seconds_by_op") and steps_traced(run):
            try:
                from veles_tpu.runtime import program_scopes
            except ImportError:     # a program that notes no tables
                return None
            tables = program_scopes.noted()
            if tables:
                t0 = time.perf_counter()
                run[KEY] = program_scopes.seconds_by_scope(
                    trace["seconds_by_op"], tables)
                run[KEY]["join_s"] = time.perf_counter() - t0
                print_table(run, run[KEY])
    return run[KEY]


def ms_a_step(run, pick):
    """The rows ``pick`` accepts, in ms a traced train step; None where
    it accepts none."""
    scopes = joined(run)
    if scopes is None:
        return None
    rows = [r for r in scopes["rows"] if pick(r)]
    if not rows:
        return None
    return 1000.0 * sum(r["seconds"] for r in rows) / steps_traced(run)


def of_classes(run, *classes):
    return ms_a_step(run, lambda r: r["class"] in classes)


def print_table(run, scopes, out=None):
    """The whole table on standard error, heaviest first: what a chip
    session reads instead of sorting fusions by their shapes."""
    out = out or sys.stderr
    per_step = 1000.0 / steps_traced(run)
    busy = run["trace"].get("busy_s") or 0.0
    print(f"device time by unit, ms a train step of the traced epochs "
          f"(busy {busy * per_step:.3f}, rows + unscoped + ambiguous + "
          f"unmatched {scopes['total_s'] * per_step:.3f}, scoped "
          f"{scopes['scoped_s'] * per_step:.3f}; joined in "
          f"{scopes['join_s']:.3f} s)", file=out)
    by_unit = {}
    for r in scopes["rows"]:
        u = by_unit.setdefault((r["unit"], r["class"]),
                               {"forward": 0.0, "backward": 0.0,
                                "recomputed": 0.0})
        u[r["direction"]] += r["seconds"]
        u["recomputed"] += r["recomputed_s"]
    print("  unit | class | forward | backward (of it recomputed) | sum",
          file=out)
    for (unit, klass), u in sorted(
            by_unit.items(), key=lambda kv: -kv[1]["forward"]
            - kv[1]["backward"]):
        print(f"  {unit} | {klass} | {u['forward'] * per_step:.3f} | "
              f"{u['backward'] * per_step:.3f} "
              f"({u['recomputed'] * per_step:.3f}) | "
              f"{(u['forward'] + u['backward']) * per_step:.3f}", file=out)
    print("  program | unit | class | path | direction | ms", file=out)
    for r in scopes["rows"]:
        print(f"  {r['program']} | {r['unit']} | {r['class']} | "
              f"{r['path'] or '-'} | {r['direction']} | "
              f"{r['seconds'] * per_step:.4f}", file=out)
    for kind in ("unscoped", "ambiguous", "unmatched"):
        print(f"  {kind}: {scopes[kind]['seconds'] * per_step:.4f}",
              file=out)
        for text, seconds in scopes[kind]["heaviest"]:
            print(f"    {seconds * per_step:.4f}  {text[:240]}", file=out)
    out.flush()
