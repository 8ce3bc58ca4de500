"""The window as the program's own spans tell it: the newest ``train_run``
span in the program's ring (``veles_tpu.runtime.metrics.span_ring()``)
and its children, grouped by epoch.  ``eval_share`` and
``boundary_host_share`` read their numbers here.

The newest ``train_run`` is the window, because the check that follows
it never calls ``Trainer.run()``; it is taken for the window only if it
lasted what the driver measured (``measured.window_s``, within 1 %).  An
epoch's cycle is its ``train_epoch`` (first dispatch to the end of the
drain, so the device's seconds of training as the host can see them),
``eval``, ``epoch_decision`` (it opens twice an epoch) and ``snapshot``
spans.  A reading is taken on the window's typical epoch: each of the
four at its lower median over the epochs, not the run's totals and not a
median of per-epoch ratios.  In a traced run the driver starts and stops
the profiler inside two of the window's ``epoch_decision`` spans, seconds
each, and the window may hold no more than four epochs: those two must
not set the reading of any share, and they only ever add time.

A program that keeps no such spans gives nothing, and nothing is
returned.
"""

from statistics import median_low

CYCLE = ("train_epoch", "eval", "epoch_decision", "snapshot")
MIN_EPOCHS = 3
WINDOW_TOLERANCE = 0.01


def epoch_cycles(run):
    """``[{span name: seconds}]`` by epoch of the window, or None."""
    from veles_tpu.runtime.metrics import span_ring
    spans = [e for e in span_ring().snapshot() if e.get("ph") == "X"]
    runs = [e for e in spans if e["name"] == "train_run"
            and "id" in e.get("args", {})]
    if not runs:
        return None
    window = max(runs, key=lambda e: e["ts"])
    window_s = run["measured"]["window_s"]
    if abs(window["dur"] * 1e-6 - window_s) > WINDOW_TOLERANCE * window_s:
        return None
    cycles = {}
    for e in spans:
        args = e.get("args", {})
        if args.get("parent") == window["args"]["id"] \
                and e["name"] in CYCLE and "epoch" in args:
            cycle = cycles.setdefault(args["epoch"], dict.fromkeys(CYCLE, 0.0))
            cycle[e["name"]] += e["dur"] * 1e-6
    cycles = [c for _, c in sorted(cycles.items()) if c["train_epoch"] > 0]
    return cycles if len(cycles) >= MIN_EPOCHS else None


def share(run, names):
    """The seconds of the spans ``names`` over the cycle of the window's
    typical epoch, in %."""
    cycles = epoch_cycles(run)
    if cycles is None:
        return None
    typical = {n: median_low(c[n] for c in cycles) for n in CYCLE}
    return 100.0 * sum(typical[n] for n in names) / sum(typical.values())
