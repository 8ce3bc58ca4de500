"""StepCache: ahead-of-time step compilation cache + persistent XLA cache.

On TPU a sharded (or 1F1B-pipelined) train-step compile is the single most
expensive host-side event in a run — tens of seconds for a real model.  The
rebuild's two-program design (SURVEY.md §7) makes the program set small and
static, so the lifecycle goal is simple: **compile each program exactly
once per workflow lifetime**, and never again on a Decision rollback, a
``Trainer.restore``, or a re-``initialize`` with unchanged shapes.

Three layers:

* the traced lr multiplier (``ops.optimizers.LR_MULT_KEY``) removes the
  only *semantic* reason the Trainer ever re-traced a step;
* this in-process cache AOT-compiles each step via ``.lower().compile()``
  and keys it on everything that determines the traced program — the
  workflow instance (pinned), its graph checksum, the state/batch
  structures, mesh axes + devices, sharding-rule identity, optimizer
  configuration, and the pipeline schedule knobs.  Its counters
  (``compiles`` / ``hits`` / ``recompiles``) are the observable contract
  tests assert on;
* JAX's persistent compilation cache (:func:`enable_persistent_cache`,
  ``root.common.compile_cache`` / ``--compile-cache``) carries compiled
  executables ACROSS processes, keyed on the HLO — a restarted run with
  an unchanged program skips XLA entirely.

Per-program cost analysis (FLOPs, bytes accessed, compile wall seconds)
is logged through :class:`~veles_tpu.runtime.metrics.span` and the
event-trace path, so ``root.common.trace_file`` timelines (and the span
ring) show compile cost next to step cost.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.experimental.compilation_cache import compilation_cache

from ..config import root
from ..logger import Logger
from . import program_scopes
from .metrics import registry, span


#: Where the persistent cache lives when nothing outside places it: one
#: fixed directory inside the checkout, resolved from the package's own
#: location so every cwd and every process of one checkout share it (the
#: directory is part of what JAX keys an entry on — one that moves never
#: hits).  Git-ignored with the rest of ``.veles_tpu/``.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".veles_tpu", "compile_cache")


def enable_persistent_cache(cache_dir: Optional[str] = None) -> str:
    """Switch JAX's persistent compilation cache on and return the
    directory it uses.  Idempotent, safe to call before every compile;
    ``Trainer.initialize``, ``DecodeEngine`` and ``chip_smoke.py`` all
    come through here.

    Placement: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and no directory is set in code.  Otherwise ``cache_dir``,
    then ``root.common.compile_cache`` (``--compile-cache``), then
    :data:`DEFAULT_COMPILE_CACHE`.

    The persistent cache is keyed on the optimized HLO + compile options,
    so it composes with (rather than replaces) the in-process StepCache:
    a process restart re-traces but skips the XLA backend compile.
    """
    # jax's own 1 s gate would skip the small programs; persist
    # everything unless the config says otherwise
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(root.common.get("compile_cache_min_compile_secs", 0.0)))
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    cache_dir = os.path.abspath(os.path.expanduser(str(
        cache_dir or root.common.get("compile_cache", "")
        or DEFAULT_COMPILE_CACHE)))
    if jax.config.jax_compilation_cache_dir != cache_dir:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # jax opens its cache object once; a process that already
        # compiled against another directory has to drop it
        compilation_cache.reset_cache()
    return cache_dir


def _leaf_sig(path, leaf) -> Tuple[str, str, str]:
    return (jax.tree_util.keystr(path),
            str(getattr(leaf, "shape", ())),
            str(getattr(leaf, "dtype", type(leaf).__name__)))


def tree_signature(tree) -> Tuple:
    """Hashable (path, shape, dtype) signature of a pytree of arrays or
    ShapeDtypeStructs — the part of a step's identity its checksum does
    not cover (layer widths, optimizer slot layout, batch geometry)."""
    return tuple(_leaf_sig(p, l) for p, l in
                 jax.tree_util.tree_leaves_with_path(tree))


def _optimizer_signature(optimizer) -> Tuple:
    """Scalar hyperparameters by value + schedule IDENTITY.  The schedule
    is an opaque closure baked into the trace, so it can only be compared
    by object identity — a rebuilt optimizer therefore always misses even
    with identical settings (conservative: a stale hit would silently
    train with the wrong lr curve).  The scalars still matter: they make
    a mutated optimizer on the SAME schedule object miss."""
    scalars = tuple(sorted(
        (k, v) for k, v in vars(optimizer).items()
        if isinstance(v, (int, float, bool, str))))
    per_unit = getattr(optimizer, "per_unit", None)
    return (type(optimizer).__name__, scalars,
            id(getattr(optimizer, "schedule", None)),
            repr(sorted(per_unit.items())) if per_unit else None)


class StepCache(Logger):
    """Process-level cache of AOT-compiled step executables.

    ``get_step(kind, key, builder, args)`` returns the cached
    ``(step_fn, state_shardings, batch_shardings)`` for ``(kind, key)``
    or invokes ``builder`` once, lowers the jitted function against the
    argument ShapeDtypeStructs, compiles it, logs its cost analysis, and
    caches the executable.  ``builder`` must return the
    ``(jitted_fn, state_sh, batch_sh)`` triple of the Workflow ``make_*``
    contract (state_sh/batch_sh may be None off-mesh).

    Counters: ``compiles`` is the number of trace+compile events ever,
    ``hits`` the number served from cache, ``recompiles`` the compiles
    beyond one per distinct program — the quantity the recompile-free
    lifecycle keeps at zero across rollbacks and restores.
    """

    def __init__(self):
        self._entries: Dict[Any, dict] = {}
        self.compiles = 0
        self.hits = 0
        self.compile_wall_s = 0.0
        # process-global compile series next to the per-cache counters
        # (runtime/metrics.py): /metrics shows compiles across EVERY
        # cache in the process, so "flat under load" is checkable from
        # one scrape while stats() keeps the per-cache contract
        reg = registry()
        self._m_compiles = reg.counter(
            "vt_compile_total",
            "trace+compile events by program kind (train / eval / "
            "decode / prefill / verify) across every StepCache in the "
            "process", labels=("program",))
        self._m_hits = reg.counter(
            "vt_compile_hits_total",
            "step programs served from cache", labels=("program",))
        self._m_wall = reg.counter(
            "vt_compile_wall_seconds_total",
            "wall seconds spent tracing+compiling step programs")
        # per-program-kind cost analysis (the goodput/MFU numerators,
        # docs/observability.md "Goodput & MFU"): gauges because the
        # inventory is a point-in-time fact of the newest cache to
        # compile that kind, not a monotone event count
        self._g_flops = reg.gauge(
            "vt_program_flops",
            "XLA cost-analysis flops per execution, summed over the "
            "compiled programs of a kind (prefill sums its buckets)",
            labels=("program",))
        self._g_bytes = reg.gauge(
            "vt_program_bytes_accessed",
            "XLA cost-analysis bytes accessed per execution, summed "
            "over the compiled programs of a kind", labels=("program",))

    @property
    def recompiles(self) -> int:
        return self.compiles - len(self._entries)

    # -- keys ---------------------------------------------------------------
    def trainer_key(self, workflow, optimizer, wstate, batch_spec, *,
                    mesh=None, rule=None, pipeline: Tuple = ()) -> Tuple:
        """Cache key for a Trainer's step programs.

        The workflow INSTANCE anchors the key (unit hyperparameters like
        dropout ratios live on unit objects and are invisible to both the
        topology checksum and the state signature); the entry pins a
        strong reference so ``id`` stays unique while cached.  The
        structural components make shape/mesh/optimizer changes miss
        instead of serving a stale executable.
        """
        mesh_sig = None
        if mesh is not None:
            mesh_sig = (tuple(mesh.shape.items()),
                        tuple(d.id for d in mesh.devices.flat))
        return (id(workflow), workflow.checksum(),
                tree_signature(wstate), tree_signature(batch_spec),
                mesh_sig, id(rule) if rule is not None else None,
                _optimizer_signature(optimizer), tuple(pipeline))

    # -- the cache ----------------------------------------------------------
    def get_step(self, kind: str, key: Tuple,
                 builder: Callable[[], Tuple], args: Tuple, *,
                 pin: Tuple = (), units: Optional[Dict] = None) -> Tuple:
        """Fetch or build+AOT-compile the ``kind`` ('train'/'eval') step.

        Every program compiled here is noted in
        :mod:`~veles_tpu.runtime.program_scopes`: which unit, sub-scope
        and direction each of its instructions belongs to, read from the
        compiled module's text, so a device trace's events can be summed
        by unit.  ``units`` (``program_scopes.workflow_units``) tells the
        table the units' names and classes; the table holds text alone,
        never the executable."""
        full_key = (kind,) + tuple(key)
        ent = self._entries.get(full_key)
        if ent is not None:
            self.hits += 1
            self._m_hits.labels(program=kind).inc()
            return ent["fn"], ent["state_sh"], ent["batch_sh"]

        with span("step_compile", cat="compile", program=kind) as sp:
            t0 = time.perf_counter()
            fn, state_sh, batch_sh = builder()
            # a program the compiler refuses fails HERE, by name, not
            # later inside whichever call first runs a lazy jit
            compiled = fn.lower(*args).compile()
            wall = time.perf_counter() - t0
            program_scopes.note_compiled(kind, compiled, units,
                                         into=sp.args)
        self.compiles += 1
        self.compile_wall_s += wall
        self._m_compiles.labels(program=kind).inc()
        self._m_wall.inc(wall)

        cost: Dict[str, float] = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            for label, k in (("flops", "flops"),
                             ("bytes_accessed", "bytes accessed")):
                if k in ca:
                    cost[label] = float(ca[k])
        except Exception:  # cost analysis is best-effort observability
            pass
        self.event("step_compile", program=kind, wall_s=round(wall, 4),
                   **cost)
        self.info(
            "compiled %s step in %.2fs (%.3g GFLOP/step, %.3g MB/step)",
            kind, wall, cost.get("flops", 0.0) / 1e9,
            cost.get("bytes_accessed", 0.0) / 1e6)
        self._entries[full_key] = {
            "fn": compiled,
            "state_sh": state_sh, "batch_sh": batch_sh,
            "wall_s": wall, "cost": cost,
            # strong refs keep id()-anchored key components unique for
            # the cache's lifetime (id reuse after GC would alias keys)
            "pin": pin,
        }
        kc = self.program_cost(kind)
        self._g_flops.labels(program=kind).set(kc["flops"])
        self._g_bytes.labels(program=kind).set(kc["bytes_accessed"])
        return (self._entries[full_key]["fn"], state_sh, batch_sh)

    def entry_cost(self, kind: str, key: Tuple) -> Dict[str, float]:
        """Cost analysis of ONE cached program — the entry the caller
        actually executes.  Use this (not :meth:`program_cost`) when the
        cache can hold superseded programs of the same kind: a Trainer
        whose optimizer was rebuilt keeps the old train entry forever
        (conservative cache policy), and summing both would double the
        reported flops — on the very metric meant as the honesty
        check."""
        ent = self._entries.get((kind,) + tuple(key))
        if ent is None:
            return {"flops": 0.0, "bytes_accessed": 0.0}
        return {"flops": float(ent["cost"].get("flops", 0.0)),
                "bytes_accessed":
                    float(ent["cost"].get("bytes_accessed", 0.0))}

    def program_cost(self, kind: str) -> Dict[str, float]:
        """Summed cost analysis of this cache's compiled ``kind``
        programs: ``{"flops", "bytes_accessed"}`` per execution (zeros
        when XLA reported nothing — consumers treat 0 as unknown).
        Correct when every entry of the kind is live inventory (an
        engine's prefill buckets + its one decode step); see
        :meth:`entry_cost` for the superseded-entries caveat."""
        flops = bytes_acc = 0.0
        for full_key, ent in self._entries.items():
            if full_key[0] != kind:
                continue
            flops += float(ent["cost"].get("flops", 0.0))
            bytes_acc += float(ent["cost"].get("bytes_accessed", 0.0))
        return {"flops": flops, "bytes_accessed": bytes_acc}

    def stats(self) -> Dict[str, Any]:
        """JSON-able summary for benchmarks and status pages."""
        return {"programs": len(self._entries), "compiles": self.compiles,
                "hits": self.hits, "recompiles": self.recompiles,
                "compile_wall_s": round(self.compile_wall_s, 3)}
