"""Driver ``train_counted``: driver ``train``'s timed window over
``Trainer.run()``, for a configuration that names its own counts
(``"counts"``: a module beside ``counts.py``) and whose float32 state
nearly fills the device.

What differs from ``train`` (whose pieces are imported where they serve
as they are):

* the counts come from the module the configuration names, not from
  ``counts.py``, which raises on a layer type it does not know;
* the initialiser knows stacked banks and norm scales of any name: a
  vector called ``scale`` or ``*_norm`` is ones, any other vector zeros,
  a matrix or a bank of matrices uniform in +-1/sqrt(fan_in) with fan_in
  its second-last axis (each expert's own; the embedding's is the rows
  it holds);
* the reference's steps go leaf by leaf (``train_steps_by_leaf``);
* beside the norms, the check compares the routes that landed on held
  experts at step 1, the program's counters against the reference's own
  routing of the same rows (``routed_rows_gap``);
* the window's counters of routed and computed rows go into ``measured``
  for the per-layer readers.

Token rows only; Adam only; no stochastic units.  The next ``benchmark``
issue folds this file into ``train`` by letting a configuration name its
counts and its initialiser (PERF.md section 7).
"""

from __future__ import annotations

import functools
import importlib
import os
import time
import zlib

import numpy as np

import compare
import trace_reduce
from config_io import expand_layers, items_per_row
from drivers.train import (CHECK_STEPS, FEEDS, StepRecorder, Tracer,
                           configure_program, device_peak_bytes,
                           flatten_norms, seed_key, window_decision,
                           workflow_config)
from drivers import train as _train
from references import train_steps, train_steps_by_leaf

ROWS_METRIC = "vt_moe_rows_total"
ACTIVE_METRIC = "vt_moe_active_experts_total"


# -- weights from the seed ------------------------------------------------------

def make_leaf(path, shape, dtype, seed):
    import jax
    name = str(getattr(path[-1], "key", path[-1]))
    if len(shape) < 2:
        fill = 1.0 if name == "scale" or name.endswith("_norm") else 0.0
    else:
        fill = None
    return _leaf_maker(tuple(shape), np.dtype(dtype), fill, seed)(
        np.uint32(zlib.crc32(jax.tree_util.keystr(path).encode())))


@functools.lru_cache(maxsize=None)
def _leaf_maker(shape, dtype, fill, seed):
    """Jitted ``crc of the leaf's path -> the leaf``: one program a shape,
    whether it runs alone or inside ``make_params``."""
    import jax
    import jax.numpy as jnp

    def make(crc):
        if fill is not None:
            return jnp.full(shape, fill, dtype)
        limit = 1.0 / np.sqrt(shape[-2])
        return jax.random.uniform(jax.random.fold_in(seed_key(seed), crc),
                                  shape, dtype, -limit, limit)

    return jax.jit(make)


def make_params(struct, seed):
    """The parameter tree of ``struct`` (shapes only) in one jitted call."""
    import jax
    return jax.jit(lambda: jax.tree_util.tree_map_with_path(
        lambda path, s: make_leaf(path, s.shape, s.dtype, seed), struct))()


def leaf_remaker(struct, seed):
    """``path -> that leaf of make_params(struct, seed)``, made alone."""
    import jax
    specs = dict(jax.tree_util.tree_leaves_with_path(struct))
    return lambda path: make_leaf(path, specs[path].shape,
                                  specs[path].dtype, seed)


# -- what the first steps left behind -------------------------------------------

class CountingRecorder(StepRecorder):
    """``StepRecorder`` that also keeps the first step's unit counters."""

    first_counters = None

    def __call__(self, wstate, batch):
        first = self.calls == 0
        wstate, mets = super().__call__(wstate, batch)
        if first:
            self.first_counters = {k: v for k, v in mets.items()
                                   if k.startswith("counters/")}
        return wstate, mets


def norm_readers(optimizer, optimizer_args, struct, seed):
    """Jitted ``opt_state -> first gradient's norms`` and ``params ->
    norms of the change from the initial parameters``, by leaf of a tree
    of any depth."""
    import jax
    import jax.numpy as jnp
    if optimizer != "adam":
        raise ValueError(f"no gradient reader for optimizer {optimizer!r}")
    b1 = float(optimizer_args.get("b1", 0.9))

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    @jax.jit
    def first_grad_norms(opt_state):
        # m1 = (1 - b1) * g; a leaf's slot is (m, v)
        return {u: jax.tree.map(lambda _, slot: norm(slot[0]) / (1.0 - b1),
                                struct[u], opt_state[u]) for u in struct}

    @jax.jit
    def change_norms(params):
        return jax.tree.map(lambda a, b: norm(a - b), params,
                            make_params(struct, seed))

    return first_grad_norms, change_norms


def routed_rows(recorder):
    """{unit: routes on held experts at step 1}, from the program's own
    counters."""
    import jax
    got = jax.device_get(recorder.first_counters or {})
    return {k.split("/")[1]: int(v) for k, v in got.items()
            if k.endswith("/rows_routed")}


def window_rows():
    """The program's ``vt_moe_rows_total`` so far by class and kind, and
    ``vt_moe_active_experts_total`` by class (as kind
    ``experts_active``), summed over units; nothing if the program keeps
    no such counters."""
    from veles_tpu.runtime.metrics import registry
    out = {}
    for name in (ROWS_METRIC, ACTIVE_METRIC):
        metric = registry().get(name)
        if metric is None:
            continue
        for key, child in metric._snapshot():
            labels = dict(zip(metric.labelnames, key))
            kind = labels.get("kind", "experts_active")
            kinds = out.setdefault(labels["klass"], {})
            kinds[kind] = kinds.get(kind, 0) + child.value
    return out


# -- the check --------------------------------------------------------------------

def check(cfg, feed, seed, struct, cast="float32", leave_out=()):
    """Follow the first steps with the reference from the same weights and
    rows.  ``cast`` other than float32 is the control; ``leave_out`` plants
    a fault in the reference (``references/afmoe.py``).  Returns (the
    reference's readings with its own routing of step 1's rows under
    ``routed_rows``, its seconds)."""
    import jax
    t0 = time.perf_counter()
    reference = importlib.import_module("references." + cfg["reference"])
    layers = expand_layers(cfg)
    loss_sum = reference.make_loss(layers, leave_out)
    batches = [feed.reference_rows(reference, i) for i in range(CHECK_STEPS)]
    with jax.default_matmul_precision("highest"):
        _, counts = jax.jit(
            lambda p, rows: reference.make_forward(layers)(
                p, rows, train_steps.cast_float32))(
                    make_params(struct, seed), batches[0])
    counts = {k: int(v) for k, v in jax.device_get(counts).items()}
    # the parameters are handed over unnamed: follow() drops each leaf as
    # it updates it
    ref = train_steps_by_leaf.follow(
        loss_sum, make_params(struct, seed), batches,
        leaf_remaker(struct, seed),
        optimizer_args=cfg["workflow"].get("optimizer_args", {}),
        cast=train_steps.CASTS[cast])
    ref["routed_rows"] = counts
    return ref, time.perf_counter() - t0


def compare_routing(got, ref):
    """``routed_rows_gap``: by how many routes the program's count and the
    reference's differ, layer by layer, over the reference's count."""
    if set(got) != set(ref):
        raise ValueError(f"routed layers differ: program {sorted(got)}, "
                         f"reference {sorted(ref)}")
    gap = sum(abs(got[u] - ref[u]) for u in ref) / max(sum(ref.values()), 1)
    return {"routed_rows_gap": (gap, f"program {got} reference {ref}")}


def program_readings(s):
    import jax
    recorder = s["recorder"]
    return {"losses": [float(x) for x in jax.device_get(recorder.losses)],
            "grad_norms": flatten_norms(recorder.grad_norms),
            "change_norms": flatten_norms(recorder.change),
            "routed_rows": routed_rows(recorder)}


def compare_all(got, ref):
    numbers = compare.compare_training(got, ref)
    numbers.update(compare_routing(got["routed_rows"], ref["routed_rows"]))
    return numbers


# -- the run ----------------------------------------------------------------------

def setup(cell, cfg, seed):
    """Everything before the window: data, trainer, weights, and the
    warm-up epoch with its recorder."""
    import jax
    from veles_tpu import prng
    from veles_tpu.models.standard import StandardWorkflow
    from veles_tpu.plotting import MetricsRecorder

    traffic = cell["traffic"]
    phases, t = {}, time.perf_counter()

    def lap(name):
        nonlocal t
        now = time.perf_counter()
        phases[name], t = now - t, now

    prng.streams.reset()
    prng.seed("loader", seed % (2 ** 31 - 1))
    feed = FEEDS[cfg["data"]["kind"]](cfg, traffic, seed)
    lap("data_s")
    sw = StandardWorkflow(workflow_config(cfg))
    loader = feed.loader()
    trainer = sw.make_trainer(loader, decision=window_decision(0.0))
    trainer.recorder = MetricsRecorder()
    trainer.initialize(seed=seed % (2 ** 31 - 1))
    jax.block_until_ready(trainer.wstate)
    lap("initialize_s")
    struct = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        trainer.wstate["params"])
    # the program's own first parameters go before the benchmark's come
    trainer.wstate = {**trainer.wstate, "params": None}
    trainer.wstate["params"] = make_params(struct, seed)
    jax.block_until_ready(trainer.wstate)
    lap("weights_s")

    wf = cfg["workflow"]
    recorder = CountingRecorder(
        trainer._train_step,
        *norm_readers(wf["optimizer"], wf.get("optimizer_args", {}),
                      struct, seed))
    stop_noting = feed.record(loader)
    compiled_step = trainer._train_step
    trainer._train_step = recorder
    try:
        trainer.run()                      # the warm-up epoch
    finally:
        trainer._train_step = compiled_step
        stop_noting()
    jax.block_until_ready(trainer.wstate)
    lap("warmup_epoch_s")
    if recorder.calls < CHECK_STEPS:
        raise RuntimeError("the warm-up epoch is shorter than the steps "
                           "the check follows")
    return dict(feed=feed, sw=sw, loader=loader, trainer=trainer,
                struct=struct, recorder=recorder, phases=phases)


def run(cell, cfg, args, t_start):
    import jax
    import jax.monitoring as monitoring

    before_driver_s = time.perf_counter() - t_start
    cache_dir = configure_program()
    counts = importlib.import_module(cfg["counts"])
    traffic = cell["traffic"]
    seed = int(args.seed)
    s = setup(cell, cfg, seed)
    s["phases"]["before_driver_s"] = before_driver_s
    trainer, feed = s["trainer"], s["feed"]
    n_train = int(traffic["n_train"])
    per_row = items_per_row(cfg, traffic)

    compiles = []

    def on_compile(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    tracer = None
    if int(args.trace):
        tracer = Tracer(os.path.join(_train.CACHE, "trace",
                                     f"{cell['name']}-{os.getpid()}"))
    stats0 = trainer.step_cache.stats()
    wait0 = trainer._m_phase.labels(phase="data_wait").sum
    skipped0 = trainer.anomaly_steps_skipped
    epoch0 = s["loader"].epoch_number
    rows0 = window_rows()
    trainer.decision = decision = window_decision(float(args.seconds),
                                                  tracer)
    monitoring.register_event_duration_secs_listener(on_compile)
    setup_s = time.perf_counter() - t_start
    t0 = decision.started = time.perf_counter()
    try:
        trainer.run()                      # the window
        jax.block_until_ready(trainer.wstate)
        window_s = time.perf_counter() - t0
    finally:
        monitoring.unregister_event_duration_listener(on_compile)
        if tracer is not None:
            tracer.stop()
    epochs = s["loader"].epoch_number - epoch0
    stats1 = trainer.step_cache.stats()
    if stats1["recompiles"] != stats0["recompiles"] or \
            stats1["compiles"] != stats0["compiles"] or compiles:
        raise RuntimeError(
            f"compiled inside the window: step cache {stats0} -> {stats1}, "
            f"jax compiles {compiles}")
    items = epochs * n_train * per_row
    batch = int(traffic["batch"])
    steps = epochs * (n_train // batch)
    data_wait_s = trainer._m_phase.labels(phase="data_wait").sum - wait0
    failed = trainer.anomaly_steps_skipped - skipped0
    peak = max(device_peak_bytes(d) for d in jax.local_devices())
    rows = {klass: {kind: n - rows0.get(klass, {}).get(kind, 0)
                    for kind, n in kinds.items()}
            for klass, kinds in window_rows().items()}

    got = program_readings(s)
    struct, setup_phases = s["struct"], s["phases"]
    trainer.wstate = None
    trainer._train_step = trainer._eval_step = None
    del s, trainer, decision

    ref, reference_s = check(cfg, feed, seed, struct)
    numbers = compare_all(got, ref)
    compared, correct = compare.verdict(numbers, cell["check"]["limits"])

    item = cfg["item"]
    measured = {
        "window_s": window_s, "epochs": epochs, "steps": steps,
        "items": items, "items_per_s": items / window_s,
        "data_wait_s": data_wait_s, "reference_s": reference_s,
        "compile_cache": cache_dir, "item": item,
        "setup_phases": setup_phases,
        "items_per_epoch": n_train * per_row,
        "train_flops_per_item":
            counts.model_counts(cfg, traffic)["train_flops_per_item"],
        # for the routed experts' readers: the window's rows by class and
        # kind, its batches an epoch by class, the layers' shapes
        "routed_rows": rows,
        "batches_per_epoch": {
            "train": n_train // batch,
            "validation": -(-int(traffic["n_valid"]) // batch)},
        "routed_layers": counts.routed_layers(cfg),
    }
    result = {
        "correct": bool(correct), "attempted": int(steps),
        "failed": int(failed),
        "end_to_end": {f"train_{item}_per_s": items / window_s,
                       "setup_s": setup_s},
        "memory_peak_bytes": int(peak), "measured": measured,
        "details": {k: v[1] for k, v in numbers.items()},
        "compared": compared,
    }
    if tracer is not None:
        result["trace"] = trace_reduce.reduce_directory(
            tracer.directory, chips=int(cell["chips"]))
    return result
