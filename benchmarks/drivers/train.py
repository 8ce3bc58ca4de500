"""Driver ``train``: a timed window over ``Trainer.run()``.

Set-up builds the configuration's ``StandardWorkflow`` and loader and
``make_trainer``; one warm-up ``Trainer.run()`` of one epoch compiles (or
loads) both step programs and the eval step, and its first steps are the
ones the reference follows.  The window then drives the same trainer's
``Trainer.run()`` — the public loop with its validation pass, anomaly
check, decision and recorder each epoch — under a ``Decision`` that ends
it at the first epoch boundary after ``--seconds``.

From the program this file takes the system under test (``StandardWorkflow``,
the loaders, ``Trainer``), its counters (``StepCache.stats()``,
``vt_train_phase_seconds``) and, for the check, the state the optimizer
leaves behind and the multipliers its dropout units draw.  Weights and data
are the benchmark's own, made on the device from ``--seed``.
"""

from __future__ import annotations

import importlib
import os
import time
import zlib

import numpy as np

import compare
import counts
import trace_reduce
from config_io import HERE, expand_layers, items_per_row
from references import train_steps

CACHE = os.path.join(HERE, ".cache")
#: steps of the warm-up epoch that the reference follows
CHECK_STEPS = 3
#: seconds of the window that a ``--trace 1`` run traces, and where it starts
TRACE_AFTER_S = 2.0
TRACE_SPAN_S = 3.0


def configure_program():
    """The program's caches go under the benchmark's own directory: the
    autotune DB always, JAX's compile cache unless the environment places
    it."""
    from veles_tpu.config import root
    from veles_tpu.runtime.step_cache import enable_persistent_cache
    root.common.cache_dir = os.path.join(CACHE, "veles_tpu")
    os.makedirs(root.common.cache_dir, exist_ok=True)
    root.common.compile_cache = os.path.join(CACHE, "compile")
    return enable_persistent_cache()


# -- weights and data, from the seed, on the device ---------------------------

def seed_key(seed):
    """The key that data and weights are drawn from: XLA's own bit
    generator, which fills gigabytes in a fraction of the time threefry
    takes to compile and to run."""
    import jax
    return jax.random.key(seed, impl="rbg")


def make_params(struct, seed):
    """The parameter tree of ``struct`` (shapes only) in one jitted call:
    a leaf called ``scale`` is ones, any other vector is zeros, and a
    matrix or filter bank is uniform in +-1/sqrt(fan_in), fan_in the
    product of all its axes but the last."""
    import jax
    import jax.numpy as jnp

    def build():
        base = seed_key(seed)

        def leaf(path, s):
            name = str(getattr(path[-1], "key", path[-1]))
            if name == "scale":
                return jnp.ones(s.shape, s.dtype)
            if len(s.shape) < 2:
                return jnp.zeros(s.shape, s.dtype)
            limit = 1.0 / np.sqrt(np.prod(s.shape[:-1]))
            key = jax.random.fold_in(
                base, zlib.crc32(jax.tree_util.keystr(path).encode()))
            return jax.random.uniform(key, s.shape, s.dtype, -limit, limit)

        return jax.tree_util.tree_map_with_path(leaf, struct)

    return jax.jit(build)()


class ImageStoreFeed:
    """Uniform random uint8 images and labels resident on the device, fed
    through ``FullBatchAugmentedLoader`` (gather, crop, mirror on device)."""

    def __init__(self, cfg, traffic, seed):
        import jax
        import jax.numpy as jnp
        d = cfg["data"]
        self.crop = int(d["crop_hw"])
        n_train, n_valid = int(traffic["n_train"]), int(traffic["n_valid"])
        hw, ch = int(d["store_hw"]), int(d["channels"])

        def part(key, n):
            k1, k2 = jax.random.split(key)
            return (jax.random.bits(k1, (n, hw, hw, ch), jnp.uint8),
                    jax.random.randint(k2, (n,), 0, int(d["n_classes"]),
                                       jnp.int32))

        @jax.jit
        def make():
            kt, kv = jax.random.split(jax.random.fold_in(
                seed_key(seed), 0xDA7A))
            return part(kt, n_train), part(kv, n_valid)

        self.train, self.valid = make()
        self.mirror = bool(d.get("mirror", True))
        self.batch = int(traffic["batch"])
        self.drawn = []          # (idx, offs, flips) of each TRAIN batch

    def loader(self):
        from veles_tpu.loader.base import TRAIN, VALID
        from veles_tpu.loader.fullbatch import FullBatchAugmentedLoader
        self._train_klass = TRAIN
        return FullBatchAugmentedLoader(
            {TRAIN: self.train[0], VALID: self.valid[0]},
            {TRAIN: self.train[1], VALID: self.valid[1]},
            minibatch_size=self.batch, crop_hw=(self.crop, self.crop),
            mirror=self.mirror)

    def record(self, loader):
        """Note the rows, offsets and mirrors the loader draws for each
        TRAIN batch from now on; returns the call that stops it."""
        make_batch, draw = loader.make_batch, loader._draw_aug
        pending = {}

        def noting_draw(n, klass, anchor):
            offs, flips = draw(n, klass, anchor)
            pending["aug"] = (offs.copy(), flips.copy())
            return offs, flips

        def noting_make(chunk, klass):
            batch = make_batch(chunk, klass)
            if klass == self._train_klass and len(self.drawn) < CHECK_STEPS:
                self.drawn.append((np.asarray(chunk).copy(),)
                                  + pending["aug"])
            return batch

        loader._draw_aug, loader.make_batch = noting_draw, noting_make

        def stop():
            del loader._draw_aug, loader.make_batch
        return stop

    def reference_rows(self, reference, step):
        import jax.numpy as jnp
        idx, offs, flips = self.drawn[step]
        return reference.build_rows(
            self.train[0], self.train[1], jnp.asarray(idx, jnp.int32),
            jnp.asarray(offs, jnp.int32), jnp.asarray(flips), self.crop)


class TokenRowsFeed:
    """Random token ids from the seed; a row's labels are its next
    tokens.  Made on the device, served by the host-side ``ArrayLoader``
    (a batch is a few KB)."""

    def __init__(self, cfg, traffic, seed):
        import jax
        import jax.numpy as jnp
        n_train, n_valid = int(traffic["n_train"]), int(traffic["n_valid"])
        t = int(traffic["seq_len"])
        tokens = jax.jit(lambda: jax.random.randint(
            jax.random.fold_in(seed_key(seed), 0xDA7A),
            (n_train + n_valid, t + 1), 0, int(cfg["vocab_size"]),
            jnp.int32))()
        self.tokens = np.asarray(tokens)
        self.n_valid = n_valid
        self.batch = int(traffic["batch"])
        self.drawn = []

    def loader(self):
        from veles_tpu.loader.base import TRAIN, VALID, ArrayLoader
        self._train_klass = TRAIN
        tr, va = self.tokens[self.n_valid:], self.tokens[:self.n_valid]
        return ArrayLoader(
            {TRAIN: tr[:, :-1], VALID: va[:, :-1]},
            {TRAIN: tr[:, 1:], VALID: va[:, 1:]},
            minibatch_size=self.batch)

    def record(self, loader):
        make_batch = loader.make_batch

        def noting_make(chunk, klass):
            if klass == self._train_klass and len(self.drawn) < CHECK_STEPS:
                self.drawn.append(np.asarray(chunk).copy())
            return make_batch(chunk, klass)

        loader.make_batch = noting_make

        def stop():
            del loader.make_batch
        return stop

    def reference_rows(self, reference, step):
        import jax.numpy as jnp
        return reference.build_rows(
            jnp.asarray(self.tokens[self.n_valid:]),
            jnp.asarray(self.drawn[step], jnp.int32))


FEEDS = {"image_store": ImageStoreFeed, "token_rows": TokenRowsFeed}


# -- the decision that ends the window ----------------------------------------

def window_decision(seconds, tracer=None):
    """A ``Decision`` that behaves as the program's own each epoch and
    completes at the first epoch boundary after ``seconds``."""
    import jax
    from veles_tpu.runtime import Decision

    class WindowDecision(Decision):
        def __init__(self):
            super().__init__(max_epochs=None, fail_iterations=10 ** 9,
                             metric="loss")
            self.started = time.perf_counter()

        def on_epoch(self, epoch, train_metrics, valid_metrics):
            elapsed = time.perf_counter() - self.started
            if tracer is not None:
                tracer.maybe_start(elapsed)
            with jax.profiler.TraceAnnotation("epoch_boundary"):
                super().on_epoch(epoch, train_metrics, valid_metrics)
            if tracer is not None:
                tracer.maybe_stop()
            self.complete = elapsed >= seconds
            return self.complete

    return WindowDecision()


class Tracer:
    """Starts the profiler at the first epoch boundary after
    ``TRACE_AFTER_S`` and stops it at the first one ``TRACE_SPAN_S``
    later, so the traced span is whole epochs, boundaries included."""

    def __init__(self, directory):
        self.directory = directory
        self.state = "waiting"
        self.t_start = None

    def maybe_start(self, elapsed):
        import jax
        if self.state == "waiting" and elapsed >= TRACE_AFTER_S:
            # the device's operations and the host's annotations; not
            # every Python call, which slows the host that feeds the chip
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            self.state, self.t_start = "tracing", time.perf_counter()

    def maybe_stop(self):
        if self.state == "tracing" and \
                time.perf_counter() - self.t_start >= TRACE_SPAN_S:
            self.stop()

    def stop(self):
        import jax
        if self.state == "tracing":
            jax.profiler.stop_trace()
            self.state = "done"


# -- what the first steps left behind -----------------------------------------

class StepRecorder:
    """Stands in front of the trainer's compiled train step for the warm-up
    epoch: notes each of the first steps' loss and key, the first
    gradient's norms from the optimizer's state after step 1, and the
    norms of the parameters' change after the last.  Only scalars stay on
    the device."""

    def __init__(self, step, first_grad_norms, change_norms):
        self.step = step
        self.first_grad_norms = first_grad_norms
        self.change_norms = change_norms
        self.calls = 0
        self.losses, self.keys = [], []
        self.grad_norms = self.change = None

    def __call__(self, wstate, batch):
        import jax
        i = self.calls
        self.calls += 1
        if i >= CHECK_STEPS:
            return self.step(wstate, batch)
        self.keys.append(jax.random.key_data(wstate["key"]) + 0)
        wstate, mets = self.step(wstate, batch)
        self.losses.append(mets["loss"])
        if i == 0:
            self.grad_norms = self.first_grad_norms(wstate["opt_state"])
        if i == CHECK_STEPS - 1:
            self.change = self.change_norms(wstate["params"])
        return wstate, mets


def norm_readers(optimizer, optimizer_args, struct, seed):
    """Jitted ``opt_state -> first gradient's norms`` and ``params ->
    norms of the change from the initial parameters``, each by leaf.  The
    initial parameters are made again from the seed inside the call, so no
    copy of them is kept."""
    import jax
    import jax.numpy as jnp

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    def first_gradient(slot, p0):
        if optimizer == "momentum":
            # v1 = g + l2 * p0
            return slot - float(optimizer_args.get("l2", 0.0)) * p0
        if optimizer == "adam":
            # m1 = (1 - b1) * g
            return slot[0] / (1.0 - float(optimizer_args.get("b1", 0.9)))
        raise ValueError(f"no gradient reader for optimizer {optimizer!r}")

    @jax.jit
    def first_grad_norms(opt_state):
        p0 = make_params(struct, seed)
        return {u: {n: norm(first_gradient(opt_state[u][n], p0[u][n]))
                    for n in p0[u]} for u in p0}

    @jax.jit
    def change_norms(params):
        p0 = make_params(struct, seed)
        return jax.tree.map(lambda a, b: norm(a - b), params, p0)

    return first_grad_norms, change_norms


def dropout_multipliers(workflow, cfg, traffic, key_data):
    """What each stochastic unit multiplied its input by at one step:
    the unit's own ``apply`` on ones, under the key the step gave it
    (``Workflow._build_step``: the second half of a split of the state's
    key)."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.units.base import Context
    sub = jax.random.split(jax.random.wrap_key_data(key_data))[1]
    ctx = Context(train=True, key=sub)
    shapes = {name: shape for name, _, _, _, _, shape
              in counts.walk(cfg, traffic)}
    batch = int(traffic["batch"])
    out, before = {}, None
    for layer in expand_layers(cfg):
        name = layer["name"]
        unit = workflow[name]
        if getattr(unit, "stochastic", False):
            ones = jnp.ones((batch,) + tuple(shapes[before]), jnp.float32)
            out[name] = jax.jit(
                lambda x, _u=unit: _u.apply({}, {}, [x], ctx)[0])(ones)
        before = name
    return out


# -- the run -------------------------------------------------------------------

def device_peak_bytes(device):
    """The most of the device's memory that was taken at once: the live
    buffers at their peak plus what the runtime reserved for the compiled
    programs' temporaries at its peak.  PJRT counts the two apart (the
    free memory it reports is the limit less both), and a training step's
    activations are all in the second."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def workflow_config(cfg):
    """The dict ``StandardWorkflow`` takes: the file's ``workflow`` with its
    layer list expanded and ``{"fill", "shape"}`` values made arrays."""
    def arrays(v):
        if isinstance(v, dict) and set(v) == {"fill", "shape"}:
            return np.full(tuple(v["shape"]), v["fill"], np.float32)
        return v
    wf = dict(cfg["workflow"])
    wf["layers"] = [{k: arrays(v) for k, v in layer.items()}
                    for layer in expand_layers(cfg)]
    return wf


def check(cell, cfg, feed, multipliers, seed, struct, cast="float32",
          rows_kept=1.0):
    """Follow the first steps with the reference from the same weights and
    rows.  ``cast`` other than float32 and ``rows_kept`` under 1 are for
    the control and for the planted fault (part of the batch left out).
    Returns (the reference's readings, its seconds)."""
    t0 = time.perf_counter()
    reference = importlib.import_module("references." + cfg["reference"])
    loss_sum = reference.make_loss(expand_layers(cfg))
    traffic = cell["traffic"]
    batches = []
    for i in range(CHECK_STEPS):
        rows = feed.reference_rows(reference, i)
        rows.update(multipliers[i])
        batches.append(rows)
    if rows_kept < 1.0:
        keep = int(int(traffic["batch"]) * rows_kept)
        batches = [{k: v[:keep] for k, v in b.items()} for b in batches]
    params0 = make_params(struct, seed)
    wf = cfg["workflow"]
    ref = train_steps.follow(
        loss_sum, params0, batches, optimizer=wf["optimizer"],
        optimizer_args=wf.get("optimizer_args", {}),
        block_rows=int(cell["check"]["block_rows"]),
        cast=train_steps.CASTS[cast])
    return ref, time.perf_counter() - t0


def program_readings(s, cell, cfg):
    """What the check needs of the program once its first steps are done:
    the recorder's readings, the multipliers its stochastic units drew at
    those steps, and how much each kept."""
    import jax
    recorder, traffic = s["recorder"], cell["traffic"]
    got = {"losses": [float(x) for x in jax.device_get(recorder.losses)],
           "grad_norms": flatten_norms(recorder.grad_norms),
           "change_norms": flatten_norms(recorder.change)}
    multipliers = [dropout_multipliers(s["sw"].workflow, cfg, traffic, k)
                   for k in recorder.keys]
    numbers = {}
    for name, mult in multipliers[0].items():
        ratio = float(next(l for l in expand_layers(cfg)
                           if l["name"] == name)["dropout_ratio"])
        kept = float((mult != 0).mean())
        numbers[f"keep_gap_{name}"] = (abs(kept - (1.0 - ratio)),
                                       f"kept {kept!r}")
    return got, multipliers, numbers


def flatten_norms(tree):
    """{"unit/leaf": norm} of the recorder's nested scalars, named as the
    reference names its leaves."""
    import jax
    flat = jax.tree_util.tree_leaves_with_path(jax.device_get(tree))
    return {train_steps.leaf_name(p): float(v) for p, v in flat}


def setup(cell, cfg, seed):
    """Everything before the window: data, trainer, weights, and the
    warm-up epoch with its recorder.  Returns the pieces the window and
    the check need."""
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
    jax.block_until_ready(getattr(feed, "train", None))
    lap("data_s")
    sw = StandardWorkflow(workflow_config(cfg))
    loader = feed.loader()
    trainer = sw.make_trainer(loader, decision=window_decision(0.0))
    trainer.recorder = MetricsRecorder()
    trainer.initialize(seed=seed % (2 ** 31 - 1))
    if cfg["data"]["kind"] == "image_store" and not loader.on_device:
        raise RuntimeError("the image store is not resident on the device")
    jax.block_until_ready(trainer.wstate)
    lap("initialize_s")
    struct = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        trainer.wstate["params"])
    trainer.wstate = {**trainer.wstate, "params": make_params(struct, seed)}
    jax.block_until_ready(trainer.wstate)
    lap("weights_s")

    wf = cfg["workflow"]
    recorder = StepRecorder(
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
    traffic = cell["traffic"]
    seed = int(args.seed)
    s = setup(cell, cfg, seed)
    s["phases"]["before_driver_s"] = before_driver_s
    trainer, recorder, feed = s["trainer"], s["recorder"], s["feed"]
    n_train = int(traffic["n_train"])
    per_row = items_per_row(cfg, traffic)

    compiles = []

    def on_compile(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    tracer = None
    if int(args.trace):
        tracer = Tracer(os.path.join(CACHE, "trace",
                                     f"{cell['name']}-{os.getpid()}"))
    stats0 = trainer.step_cache.stats()
    wait0 = trainer._m_phase.labels(phase="data_wait").sum
    skipped0 = trainer.anomaly_steps_skipped
    epoch0 = s["loader"].epoch_number
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
    steps = epochs * (n_train // int(traffic["batch"]))
    data_wait_s = trainer._m_phase.labels(phase="data_wait").sum - wait0
    failed = trainer.anomaly_steps_skipped - skipped0
    peak = max(device_peak_bytes(d) for d in jax.local_devices())
    setup_phases = s["phases"]

    got, multipliers, numbers = program_readings(s, cell, cfg)
    struct = s["struct"]
    trainer.wstate = None
    trainer._train_step = trainer._eval_step = None
    del s, trainer, recorder, decision

    ref, reference_s = check(cell, cfg, feed, multipliers, seed, struct)
    numbers.update(compare.compare_training(got, ref))
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
