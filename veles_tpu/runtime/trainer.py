"""Trainer: the host-side epoch loop tying loader + workflow + decision +
snapshotter together.

This replaces the reference's gate-driven Repeater loop (reference:
veles/plumbing.py:17 Repeater; Decision closing gates; EndPoint firing
``on_workflow_finished``, veles/workflow.py:351-377). All data-dependent
control flow (epochs, early stop, rollback, checkpoint cadence) lives here
on the host; everything per-step is the compiled train/eval functions.

Metric aggregation matches the reference Decision semantics: per-epoch sums
of n_err / mse over served (non-padded) samples → error % / RMSE.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import prng
from ..config import root
from ..loader.base import CLASS_NAMES, TRAIN, VALID, TEST, Loader
from ..logger import Logger
from ..ops.optimizers import (ANOM_CONSEC_KEY, LR_MULT_KEY, Optimizer,
                              reserved_opt_neutral)
from ..units.workflow import Workflow
from .benchmark import epoch_goodput, resolve_peak_tflops
from .decision import Decision
from .memory import memory_monitor, tree_bytes
from .metrics import registry, span
from .snapshotter import (Snapshotter, _to_numpy, restore_with_walkback)
from . import program_scopes
from .step_cache import StepCache, enable_persistent_cache


def aggregate_epoch_metrics(sums: Dict[str, float]) -> Dict[str, float]:
    n = max(sums.get("n_samples", 0.0), 1.0)
    out = dict(sums)
    if "n_err" in sums:
        out["error_pct"] = 100.0 * sums["n_err"] / n
    if "mse_sum" in sums:
        out["rmse"] = float(np.sqrt(sums["mse_sum"] / n))
    # per-batch means exclude sentinel-skipped steps (their metrics were
    # zeroed in-graph): dividing by the raw batch count would bias the
    # epoch loss low on any epoch with anomalies
    trained = max(sums.get("n_batches", 0.0)
                  - sums.get("anomaly_steps", 0.0), 1.0)
    if "loss" in sums and "n_batches" in sums:
        out["loss"] = sums["loss"] / trained
    if "grad_norm" in sums and "n_batches" in sums:
        out["grad_norm"] = sums["grad_norm"] / trained
    return out


class Trainer(Logger):
    """Standalone (or per-host SPMD) training driver."""

    def __init__(self, workflow: Workflow, loader: Loader,
                 optimizer: Optimizer, decision: Optional[Decision] = None,
                 snapshotter: Optional[Snapshotter] = None, *,
                 mesh=None, rule=None, recorder=None, status=None,
                 prefetch: int = 2, pipeline_microbatches=None,
                 pipeline_interleave: int = 1,
                 step_cache: Optional[StepCache] = None):
        self.workflow = workflow
        self.loader = loader
        self.optimizer = optimizer
        self.decision = decision or Decision(max_epochs=10)
        self.snapshotter = snapshotter
        self.mesh = mesh          # jax.sharding.Mesh for SPMD training
        self.rule = rule          # parameter sharding rule (parallel.mesh)
        self.recorder = recorder  # plotting.MetricsRecorder (optional)
        self.status = status      # runtime.status.StatusReporter (optional)
        self.prefetch = prefetch  # batch prefetch depth (0 = synchronous)
        # When set and the mesh has a pipe axis > 1, training runs on the
        # fused 1F1B schedule (Workflow.make_pipeline_train_step) instead
        # of AD-through-GPipe; eval keeps the forward GPipe path.
        self.pipeline_microbatches = pipeline_microbatches
        # v>1: the interleaved (virtual-stage) 1F1B schedule —
        # the stack needs v*pipe uniform stages
        self.pipeline_interleave = int(pipeline_interleave)
        # AOT step-compilation cache: each program compiles once per
        # workflow lifetime; rollbacks/restores are cache hits (the lr
        # drop rides opt_state as a traced scalar, see ops.optimizers).
        self.step_cache = step_cache if step_cache is not None \
            else StepCache()
        self._batch_sh = None
        self._state_sh = None
        self._batch_spec = None
        self.wstate = None
        self._train_cost = {"flops": 0.0, "bytes_accessed": 0.0}
        self._last_mfu = 0.0    # THIS trainer's last epoch (the gauge
        #                         is process-global; two trainers in
        #                         one process must not read each other)
        self._train_step = None
        self._eval_step = None
        self._eval_entry = None
        self._best_wstate = None
        self.results: Dict[str, Any] = {}
        # fault-tolerance gauges (docs/robustness.md): fed to
        # StatusReporter every epoch and into results/bench output
        self.anomaly_steps_skipped = 0
        self.anomaly_rollbacks = 0
        self.snapshot_walkbacks = 0
        # phase breakdown (docs/observability.md "Metrics & tracing"):
        # where a training second actually goes.  Per step — blocked on
        # the loader, moving the batch H2D, dispatching the step (host
        # wall: dispatch + any implicit sync the NEXT phase forces,
        # never a device sync of its own).  Per epoch, each beside the
        # span of the same work in run() — train_epoch (first dispatch
        # to the end of the drain: the device's seconds as the host can
        # honestly see them), train_drain, eval, boundary, snapshot.
        reg = registry()
        self._m_phase = reg.histogram(
            "vt_train_phase_seconds",
            "wall time by phase: per step data_wait | h2d | step; per "
            "epoch train_epoch | train_drain | eval | boundary | snapshot",
            labels=("phase",))
        self._m_steps = reg.counter(
            "vt_train_steps_total",
            "batches dispatched to the compiled train / eval step, by "
            "the loader's class", labels=("klass",))
        self._step_num = 0      # train steps this trainer has dispatched
        # the current run()'s seconds by phase (its span's args)
        self._spent = dict.fromkeys(
            ("train_epoch", "eval", "boundary", "snapshot"), 0.0)
        self._m_anom = reg.counter(
            "vt_train_anomaly_skips_total",
            "train steps skipped by the in-graph anomaly sentinel")
        self._g_epoch = reg.gauge(
            "vt_train_epoch", "current training epoch")
        # goodput (docs/observability.md "Goodput & MFU"): the train
        # program's cost analysis over the epoch wall, against the
        # device's published peak (runtime/benchmark.py DEVICE_PEAKS, or
        # the root.common.observe.peak_tflops override)
        self._g_flops_sec = reg.gauge(
            "vt_train_flops_per_sec",
            "achieved training flops/s over the last train-epoch wall "
            "(loader data waits included; eval and snapshot phases are "
            "outside it — vt_train_phase_seconds shows where they go)")
        self._g_mfu = reg.gauge(
            "vt_train_mfu",
            "model FLOPs utilization of the last train epoch against "
            "the device's published peak (0 = not measured: no TPU)")

    # -- setup -------------------------------------------------------------
    def initialize(self, seed: Optional[int] = None,
                   wstate: Optional[dict] = None) -> None:
        self.loader.initialize()
        batch = next(self.loader.iter_epoch(
            TRAIN if self.loader.class_lengths[TRAIN] else VALID))
        specs = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype
                                         if not hasattr(v, "dtype")
                                         else v.dtype)
                 for k, v in batch.items()}
        self.workflow.build(specs)
        if wstate is not None:
            self.wstate = wstate
        else:
            key = prng.get("init").next_key() if seed is None \
                else jax.random.key(seed)
            self.wstate = self.workflow.init_state(key, self.optimizer)
        from ..parallel.distributed import host_count, is_multihost
        if (is_multihost() and self.snapshotter is not None
                and self.snapshotter.time_interval > 0):
            raise ValueError(
                "time_interval snapshot throttling is wall-clock and can "
                "diverge across hosts (the payload gather is a collective "
                "every host must join); use epoch-interval throttling on "
                "multi-host runs")
        if self.mesh is not None and is_multihost():
            # Each host serves a local shard; the compiled step sees the
            # GLOBAL batch (host shards stitched on the data axis by
            # to_global_batch in the epoch loop).
            specs = {k: jax.ShapeDtypeStruct(
                (s.shape[0] * host_count(),) + tuple(s.shape[1:]), s.dtype)
                for k, s in specs.items()}
        self._batch_spec = specs
        # Persistent XLA compilation cache: must be active BEFORE the
        # first compile to be of any use.
        enable_persistent_cache()
        self._compile_steps()
        if self._state_sh is not None:
            self.wstate = self._place_state(self.wstate)
        # aval-derived memory ledger (runtime/memory.py, /memory.json):
        # what this trainer pinned, in exact bytes — the fit check the
        # ZeRO-sharding and quantization ROADMAP items start from
        import weakref

        from .memory import drop_stamped_components
        mem = memory_monitor()
        stamps = {
            name: mem.set_component(name, nbytes) for name, nbytes in (
                ("train.params",
                 tree_bytes(self.wstate.get("params", {}))),
                ("train.opt_state",
                 tree_bytes(self.wstate.get("opt_state", {}))),
                ("train.prefetch_staging",
                 max(self.prefetch, 0) * tree_bytes(self._batch_spec)),
            )}
        # stamped drop on GC: a freed trainer's bytes leave /memory.json
        # unless a newer registrant took the names over
        self._mem_finalizer = weakref.finalize(
            self, drop_stamped_components, stamps)
        mem.ensure_poller()
        self.info("workflow %s: %d params", self.workflow.name,
                  self.workflow.n_params(self.wstate))

    def _compile_steps(self) -> None:
        """Build (or fetch from the StepCache) the AOT-compiled train/eval
        steps, preserving mesh shardings.  Compiled exactly ONCE per
        workflow lifetime: a Decision rollback or ``restore`` with
        ``lr_multiplier != 1`` is a pure cache hit — the lr drop is a
        traced opt_state scalar, not a new Python closure."""
        state_struct = self.workflow.state_struct(self.wstate)
        key = self.step_cache.trainer_key(
            self.workflow, self.optimizer, self.wstate, self._batch_spec,
            mesh=self.mesh, rule=self.rule,
            pipeline=(self.pipeline_microbatches,
                      self.pipeline_interleave))
        pin = (self.workflow, self.rule, self.optimizer)
        args = (state_struct, dict(self._batch_spec))
        # the units' names and classes go with the programs, so the
        # tables noted at compile time can tell a unit's instructions
        units = program_scopes.workflow_units(self.workflow)
        if self.mesh is not None:
            fused_pp = (self.pipeline_microbatches is not None
                        and self.mesh.shape.get("pipe", 1) > 1)
            if self.pipeline_interleave > 1 and not fused_pp:
                raise ValueError(
                    "pipeline_interleave needs the fused 1F1B schedule: "
                    "set pipeline_microbatches and give the mesh a "
                    "'pipe' axis > 1 (otherwise the v*S-stage stack "
                    "would silently train sequentially)")
            if fused_pp:
                # Ragged tail batches are fine since round 5: the fused
                # step weights each microbatch's loss by its mask count
                # and normalizes by the batch total, landing exactly on
                # the AD path's global masked mean
                # (pipeline_compile.build_pipeline_step).
                def build_train():
                    return self.workflow.make_pipeline_train_step(
                        self.optimizer, self.mesh, self.wstate,
                        self._batch_spec, rule=self.rule,
                        n_microbatches=self.pipeline_microbatches,
                        interleave=self.pipeline_interleave)
            else:
                def build_train():
                    return self.workflow.make_sharded_train_step(
                        self.optimizer, self.mesh, self.wstate,
                        self._batch_spec, rule=self.rule)

            def build_eval():
                return self.workflow.make_sharded_eval_step(
                    self.mesh, self.wstate, self._batch_spec,
                    rule=self.rule)
        else:
            def build_train():
                return (self.workflow.make_train_step(self.optimizer),
                        None, None)

            def build_eval():
                return self.workflow.make_eval_step(), None, None

        self._train_step, self._state_sh, self._batch_sh = \
            self.step_cache.get_step("train", key, build_train, args,
                                     pin=pin, units=units)
        # the cost of THIS trainer's live train program — never the
        # kind-sum, which double-counts superseded entries after an
        # optimizer rebuild (the cache keeps them by design)
        self._train_cost = self.step_cache.entry_cost("train", key)
        # The eval program compiles LAZILY on the first eval epoch — a
        # train-only run (no VALID/TEST data, bench loops) never pays
        # for a program it does not execute.
        self._eval_step = None
        self._eval_entry = (key, build_eval, args, pin, units)

    def _ensure_eval_step(self):
        if self._eval_step is None:
            key, build_eval, args, pin, units = self._eval_entry
            self._eval_step, _, _ = \
                self.step_cache.get_step("eval", key, build_eval, args,
                                         pin=pin, units=units)
        return self._eval_step

    # -- epoch passes -------------------------------------------------------
    def _batches(self, klass: int, epoch):
        """DEVICE-PLACED batch stream with background prefetch: host-side
        minibatch assembly (gather/decode/normalize) AND the H2D transfer
        (``_place_batch``: ``jax.device_put`` under the batch shardings,
        multihost ``to_global_batch`` included) run in the worker thread,
        overlapping the previous step's compute — the double-buffered
        host→device feed of SURVEY.md §7.7 (the reference got overlap
        accidentally from its thread-pool unit graph).  The queue depth
        (``prefetch``) bounds the number of batches resident in HBM, so
        the default of 2 is a classic device-side double buffer.  The
        ``prefetch=0`` synchronous fallback places batches inline with
        identical semantics."""
        it = self.loader.iter_epoch(klass, epoch)
        if self.prefetch <= 0:
            for item in it:
                yield self._place_batch(item)
            return
        import queue
        import threading
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        _end = object()
        stop = threading.Event()

        def guarded_put(item) -> bool:
            # Bounded put that gives up when the consumer is gone —
            # otherwise an abandoned epoch (step raised, early stop) leaves
            # the worker blocked forever and, for streaming loaders,
            # silently draining samples nobody will see.
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for item in it:
                    # H2D inside the worker: device_put is async and
                    # thread-safe, so the transfer of batch N+1 rides
                    # under step N's compute instead of serializing in
                    # the consumer loop.
                    if not guarded_put(self._place_batch(item)):
                        return
                guarded_put(_end)
            except BaseException as e:  # re-raised on the consumer side
                guarded_put(e)

        threading.Thread(target=worker, daemon=True).start()
        try:
            while True:
                item = q.get()
                if item is _end:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()

    def _place_state(self, wstate):
        """Place the (host-identical) state under the mesh shardings; on
        multi-host the shardings span non-addressable devices, which
        device_put refuses."""
        from ..parallel.distributed import is_multihost, place_global_state
        if is_multihost():
            return place_global_state(wstate, self._state_sh)
        return jax.device_put(wstate, self._state_sh)

    def _place_batch(self, batch):
        """H2D placement under the compiled step's batch shardings.
        Called from the prefetch worker thread (see ``_batches``); the
        single/multi-host branching lives in distributed.place_batch."""
        if self._batch_sh is None:
            return batch
        from ..parallel.distributed import place_batch
        t0 = time.monotonic()
        placed = place_batch(batch, self.mesh, self._batch_sh)
        # dispatch wall of the H2D transfer (device_put is async; the
        # actual copy overlaps the previous step by design — this
        # phase going fat means the transfer no longer hides)
        self._m_phase.labels(phase="h2d").observe(time.monotonic() - t0)
        return placed

    def _run_epoch_train(self, epoch: int) -> Dict[str, float]:
        sums: Dict[str, Any] = {}
        phase = self._m_phase
        steps = self._m_steps.labels(klass=CLASS_NAMES[TRAIN])
        with span("train_epoch", cat="train", epoch=epoch) as sp:
            # _batches yields batches already device-placed (H2D runs in
            # the prefetch worker, overlapped with the previous step);
            # data_wait is the time THIS thread blocked on the feed —
            # near zero while prefetch keeps up, the loader's share of
            # the step when it does not
            it = iter(self._batches(TRAIN, epoch))
            last: Dict[str, Any] = {}
            while True:
                t0 = time.monotonic()
                batch = next(it, None)
                if batch is None:
                    # exhausted next() is generator teardown, not batch
                    # wait — recording it would skew the distribution
                    # and leave data_wait one count ahead of step
                    break
                phase.labels(phase="data_wait").observe(
                    time.monotonic() - t0)
                t0 = time.monotonic()
                # profiler only (the ring holds 512 spans): a capture's
                # host plane carries the trainer's own step number
                with jax.profiler.StepTraceAnnotation(
                        "train_step", step_num=self._step_num):
                    self.wstate, mets = self._train_step(self.wstate, batch)
                self._step_num += 1
                steps.inc()
                # Accumulate ON DEVICE — a float() here would sync the
                # pipeline every step (the reference's --sync-run behavior,
                # veles/accelerated_units.py:186-193, as an accident).
                for k, v in mets.items():
                    sums[k] = sums[k] + v if k in sums else v
                last = mets
                sums["n_batches"] = sums.get("n_batches", 0) + 1
                phase.labels(phase="step").observe(
                    time.monotonic() - t0)
            # the one host sync of the epoch: it ends when the device has
            # finished the last step, so the enclosing span's end is the
            # device's
            with span("train_drain", cat="train", epoch=epoch) as drain:
                counters = self._drain_unit_counters(sums, last)
                totals = {k: float(v) for k, v in sums.items()}
            self._publish_unit_counters(TRAIN, counters)
            mets = aggregate_epoch_metrics(totals)
            sp.args.update((k, round(v, 6)) for k, v in mets.items()
                           if isinstance(v, float))
        phase.labels(phase="train_drain").observe(drain.seconds)
        self._phase_done("train_epoch", sp)
        return mets

    def _run_epoch_eval(self, klass: int, epoch: int) -> Dict[str, float]:
        if self.loader.class_lengths[klass] == 0:
            return {}
        self._ensure_eval_step()
        sums: Dict[str, float] = {}
        steps = self._m_steps.labels(klass=CLASS_NAMES[klass])
        with span("eval", cat="train", epoch=epoch,
                  klass=CLASS_NAMES[klass]) as sp:
            for batch in self._batches(klass, epoch):
                mets = self._eval_step(self.wstate, batch)
                steps.inc()
                for k, v in mets.items():
                    sums[k] = sums[k] + v if k in sums else v
                sums["n_batches"] = sums.get("n_batches", 0) + 1
            counters = self._drain_unit_counters(sums, mets)
            totals = {k: float(v) for k, v in sums.items()}
        self._publish_unit_counters(klass, counters)
        self._phase_done("eval", sp)
        return aggregate_epoch_metrics(totals)

    @staticmethod
    def _drain_unit_counters(sums: Dict[str, Any], last: Dict[str, Any]):
        """Take the units' counters (``Workflow._unit_counters``) out of
        an epoch's device sums, and read them with the last step's: part
        of the epoch's one drain.  ``{unit: ({name: sum}, {name: last})}``."""
        out: Dict[str, Any] = {}
        for key in [k for k in sums if k.startswith("counters/")]:
            _, unit, name = key.split("/", 2)
            summed, final = out.setdefault(unit, ({}, {}))
            summed[name] = float(sums.pop(key))
            final[name] = float(last[key])
        return out

    def _publish_unit_counters(self, klass: int, counters) -> None:
        for unit, (summed, final) in counters.items():
            self.workflow[unit].publish_counters(
                CLASS_NAMES[klass], summed, final)

    def _phase_done(self, phase: str, sp) -> None:
        """Book a closed span's seconds under ``phase``, one of the four
        that partition a run: the process's histogram, and this run's
        own sums."""
        self._m_phase.labels(phase=phase).observe(sp.seconds)
        self._spent[phase] += sp.seconds

    # -- main loop ----------------------------------------------------------
    def run(self) -> Dict[str, Any]:
        if self.wstate is None:
            self.initialize()
        # one span tree per call: train_run > (train_epoch > train_drain,
        # epoch_decision, eval, epoch_decision, snapshot) per epoch.
        # train_epoch, eval, boundary and snapshot partition the run; what
        # they leave is its self time.  The run's own sums go into the
        # span's args, so a reader of one run needs no snapshot of the
        # process-global histogram at its start.
        with span("train_run", cat="train") as run_span:
            mono0, epoch0, step0 = (time.monotonic(),
                                    self.loader.epoch_number, self._step_num)
            self._spent = dict.fromkeys(self._spent, 0.0)
            self._run()
            spent = self._spent
            run_span.args.update(
                epochs=self.loader.epoch_number - epoch0,
                steps=self._step_num - step0,
                self_s=round(time.monotonic() - mono0
                             - sum(spent.values()), 6),
                **{f"{k}_s": round(v, 6) for k, v in spent.items()})
        return self.results

    def _run(self) -> None:
        t0 = time.time()
        samples_done = 0
        epoch = self.loader.epoch_number
        while not self.decision.complete:
            t_ep = time.time()
            self._g_epoch.set(epoch)
            train_mets = self._run_epoch_train(epoch)
            t_train = time.time()
            samples_done += int(train_mets.get("n_samples", 0))
            # epoch goodput: the compiled step's cost analysis times the
            # steps run, over the epoch wall — and MFU against the
            # device's published peak (runtime/benchmark.py).  Host arithmetic
            # only; the compiled programs are untouched.
            goodput = epoch_goodput(
                self._train_cost["flops"],
                train_mets.get("n_batches", 0.0),
                max(t_train - t_ep, 1e-9))
            self._g_flops_sec.set(goodput["flops_per_sec"])
            self._g_mfu.set(goodput["mfu"])
            self._last_mfu = goodput["mfu"]
            # anomaly accounting + (possibly) rollback escalation BEFORE
            # eval, so a rolled-back epoch validates the restored weights
            # — which is why epoch_decision opens twice an epoch
            with span("epoch_decision", cat="train", epoch=epoch) as sp:
                self._check_anomalies(epoch, train_mets)
            self._phase_done("boundary", sp)
            valid_mets = self._run_epoch_eval(VALID, epoch)
            if root.common.timings:
                # reference: per-unit/root.common.timings wall prints
                # (veles/units.py:144-149).  These are the epoch's wall
                # seconds; the step's device time by unit is read from a
                # trace joined to the scope tables noted at compile time
                # (runtime/program_scopes.py), not from per-unit jits.
                self.info(
                    "epoch %d timings: train %.3fs (%.0f samples/s), "
                    "eval %.3fs", epoch, t_train - t_ep,
                    train_mets.get("n_samples", 0.0)
                    / max(t_train - t_ep, 1e-9),
                    time.time() - t_train)
            with span("epoch_decision", cat="train", epoch=epoch) as sp:
                stop = self._decide(epoch, train_mets, valid_mets, goodput)
            self._phase_done("boundary", sp)
            if (self.snapshotter is not None
                    and self.snapshotter.tick(best=self.decision.improved)):
                # tick() is deterministic across hosts, so throttled
                # epochs skip the payload entirely (no wasted device→host
                # copy). On a snapshot epoch the payload is built on EVERY
                # host — gathering sharded state is a collective — but
                # only host 0 writes (reference: slaves never snapshot,
                # veles/snapshotter.py:160). Multi-host runs must give
                # every host a snapshotter with the same interval;
                # wall-clock time_interval throttling can diverge across
                # hosts and is rejected at initialize().
                with span("snapshot", cat="train", epoch=epoch) as sp:
                    payload = self._payload()
                    if jax.process_index() == 0:
                        self.snapshotter.save(f"ep{epoch}", payload,
                                              best=self.decision.improved)
                self._phase_done("snapshot", sp)
            epoch = self.loader.epoch_number
            if stop:
                break

        elapsed = time.time() - t0
        test_mets = self._run_epoch_eval(TEST, epoch)
        flops_per_step = self._train_cost["flops"]
        self.results = self.workflow.gather_results({
            "best_value": self.decision.best_value,
            "best_epoch": self.decision.best_epoch,
            "epochs": epoch,
            "elapsed_s": elapsed,
            "train_samples_per_s": samples_done / max(elapsed, 1e-9),
            "train_step_flops": flops_per_step,
            # unrounded: a CPU-tier MFU is ~1e-7 and must not round to
            # a fake zero (display rounding belongs to the status page)
            "train_mfu": self._last_mfu,
            "peak_tflops": resolve_peak_tflops(),
            "anomaly_steps_skipped": self.anomaly_steps_skipped,
            "anomaly_rollbacks": self.anomaly_rollbacks,
            "snapshot_walkbacks": self.snapshot_walkbacks,
            **{f"test_{k}": v for k, v in test_mets.items()},
        })

    def _decide(self, epoch, train_mets, valid_mets, goodput) -> bool:
        """The host work of an epoch boundary after validation: the
        decision, the recorder and status page, the best-state copy, a
        rollback, and the loader's advance.  Returns the decision's
        stop."""
        stop = self.decision.on_epoch(epoch, train_mets, valid_mets)
        if self.recorder is not None:
            self.recorder.record(
                epoch,
                **{f"train_{k}": v for k, v in train_mets.items()},
                **{f"valid_{k}": v for k, v in valid_mets.items()})
        if self.status is not None:
            self.status.update(
                epoch=epoch, best_value=self.decision.best_value,
                best_epoch=self.decision.best_epoch,
                train_mfu=round(goodput["mfu"], 4),
                train_flops_per_sec=round(
                    goodput["flops_per_sec"], 1),
                anomaly_steps_skipped=self.anomaly_steps_skipped,
                anomaly_rollbacks=self.anomaly_rollbacks,
                snapshot_walkbacks=self.snapshot_walkbacks,
                **{f"valid_{k}": v for k, v in valid_mets.items()})

        if (self.decision.improved
                and (self.decision.rollback_after is not None
                     or self._anomaly_patience() > 0)):
            # Host-side copy: train_step donates wstate buffers, so an
            # on-device alias would reference deleted arrays by the time
            # a rollback happens. (All hosts reach this branch — the
            # decision is identical everywhere — so the collective
            # gather inside _host_state_copy is safe.)
            self._best_wstate = self._host_state_copy()
        if self.decision.want_rollback and self._best_wstate is not None:
            # Reference: rollback to best snapshot + lr drop
            # (manualrst_veles_algorithms.rst:164). The cumulative
            # multiplier is written into the restored state's traced
            # opt_state scalar — the compiled steps are untouched
            # (ZERO recompiles; the restore re-places onto the mesh).
            self.wstate = Snapshotter.restore_wstate(
                {"wstate": self._best_wstate}, like=self.wstate,
                shardings=self._state_sh)
            self.wstate = self._apply_lr_multiplier(self.wstate)

        # Advance the loader first so a restored checkpoint resumes at
        # the *next* epoch instead of repeating the completed one.
        self.loader.next_epoch()
        return stop

    # -- anomaly sentinel escalation ----------------------------------------
    def _anomaly_patience(self) -> int:
        return int(root.common.train.get("anomaly_patience", 0) or 0)

    def _check_anomalies(self, epoch: int, train_mets: Dict[str, float]
                         ) -> None:
        """Epoch-granularity half of the sentinel: accumulate the skip
        count the in-graph guard already summed on device, and when the
        traced consecutive-anomaly counter crosses
        ``root.common.train.anomaly_patience``, escalate to the Decision
        rollback ladder — restore the best/last-snapshot weights and
        scale the traced lr multiplier down.  One small device_get per
        epoch; the per-step path never syncs."""
        skipped = int(train_mets.get("anomaly_steps", 0))
        if skipped:
            self.anomaly_steps_skipped += skipped
            self._m_anom.inc(skipped)
            self.warning("epoch %d: %d anomalous step(s) skipped "
                         "(non-finite loss/grad norm)", epoch, skipped)
        patience = self._anomaly_patience()
        if patience <= 0:
            return
        opt_state = (self.wstate or {}).get("opt_state")
        if not isinstance(opt_state, dict) \
                or ANOM_CONSEC_KEY not in opt_state:
            return
        consec = int(jax.device_get(opt_state[ANOM_CONSEC_KEY]))
        if consec >= patience:
            self._escalate_anomaly(epoch, consec)

    def _escalate_anomaly(self, epoch: int, consec: int) -> None:
        """The escalation rung above per-step skipping (reference:
        "rollback to best snapshot on failure + lr change",
        manualrst_veles_algorithms.rst:164 item 11): skipping alone can't
        cure a persistently diverging run, so restore known-good weights
        and train gentler.  Pure state writes — the compiled step
        programs are untouched (ZERO recompiles, tests/test_faults.py)."""
        self.anomaly_rollbacks += 1
        dec = self.decision
        dec.lr_multiplier *= dec.rollback_lr_scale
        source = None
        if self._best_wstate is not None:
            self.wstate = Snapshotter.restore_wstate(
                {"wstate": self._best_wstate}, like=self.wstate,
                shardings=self._state_sh)
            source = "in-memory best state"
        elif self.snapshotter is not None \
                and self.snapshotter.last_path is not None:
            payload, used, skipped = restore_with_walkback(
                self.snapshotter.last_path)
            self._note_walkback(skipped)
            self._adapt_reserved_opt_keys(payload)
            self.wstate = Snapshotter.restore_wstate(
                payload, like=self.wstate, shardings=self._state_sh)
            source = used
        else:
            self.warning("anomaly escalation has no snapshot or best "
                         "state to roll back to; keeping current params")
        self.wstate = self._apply_lr_multiplier(self.wstate)
        self.wstate = self._write_opt_scalars(
            self.wstate, {ANOM_CONSEC_KEY: np.zeros((), np.int32)})
        self.error(
            "anomaly escalation at epoch %d: %d consecutive anomalous "
            "steps >= patience %d — restored %s, lr multiplier now %g",
            epoch, consec, self._anomaly_patience(),
            source or "nothing", dec.lr_multiplier)
        if self.status is not None:
            self.status.record_event(
                "anomaly_rollback", epoch=epoch, consecutive=consec,
                lr_multiplier=dec.lr_multiplier,
                restored=source or "none")

    def _note_walkback(self, skipped) -> None:
        if not skipped:
            return
        self.snapshot_walkbacks += len(skipped)
        for s in skipped:
            self.warning("snapshot walk-back: skipped %s (%s)",
                         s["path"], s["reason"])
        if self.status is not None:
            self.status.record_event(
                "snapshot_walkback", skipped=[s["path"] for s in skipped])

    # -- traced lr multiplier ----------------------------------------------
    def _apply_lr_multiplier(self, wstate):
        """Write ``decision.lr_multiplier`` into the traced opt_state
        scalar the compiled step multiplies onto its base schedule —
        the recompile-free replacement for swapping in a scaled Python
        schedule closure and re-tracing both step programs."""
        mult = float(getattr(self.decision, "lr_multiplier", 1.0))
        opt_state = wstate.get("opt_state")
        if not isinstance(opt_state, dict) or LR_MULT_KEY not in opt_state:
            if mult != 1.0:
                self.warning(
                    "optimizer state carries no %s slot; lr multiplier "
                    "%g NOT applied (optimizer-less workflow?)",
                    LR_MULT_KEY, mult)
            return wstate
        return self._write_opt_scalars(
            wstate, {LR_MULT_KEY: np.asarray(mult, np.float32)})

    def _write_opt_scalars(self, wstate, values: Dict[str, Any]):
        """Host-side write of reserved opt_state scalars (the traced lr
        multiplier and anomaly counters) under the live shardings —
        the recompile-free state-mutation primitive all the rollback
        paths share.  Keys absent from the state are skipped."""
        opt_state = (wstate or {}).get("opt_state")
        if not isinstance(opt_state, dict):
            return wstate
        placed = {}
        for k, v in values.items():
            if k not in opt_state:
                continue
            leaf = jnp.asarray(v)
            if self._state_sh is not None:
                sh = self._state_sh["opt_state"][k]
                from ..parallel.distributed import (is_multihost,
                                                    place_global_state)
                leaf = place_global_state(leaf, sh) if is_multihost() \
                    else jax.device_put(leaf, sh)
            placed[k] = leaf
        if not placed:
            return wstate
        return {**wstate, "opt_state": {**opt_state, **placed}}

    def effective_lr(self, step: int = 0) -> float:
        """The learning rate the compiled step applies at ``step``: the
        base schedule × the traced rollback multiplier riding opt_state
        (``optimizer.schedule`` itself is never mutated anymore)."""
        lr = float(self.optimizer.schedule(step))
        opt_state = (self.wstate or {}).get("opt_state")
        if isinstance(opt_state, dict) and LR_MULT_KEY in opt_state:
            lr *= float(jax.device_get(opt_state[LR_MULT_KEY]))
        return lr

    def _host_state_copy(self):
        """Numpy copy of wstate; all-gathers non-addressable (multi-host
        rule-sharded) leaves — collective, call on every host."""
        from ..parallel.distributed import gather_to_host, is_multihost
        if is_multihost():
            return gather_to_host(self.wstate)
        return _to_numpy(self.wstate)

    def _payload(self) -> Dict[str, Any]:
        return {
            "wstate": self._host_state_copy(),
            "loader": self.loader.state(),
            "decision": self.decision.state(),
            "prng": prng.streams.state(),
            "config": root.to_dict(),
            "workflow_checksum": self.workflow.checksum(),
        }

    def _adapt_reserved_opt_keys(self, payload: Dict[str, Any]) -> None:
        """Bridge snapshot ↔ live reserved opt_state scalars: snapshots
        predating the traced multiplier / anomaly counters get neutral
        slots injected so the structural tree-map succeeds, and slots
        the live state doesn't carry (sentinel disabled, optimizer-less
        workflow) are dropped from the restored tree."""
        saved = payload.get("wstate")
        live_os = (self.wstate or {}).get("opt_state")
        if not (isinstance(saved, dict) and isinstance(live_os, dict)
                and isinstance(saved.get("opt_state"), dict)):
            return
        saved_os = saved["opt_state"]
        for k, neutral in reserved_opt_neutral().items():
            if k in live_os and k not in saved_os:
                saved_os[k] = neutral
            elif k in saved_os and k not in live_os:
                del saved_os[k]

    def restore(self, path: str, *, force: bool = False) -> None:
        """Resume from a snapshot manifest (reference CLI restore path,
        veles/__main__.py:539-589). Checksum mismatch is fatal unless
        ``force`` (the reference validated the workflow checksum in its
        distributed handshake, veles/server.py:478-492).

        Filesystem snapshots verify the manifest's tensors sha256 and,
        when the named snapshot is corrupt (truncated write, bit rot),
        WALK BACK through the retained snapshots to the newest valid one
        — logging every snapshot skipped and counting it in the
        ``snapshot_walkbacks`` gauge (docs/robustness.md)."""
        payload, used, skipped = restore_with_walkback(path)
        self._note_walkback(skipped)
        if skipped:
            self.warning("restoring %s instead of corrupt %s", used, path)
        if self.wstate is None:
            self.initialize()
        if payload.get("workflow_checksum") != self.workflow.checksum():
            msg = ("snapshot was taken from a different workflow "
                   f"(checksum {payload.get('workflow_checksum')!r} != "
                   f"{self.workflow.checksum()!r})")
            if not force:
                raise ValueError(msg + "; pass force=True to override")
            self.warning("%s — forcing restore", msg)
        self._adapt_reserved_opt_keys(payload)
        self.wstate = Snapshotter.restore_wstate(payload, like=self.wstate,
                                                 shardings=self._state_sh)
        self.loader.set_state(payload["loader"])
        self.decision.set_state(payload["decision"])
        prng.streams.set_state(payload["prng"])
        # Re-apply accumulated rollback lr drops as the traced opt_state
        # scalar (else a resumed run trains at the original, too-high lr).
        # The compiled steps are untouched: restore is recompile-free.
        self.wstate = self._apply_lr_multiplier(self.wstate)
