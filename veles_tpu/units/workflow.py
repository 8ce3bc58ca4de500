"""Workflow: the unit container + compiled step functions.

TPU-native re-design of the reference Workflow/scheduler (reference:
veles/workflow.py:87 — ordered unit set, dependency-ordered initialize
:303-349, run-by-gate-propagation :351-369; hot loop veles/units.py:782-803).

THE core architectural change of the rebuild: instead of a thread pool
propagating "gate open" notifications between live unit objects, the unit DAG
is topologically sorted once and traced into **two compiled XLA programs** —
``train_step`` (forward + backward + optimizer update, one fused program the
MXU pipeline never leaves) and ``eval_step``. The reference's data-dependent
gating (Decision blocking gradient units during validation,
SURVEY.md §7 "hard parts") maps exactly onto this train/eval phase split.

What survives from the reference design:
  * the Workflow as an inspectable container of named units,
  * wiring checks at build time (replacing ``demand()``'s runtime None
    checks, veles/units.py:682),
  * ``gather_results`` metric aggregation (veles/workflow.py:827-849),
  * graph export for visualization (DOT; veles/workflow.py:628),
  * checksum identifying the workflow for distributed handshakes
    (veles/workflow.py:851).
"""

from __future__ import annotations

import hashlib
import json
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..logger import Logger
from ..ops.optimizers import Optimizer, guarded_update, tree_select
from .base import Context, Spec, Unit


class WorkflowError(Exception):
    pass


#: where ``forward`` leaves the evaluator's metrics among its outputs (no
#: unit can take the name: "@" marks a batch key)
_EVALUATED = "@evaluated"


def new_state(params, state, opt_state, step, key):
    """The workflow state pytree: everything that is sharded, donated and
    checkpointed. Replaces the reference's pickled live-object graph
    (veles/snapshotter.py:387-409 pickled the whole Workflow)."""
    return {"params": params, "state": state, "opt_state": opt_state,
            "step": step, "key": key}


class Workflow(Logger):
    """Container + compiler for a unit DAG.

    Usage::

        wf = Workflow("mnist")
        h = wf.add(All2AllTanh(100, name="fc1", inputs=("@input",)))
        o = wf.add(All2AllSoftmax(10, name="fc2", inputs=("fc1",)))
        wf.add(EvaluatorSoftmax(name="ev", inputs=("fc2", "@labels")))
        wf.build({"@input": Spec((B, 784), f32), "@labels": Spec((B,), i32)})
        opt = SGD(0.1)
        wstate = wf.init_state(jax.random.key(0), opt)
        train = wf.make_train_step(opt)
        wstate, metrics = train(wstate, batch)
    """

    def __init__(self, name: str = "Workflow"):
        self.name = name
        self.units: List[Unit] = []
        self._by_name: Dict[str, Unit] = {}
        self._order: Optional[List[Unit]] = None
        self._specs: Dict[str, Spec] = {}
        self._input_specs: Dict[str, Spec] = {}
        self.evaluator: Optional[Unit] = None
        self.mesh = None
        self.state_sharding = None

    # -- construction ------------------------------------------------------
    def add(self, unit: Unit) -> Unit:
        if unit.name in self._by_name:
            raise WorkflowError(f"duplicate unit name {unit.name!r}")
        self.units.append(unit)
        self._by_name[unit.name] = unit
        self._order = None
        if getattr(unit, "is_evaluator", False):
            self.evaluator = unit
        return unit

    def __getitem__(self, name: str) -> Unit:
        return self._by_name[name]

    def __contains__(self, name):
        return name in self._by_name

    def topo_order(self) -> List[Unit]:
        """Topological order over data edges. Build-time cycle/wiring check
        (replaces runtime gate deadlock debugging in the reference)."""
        if self._order is not None:
            return self._order
        order, seen, visiting = [], set(), set()

        def visit(u: Unit):
            if u.name in seen:
                return
            if u.name in visiting:
                raise WorkflowError(f"cycle through unit {u.name!r}")
            visiting.add(u.name)
            for src in u.inputs:
                if src.startswith("@"):
                    continue
                if src not in self._by_name:
                    raise WorkflowError(
                        f"unit {u.name!r} consumes unknown source {src!r}")
                visit(self._by_name[src])
            visiting.discard(u.name)
            seen.add(u.name)
            order.append(u)

        for u in self.units:
            visit(u)
        self._order = order
        return order

    def build(self, input_specs: Dict[str, Spec]) -> Dict[str, Spec]:
        """Infer output specs in topo order; validates all wiring."""
        self._input_specs = dict(input_specs)
        specs = dict(input_specs)
        for u in self.topo_order():
            in_specs = []
            for src in u.inputs:
                if src not in specs:
                    raise WorkflowError(
                        f"unit {u.name!r} needs {src!r} which is neither a "
                        f"batch key nor an upstream unit output")
                in_specs.append(specs[src])
            u.prepare(in_specs)
            specs[u.name] = u.output_spec(in_specs)
        self._specs = specs
        return specs

    # -- state -------------------------------------------------------------
    def init_state(self, key: jax.Array,
                   optimizer: Optional[Optimizer] = None) -> dict:
        if not self._specs:
            raise WorkflowError("call build() before init_state()")
        params, state = {}, {}
        keys = jax.random.split(key, len(self.topo_order()) + 1)
        for u, k in zip(self.topo_order(), keys[:-1]):
            in_specs = [self._specs[s] for s in u.inputs]
            p, s = u.init(k, in_specs)
            if p:
                params[u.name] = p
            if s:
                state[u.name] = s
        opt_state = optimizer.init(params) if optimizer is not None else {}
        return new_state(params, state, opt_state,
                         jnp.zeros((), jnp.int32), keys[-1])

    # -- tracing -----------------------------------------------------------
    def forward(self, params, state, batch: Dict[str, jax.Array],
                ctx: Context, *, only: Optional[set] = None
                ) -> Tuple[Dict[str, jax.Array], dict]:
        """Pure forward over the DAG; returns (all outputs, new unit state).
        This is the reference's hot loop (veles/units.py:782-803) as a trace.
        ``only`` restricts execution to a subset of unit names (ancestors of
        a prediction target, so inference needs no labels)."""
        outputs = dict(batch)
        nstate = {}
        for u in self.topo_order():
            if only is not None and u.name not in only:
                continue
            xs = [outputs[s] for s in u.inputs]
            up = params.get(u.name, {})
            us = state.get(u.name, {})
            if u is self.evaluator:
                # one pass over the evaluator's inputs a step: the loss
                # is the unit's output, the rest waits for ``_metrics``
                with jax.named_scope(u.name):
                    mets = u.evaluate(up, us, xs, ctx)
                outputs[u.name], outputs[_EVALUATED] = mets["loss"], mets
                continue
            # the unit's name scopes its operations in the compiled
            # program (jvp(name) / transpose(jvp(name)) under AD), which
            # is how a profile tells conv2's backward from lrn1's
            def apply(p, s, *xs, _u=u):
                with jax.named_scope(_u.name):
                    return _u.apply(p, s, list(xs), ctx)

            if getattr(u, "remat", False) and ctx.train:
                # activation rematerialization: recompute this unit's
                # internals in the backward instead of taping them —
                # jax.checkpoint over the unit apply (build brief: trade
                # FLOPs for HBM). Stochastic units are safe: the ctx key
                # is a closed-over tracer, so the recompute draws the
                # SAME mask.
                y, ns = jax.checkpoint(apply)(up, us, *xs)
            else:
                y, ns = apply(up, us, *xs)
            outputs[u.name] = y
            # lint: disable=VT101 dict emptiness is static structure at
            # trace time (sparse nstate, not a value-dependent branch)
            if ns:
                nstate[u.name] = ns
        return outputs, nstate

    def ancestors(self, name: str) -> set:
        """Unit names needed to compute ``name`` (inclusive)."""
        need, stack = set(), [name]
        while stack:
            n = stack.pop()
            if n in need or n.startswith("@"):
                continue
            need.add(n)
            stack.extend(self._by_name[n].inputs)
        return need

    @staticmethod
    def _metrics(outputs) -> Dict[str, jax.Array]:
        """The step's metrics: what the evaluator's one ``evaluate`` of
        ``forward`` yielded beside the loss."""
        return outputs.get(_EVALUATED, {})

    @staticmethod
    def _unit_counters(nstate) -> Dict[str, jax.Array]:
        """What units count in a step beside their output (the scalars
        under ``counters`` in a unit's new state), keyed
        ``counters/<unit>/<name>`` for the step's metrics: they leave the
        compiled step with the epoch's metrics and reach the host at the
        epoch's drain, where the trainer hands them to the unit's
        ``publish_counters``."""
        return {f"counters/{name}/{k}": v for name, ns in nstate.items()
                for k, v in ns.get("counters", {}).items()}

    # -- compiled steps ----------------------------------------------------
    def _build_step(self, optimizer: Optimizer) -> Callable:
        """The pure (wstate, batch) -> (wstate, metrics) train function.

        Carries the in-graph anomaly sentinel (``ops.optimizers.
        guarded_update``): a non-finite loss or gradient norm skips the
        whole update via a traced select — params, optimizer slots and
        unit state carry through unchanged, the skip counters in
        opt_state advance, and the step's metrics zero out so epoch
        aggregates stay finite.  All of it is data flow inside the one
        compiled program: no host sync per step, no recompile on a bad
        step (docs/robustness.md)."""
        selfupd = [u for u in self.units if getattr(u, "self_updating", False)]

        aux_units = [u for u in self.units
                     if getattr(u, "has_aux_loss", False)]

        # trace-time knobs: flipping them re-traces (a new build), so a
        # running program's behavior never changes under its feet
        from ..config import root
        sentinel = bool(root.common.train.get("sentinel", True))
        clip = float(root.common.train.get("clip_norm", 0.0) or 0.0)
        from ..runtime.faults import get_plan  # late: avoids import cycle
        inject = get_plan().nan_grad_at_step

        def step(wstate, batch):
            key, sub = jax.random.split(wstate["key"])
            ctx = Context(train=True, key=sub, mesh=self.mesh)

            if self.evaluator is not None:
                def loss_fn(params):
                    outputs, nstate = self.forward(
                        params, wstate["state"], batch, ctx)
                    loss = outputs[self.evaluator.name]
                    mets = self._metrics(outputs)
                    # auxiliary losses (e.g. MoE load balance) ride the
                    # unit-state channel and are summed into the training
                    # loss with per-unit weights
                    for u in aux_units:
                        aux = nstate[u.name]["aux_loss"]
                        loss = loss + u.aux_weight * aux
                        mets = {**mets, f"aux_{u.name}": aux}
                    return loss, (outputs, nstate, mets)

                grads, (outputs, nstate, mets) = jax.grad(
                    loss_fn, has_aux=True)(wstate["params"])
                with jax.named_scope("optimizer"):
                    params, opt_state, ok, gnorm = guarded_update(
                        optimizer, grads, wstate["opt_state"],
                        wstate["params"], wstate["step"],
                        outputs[self.evaluator.name], clip_norm=clip,
                        sentinel=sentinel, inject_nan_steps=inject)
                if ok is not None:
                    # a skipped step contributes nothing to the epoch
                    # aggregates (its loss/n_samples would be NaN or
                    # meaningless) and one tick to the anomaly count
                    mets = {k: jnp.where(ok, v, jnp.zeros_like(v))
                            for k, v in mets.items()}
                    mets["anomaly_steps"] = (~ok).astype(jnp.float32)
                if gnorm is not None:
                    # gated too: a skipped step's NaN norm must not
                    # poison the epoch grad_norm aggregate
                    mets["grad_norm"] = gnorm if ok is None \
                        else jnp.where(ok, gnorm, 0.0)
                # not gated: the forward ran whether or not the update did
                mets.update(self._unit_counters(nstate))
            else:  # pure self-organizing workflows (SOM etc.)
                outputs, nstate = self.forward(
                    wstate["params"], wstate["state"], batch, ctx)
                mets = {}
                params, opt_state = wstate["params"], wstate["opt_state"]
                ok = None

            state = {**wstate["state"], **nstate}
            for u in selfupd:
                xs = [outputs[s] for s in u.inputs]
                state[u.name] = u.update_state(
                    params.get(u.name, {}), state.get(u.name, {}), xs, ctx)
            if ok is not None:
                # unit state (normalizer stats, recurrent carries, aux
                # accumulators) also freezes on an anomalous step — the
                # skip must be a complete no-op on the training state
                state = {k: (tree_select(ok, v, wstate["state"][k])
                             if k in wstate["state"] else v)
                         for k, v in state.items()}

            nws = new_state(params, state, opt_state,
                            wstate["step"] + 1, key)
            return nws, mets

        return step

    def make_train_step(self, optimizer: Optimizer, *, jit: bool = True,
                        donate: bool = True) -> Callable:
        """(wstate, batch) -> (wstate, metrics): forward + grad + update as
        ONE XLA program. Single-device / auto-sharded form; for explicit
        mesh placement use :meth:`make_sharded_train_step`."""
        step = self._build_step(optimizer)
        if jit:
            return jax.jit(step, donate_argnums=(0,) if donate else ())
        return step

    def make_sharded_train_step(self, optimizer: Optimizer, mesh,
                                wstate, batch_spec, *, rule=None,
                                donate: bool = True):
        """Compile the train step under an explicit device mesh.

        Shardings are computed from ``rule`` over the state pytree (see
        veles_tpu.parallel.mesh) and from the batch spec (leading axis over
        data×fsdp). GSPMD inserts the gradient psum over ICI — the TPU
        replacement for the reference's master-side update merging
        (veles/workflow.py:533-548, SURVEY.md §2.5).

        Returns (step_fn, state_shardings, batch_shardings); place the
        initial wstate with ``jax.device_put(wstate, state_shardings)``.
        """
        from ..parallel.mesh import batch_shardings, state_shardings
        state_sh = state_shardings(wstate, mesh, rule)
        batch_sh = batch_shardings(batch_spec, mesh)
        self.mesh = mesh  # BEFORE _build_step: the traced ctx carries it
        self.state_sharding = state_sh
        step = self._build_step(optimizer)
        fn = jax.jit(step,
                     in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None),
                     donate_argnums=(0,) if donate else ())
        return fn, state_sh, batch_sh

    def make_pipeline_train_step(self, optimizer: Optimizer, mesh,
                                 wstate, batch_spec, *,
                                 n_microbatches: int, rule=None,
                                 batch_axes: Sequence[str] = ("data",
                                                              "fsdp"),
                                 donate: bool = True,
                                 interleave: int = 1):
        """Compile the FUSED 1F1B pipeline training step (the model IS the
        pipeline): pre-units fold into stage 0, post-units + evaluator
        loss into the last stage, one PipelineStack supplies the stages.
        Same return contract as :meth:`make_sharded_train_step` —
        ``(step_fn, state_shardings, batch_shardings)`` — so the Trainer
        swaps schedules on a config switch.  Backward memory is bounded
        by pipeline depth, not microbatch count (parallel/pipeline.py).

        ``interleave=v`` runs the INTERLEAVED schedule: the stack must
        have v·S uniform stages, device d hosts virtual chunks d, S+d,
        ... — up to ~2× less pipeline bubble than folding the chunks
        into plain 1F1B (see parallel/pipeline.py::_interleaved_local
        for the exact accounting) at v× the activation stash.
        """
        from ..parallel.pipeline_compile import build_pipeline_step
        return build_pipeline_step(
            self, optimizer, mesh, wstate, batch_spec,
            n_microbatches=n_microbatches, rule=rule,
            batch_axes=batch_axes, donate=donate,
            interleave=interleave)

    def make_sharded_eval_step(self, mesh, wstate, batch_spec, *, rule=None):
        from ..parallel.mesh import batch_shardings, state_shardings
        state_sh = state_shardings(wstate, mesh, rule)
        batch_sh = batch_shardings(batch_spec, mesh)
        self.mesh = mesh

        def step(wstate, batch):
            ctx = Context(train=False, key=None, mesh=self.mesh)
            outputs, nstate = self.forward(wstate["params"],
                                           wstate["state"], batch, ctx)
            return {**self._metrics(outputs),
                    **self._unit_counters(nstate)}

        return jax.jit(step, in_shardings=(state_sh, batch_sh),
                       out_shardings=None), state_sh, batch_sh

    def make_eval_step(self, *, jit: bool = True) -> Callable:
        """(wstate, batch) -> metrics. Separate compiled program = the
        reference's Decision-gated validation phase."""

        def step(wstate, batch):
            ctx = Context(train=False, key=None, mesh=self.mesh)
            outputs, nstate = self.forward(wstate["params"],
                                           wstate["state"], batch, ctx)
            return {**self._metrics(outputs),
                    **self._unit_counters(nstate)}

        return jax.jit(step) if jit else step

    def default_output(self) -> str:
        """Name of the last forward (non-evaluator) unit — the chain's
        natural prediction head (shared by predict/serve/decode)."""
        cands = [u.name for u in self.topo_order()
                 if not getattr(u, "is_evaluator", False)]
        if not cands:
            raise WorkflowError("no forward units")
        return cands[-1]

    def make_predict_step(self, output_unit: Optional[str] = None, *,
                          jit: bool = True) -> Callable:
        """(wstate, batch) -> output of the last forward (or named) unit."""
        if output_unit is None:
            output_unit = self.default_output()
        needed = self.ancestors(output_unit)

        def step(wstate, batch):
            ctx = Context(train=False, key=None, mesh=self.mesh)
            outputs, _ = self.forward(wstate["params"], wstate["state"],
                                      batch, ctx, only=needed)
            return outputs[output_unit]

        return jax.jit(step) if jit else step

    @staticmethod
    def state_struct(wstate) -> dict:
        """ShapeDtypeStruct skeleton of a workflow state pytree — the
        argument signature ``runtime.step_cache.StepCache`` lowers the
        step programs against (AOT ``.lower().compile()``), typed PRNG
        key leaves included."""
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                getattr(x, "shape", ()), x.dtype), wstate)

    # -- introspection / parity extras -------------------------------------
    def checksum(self) -> str:
        """Stable identity of the graph topology (reference:
        veles/workflow.py:851 — used in the distributed handshake)."""
        desc = [(u.name, type(u).__name__, list(u.inputs))
                for u in self.topo_order()]
        return hashlib.sha256(
            json.dumps(desc, sort_keys=True).encode()).hexdigest()

    def generate_graph(self) -> str:
        """DOT source of the data DAG (reference: veles/workflow.py:628)."""
        lines = [f'digraph "{self.name}" {{', "  rankdir=LR;"]
        inputs = {s for u in self.units for s in u.inputs
                  if s.startswith("@")}
        for i in sorted(inputs):
            lines.append(f'  "{i}" [shape=oval, style=dashed];')
        for u in self.units:
            shape = "diamond" if getattr(u, "is_evaluator", False) else "box"
            lines.append(
                f'  "{u.name}" [shape={shape}, '
                f'label="{u.name}\\n{type(u).__name__}"];')
            for s in u.inputs:
                lines.append(f'  "{s}" -> "{u.name}";')
        lines.append("}")
        return "\n".join(lines)

    def generate_svg(self) -> str:
        """Self-contained SVG of the data DAG — a native renderer for the
        browser workflow viewer (reference: the web UI's live graph,
        /root/reference/web/viz.js fed by veles/workflow.py:628's DOT).
        The reference shelled out to graphviz; this image has none, so a
        simple layered layout (layer = 1 + max layer of inputs, left to
        right) is computed here — exact enough for the linear-ish unit
        chains workflows are."""
        layer: Dict[str, int] = {}
        inputs = sorted({s for u in self.units for s in u.inputs
                         if s.startswith("@")})
        for s in inputs:
            layer[s] = 0
        for u in self.topo_order():
            layer[u.name] = 1 + max(
                (layer.get(s, 0) for s in u.inputs), default=0)
        cols: Dict[int, List[str]] = {}
        kinds: Dict[str, str] = {s: "input" for s in inputs}
        for u in self.topo_order():
            kinds[u.name] = ("evaluator"
                             if getattr(u, "is_evaluator", False)
                             else type(u).__name__)
        for name, li in layer.items():
            cols.setdefault(li, []).append(name)
        BW, BH, GX, GY, PAD = 148, 42, 52, 18, 16
        pos: Dict[str, Tuple[int, int]] = {}
        for li in sorted(cols):
            for ri, name in enumerate(sorted(cols[li])):
                pos[name] = (PAD + li * (BW + GX),
                             PAD + ri * (BH + GY))
        width = PAD * 2 + (max(cols) + 1) * (BW + GX) - GX
        height = PAD * 2 + max(
            len(v) for v in cols.values()) * (BH + GY) - GY
        fills = {"input": "#eef", "evaluator": "#fee"}
        parts = [
            f'<svg xmlns="http://www.w3.org/2000/svg" width="{width}" '
            f'height="{height}" font-family="monospace" font-size="11">',
            '<defs><marker id="arr" viewBox="0 0 10 10" refX="9" refY="5"'
            ' markerWidth="6" markerHeight="6" orient="auto">'
            '<path d="M0,0L10,5L0,10z" fill="#555"/></marker></defs>']
        for u in self.units:
            x1, y1 = pos[u.name]
            for s in u.inputs:
                if s not in pos:
                    continue
                x0, y0 = pos[s]
                parts.append(
                    f'<line x1="{x0 + BW}" y1="{y0 + BH // 2}" '
                    f'x2="{x1}" y2="{y1 + BH // 2}" stroke="#555" '
                    'marker-end="url(#arr)"/>')
        from html import escape
        for name, (x, y) in pos.items():
            kind = kinds.get(name, "")
            fill = fills.get(kind, "#efe")
            dash = ' stroke-dasharray="4 2"' if kind == "input" else ""
            label = name if kind in ("input", "") else kind
            parts.append(
                f'<rect x="{x}" y="{y}" width="{BW}" height="{BH}" '
                f'rx="6" fill="{fill}" stroke="#333"{dash}/>')
            parts.append(f'<text x="{x + 6}" y="{y + 17}">'
                         f'{escape(name[:20])}</text>')
            if label != name:
                parts.append(
                    f'<text x="{x + 6}" y="{y + 33}" fill="#666">'
                    f'{escape(label[:20])}</text>')
        parts.append("</svg>")
        return "\n".join(parts)

    def n_params(self, wstate) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(wstate["params"]))

    def profile_units(self, wstate, batch, *, train: bool = False,
                      reps: int = 3) -> List[Dict[str, Any]]:
        """Per-unit wall timing: run each unit's apply as its own jitted
        call with a forced device sync — the analog of the reference's
        ``--sync-run`` honest per-unit timers (veles/accelerated_units.py
        :186-193, per-unit timers veles/units.py:805-817).

        This is NOT how the production step's cost is attributed: a unit
        jitted alone and synced is another program than the unit inside
        the fused step (forward only, its operands' layouts and what XLA
        fuses across units both lost; a kernel timed alone has read three
        times off, PERF.md section 6, PR 30).  The production step's
        device time by unit, forward and backward, comes from its own
        trace: every compiled step program is noted in
        ``runtime/program_scopes.py`` (which unit each instruction
        belongs to), and ``program_scopes.seconds_by_scope`` joins a
        device trace's events to that (docs/observability.md "Device
        time by unit").  What this mode is still good for: a machine
        with no device trace (a CPU run, a backend whose profiler names
        no operations), as a rough forward-only ranking of the units."""
        import time as _time
        ctx = Context(train=train, key=wstate.get("key"))
        outputs = dict(batch)
        rows = []

        def drain(tree):
            leaf = jax.tree.leaves(tree)[0]
            jax.device_get(leaf.ravel()[:1])  # scalar read = full sync

        for u in self.topo_order():
            xs = [outputs[s] for s in u.inputs]
            fn = jax.jit(lambda p, s, *xs, _u=u: _u.apply(p, s, list(xs),
                                                          ctx))
            params = wstate["params"].get(u.name, {})
            state = wstate["state"].get(u.name, {})
            y, _ = fn(params, state, *xs)
            drain(y)  # compile + warm
            best = float("inf")
            for _ in range(reps):
                t0 = _time.perf_counter()
                y, _ = fn(params, state, *xs)
                drain(y)
                best = min(best, _time.perf_counter() - t0)
            outputs[u.name] = y
            rows.append({"unit": u.name, "type": type(u).__name__,
                         "ms": best * 1e3})
        return rows

    @staticmethod
    def format_profile(rows: List[Dict[str, Any]], top: int = 5) -> str:
        """Top-N table with share of total (reference: Workflow.print_stats
        top-5 table, veles/workflow.py:788-825)."""
        total = sum(r["ms"] for r in rows) or 1e-9
        ranked = sorted(rows, key=lambda r: -r["ms"])[:top]
        lines = [f"{'unit':>20s} {'type':>18s} {'ms':>9s} {'share':>7s}"]
        for r in ranked:
            lines.append(f"{r['unit']:>20s} {r['type']:>18s} "
                         f"{r['ms']:9.3f} {100 * r['ms'] / total:6.1f}%")
        lines.append(f"{'TOTAL':>20s} {'':>18s} {total:9.3f}")
        return "\n".join(lines)

    def gather_results(self, metrics: Dict[str, Any]) -> Dict[str, Any]:
        """JSON-able result dict (reference: IResultProvider →
        gather_results → --result-file, veles/workflow.py:827-849)."""
        out = {"workflow": self.name, "checksum": self.checksum()}
        for k, v in metrics.items():
            try:
                out[k] = float(v)
            except (TypeError, ValueError):
                out[k] = repr(v)
        return out
