"""Parallelism-aware NN units: sequence-parallel attention, pipelined
stacks, mixture-of-experts — the sp/pp/ep axes as *product features*
constructible from StandardWorkflow configs (round-1 verdict #3: these were
library functions exercised only by dryrun demos).

No reference counterpart (SURVEY.md §5.7/§2.5: the reference's only
parallel axis was the batch); the build brief makes long-context and
multi-axis distribution first-class, so these are new TPU-native designs
layered on parallel/{ring_attention,pipeline,moe}.py.

Mesh discipline: each unit reads its axis size off ``ctx.mesh`` (threaded
by Workflow.make_sharded_train_step).  On a single device — or when the
relevant mesh axis has size 1 — every unit falls back to the numerically
identical local computation, so the same config runs anywhere.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..ops import smart_uniform_init as _uniform_init
from .base import Context, Forward, Spec


class MultiHeadAttention(Forward):
    """Self-attention over (B, T, E) activations.

    Sequence parallelism: when ``ctx.mesh`` has a ``seq`` axis > 1, the
    attention core runs as ring attention (parallel/ring_attention.py) —
    K/V blocks rotate over ICI while each device holds one sequence shard.
    Otherwise the blockwise/flash local kernel handles arbitrary T on one
    device.  Projections are plain gemms GSPMD shards by rule.

    Latent K and V (``kv_latent``; DeepSeek-V2's multi-head latent
    attention in the form of Hugging Face ``transformers``
    ``models/deepseek_v3/modeling_deepseek_v3.py``, without the rotary
    embedding, as Kimi Linear's ``mla_use_nope`` has it)::

        [c, k_shared] = x W_kv_down          kv_latent | k_shared a token
        k_h = [RMS(c; kv_norm) Wk_up | k_shared],  v_h = RMS(c; kv_norm) Wv_up
        q_h = (x Wq)_h                       head_dim = its k_h's width

    ``k_shared`` channels of every head's key are one row a token for
    all heads; values are ``v_head_dim`` wide.  The core is the one of
    every call, values padded with zeros to ``head_dim`` and the output
    cut back to ``v_head_dim`` (exact: a zero column of V gives a zero
    column of O).  No GQA, QK norm, gate or rotary embedding with it.
    """

    stochastic = False

    def __init__(self, n_heads: int, head_dim: Optional[int] = None,
                 name=None, inputs=("@input",), *, causal: bool = True,
                 seq_axis: str = "seq", block_size: int = 512,
                 compute_dtype=None, window: Optional[int] = None,
                 n_kv_heads: Optional[int] = None, rope: bool = False,
                 residual: bool = False,
                 use_flash: Optional[bool] = None,
                 qk_norm=False, gate: bool = False,
                 norm_eps: float = 1e-5, kv_latent: Optional[int] = None,
                 k_shared: int = 0, v_head_dim: Optional[int] = None):
        super().__init__(name, inputs)
        self.n_heads = int(n_heads)
        self.head_dim = head_dim
        self.causal = causal
        self.seq_axis = seq_axis
        self.block_size = int(block_size)
        self.compute_dtype = compute_dtype
        # sliding-window width (causal local attention); None = full
        self.window = None if window is None else int(window)
        self.rope = bool(rope)  # rotary position embedding on q/k
        # y = x + attn(x): the transformer residual stream (stacked
        # attention layers can't compose circuits without it)
        self.residual = bool(residual)
        # RMS normalisation of q and k (learnable scales ``q_norm``,
        # ``k_norm``), before the rotary embedding: True or "head", of
        # every head over the head's channels; "projection", of the whole
        # of ``x Wq`` and of ``x Wk`` over all the held channels, before
        # the heads are split
        if qk_norm not in (False, None, True, "head", "projection"):
            raise ValueError(f"qk_norm is True, 'head' or 'projection', "
                             f"not {qk_norm!r}")
        self.qk_norm = "head" if qk_norm is True else (qk_norm or None)
        self.norm_eps = float(norm_eps)
        # the heads' output times sigmoid(x Wg) before the out projection
        self.gate = bool(gate)
        # grouped-query attention: fewer K/V heads than Q heads
        from ..ops import check_gqa_heads
        self.n_kv_heads = (self.n_heads if n_kv_heads is None
                           else int(n_kv_heads))
        check_gqa_heads(self.n_heads, self.n_kv_heads)
        self.kv_latent = None if kv_latent is None else int(kv_latent)
        self.k_shared = int(k_shared)
        self.v_head_dim = None if v_head_dim is None else int(v_head_dim)
        if self.kv_latent is not None:
            if head_dim is None or self.n_kv_heads != self.n_heads \
                    or self.qk_norm or self.gate or self.rope:
                raise ValueError(
                    "latent K/V take a head_dim and no kv heads, QK norm, "
                    "gate or rotary embedding")
            if not 0 <= self.k_shared < int(head_dim) \
                    or not 0 < (self.v_head_dim or int(head_dim)) \
                    <= int(head_dim):
                raise ValueError(
                    f"k_shared {k_shared} and v_head_dim {v_head_dim} do "
                    f"not fit head_dim {head_dim}")
        elif self.k_shared or self.v_head_dim is not None:
            raise ValueError("k_shared and v_head_dim are latent K/V's")
        # None = measured Pallas-vs-XLA pick at build shape (prepare);
        # True/False forces; falls back to the platform default
        self.use_flash = use_flash
        self._resolved_flash = use_flash
        # per-shape autotuned (block_q, block_k) for the flash kernel;
        # None = the kernel's globally-swept defaults
        self._resolved_blocks = None

    def prepare(self, in_specs):
        """Measure flash-kernel vs XLA blockwise attention fwd+bwd at
        the actual (B, T, H, D) build shape and persist the winner — the
        reference's per-device bench-and-persist discipline
        (veles/backends.py:672-731) applied to the framework's most
        important op (round-3 verdict #6)."""
        from .. import ops
        from ..config import root
        if self.use_flash is not None:
            self._resolved_flash = self.use_flash
            return
        if not bool(root.common.autotune):
            self._resolved_flash = None  # platform default at apply
            return
        if not ops.use_pallas_default():
            self._resolved_flash = False  # off-TPU: measurement-free
            return
        import numpy as np
        from ..parallel.ring_attention import blockwise_attention
        from ..runtime import autotune
        spec = in_specs[0]
        B, T, E = spec.shape
        H, Hk = self.n_heads, self.n_kv_heads
        D = self.head_dim or E // H
        dt = self.compute_dtype or spec.dtype
        # Very long sequences are the sequence-parallel territory where
        # apply() takes the ring-attention path and ignores this pick —
        # and where a full-shape fwd+bwd probe could OOM one device at
        # build time. Skip the measurement past a probe budget.
        if B * T * (H + 2 * Hk) * D > 10 ** 8:
            self._resolved_flash = None  # platform default
            return
        shapes = [(B, T, H, D), (B, T, Hk, D), (B, T, Hk, D)]
        specs = [jax.ShapeDtypeStruct(s, dt) for s in shapes]

        def parse(name):
            # swept candidates carry their blocks in the name; a
            # pre-sweep DB record fails lookup's candidate-set check
            # and simply re-measures once
            if name.startswith("flash_"):
                bq, bk = name[len("flash_"):].split("x")
                return True, (int(bq), int(bk))
            return False, None

        # flash candidates: the global on-chip default plus per-shape
        # alternatives; dedupe by the kernel's EFFECTIVE clamped blocks
        # so tiny T doesn't measure the same program four times.  Four
        # shapes, because a cold start's set-up is this sweep; two of
        # them large, because at long T the backward kernels are bound
        # by the MXU's half-filled D = 64 passes and fewer, larger steps
        # win (PERF.md section 6, PR 28)
        from ..ops.pallas_kernels import _flash_blocks, flash_heads_per_step
        cand_blocks, seen = [], set()
        for bq, bk in ((256, 1024), (512, 512), (1024, 512), (1024, 1024)):
            eff = _flash_blocks(T, T, bq, bk)
            if eff not in seen:
                seen.add(eff)
                cand_blocks.append((bq, bk))
        # block_size changes the XLA candidate's schedule, so it keys
        # the persisted winner alongside causal/window/kv-heads; so do
        # the heads a grid step of each flash candidate takes (0: over
        # transposed copies), since they make its program
        heads = "-".join(str(flash_heads_per_step(*shapes[:2], bq, bk))
                         for bq, bk in cand_blocks)
        op = (f"attention_fwd_bwd_c{int(self.causal)}"
              f"_w{self.window}_hk{Hk}_bs{self.block_size}_l{heads}")
        names = tuple(f"flash_{bq}x{bk}" for bq, bk in cand_blocks) \
            + ("xla",)
        cached = autotune.lookup(op, names, specs)
        if cached is not None:
            self._resolved_flash, self._resolved_blocks = parse(cached)
            return
        rng = np.random.default_rng(0)
        args = [jnp.asarray(rng.standard_normal(s), dt) for s in shapes]

        def run(use_flash, blocks=None):
            return self._probe(functools.partial(
                blockwise_attention, block_size=self.block_size,
                causal=self.causal, window=self.window,
                use_flash=use_flash, flash_blocks=blocks))

        candidates = {f"flash_{bq}x{bk}": run(True, (bq, bk))
                      for bq, bk in cand_blocks}
        candidates["xla"] = run(False)
        winner = autotune.pick(op, candidates, args,
                               default=f"flash_{cand_blocks[0][0]}"
                                       f"x{cand_blocks[0][1]}")
        self._resolved_flash, self._resolved_blocks = parse(winner)

    @staticmethod
    def _probe(attend):
        """What ``prepare`` times for one candidate: forward and backward
        of ``attend(q, k, v)``, as a training step runs them.
        ``autotune.measure`` chains its repetitions through the first
        output alone, and whatever that output does not need is removed
        from every repetition but the last: a bare ``value_and_grad``
        (primal first) loses the two backward kernels that way.  So the
        one output needs the primal and all three gradients."""
        def f(q, k, v):
            loss, grads = jax.value_and_grad(
                lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)
            return loss + 1e-30 * sum(
                jnp.sum(g.astype(jnp.float32)) for g in grads)
        return f

    def output_spec(self, in_specs: Sequence[Spec]) -> Spec:
        return in_specs[0]

    def init(self, key, in_specs):
        E = in_specs[0].shape[-1]
        H, Hk = self.n_heads, self.n_kv_heads
        D = self.head_dim or E // H
        if self.head_dim is None and E % H:
            raise ValueError(f"model dim {E} not divisible by {H} heads")
        if self.kv_latent is not None:
            return self._latent_params(key, E, H, D), {}
        kq, kk, kv, ko = jax.random.split(key, 4)
        params = {
            "wq": _uniform_init(kq, (E, H * D), E),
            "wk": _uniform_init(kk, (E, Hk * D), E),
            "wv": _uniform_init(kv, (E, Hk * D), E),
            "wo": _uniform_init(ko, (H * D, E), H * D),
        }
        if self.qk_norm == "head":
            params["q_norm"] = jnp.ones((D,))
            params["k_norm"] = jnp.ones((D,))
        elif self.qk_norm:
            params["q_norm"] = jnp.ones((H * D,))
            params["k_norm"] = jnp.ones((Hk * D,))
        if self.gate:
            # a key of its own: the four above stay what they were
            params["wg"] = _uniform_init(jax.random.fold_in(key, 4),
                                         (E, H * D), E)
        return params, {}

    def _latent_params(self, key, E, H, D):
        c, dv = self.kv_latent, self.v_head_dim or D
        kq, kd, kk, kv, ko = jax.random.split(key, 5)
        return {
            "wq": _uniform_init(kq, (E, H * D), E),
            "w_kv_down": _uniform_init(kd, (E, c + self.k_shared), E),
            "kv_norm": jnp.ones((c,)),
            "wk_up": _uniform_init(kk, (c, H * (D - self.k_shared)), c),
            "wv_up": _uniform_init(kv, (c, H * dv), c),
            "wo": _uniform_init(ko, (H * dv, E), H * dv),
        }

    def _latent_kv(self, params, xq, dt):
        """(k, v) (B, T, H, head_dim) from the latent row, v padded with
        zeros to the key's width."""
        from ..runtime.metrics import registry
        from .nn import rms_normalize
        registry().gauge(
            "vt_attn_latent", "width of the latent row K and V come from, "
            "in the attention unit's last traced call",
            labels=("unit",)).labels(unit=self.name).set(self.kv_latent)
        B, T, _ = xq.shape
        H, c = self.n_heads, self.kv_latent
        with jax.named_scope("attn_kv_down"):
            down = xq @ params["w_kv_down"].astype(dt)
            latent = rms_normalize(down[..., :c], params["kv_norm"],
                                   self.norm_eps)
            shared = down[..., c:]
        with jax.named_scope("attn_kv_up"):
            k = (latent @ params["wk_up"].astype(dt)).reshape(B, T, H, -1)
            k = jnp.concatenate(
                [k, jnp.broadcast_to(shared[:, :, None],
                                     (B, T, H, self.k_shared))], axis=-1)
            v = (latent @ params["wv_up"].astype(dt)).reshape(B, T, H, -1)
            v = jnp.pad(v, ((0, 0),) * 3 + ((0, k.shape[-1] - v.shape[-1]),))
        return k, v

    def apply(self, params, state, xs, ctx: Context):
        from ..parallel.ring_attention import (_ring_attention_local,
                                               blockwise_attention,
                                               ring_attention)
        x = xs[0]
        B, T, E = x.shape
        H = self.n_heads
        dt = self.compute_dtype or x.dtype
        xq = x.astype(dt)
        mode = ctx.collective_mode(self.seq_axis)

        if self.qk_norm:
            from ..runtime.metrics import registry
            from .nn import rms_normalize
            registry().gauge(
                "vt_attn_qk_norm",
                "1 on the kind of QK norm the attention unit's last traced "
                "call took", labels=("unit", "kind")).labels(
                    unit=self.name, kind=self.qk_norm).set(1)
        whole = self.qk_norm == "projection"

        def proj(w, nh, norm=None):
            y = xq @ w.astype(dt)
            if norm is not None:
                y = rms_normalize(y, params[norm], self.norm_eps)
            return y.reshape(B, T, nh, -1)

        q = proj(params["wq"], H, "q_norm" if whole else None)
        if self.kv_latent is None:
            k = proj(params["wk"], self.n_kv_heads,
                     "k_norm" if whole else None)
            v = proj(params["wv"], self.n_kv_heads)
        else:
            k, v = self._latent_kv(params, xq, dt)
        if self.qk_norm == "head":
            q = rms_normalize(q, params["q_norm"], self.norm_eps)
            k = rms_normalize(k, params["k_norm"], self.norm_eps)
        if self.rope:
            from ..ops import rotary_embedding
            # manual mode: x is this rank's T-shard inside an enclosing
            # shard_map (a pipeline schedule) — rotate by GLOBAL
            # positions (rank offset); elsewhere x is logically global
            off = (jax.lax.axis_index(self.seq_axis) * T
                   if mode == "manual" else 0)
            q = rotary_embedding(q, offset=off)
            k = rotary_embedding(k, offset=off)
        if mode == "manual":
            # inside the fused-1F1B / schedule shard_map: the wrapper
            # would illegally nest, but the ring body's raw ppermutes
            # over the seq axis are legal — call it directly
            o = _ring_attention_local(q, k, v, axis_name=self.seq_axis,
                                      causal=self.causal, scale=None,
                                      window=self.window)
        elif mode == "wrapper":
            o = ring_attention(q, k, v, ctx.mesh, axis_name=self.seq_axis,
                               causal=self.causal, window=self.window)
        else:
            from .. import ops
            use_flash = (ops.use_pallas_default()
                         if self._resolved_flash is None
                         else self._resolved_flash)
            attend = functools.partial(
                blockwise_attention, block_size=self.block_size,
                causal=self.causal, window=self.window,
                use_flash=use_flash, flash_blocks=self._resolved_blocks)
            if use_flash and ctx.mesh is not None \
                    and ctx.manual_axes is None:
                # the flash kernel under a GSPMD mesh: each device runs
                # it on its own batch rows (and heads, where a 'model'
                # axis tiles them) — see parallel.mesh.shard_batch
                from jax.sharding import PartitionSpec as P
                from ..parallel.mesh import batch_axes, shard_batch
                tp = ctx.axis_size("model")
                heads = "model" if tp > 1 and H % tp == 0 \
                    and self.n_kv_heads % tp == 0 else None
                spec = P(batch_axes(ctx.mesh, B), None, heads, None)
                attend = shard_batch(attend, ctx.mesh, (spec,) * 3, spec)
            o = attend(q, k, v)
        if self.v_head_dim is not None:
            o = o[..., :self.v_head_dim]
        o = o.reshape(B, T, -1)
        if self.gate:
            g = jax.nn.sigmoid((xq @ params["wg"].astype(dt))
                               .astype(jnp.float32))
            o = (o * g).astype(dt)
        y = o @ params["wo"].astype(dt)
        if self.residual:
            y = y + xq
        return y.astype(x.dtype), state


class MoEFFN(Forward):
    """Mixture-of-experts FFN over (B, T, E) or (N, E) activations.

    Expert parallelism: the expert banks shard over the ``expert`` mesh
    axis (see ``expert_rules`` below); the dispatch/combine einsums become
    all_to_all over ICI under GSPMD.  The Switch/GShard load-balance
    auxiliary loss rides the unit-state channel — Workflow._build_step sums
    ``aux_loss * aux_weight`` into the training loss automatically.
    """

    has_aux_loss = True

    def __init__(self, n_experts: int, d_hidden: int, name=None,
                 inputs=("@input",), *, top_k: int = 2,
                 capacity_factor: float = 1.25, aux_weight: float = 0.01,
                 dispatch_mode: str = "sort", expert_axis: str = "expert"):
        super().__init__(name, inputs)
        self.n_experts = int(n_experts)
        self.d_hidden = int(d_hidden)
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        self.aux_weight = float(aux_weight)
        # "sort" (scalable scatter/gather) or "dense" (one-hot einsums);
        # see parallel/moe.py module docstring
        self.dispatch_mode = dispatch_mode
        self.expert_axis = expert_axis

    def output_spec(self, in_specs):
        return in_specs[0]

    def init(self, key, in_specs):
        from ..parallel.moe import init_moe_params
        E = in_specs[0].shape[-1]
        params = init_moe_params(key, self.n_experts, E, self.d_hidden)
        return params, {"aux_loss": jnp.zeros((), jnp.float32)}

    def apply(self, params, state, xs, ctx: Context):
        from ..parallel.moe import moe_apply, moe_apply_manual
        x = xs[0]
        flat = x.reshape(-1, x.shape[-1])
        if ctx.collective_mode(self.expert_axis) == "manual":
            # inside a pipeline-schedule shard_map with tokens sharded
            # over the expert axis: explicit all_to_all dispatch to the
            # rank owning each expert (round-4 verdict #3); GSPMD cannot
            # see inside the manual body, so the exchange is hand-written
            y, aux = moe_apply_manual(
                params, flat, axis_name=self.expert_axis,
                top_k=self.top_k, capacity_factor=self.capacity_factor)
        else:
            # ordinary jit: GSPMD lowers the dispatch/combine einsums to
            # all_to_all when the expert banks are sharded; with no
            # expert axis this IS the local dense-expert formulation
            y, aux = moe_apply(params, flat, top_k=self.top_k,
                               capacity_factor=self.capacity_factor,
                               dispatch_mode=self.dispatch_mode)
        return (y.reshape(x.shape),
                {"aux_loss": aux.astype(jnp.float32)})


class RoutedExpertsFFN(Forward):
    """Dropless routed experts beside an optional shared expert, over
    (B, T, E) or (N, E) activations: ``y = shared(x) + sum over the
    top_k experts e of w_e expert_e(x)``, every expert a gated MLP
    ``Wd(act(Wg x) * (Wu x))`` of width ``d_hidden`` or, with ``gated``
    false, ``Wd act(Wu x)`` (no ``wg``: two products an expert);
    ``activation`` names ``act`` (``"silu"``; ``"relu2"`` is ReLU
    squared).  The shared expert has the same form.

    No capacity and no drops: an expert's rows are whatever the router
    gives it, zero included (``parallel/moe.routed_experts_apply``); the
    router's scores are sigmoids.  The
    router is ``n_experts`` wide; the unit holds ``experts_held`` of them,
    ``expert_offset`` onwards (all by default), and computes their part
    of the result: a route to an expert held elsewhere adds nothing here,
    which is one chip's share of a layer whose experts are divided over
    several.  On one device there is no exchange.  ``shared_width`` > 0
    adds the shared expert, which every token passes through.

    ``route_bias`` is unit state: added to the scores for the choice of
    experts only, reached by no gradient, and left as it is by the step.
    Each step's row counts ride the state's ``counters`` to the epoch's
    drain (``publish_counters``).  With the Pallas kernels (``use_pallas``;
    by the platform when None) the combine and the dispatch's gradient
    sum the buffer's rows by token and fetch the held routes' rows alone;
    gauge ``vt_moe_combine_path{unit, path="rows"|"routes"}``, set when
    traced, says which way a call went, and ``vt_moe_plan_path{unit,
    path="sorted"|"scattered"}`` whether the rows' routes came from the
    sort's order alone or the back-index was scattered as well.
    """

    def __init__(self, n_experts: int, d_hidden: int, name=None,
                 inputs=("@input",), *, top_k: int = 2,
                 experts_held: Optional[int] = None, expert_offset: int = 0,
                 route_norm: bool = True, route_scale: float = 1.0,
                 shared_width: int = 0, gated: bool = True,
                 activation: str = "silu",
                 block_rows: int = 128, compute_dtype=None,
                 use_pallas: Optional[bool] = None):
        super().__init__(name, inputs)
        self.gated, self.activation = bool(gated), activation
        self.n_experts = int(n_experts)
        self.d_hidden = int(d_hidden)
        self.top_k = int(top_k)
        self.experts_held = self.n_experts if experts_held is None \
            else int(experts_held)
        self.expert_offset = int(expert_offset)
        if not 0 <= self.expert_offset \
                <= self.n_experts - self.experts_held:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are "
                f"not among the router's {self.n_experts}")
        self.route_norm = bool(route_norm)
        self.route_scale = float(route_scale)
        self.shared_width = int(shared_width)
        self.block_rows = int(block_rows)
        self.compute_dtype = compute_dtype
        self.use_pallas = use_pallas

    def output_spec(self, in_specs):
        return in_specs[0]

    def init(self, key, in_specs):
        E, H, G = in_specs[0].shape[-1], self.d_hidden, self.experts_held
        kr, kg, ku, kd, ks = jax.random.split(key, 5)
        params = {
            "router": _uniform_init(kr, (E, self.n_experts), E),
            "wu": _uniform_init(ku, (G, E, H), E),
            "wd": _uniform_init(kd, (G, H, E), H),
        }
        if self.gated:
            params["wg"] = _uniform_init(kg, (G, E, H), E)
        if self.shared_width:
            S = self.shared_width
            k1, k2, k3 = jax.random.split(ks, 3)
            params.update(shared_wu=_uniform_init(k2, (E, S), E),
                          shared_wd=_uniform_init(k3, (S, E), S))
            if self.gated:
                params["shared_wg"] = _uniform_init(k1, (E, S), E)
        # one buffer each: the step donates its state
        state = {"route_bias": jnp.zeros((self.n_experts,), jnp.float32),
                 "counters": {k: jnp.zeros((), jnp.int32) for k in (
                     "rows_routed", "rows_computed", "experts_active",
                     "expert_rows_max")}}
        return params, state

    def apply(self, params, state, xs, ctx: Context):
        from .. import ops
        from ..parallel.moe import routed_experts_apply
        from ..runtime.metrics import registry
        from .nn import gated_mlp
        x = xs[0]
        flat = x.reshape(-1, x.shape[-1])
        use_pallas = ops.use_pallas_default() if self.use_pallas is None \
            else self.use_pallas
        self._note_path(registry().gauge(
            "vt_moe_combine_path",
            "1 on the way the routed experts' combine and dispatch "
            "gradient went when last traced: rows = the kernel that sums "
            "the buffer's rows by token; routes = a gather of every route",
            labels=("unit", "path")),
            ("rows", "routes"), "rows" if use_pallas else "routes")
        self._note_path(registry().gauge(
            "vt_moe_plan_path",
            "1 on the way the routed experts' rows were planned when last "
            "traced: sorted = each row's route gathered from the sort's "
            "order, nothing indexed by every route; scattered = the rows "
            "scattered to their routes besides, the back-index that "
            "take_rows needs", labels=("unit", "path")),
            ("sorted", "scattered"), "sorted" if use_pallas else "scattered")
        y, counters = routed_experts_apply(
            params, flat, top_k=self.top_k, n_held=self.experts_held,
            offset=self.expert_offset, bias=state["route_bias"], route_norm=self.route_norm,
            route_scale=self.route_scale, block_rows=self.block_rows,
            compute_dtype=self.compute_dtype, use_pallas=use_pallas,
            activation=self.activation)
        if self.shared_width:
            with jax.named_scope("moe_shared"):
                y = y + gated_mlp(flat, params.get("shared_wg"),
                                  params["shared_wu"], params["shared_wd"],
                                  self.activation, self.compute_dtype)
        return (y.reshape(x.shape).astype(x.dtype),
                {"route_bias": state["route_bias"], "counters": counters})

    def _note_path(self, gauge, paths, path):
        for p in paths:
            gauge.labels(unit=self.name, path=p).set(float(p == path))

    def publish_counters(self, klass: str, sums: dict, last: dict) -> None:
        """An epoch's counters of this unit, on the host after the drain
        (``Trainer``): the sums over the epoch's steps of one class, and
        the last step's."""
        from ..runtime.metrics import registry
        reg = registry()
        rows = reg.counter(
            "vt_moe_rows_total",
            "rows of routed experts: routed = routes that landed on the "
            "experts held here; computed = rows the grouped products ran, "
            "tile padding included", labels=("unit", "klass", "kind"))
        rows.labels(unit=self.name, klass=klass, kind="routed").inc(
            sums["rows_routed"])
        rows.labels(unit=self.name, klass=klass, kind="computed").inc(
            sums["rows_computed"])
        reg.counter(
            "vt_moe_active_experts_total",
            "held experts that got at least one row, summed over steps: "
            "the matrices the grouped products read",
            labels=("unit", "klass")).labels(
                unit=self.name, klass=klass).inc(sums["experts_active"])
        reg.gauge(
            "vt_moe_expert_rows_max",
            "rows of the fullest held expert in the epoch's last step",
            labels=("unit",)).labels(unit=self.name).set(
                last["expert_rows_max"])


class PipelineStack(Forward):
    """A stack of S stages pipelined over the ``pipe`` mesh axis.

    Two forms:

    * **Homogeneous (legacy)**: ``PipelineStack(n_stages, d_hidden)`` — S
      identical residual-MLP blocks, params stage-stacked ``(S, ...)`` and
      sharded ``P('pipe')``.
    * **Config stages (round-3)**: ``PipelineStack(stages=[[cfg, ...],
      ...])`` — each stage is an arbitrary layer-config sublist (e.g. an
      attention block ``[{"type": "attention", "residual": True}, {"type":
      "layer_norm"}]``), resolved through ``models.standard.LAYER_TYPES``.
      Stages may differ (the heterogeneous ravel+switch machinery of
      ``parallel/pipeline.py`` handles mixed param structures); every
      stage must PRESERVE the activation shape/dtype — that is what
      physically rides the pipeline ring.

    With pipe size 1 (or no mesh) stages run sequentially — the same
    math, so configs are portable.  Under ``Workflow.make_pipeline_
    train_step`` the stack trains on the fused 1F1B schedule; under plain
    AD it forwards on the GPipe schedule.  The batch is split into
    microbatches along axis 0; batch size must divide evenly.
    """

    def __init__(self, n_stages: Optional[int] = None,
                 d_hidden: Optional[int] = None, name=None,
                 inputs=("@input",), *, pipe_axis: str = "pipe",
                 n_microbatches: Optional[int] = None,
                 stages: Optional[Sequence[Sequence[dict]]] = None,
                 compute_dtype=None):
        super().__init__(name, inputs)
        self.pipe_axis = pipe_axis
        self.n_microbatches = n_microbatches
        self.stages_cfg = stages
        if stages is not None:
            self.n_stages = len(stages)
            self.d_hidden = None
            self._stage_units = [
                self._build_stage_units(i, cfg, compute_dtype)
                for i, cfg in enumerate(stages)]
            subs = [u for us in self._stage_units for u in us]
            # sub-unit aux losses surface through the stack's own aux
            # channel (weights already applied per sub-unit, so the
            # stack-level weight is 1); stochastic sub-units make the
            # stack itself stochastic for workflow bookkeeping
            self.has_aux_loss = any(
                getattr(u, "has_aux_loss", False) for u in subs)
            self.aux_weight = 1.0
            self.stochastic = any(
                getattr(u, "stochastic", False) for u in subs)
        else:
            if n_stages is None or d_hidden is None:
                raise ValueError(
                    "PipelineStack needs (n_stages, d_hidden) or stages=")
            self.n_stages = int(n_stages)
            self.d_hidden = int(d_hidden)
            self._stage_units = None
            self.has_aux_loss = False
            self.aux_weight = 1.0

    @staticmethod
    def _build_stage_units(i: int, cfg: Sequence[dict], compute_dtype):
        # Lazy import: models.standard imports this module at load time;
        # by the time a stack is instantiated the registry exists.
        from ..models.standard import COMPUTE_DTYPE_TYPES, LAYER_TYPES
        units = []
        for j, spec in enumerate(cfg):
            spec = dict(spec)
            ltype = spec.pop("type")
            lname = spec.pop("name", f"s{i}u{j}_{ltype}")
            # stage bodies are already rematerialized by both pipeline
            # schedules (GPipe wraps each stage in jax.checkpoint; 1F1B
            # recomputes inside the VJP), so a per-sub-unit remat flag
            # is a no-op here — accept and drop it for config symmetry
            spec.pop("remat", None)
            if "hyperparams" in spec:
                # per-layer optimizer hyperparams key on unit names; the
                # stack is ONE unit, so they cannot reach the optimizer
                # table — reject instead of silently dropping them
                raise ValueError(
                    f"per-layer 'hyperparams' on {lname!r} are not "
                    "supported inside pipeline stages (the stack is one "
                    "optimizer unit); set them on the stack's unit name")
            if compute_dtype is not None and ltype.startswith(
                    COMPUTE_DTYPE_TYPES):
                spec.setdefault("compute_dtype", compute_dtype)
            # Stochastic units (dropout) and aux-loss units (MoE) are
            # fine inside stages: both pipeline schedules thread a
            # per-microbatch key (fold_in(step_key, mb_index)) and an
            # aux-loss channel through the stage contract.
            units.append(LAYER_TYPES[ltype](name=lname, inputs=("@x",),
                                            **spec))
        return units

    def _thread_stage_specs(self, spec, visit=None):
        """Single source of truth for threading the activation spec
        through every stage sub-unit (prepare/output_spec/init all need
        this walk). ``visit(unit, in_spec)`` runs before each unit's
        output_spec advances the spec; returns per-stage final specs."""
        outs = []
        for units in self._stage_units:
            s = spec
            for u in units:
                if visit is not None:
                    visit(u, s)
                s = u.output_spec([s])
            outs.append(s)
        return outs

    def prepare(self, in_specs):
        # Composite unit: Workflow.build only calls prepare() on
        # top-level units, so the stack must propagate it to its stage
        # sub-units (an attention unit inside a stage measures its pick
        # here).
        if self._stage_units is not None:
            self._thread_stage_specs(
                in_specs[0], lambda u, s: u.prepare([s]))

    def output_spec(self, in_specs):
        if self._stage_units is not None:
            spec = in_specs[0]
            for i, s in enumerate(self._thread_stage_specs(spec)):
                if (tuple(s.shape), s.dtype) != (tuple(spec.shape),
                                                 spec.dtype):
                    raise ValueError(
                        f"pipeline stage {i} must preserve the activation "
                        f"spec {tuple(spec.shape)}/{spec.dtype} (it rides "
                        f"the ring), got {tuple(s.shape)}/{s.dtype}")
        return in_specs[0]

    def init(self, key, in_specs):
        if self._stage_units is not None:
            params = {}
            keys = jax.random.split(key, self.n_stages)
            for i, (units, k) in enumerate(zip(self._stage_units, keys)):
                spec = in_specs[0]
                sp, uks = {}, jax.random.split(k, max(len(units), 1))
                for u, uk in zip(units, uks):
                    p, s = u.init(uk, [spec])
                    # an aux-loss channel is a per-step OUTPUT, not
                    # persistent state — it rides the stack's own aux
                    # accumulator, so it needs no stage state
                    if s and set(s) - {"aux_loss"}:
                        raise ValueError(
                            f"stateful unit {u.name!r} inside a pipeline "
                            "stage is unsupported (stage state does not "
                            "ride the ring)")
                    if p:
                        sp[u.name] = p
                    spec = u.output_spec([spec])
                params[f"s{i}"] = sp
            state = ({"aux_loss": jnp.zeros((), jnp.float32)}
                     if self.has_aux_loss else {})
            return params, state
        E = in_specs[0].shape[-1]
        H = self.d_hidden
        keys = jax.random.split(key, self.n_stages)

        def one(k):
            k1, k2 = jax.random.split(k)
            return {"w1": _uniform_init(k1, (E, H), E),
                    "w2": _uniform_init(k2, (H, E), H)}

        from ..parallel.pipeline import stack_stage_params
        stacked = stack_stage_params([one(k) for k in keys])
        # flat per-unit param dict (optimizer contract); the leading axis
        # of each stage_* array is the stage axis sharded over 'pipe'
        return {"stage_w1": stacked["w1"], "stage_w2": stacked["w2"]}, {}

    @staticmethod
    def _stage_fn(p, x):
        return x + jax.nn.relu(x @ p["w1"]) @ p["w2"]

    # -- per-stage access (the fused-1F1B compiler's contract,
    # parallel/pipeline_compile.py) ---------------------------------------
    def stage_param_slice(self, params, i: int):
        """Stage i's param pytree, as stage_apply(i, ...) consumes it."""
        if self._stage_units is not None:
            return params[f"s{i}"]
        return {"w1": params["stage_w1"][i], "w2": params["stage_w2"][i]}

    def restack_stage_grads(self, glist):
        """Inverse of stage_param_slice over a list of per-stage grads."""
        if self._stage_units is not None:
            return {f"s{i}": g for i, g in enumerate(glist)}
        return {"stage_w1": jnp.stack([g["w1"] for g in glist]),
                "stage_w2": jnp.stack([g["w2"] for g in glist])}

    def stage_apply(self, i: int, p, x, ctx: Context):
        """Apply stage i's computation to one activation block."""
        return self.stage_apply_aux(i, p, x, ctx)[0]

    def stage_apply_aux(self, i: int, p, x, ctx: Context):
        """Stage i on one activation block -> ``(y, aux)`` where ``aux``
        is the weighted sum of the stage's unit aux losses (MoE load
        balance) — the fused-1F1B compiler's stage contract."""
        aux = jnp.zeros((), jnp.float32)
        if self._stage_units is not None:
            for u in self._stage_units[i]:
                x, st = u.apply(p.get(u.name, {}), {}, [x], ctx)
                if getattr(u, "has_aux_loss", False):
                    aux = aux + u.aux_weight * st["aux_loss"]
            return x, aux
        return self._stage_fn(p, x), aux

    def _inner_ctx(self, ctx: Context) -> Context:
        # Stage bodies execute inside pipeline_apply's shard_map; a unit
        # starting its own collective there (ring attention reading
        # ctx.mesh) would illegally nest shard_maps — so stage units see
        # mesh=None and use their local formulations.
        return Context(train=ctx.train, key=ctx.key, mesh=None)

    def apply(self, params, state, xs, ctx: Context):
        x = xs[0]
        S = ctx.axis_size(self.pipe_axis)
        n_mb = self.n_microbatches or S
        if S > 1 and S != self.n_stages:
            if self.n_stages % S == 0 and not ctx.train:
                # interleaved fused training (n_stages = v·S virtual
                # chunks): the GPipe forward has no interleaved
                # schedule, so EVAL/PREDICT run the numerically
                # identical sequential form (GSPMD still shards the
                # batch over the data axes).  At TRAIN time a mismatch
                # stays an error — silently idling the pipe axis would
                # be a large hidden perf cliff.
                S = 1
            else:
                raise ValueError(
                    f"PipelineStack has {self.n_stages} stages but the "
                    f"{self.pipe_axis!r} mesh axis is {S}"
                    + (" (interleaved stacks train via "
                       "pipeline_microbatches + pipeline_interleave)"
                       if self.n_stages % S == 0 else ""))
        if S > 1:
            if x.shape[0] % n_mb and ctx.train:
                # At eval/predict an indivisible batch (single-sample
                # serving) falls through to the numerically identical
                # sequential path below; during TRAINING it is a config
                # error — silently idling the whole pipe axis would be a
                # large hidden perf cliff.
                raise ValueError(
                    f"batch {x.shape[0]} not divisible into {n_mb} "
                    "microbatches")
        rich = self.has_aux_loss or getattr(self, "stochastic", False)
        if S > 1 and x.shape[0] % n_mb == 0:
            from ..parallel.pipeline import pick_batch_axes, pipeline_apply
            B = x.shape[0]
            xm = x.reshape((n_mb, B // n_mb) + x.shape[1:])
            dp = pick_batch_axes(
                {a: ctx.axis_size(a) for a in ("data", "fsdp")}, B // n_mb)
            if self._stage_units is not None and rich:
                # keyed schedule: per-microbatch keys fold_in(step_key,
                # mb) — identical to the fused 1F1B derivation, so both
                # schedules draw the same dropout masks — and sub-unit
                # aux losses return through the stack's aux channel
                rng = ctx.key if ctx.key is not None else jax.random.key(0)
                fns = [(lambda p, x, k, _i=i: self.stage_apply_aux(
                            _i, p, x,
                            Context(train=ctx.train, key=k, mesh=None)))
                       for i in range(self.n_stages)]
                plist = [params[f"s{i}"] for i in range(self.n_stages)]
                y, aux = pipeline_apply(fns, plist, xm, ctx.mesh,
                                        axis_name=self.pipe_axis,
                                        batch_axes=tuple(dp), rng=rng)
                return y.reshape(x.shape), (
                    {"aux_loss": aux} if self.has_aux_loss else state)
            if self._stage_units is not None:
                ictx = self._inner_ctx(ctx)
                fns = [(lambda p, x, _i=i: self.stage_apply(_i, p, x, ictx))
                       for i in range(self.n_stages)]
                plist = [params[f"s{i}"] for i in range(self.n_stages)]
                y = pipeline_apply(fns, plist, xm, ctx.mesh,
                                   axis_name=self.pipe_axis,
                                   batch_axes=tuple(dp))
            else:
                stages = {"w1": params["stage_w1"],
                          "w2": params["stage_w2"]}
                y = pipeline_apply(self._stage_fn, stages, xm, ctx.mesh,
                                   axis_name=self.pipe_axis,
                                   batch_axes=tuple(dp))
            return y.reshape(x.shape), state
        if self._stage_units is not None:
            aux_t = jnp.zeros((), jnp.float32)
            for i in range(self.n_stages):
                x, a = self.stage_apply_aux(i, params[f"s{i}"], x, ctx)
                aux_t = aux_t + a
            return x, ({"aux_loss": aux_t} if self.has_aux_loss else state)
        stages = {"w1": params["stage_w1"], "w2": params["stage_w2"]}

        # sequential fallback: scan over the stage axis
        def body(h, p):
            return self._stage_fn(p, h), None

        y, _ = jax.lax.scan(body, x, stages)
        return y, state


def expert_rules(axis: str = "expert"):
    """Sharding rule for MoEFFN params: expert banks split on the expert
    axis, router replicated (compose with other rules via
    parallel.mesh.compose_rules)."""
    from jax.sharding import PartitionSpec as P

    def rule(path, spec):
        if len(path) >= 2 and path[-1] in ("w1", "w2") \
                and spec.ndim == 3:
            return P(axis)
        return P()

    return rule


def pipeline_rules(axis: str = "pipe"):
    """Sharding rule for PipelineStack params: stage axis over 'pipe'."""
    from jax.sharding import PartitionSpec as P

    def rule(path, spec):
        if path and path[-1].startswith("stage_"):
            return P(axis, *([None] * (spec.ndim - 1)))
        return P()

    return rule
