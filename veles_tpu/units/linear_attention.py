"""Linear-attention mixer units.

``GatedDeltaNet``: the gated delta-rule mixer of Yang, Kautz and
Hatamizadeh 2025 as the hybrid decoders of 2025-26 ship it (Hugging Face
``transformers`` ``modeling_qwen3_next.py`` ``Qwen3NextGatedDeltaNet``,
the "torch" path without its kernels): projections to a query, a key, a
value, a gate, a step and a decay; depthwise causal convolutions; the
recurrence as a chunked program (``ops/gated_delta.py``); a gated RMS
norm a head; the output projection.

``KimiDeltaAttention``: the same rule with a decay by channel (Kimi
Linear's "KDA"), its decay and its output gate each from a low-rank pair
of projections.

No reference counterpart (SURVEY.md section 5.7: the reference has no
sequence models in core).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import ops
from ..ops import gated_delta as gated_delta_ops
from .base import Context, Forward
from .nn import _cast_policy, rms_normalize
from .ssm import causal_conv_silu


def l2_normalize(x, eps: float = 1e-6):
    """``x rsqrt(sum x^2 + eps)`` over the trailing axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _whole_chunks(unit, in_specs):
    """A delta-rule mixer's output spec: its input's, T a multiple of the
    chunk (no padding is guessed: a state that runs on over padding is
    another sequence)."""
    t = in_specs[0].shape[-2]
    if t % unit.chunk:
        raise ValueError(
            f"{unit.name}: T = {t} is no multiple of the delta rule's "
            f"chunk {unit.chunk}")
    return in_specs[0]


def _note_rule(unit, kind):
    """Gauges ``vt_delta_gate`` and ``vt_delta_solve_block``, set when a
    delta-rule mixer's call is traced: which decay it takes, and the
    rows of the blocks its triangular inverse is taken in."""
    from ..runtime.metrics import registry
    registry().gauge(
        "vt_delta_gate", "1 on the decay the delta rule's last traced call "
        "took: one a head, or by channel", labels=("unit", "kind")).labels(
            unit=unit.name, kind=kind).set(1)
    registry().gauge(
        "vt_delta_solve_block", "rows of the diagonal blocks the delta "
        "rule's last traced call took its triangular inverse in",
        labels=("unit",)).labels(unit=unit.name).set(
            gated_delta_ops.solve_block(unit.chunk))


class GatedDeltaNet(Forward):
    """Gated delta-rule mixer over (B, T, E): ``n_heads`` heads, each
    with a key and a query of ``key_dim`` channels and a value of
    ``value_dim``::

        q = silu(conv_q(x Wq)), k = silu(conv_k(x Wk)), v = silu(conv_v(x Wv))
        z = x Wz;  beta = sigmoid(x Wb)  (x 2 with allow_neg_eigval)
        g = -exp(A_log) softplus(x Wa + dt_bias + dt_origin)
        q = l2norm(q) / sqrt(key_dim),  k = l2norm(k)        a head
        S_t = exp(g_t) S_{t-1}
        S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T;  o_t = S_t^T q_t
        out = ((RMS(o) * gate_norm) * silu(z)) Wo            RMS a head

    ``n_heads`` is the number of heads held: a chip that holds a share of
    a layer's heads computes their part of ``Wo``'s sum, and the unit has
    no notion of the others.  T has to be a multiple of ``chunk`` (no
    padding is guessed: a state that runs on over padding is another
    sequence).  ``dt_origin`` moves the zero of ``dt_bias`` as
    ``Mamba2Mixer``'s does (a checkpoint's bias loads as ``dt_bias -
    dt_origin``)."""

    def __init__(self, n_heads: int, key_dim: int, value_dim: int,
                 name=None, inputs=("@input",), *, conv_kernel: int = 4,
                 chunk: int = 64, norm_eps: float = 1e-6,
                 allow_neg_eigval: bool = False, dt_origin: float = 0.0,
                 compute_dtype=None):
        super().__init__(name, inputs)
        self.n_heads = int(n_heads)
        self.key_dim, self.value_dim = int(key_dim), int(value_dim)
        self.conv_kernel, self.chunk = int(conv_kernel), int(chunk)
        self.norm_eps, self.dt_origin = float(norm_eps), float(dt_origin)
        self.allow_neg_eigval = bool(allow_neg_eigval)
        self.compute_dtype = _cast_policy(compute_dtype)

    def output_spec(self, in_specs):
        return _whole_chunks(self, in_specs)

    def init(self, key, in_specs):
        e, h = in_specs[0].shape[-1], self.n_heads
        keys = h * self.key_dim
        values = h * self.value_dim
        k = jax.random.split(key, 11)
        matrix = lambda key, n: ops.smart_uniform_init(key, (e, n), e)
        taps = lambda key, n: ops.smart_uniform_init(
            key, (self.conv_kernel, n), self.conv_kernel)
        # decays uniform in (0, 16) (the lower end held off zero, whose
        # log is no number), the steps' bias one: the published
        # initialisation
        return {
            "wq": matrix(k[0], keys), "wk": matrix(k[1], keys),
            "wv": matrix(k[2], values), "wz": matrix(k[3], values),
            "wb": matrix(k[4], h), "wa": matrix(k[5], h),
            "conv_q": taps(k[6], keys), "conv_k": taps(k[7], keys),
            "conv_v": taps(k[8], values),
            "A_log": jnp.log(jax.random.uniform(k[9], (h,), minval=1e-3,
                                                maxval=16.0)),
            "dt_bias": jnp.ones((h,)) - self.dt_origin,
            "gate_norm": jnp.ones((self.value_dim,)),
            "wo": ops.smart_uniform_init(k[10], (values, e), values),
        }, {}

    def apply(self, params, state, xs, ctx: Context):
        x = xs[0]
        b, t, _ = x.shape
        h, dk, dv = self.n_heads, self.key_dim, self.value_dim
        dtype = self.compute_dtype
        from ..runtime.metrics import registry
        registry().gauge(
            "vt_gdn_chunks",
            "chunks a sequence of the delta rule's last traced call",
            labels=("unit",)).labels(unit=self.name).set(t // self.chunk)
        _note_rule(self, "head")
        registry().gauge(
            "vt_gdn_heads", "heads the delta rule's last traced call held",
            labels=("unit",)).labels(unit=self.name).set(h)
        with jax.named_scope("gdn_in_proj"):
            q, k, v, z, wb, wa = (
                ops.dense(x, params[w], compute_dtype=dtype)
                for w in ("wq", "wk", "wv", "wz", "wb", "wa"))
        with jax.named_scope("gdn_conv"):
            q, k, v = (causal_conv_silu(a, params[w])
                       for a, w in ((q, "conv_q"), (k, "conv_k"),
                                    (v, "conv_v")))
        beta = jax.nn.sigmoid(wb.astype(jnp.float32))
        if self.allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(params["A_log"]) * jax.nn.softplus(
            wa.astype(jnp.float32) + params["dt_bias"] + self.dt_origin)
        with jax.named_scope("gdn_scan"):
            # in the products' dtype already: the chunked program keeps its
            # inputs for its backward, and in float32 they are twice the
            # bytes
            to = lambda a: a.astype(dtype or a.dtype)
            o = gated_delta_ops.gated_delta(
                to(l2_normalize(q.reshape(b, t, h, dk)) * dk ** -0.5),
                to(l2_normalize(k.reshape(b, t, h, dk))),
                to(v.reshape(b, t, h, dv)), g, beta, self.chunk, dtype)
        with jax.named_scope("gdn_gate_norm"):
            y = rms_normalize(o, params["gate_norm"], self.norm_eps) \
                * jax.nn.silu(z.astype(jnp.float32).reshape(b, t, h, dv))
        with jax.named_scope("gdn_out_proj"):
            out = ops.dense(y.reshape(b, t, h * dv), params["wo"],
                            compute_dtype=dtype)
        return out.astype(x.dtype), state


class KimiDeltaAttention(Forward):
    """Kimi delta attention (Kimi Linear, Moonshot AI 2025, arXiv
    2510.26692; Hugging Face ``moonshotai/Kimi-Linear-48B-A3B-Instruct``
    ``modeling_kimi.py`` ``KimiDeltaAttention``) over (B, T, E):
    ``n_heads`` heads of ``head_dim`` channels for the key, the query and
    the value alike::

        q = silu(conv_q(x Wq)), k = silu(conv_k(x Wk)), v = silu(conv_v(x Wv))
        q = l2norm(q) / sqrt(head_dim), k = l2norm(k)          a head
        g = -exp(A_log[h]) softplus((x Wf_a) Wf_b + dt_bias + dt_origin)
                                                  (T, H, head_dim), <= 0
        beta = sigmoid(x Wb)                                    (T, H)
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
        out = (RMS(o) * o_norm * sigmoid((x Wg_a) Wg_b)) Wo    RMS a head

    The decay and the output gate come through a rank of ``head_dim``
    (``Wf_a``, ``Wg_a``: E x head_dim); no projection has a bias.
    ``A_log`` is one a head, ``dt_bias`` one a channel.  ``n_heads`` is
    the number held, as ``GatedDeltaNet``'s; T a multiple of ``chunk``;
    ``dt_origin`` moves the zero of ``dt_bias`` as there."""

    def __init__(self, n_heads: int, head_dim: int, name=None,
                 inputs=("@input",), *, conv_kernel: int = 4,
                 chunk: int = 64, norm_eps: float = 1e-5,
                 dt_origin: float = 0.0, compute_dtype=None):
        super().__init__(name, inputs)
        self.n_heads, self.head_dim = int(n_heads), int(head_dim)
        self.conv_kernel, self.chunk = int(conv_kernel), int(chunk)
        self.norm_eps, self.dt_origin = float(norm_eps), float(dt_origin)
        self.compute_dtype = _cast_policy(compute_dtype)

    def output_spec(self, in_specs):
        return _whole_chunks(self, in_specs)

    def init(self, key, in_specs):
        e, h, d = in_specs[0].shape[-1], self.n_heads, self.head_dim
        width = h * d
        k = jax.random.split(key, 13)
        matrix = lambda key, m, n: ops.smart_uniform_init(key, (m, n), m)
        taps = lambda key: ops.smart_uniform_init(
            key, (self.conv_kernel, width), self.conv_kernel)
        # decays uniform in (1, 16), the published initialisation; the
        # step's bias at its origin
        return {
            "wq": matrix(k[0], e, width), "wk": matrix(k[1], e, width),
            "wv": matrix(k[2], e, width),
            "conv_q": taps(k[3]), "conv_k": taps(k[4]), "conv_v": taps(k[5]),
            "wf_a": matrix(k[6], e, d), "wf_b": matrix(k[7], d, width),
            "A_log": jnp.log(jax.random.uniform(k[8], (h,), minval=1.0,
                                                maxval=16.0)),
            "dt_bias": jnp.zeros((width,)),
            "wb": matrix(k[9], e, h),
            "wg_a": matrix(k[10], e, d), "wg_b": matrix(k[11], d, width),
            "o_norm": jnp.ones((d,)),
            "wo": matrix(k[12], width, e),
        }, {}

    def _streams(self, q, k, v, conv_q, conv_k, conv_v):
        """The convolutions and the normalisation a head: q, k, v (B, T, H,
        head_dim) as the rule takes them, in the products' dtype (the rule
        keeps its inputs for its backward, and in float32 they are twice
        the bytes)."""
        b, t, _ = q.shape
        h, d = self.n_heads, self.head_dim
        q, k, v = (causal_conv_silu(a, w).reshape(b, t, h, d)
                   for a, w in ((q, conv_q), (k, conv_k), (v, conv_v)))
        to = lambda a: a.astype(self.compute_dtype or a.dtype)
        return to(l2_normalize(q) * d ** -0.5), to(l2_normalize(k)), to(v)

    def apply(self, params, state, xs, ctx: Context):
        x = xs[0]
        b, t, _ = x.shape
        h, d = self.n_heads, self.head_dim
        dtype = self.compute_dtype
        from ..runtime.metrics import registry
        registry().gauge(
            "vt_kda_chunks",
            "chunks a sequence of the delta rule's last traced call",
            labels=("unit",)).labels(unit=self.name).set(t // self.chunk)
        _note_rule(self, "channel")
        dense = lambda a, w: ops.dense(a, params[w], compute_dtype=dtype)
        heads = lambda a: a.reshape(b, t, h, d)
        with jax.named_scope("kda_in_proj"):
            q, k, v = (dense(x, w) for w in ("wq", "wk", "wv"))
        # the elementwise stages go again in the backward from their
        # products' outputs (jax.checkpoint), not kept as (T, H d) float32
        # intermediates: 0.9 GB a step at the published widths
        with jax.named_scope("kda_conv"):
            q, k, v = jax.checkpoint(self._streams)(
                q, k, v, params["conv_q"], params["conv_k"],
                params["conv_v"])
        with jax.named_scope("kda_gate"):
            f = dense(dense(x, "wf_a"), "wf_b")
            g = jax.checkpoint(lambda f, a_log, bias: -jnp.exp(a_log)[
                :, None] * jax.nn.softplus(heads(
                    f.astype(jnp.float32) + bias + self.dt_origin)))(
                        f, params["A_log"], params["dt_bias"])
            beta = jax.nn.sigmoid(dense(x, "wb").astype(jnp.float32))
        with jax.named_scope("kda_scan"):
            o = gated_delta_ops.gated_delta(q, k, v, g, beta, self.chunk,
                                            dtype)
        with jax.named_scope("kda_gate_norm"):
            z = dense(dense(x, "wg_a"), "wg_b")
            y = jax.checkpoint(lambda o, z, scale: rms_normalize(
                o, scale, self.norm_eps) * jax.nn.sigmoid(heads(
                    z.astype(jnp.float32))))(o, z, params["o_norm"])
        with jax.named_scope("kda_out_proj"):
            out = ops.dense(y.reshape(b, t, h * d), params["wo"],
                            compute_dtype=dtype)
        return out.astype(x.dtype), state
