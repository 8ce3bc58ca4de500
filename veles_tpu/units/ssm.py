"""State-space mixer units.

``Mamba2Mixer``: the selective state-space block of Dao and Gu 2024 as
the hybrid decoders of 2025 ship it (Hugging Face ``transformers``
``modeling_nemotron_h.py`` / ``modeling_mamba2.py``, the "torch forward"
without its kernels): one input projection to a gate, a convolved stream
and the steps; a depthwise causal convolution; the recurrence as a
chunked scan (``ops/ssd.py``); a gated RMS norm by groups; the output
projection.  No reference counterpart (SURVEY.md section 5.7: the
reference has no sequence models in core).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import ops
from ..ops import ssd as ssd_ops
from .base import Context, Forward
from .nn import _cast_policy


def causal_depthwise_conv(x, w, b=None):
    """``y[t] = sum_k w[k] * x[t - (K - 1) + k] + b`` a channel, over (B,
    T, C) with ``w`` (K, C): token t sees itself and the K - 1 before it,
    zeros before the first."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, i:i + t] * w[i] for i in range(k))
    return y if b is None else y + b


def gated_group_rms_norm(y, z, scale, n_groups: int, eps: float):
    """``RMS(y * silu(z))`` over each of ``n_groups`` groups of the
    trailing axis, times ``scale``; float32."""
    h = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = h.reshape(h.shape[:-1] + (n_groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return grouped.reshape(h.shape) * scale


class Mamba2Mixer(Forward):
    """Mamba-2 mixer over (B, T, E): ``n_heads`` heads of ``head_dim``
    channels (inner width their product), ``n_groups`` groups that share
    ``B_t`` and ``C_t`` of ``state_size``::

        [z | xBC | dt] = x W_in          (inner + inner + 2 G N + H wide)
        xBC = silu(conv(xBC) + conv_b)   depthwise, causal, conv_kernel
        [x' | B | C] = xBC;  dt = softplus(dt + dt_bias + dt_origin)
        S_t = exp(dt_t A) S_{t-1} + dt_t x'_t B_t^T;  A = -exp(A_log)
        y_t = S_t C_t + D x'_t           a head, with its group's B, C
        out = (RMS_grouped(y * silu(z)) * gate_norm) W_out

    T has to be a multiple of ``chunk`` (no padding is guessed: a state
    that runs on over padding is another sequence).  ``dt_origin`` moves
    the zero of ``dt_bias``: ``dt_bias = 0`` then gives the step
    ``softplus(dt_origin)`` (a checkpoint's bias loads as ``dt_bias -
    dt_origin``)."""

    def __init__(self, n_heads: int, head_dim: int, n_groups: int,
                 state_size: int, name=None, inputs=("@input",), *,
                 conv_kernel: int = 4, chunk: int = 128,
                 norm_eps: float = 1e-5, dt_origin: float = 0.0,
                 compute_dtype=None):
        super().__init__(name, inputs)
        self.n_heads, self.head_dim = int(n_heads), int(head_dim)
        self.n_groups, self.state_size = int(n_groups), int(state_size)
        if self.n_heads % self.n_groups:
            raise ValueError(f"{self.n_heads} heads do not divide into "
                             f"{self.n_groups} groups")
        self.conv_kernel, self.chunk = int(conv_kernel), int(chunk)
        self.norm_eps, self.dt_origin = float(norm_eps), float(dt_origin)
        self.compute_dtype = _cast_policy(compute_dtype)

    @property
    def inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.n_groups * self.state_size

    def output_spec(self, in_specs):
        t = in_specs[0].shape[-2]
        if t % self.chunk:
            raise ValueError(
                f"{self.name}: T = {t} is no multiple of the scan's chunk "
                f"{self.chunk}")
        return in_specs[0]

    def init(self, key, in_specs):
        e, h = in_specs[0].shape[-1], self.n_heads
        k_in, k_conv, k_dt, k_a, k_out = jax.random.split(key, 5)
        # steps log-uniform in [0.001, 0.1], decays uniform in [1, 16]: the
        # published initialisation
        dt = jnp.exp(jax.random.uniform(
            k_dt, (h,), minval=math.log(1e-3), maxval=math.log(0.1)))
        return {
            "w_in": ops.smart_uniform_init(
                k_in, (e, self.inner + self.conv_dim + h), e),
            "conv_w": ops.smart_uniform_init(
                k_conv, (self.conv_kernel, self.conv_dim), self.conv_kernel),
            "conv_b": jnp.zeros((self.conv_dim,)),
            "dt_bias": jnp.log(jnp.expm1(dt)) - self.dt_origin,
            "A_log": jnp.log(jax.random.uniform(k_a, (h,), minval=1.0,
                                                maxval=16.0)),
            "D": jnp.ones((h,)),
            "gate_norm": jnp.ones((self.inner,)),
            "w_out": ops.smart_uniform_init(k_out, (self.inner, e),
                                            self.inner),
        }, {}

    def apply(self, params, state, xs, ctx: Context):
        x = xs[0]
        b, t, _ = x.shape
        h, p, g, n = (self.n_heads, self.head_dim, self.n_groups,
                      self.state_size)
        from ..runtime.metrics import registry
        registry().gauge(
            "vt_ssd_chunks",
            "chunks a sequence of the scan's last traced call",
            labels=("unit",)).labels(unit=self.name).set(t // self.chunk)
        with jax.named_scope("ssm_in_proj"):
            zxbcdt = ops.dense(x, params["w_in"],
                               compute_dtype=self.compute_dtype)
        z, xbc, dt = jnp.split(
            zxbcdt, [self.inner, self.inner + self.conv_dim], axis=-1)
        with jax.named_scope("ssm_conv"):
            xbc = jax.nn.silu(causal_depthwise_conv(
                xbc, params["conv_w"], params["conv_b"]))
        # in the products' dtype already: the scan keeps its inputs for
        # its backward, and in float32 they are twice the bytes
        xs_, bs, cs = jnp.split(
            xbc.astype(self.compute_dtype or xbc.dtype),
            [self.inner, self.inner + g * n], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"]
                             + self.dt_origin)
        with jax.named_scope("ssm_scan"):
            y = ssd_ops.ssd(
                xs_.reshape(b, t, h, p), dt, -jnp.exp(params["A_log"]),
                bs.reshape(b, t, g, n), cs.reshape(b, t, g, n), params["D"],
                self.chunk, self.compute_dtype)
        with jax.named_scope("ssm_gate_norm"):
            y = gated_group_rms_norm(y.reshape(b, t, self.inner), z,
                                     params["gate_norm"], g, self.norm_eps)
        with jax.named_scope("ssm_out_proj"):
            out = ops.dense(y, params["w_out"],
                            compute_dtype=self.compute_dtype)
        return out.astype(x.dtype), state
