"""State-space mixer units.

``Mamba2Mixer``: the selective state-space block of Dao and Gu 2024 as
the hybrid decoders of 2025 ship it (Hugging Face ``transformers``
``modeling_nemotron_h.py`` / ``modeling_mamba2.py``, the "torch forward"
without its kernels): one input projection to a gate, a convolved stream
and the steps; a depthwise causal convolution; the recurrence as a
chunked scan (``ops/ssd.py``); a gated RMS norm by groups; the output
projection.  No reference counterpart (SURVEY.md section 5.7: the
reference has no sequence models in core).

The two elementwise stages, ``causal_conv_silu`` and
``gated_group_rms_norm``, are differentiated by hand
(``jax.custom_vjp``, in ``jax.numpy``; PERF.md section 6, PR 39).
Autodiff's transpose of the convolution's tap sum writes K padded
arrays of the activation's size and reads them back to add them; the
written one is the same tap sum mirrored in time.  The norm works a
group at a time on slices of the channel axis, forward and backward:
XLA lays a B = 1 stream out with T in the lanes, where a (T, G)
statistic broadcast to (T, G, C / G) is an array it writes out, and a
slice's (T, 1) statistic is not.  Both keep their inputs alone for the
backward.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .. import ops
from ..ops import ssd as ssd_ops
from .base import Context, Forward
from .nn import _cast_policy


def _taps(x, k, ahead=False):
    """The K shifted reads of (B, T, C) that tap ``i`` of a causal
    convolution multiplies: ``x[t - (K - 1) + i]``, zeros before the first
    token; ``ahead`` mirrors them in time, ``x[t + (K - 1) - i]`` with
    zeros past the last.  Slices of one padded array, which XLA fuses
    into the pass that reads them."""
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (0, k - 1) if ahead else (k - 1, 0), (0, 0)))
    return [padded[:, j:j + t]
            for j in (range(k - 1, -1, -1) if ahead else range(k))]


def _tap_sum(x, w, ahead=False):
    """``sum_k w[k] * x[t - (K - 1) + k]`` over (B, T, C) with ``w`` (K,
    C); ``ahead``: ``sum_k w[k] * x[t + (K - 1) - k]``."""
    return sum(a * w[i] for i, a in enumerate(_taps(x, w.shape[0], ahead)))


def _silu_slope(x):
    """``d silu(x) / dx``."""
    s = jax.nn.sigmoid(x)
    return s * (1.0 + x * (1.0 - s))


@jax.custom_vjp
def causal_conv_silu(x, w, b=None):
    """``silu(sum_k w[k] * x[t - (K - 1) + k] + b)`` a channel, over (B,
    T, C) with ``w`` (K, C): a depthwise convolution in which token t sees
    itself and the K - 1 before it, zeros before the first, and the
    ``silu`` both mixers put behind it.

    The backward is written, not derived: autodiff transposes each tap's
    slice to a pad and writes K arrays of the activation's size before it
    adds them.  Kept are ``x``, ``w`` and ``b``; with ``d = dy *
    silu'(pre)`` (``pre``, the sum, computed again), ``dx[t] = sum_k w[k]
    * d[t + (K - 1) - k]`` is the forward's own sum mirrored in time, one
    pass over ``d``, and ``dw``, ``db`` are reductions in the pass that
    makes ``d``."""
    pre = _tap_sum(x, w)
    return jax.nn.silu(pre if b is None else pre + b)


def _causal_conv_silu_fwd(x, w, b):
    return causal_conv_silu(x, w, b), (x, w, b)


def _causal_conv_silu_bwd(kept, dy):
    x, w, b = kept
    with jax.named_scope("ssm_conv_bwd"):
        pre = _tap_sum(x, w)
        d = dy.astype(jnp.float32) * _silu_slope(
            pre if b is None else pre + b)
        dw = jnp.stack([jnp.sum(d * a, axis=(0, 1))
                        for a in _taps(x, w.shape[0])])
        db = None if b is None else jnp.sum(d, axis=(0, 1)).astype(b.dtype)
        dx = _tap_sum(d, w, ahead=True)
    return dx.astype(x.dtype), dw.astype(w.dtype), db


causal_conv_silu.defvjp(_causal_conv_silu_fwd, _causal_conv_silu_bwd)


def _gated_groups(y, z, n_groups):
    """``y`` and ``z`` in float32, a group of the trailing axis at a time,
    behind the group's slice.  By slices and not by a reshape to (..., G,
    C / G): with T in the lanes (XLA's layout where B = 1) a (T, G)
    statistic broadcast to (T, G, C / G) and reshaped back is an array
    XLA writes out; a slice's (T, 1) statistic stays inside the fusion
    that reads it."""
    size, rest = divmod(y.shape[-1], n_groups)
    if rest:
        raise ValueError(f"{y.shape[-1]} channels do not divide into "
                         f"{n_groups} groups")
    for i in range(n_groups):
        at = slice(i * size, (i + 1) * size)
        yield at, y[..., at].astype(jnp.float32), \
            z[..., at].astype(jnp.float32)


def _rms_scale(h, eps):
    return jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gated_group_rms_norm(y, z, scale, n_groups: int, eps: float):
    """``RMS(y * silu(z))`` over each of ``n_groups`` groups of the
    trailing axis, times ``scale``; float32.

    The backward is written, not derived.  Kept are ``y``, ``z`` and
    ``scale``; with ``h = y silu(z)`` and ``r = rsqrt(mean(h^2) + eps)``
    computed again and ``g = dout * scale``: ``dh = r (g - h r^2 mean(g
    h))`` a group, ``dy = dh silu(z)``, ``dz = dh y silu'(z)``,
    ``dscale = sum_t dout h r``: a pass for the group's two means and
    one for the three gradients."""
    out = []
    for at, yg, zg in _gated_groups(y, z, n_groups):
        h = yg * jax.nn.silu(zg)
        out.append(h * _rms_scale(h, eps) * scale[at])
    return jnp.concatenate(out, axis=-1)


def _gated_group_rms_norm_fwd(y, z, scale, n_groups, eps):
    return gated_group_rms_norm(y, z, scale, n_groups, eps), (y, z, scale)


def _gated_group_rms_norm_bwd(n_groups, eps, kept, dout):
    y, z, scale = kept
    dy, dz, dscale = [], [], []
    with jax.named_scope("ssm_gate_norm_bwd"):
        for at, yg, zg in _gated_groups(y, z, n_groups):
            gate = jax.nn.silu(zg)
            h = yg * gate
            r = _rms_scale(h, eps)
            do = dout[..., at].astype(jnp.float32)
            g = do * scale[at]
            dh = r * (g - h * (r * r * jnp.mean(g * h, axis=-1,
                                                keepdims=True)))
            dy.append(dh * gate)
            dz.append(dh * yg * _silu_slope(zg))
            dscale.append(jnp.sum(do * h * r, axis=tuple(range(h.ndim - 1))))
        dy, dz, dscale = (jnp.concatenate(a, axis=-1)
                          for a in (dy, dz, dscale))
    return dy.astype(y.dtype), dz.astype(z.dtype), dscale.astype(scale.dtype)


gated_group_rms_norm.defvjp(_gated_group_rms_norm_fwd,
                            _gated_group_rms_norm_bwd)


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
        written = registry().gauge(
            "vt_ssm_backward_path",
            "1 on the way a stage of the mixer's last traced call is "
            "differentiated: written = its backward pass is written by hand",
            labels=("unit", "stage", "path"))
        for stage in ("conv", "gate_norm"):
            written.labels(unit=self.name, stage=stage, path="written").set(1)
        with jax.named_scope("ssm_in_proj"):
            zxbcdt = ops.dense(x, params["w_in"],
                               compute_dtype=self.compute_dtype)
        z, xbc, dt = jnp.split(
            zxbcdt, [self.inner, self.inner + self.conv_dim], axis=-1)
        with jax.named_scope("ssm_conv"):
            xbc = causal_conv_silu(xbc, params["conv_w"], params["conv_b"])
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
