"""Local response normalization across channels (reference Znicz LRN,
docs manualrst_veles_algorithms.rst:31-60; AlexNet-style).

y = x / (k + alpha/n * sum_{j in window} x_j^2)^beta over the channel axis.

The channel-window sum is ONE formulation, ``band``: a (C, C) 0/1
band-matrix matmul on the MXU at >= HIGH precision, with a
``reduce_window`` guard above ``_BAND_MATMUL_MAX_C`` channels (a naive
windowed reduction over the minor (lane) axis is the VPU's worst case).
It won every measurement against a bf16-operand band and an f32 cumsum
difference (TPU v5 lite, forward + backward, ms a call): 3.15 / 8.72 /
13.63 at 512 x 55 x 55 x 96, 1.92 / 2.76 / 11.12 at 512 x 27 x 27 x 256;
on the CPU the cumsum lost too (docs/autotune.md).  The beta=0.75 power
runs as rsqrt(y*sqrt(y)) — two sqrts instead of exp+log."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .linear import config_precision

# Above this channel count the C×C band matrix stops being "almost free";
# fall back to reduce_window.
_BAND_MATMUL_MAX_C = 2048


def resolve_method(method: str) -> str:
    """The concrete name of a configured ``method``: configuration files
    carry ``"auto"`` and ``"band"``, and both mean the one formulation."""
    if method not in ("auto", "band"):
        raise ValueError(
            f"LRN has one formulation, method='band' ('auto' means the "
            f"same); got {method!r}")
    return "band"


def _window_sum(sq, n: int):
    c = sq.shape[-1]
    half = n // 2
    if c <= _BAND_MATMUL_MAX_C:
        idx = jnp.arange(c)
        # Asymmetric window of exactly n: out_i sums sq[j] for
        # j - i in [-half, n-1-half], matching the reduce_window pad
        # below for even n too. In sq @ band, band[j, i] pairs row j with
        # output i, and (idx[None,:]-idx[:,None])[j, i] = i - j.
        diff = idx[None, :] - idx[:, None]
        mask = (diff >= -(n - 1 - half)) & (diff <= half)
        # The f32 C×C band contraction must not let a DEFAULT bf16 MXU
        # pass truncate the f32 squared activations SILENTLY (advisor
        # r1): honour the precision_level knob but floor it at HIGH.
        prec = config_precision()
        if prec == jax.lax.Precision.DEFAULT:
            prec = jax.lax.Precision.HIGH
        return jax.lax.dot_general(
            sq.reshape(-1, c), mask.astype(sq.dtype),
            (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32).reshape(sq.shape)
    pads = [(0, 0)] * (sq.ndim - 1) + [(half, n - 1 - half)]
    return jax.lax.reduce_window(
        jnp.pad(sq, pads), 0.0, jax.lax.add,
        (1,) * (sq.ndim - 1) + (n,), (1,) * sq.ndim, "VALID")


def local_response_norm(x, *, n=5, k=2.0, alpha=1e-4, beta=0.75,
                        method="band"):
    """x: (..., C). AlexNet semantics: alpha is divided by window size n.
    ``method``: "band" (or "auto", the same): see ``resolve_method``."""
    resolve_method(method)
    ssum = _window_sum(jnp.square(x), n)
    y = k + (alpha / n) * ssum
    if beta == 0.75:
        out = x * jax.lax.rsqrt(y * jnp.sqrt(y))
    elif beta == 0.5:
        out = x * jax.lax.rsqrt(y)
    elif beta == 1.0:
        out = x / y
    else:
        out = x * jax.lax.pow(y, -beta)
    # The band-matmul accumulates in f32; keep the layer dtype-preserving
    # (build-time specs and bf16 activation bandwidth depend on it).
    return out.astype(x.dtype)
