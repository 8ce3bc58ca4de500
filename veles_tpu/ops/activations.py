"""Activation functions.

Covers the Znicz activation set (reference: docs
manualrst_veles_algorithms.rst:10-30 — all2all variants tanh/relu/softmax/
sincos). ``scaled_tanh`` is the classic 1.7159*tanh(2x/3) the 2014-era
frameworks used for FC nets; ``sincos`` alternates sin/cos over feature
index (Znicz's periodic activation).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def relu(x):
    return jnp.maximum(x, 0)


def scaled_tanh(x):
    return 1.7159 * jnp.tanh(0.6666 * x)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def softmax(x, axis=-1):
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


def sincos(x):
    """Even feature indices -> sin, odd -> cos."""
    idx = jnp.arange(x.shape[-1])
    return jnp.where(idx % 2 == 0, jnp.sin(x), jnp.cos(x))


def identity(x):
    return x


ACTIVATIONS = {
    "linear": identity,
    "relu": relu,
    "tanh": scaled_tanh,
    "raw_tanh": jnp.tanh,
    "sigmoid": sigmoid,
    "sincos": sincos,
    "silu": jax.nn.silu,
}


def rotary_embedding(x, *, base: float = 10000.0, offset: int = 0):
    """Rotary position embedding (RoPE) over (B, T, H, D) with even D:
    pairs (x[2i], x[2i+1]) rotate by angle pos / base^(2i/D).

    Elementwise in (pos, feature), so it is GSPMD-transparent: under
    sequence parallelism the T axis stays sharded and each shard rotates
    by its GLOBAL positions (offset + local index) without communication.
    """
    B, T, H, D = x.shape
    if D % 2:
        raise ValueError(f"RoPE needs an even head dim, got {D}")
    half = D // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (offset + jnp.arange(T, dtype=jnp.float32))[:, None] \
        * inv_freq[None, :]                      # (T, half)
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(B, T, H, D)
