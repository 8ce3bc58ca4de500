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


def relu2(x):
    """Squared ReLU, ``max(x, 0)^2``."""
    r = jnp.maximum(x, 0)
    return r * r


ACTIVATIONS = {
    "linear": identity,
    "relu": relu,
    "tanh": scaled_tanh,
    "raw_tanh": jnp.tanh,
    "sigmoid": sigmoid,
    "sincos": sincos,
    "silu": jax.nn.silu,
    "relu2": relu2,
}


def heads_per_lane_block(head_dim: int) -> int:
    """How many heads of ``head_dim`` channels lie side by side in one
    128-lane block of a (B, T, H * D) array: 128 / D where D divides 128
    and is smaller (D = 64: two), else 0.  Where heads share a block, XLA
    puts T in the lanes of every (B, T, H, D) array that an operation
    takes apart along D, and the flash kernels' transposed copies carry D
    padded to 128 lanes; so there the rotary embedding and the flash
    kernels (``pallas_kernels.flash_heads_per_step``) stay on
    (B, T, H * D).  At D a multiple of 128 neither holds: a head is whole
    lane blocks, and what surrounds the kernels (a reduction over D, as
    in a per-head RMS norm) keeps T in the lanes whatever they read — on
    the chip (B, T, H * D) kernels cost the Trinity cell 5.3 % (PERF.md
    section 6, PR 33)."""
    return 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 0


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
    if heads_per_lane_block(D):
        # On (B, T, H * D): out = x * cos + partner(x) * (-sin | +sin),
        # the partner of a lane being the other lane of its pair, fetched
        # by a lane roll; splitting the pairs out as below would move T
        # into the lanes, with a copy on either side of the flash kernels.
        cos = jnp.repeat(jnp.cos(ang), 2, axis=-1).astype(x.dtype)  # (T, D)
        sin = (jnp.repeat(jnp.sin(ang), 2, axis=-1)
               * jnp.tile(jnp.asarray([-1.0, 1.0], jnp.float32), half)
               ).astype(x.dtype)
        flat = x.reshape(B, T, H * D)
        even = jnp.arange(H * D) % 2 == 0
        partner = jnp.where(even, jnp.roll(flat, -1, axis=-1),
                            jnp.roll(flat, 1, axis=-1))
        out = flat * jnp.tile(cos, (1, H)) + partner * jnp.tile(sin, (1, H))
        return out.reshape(B, T, H, D)
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(B, T, H, D)
