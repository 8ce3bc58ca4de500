"""The selective state-space recurrence of a state-space mixer as a
chunked scan ("state-space duality": Dao and Gu 2024).

Per head, with the head's group's ``B_t`` and ``C_t`` (N each), a scalar
decay ``A < 0`` and a step ``dt_t > 0``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        (P x N)
    y_t = S_t C_t + D x_t

Taken token by token that is T dependent steps of a few thousand
multiply-adds.  In chunks of Q tokens it is matrix products: within a
chunk ``y = (C B^T o L o dt) x`` with ``L[i, j] = exp(sum of dt A over
j+1..i)`` for ``j <= i`` and 0 above (the ``C B^T`` product shared by a
group's heads), each chunk's own state ``(B o decay-to-end o dt)^T x``,
the states carried from chunk to chunk by a scan over T / Q entries, and
``C o decay-from-start`` times the state carried in.

Cumulative sums, exponentials and the carried state are float32; the
products take ``compute_dtype`` operands with float32 accumulation.

``ssd`` is the expression under ``jax.checkpoint``: differentiated as
written, it keeps the (chunks, heads, Q, Q) float32 decay mask and the
masked scores of every layer until its backward (134 MB each at T = 4096,
64 heads).  Checkpointed, its residuals are its inputs and the backward
computes the masks again.

The forward's per-chunk part was also a Pallas kernel, ``ssd_chunk_fwd``
(a grid over batch, chunk and group that kept a chunk's mask and scores in
VMEM).  It lost to XLA's program of this expression on the chip and went
(PERF.md section 6, PR 34, has both times): the kernel alone was fast,
but XLA fuses the convolution's output into the products' operands and
the three parts of y into one pass, and a kernel's operands and results
have to be written out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _chunks(x, dt, A, B, C, chunk):
    """The operands by chunk and group: x (b, c, q, g, r, p), dt and the
    running sum of ``dt A`` (b, c, g, r, q) float32, B and C (b, c, q, g,
    n); r heads a group."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    if t % chunk:
        raise ValueError(f"the chunked scan takes whole chunks: T = {t} is "
                         f"no multiple of {chunk}")
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    c, r = t // chunk, h // g
    dt = dt.astype(jnp.float32).reshape(b, c, chunk, g, r)
    dt = dt.transpose(0, 1, 3, 4, 2)
    cum = jnp.cumsum(dt * A.astype(jnp.float32).reshape(g, r, 1), axis=-1)
    return (x.reshape(b, c, chunk, g, r, p), dt, cum,
            B.reshape(b, c, chunk, g, n), C.reshape(b, c, chunk, g, n))


def chunk_intra(x, dt, cum, B, C, compute_dtype):
    """A chunk's own part: ``(y_intra (b, c, q, g, r, p), states (b, c,
    g, r, p, n))`` float32, the state being what the chunk's tokens leave
    at its end had it started from nothing."""
    q = x.shape[2]
    dtype = compute_dtype or x.dtype
    with jax.named_scope("ssd_intra"):
        cb = jnp.einsum("bcign,bcjgn->bcgij", C.astype(dtype),
                        B.astype(dtype),
                        preferred_element_type=jnp.float32)
        seg = cum[..., :, None] - cum[..., None, :]
        causal = jnp.tril(jnp.ones((q, q), bool))
        # masked before the exponential: above the diagonal the sum is
        # positive and may overflow, and 0 x inf is what a gradient gets
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        scores = cb[:, :, :, None] * decay * dt[..., None, :]
        y = jnp.einsum("bcgrij,bcjgrp->bcigrp", scores.astype(dtype),
                       x.astype(dtype), preferred_element_type=jnp.float32)
        to_end = jnp.exp(cum[..., -1:] - cum) * dt          # (b, c, g, r, q)
        xw = x.astype(jnp.float32) * to_end.transpose(0, 1, 4, 2, 3)[
            ..., None]
        states = jnp.einsum("bcjgrp,bcjgn->bcgrpn", xw.astype(dtype),
                            B.astype(dtype),
                            preferred_element_type=jnp.float32)
    return y, states


def carry_states(states, cum):
    """The state each chunk starts from (b, c, g, r, p, n) float32: zero
    for the first, then ``decay over the chunk x state in + the chunk's
    own``, a scan over the chunks."""
    with jax.named_scope("ssd_carry"):
        over_chunk = jnp.exp(cum[..., -1])                  # (b, c, g, r)

        def step(carried, chunk):
            own, decay = chunk
            return decay[..., None, None] * carried + own, carried

        _, carried_in = jax.lax.scan(
            step, jnp.zeros_like(states[:, 0]),
            (jnp.moveaxis(states, 1, 0), jnp.moveaxis(over_chunk, 1, 0)))
        return jnp.moveaxis(carried_in, 0, 1)


def _finish(x, cum, C, D, y_intra, states, compute_dtype):
    """``y`` (b, t, h, p) float32 from the chunks' own parts: the carried
    states' part and the skip added."""
    b, c, q, g, r, p = x.shape
    dtype = compute_dtype or x.dtype
    carried_in = carry_states(states, cum)
    with jax.named_scope("ssd_carry"):
        y_inter = jnp.einsum("bcign,bcgrpn->bcigrp", C.astype(dtype),
                             carried_in.astype(dtype),
                             preferred_element_type=jnp.float32)
        y_inter = y_inter * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    y = y_intra + y_inter \
        + x.astype(jnp.float32) * D.astype(jnp.float32).reshape(g, r, 1)
    return y.reshape(b, c * q, g * r, p)


def ssd_chunked(x, dt, A, B, C, D, chunk=128, compute_dtype=None):
    """The recurrence as the chunked expression in ``jax.numpy``: x (b, t,
    h, p), dt (b, t, h), A and D (h,), B and C (b, t, g, n) -> y (b, t, h,
    p) float32."""
    xc, dtc, cum, Bc, Cc = _chunks(x, dt, A, B, C, chunk)
    y_intra, states = chunk_intra(xc, dtc, cum, Bc, Cc, compute_dtype)
    return _finish(xc, cum, Cc, D, y_intra, states, compute_dtype)


def ssd(x, dt, A, B, C, D, chunk=128, compute_dtype=None):
    """``ssd_chunked`` keeping its inputs alone for the backward (the
    module's docstring)."""
    return jax.checkpoint(
        lambda *a: ssd_chunked(*a, chunk, compute_dtype))(x, dt, A, B, C, D)
