"""The gated delta rule of a linear-attention mixer as a chunked program
(Yang, Kautz, Hatamizadeh 2025, "Gated Delta Networks"; the chunked form
is that of Hugging Face ``transformers``
``modeling_qwen3_next.torch_chunk_gated_delta_rule``).

Per head, with a key ``k_t`` and a query ``q_t`` of ``dk`` channels, a
value ``v_t`` of ``dv``, a log-decay ``g_t <= 0`` and a step ``beta_t``::

    S_t = exp(g_t) S_{t-1}
    S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T         (dk x dv), S_0 = 0
    o_t = S_t^T q_t

The decay is one number a head and token (Gated DeltaNet) or a vector of
``dk`` a head and token, ``exp(g_t) S`` then ``Diag(exp(g_t)) S``: the
delta attention of Kimi Linear (Moonshot AI 2025, arXiv 2510.26692,
"KDA").

The transition ``exp(g_t) (I - beta_t k_t k_t^T)`` is a full matrix a
token, so no diagonal scan (``ops/ssd.py``) computes it.  In chunks of Q
tokens, with ``G`` the running sum of ``g`` inside a chunk, ``K_beta =
beta K`` and ``V_beta = beta V``::

    A = -tril((K_beta K^T) o exp(G_i - G_j), -1)          strictly lower
    T = (I - A)^-1
    U = T V_beta,   W = T (K_beta o exp(G))

and from the state ``S`` a chunk starts with::

    v_new = U - W S
    o     = (Q o exp(G)) S + tril((Q K^T) o exp(G_i - G_j)) v_new
    S'    = exp(G_last) S + (K o exp(G_last - G))^T v_new

With a decay by channel every ``exp(G_i - G_j)`` above is a vector over
the ``dk`` channels that the two (Q, Q) products sum over, so it goes
inside them, and ``exp(G_last) S`` is ``Diag(exp(G_last)) S``.  Written
``(q_i exp(G_i)) . (k_j exp(-G_j))`` the second factor overflows where
the gates are steep, so the chunk's rows go in blocks of ``SUB``: block
p's rows against the columns before it are one product,
``(q_i exp(G_i - G_r)) . (k_j exp(G_r - G_j))`` with ``r`` the block's
first row, both factors at most one; the ``SUB`` x ``SUB`` blocks on the
diagonal sum ``q_i k_j exp(G_i - G_j)`` over the channels as they stand.

``S'`` is linear in ``S``: ``S' = exp(G_last) S - P S + N`` with ``P =
Kd^T W`` (dk x dk) and ``N = Kd^T U`` (dk x dv), ``Kd = K o exp(G_last -
G)``.  ``P`` and ``N`` are products of every chunk at once, so the scan
over the T / Q chunks carries the state through one dk x dk x dv product
a step and nothing else; ``v_new`` and ``o`` of all the chunks follow
from the states it leaves, again at once.

``T``: ``A`` is nilpotent (``A^Q = 0``), so ``(I - A)^-1 = I + A + ... +
A^(Q-1)``, taken by block forward substitution in blocks of ``SOLVE``
rows (the form of flash-linear-attention's ``solve_tril``): the Q /
``SOLVE`` diagonal blocks of every system at once, row by row (``T_ii[r]
= e_r + A_ii[r] T_ii``, ``SOLVE`` - 1 steps), then the blocks below the
diagonal a block row at a time, ``T_ij = T_ii sum_{k=j}^{i-1} A_ik
T_kj``.  Both are sums of products along paths, as forward substitution
by rows over the whole chunk is, which a loop of Q - 1 steps would take
re-reading all of ``T`` each step.  The power series itself is not
summed: at correlated keys its terms are large and cancel, and ten
products of it read an error of 1e+6 where the rows read 4e-7.  A
chunk of ``SOLVE`` rows or fewer, or of a size ``SOLVE`` does not
divide, goes row by row whole (:func:`solve_block`).  Both stages hold
the systems in the last axis, the lanes: a block's 16 columns there
would fill an eighth of them.  So the products are float32 products
summed over an axis that is not the lanes', with no matrix unit and no
rounding of their operands.  The gradient needs no pass through either
loop: ``dA = T^T dT T^T``.

Running sums, exponentials, ``T`` and the carried state are float32; the
products take ``compute_dtype`` operands with float32 accumulation.

``gated_delta`` is the expression under ``jax.checkpoint``: its residuals
are its inputs, and the backward computes the masks, ``T`` and the states
again.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
#: rows of a block of a chunk in the products of a decay by channel
SUB = 16
#: rows of a diagonal block of the triangular inverse
SOLVE = 16


def _lanes(a):
    """(..., Q, Q) -> (Q, Q, M): the M systems in the last axis."""
    q = a.shape[-1]
    return a.reshape(-1, q, q).transpose(1, 2, 0)


def _rows(a):
    """``(I - a)^-1`` of strictly lower ``a`` (Q, Q, M), float32: ``T[r]
    = e_r + a[r] T``, row r from the rows above it, Q - 1 steps."""
    q = a.shape[0]

    def row(r, t):
        a_r = jax.lax.dynamic_index_in_dim(a, r, axis=0, keepdims=False)
        new = a_r + jnp.sum(a_r[:, None] * t, axis=0)
        return jax.lax.dynamic_update_index_in_dim(t, new, r, axis=0)

    # t holds T - I: rows not reached yet are zero, and so is a[r, j >= r]
    return jax.lax.fori_loop(1, q, row, jnp.zeros_like(a)) \
        + jnp.eye(q, dtype=a.dtype)[:, :, None]


def _inverse_rows(a):
    """``unit_lower_inverse`` by rows over the whole system, as autodiff
    would take it, through the loop."""
    return _rows(_lanes(a)).transpose(2, 0, 1).reshape(a.shape)


def solve_block(q):
    """The rows of the diagonal blocks ``unit_lower_inverse`` takes a
    system of Q rows in: ``SOLVE`` where Q is a larger multiple of it,
    else Q (the rows loop over the whole system)."""
    return SOLVE if q > SOLVE and q % SOLVE == 0 else q


def _inverse_blocks(a):
    q = a.shape[-1]
    s = solve_block(q)
    if s == q:
        return _inverse_rows(a)
    shape, a = a.shape, _lanes(a)
    m = a.shape[-1]
    # the diagonal blocks of every system side by side: (s, s, Q / s M)
    diag = _rows(jnp.concatenate(
        [a[i:i + s, i:i + s] for i in range(0, q, s)], -1))
    t = diag[..., :m]
    for i in range(s, q, s):
        t_ii = diag[..., i // s * m:(i // s + 1) * m]
        # sum_k A_ik T_kj over the block rows above, then T_ii by it
        x = jnp.sum(a[i:i + s, :i, None] * t[None], axis=1)
        below = jnp.sum(t_ii[:, :, None] * x[None], axis=1)
        t = jnp.concatenate([jnp.pad(t, ((0, 0), (0, s), (0, 0))),
                             jnp.concatenate([below, t_ii], 1)], 0)
    return t.transpose(2, 0, 1).reshape(shape)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I - a)^-1`` of strictly lower-triangular ``a`` (..., Q, Q),
    float32: block forward substitution (the module's docstring), the
    diagonal blocks row by row, ``T_ii[r] = e_r + a_ii[r] T_ii``, and
    each block row below them from the rows above it."""
    return _inverse_blocks(a)


def _inverse_fwd(a):
    t = _inverse_blocks(a)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    da = jnp.matmul(jnp.matmul(tt, dt, precision=_HIGHEST), tt,
                    precision=_HIGHEST)
    return (jnp.tril(da, -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _product(spec, a, b, dtype):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _decayed_products(a, b, cum, dtype):
    """``sum_d a_i[d] b_j[d] exp(G_i[d] - G_j[d])`` for ``j <= i``, zero
    above the diagonal: a and b (b, c, Q, h, dk), ``cum`` their running
    sums of the decay by channel, float32 -> (b, c, h, Q, Q) float32.  No
    factor above one is formed (the module's docstring)."""
    nb, c, q, h, dk = a.shape
    sub = min(SUB, q)
    n = q // sub
    blocks = lambda x: x.reshape(nb, c, n, sub, h, dk)
    a_blk, b_blk, cum_blk = blocks(a), blocks(b), blocks(cum)
    # the rows of block p against the columns before it, through row r
    ref = cum_blk[:, :, :, :1]                           # (b, c, n, 1, h, dk)
    a_ref = a_blk.astype(jnp.float32) * jnp.exp(cum_blk - ref)
    before = (jnp.arange(q)[None, :] < sub * jnp.arange(n)[:, None])
    b_ref = b.astype(jnp.float32)[:, :, None] * jnp.exp(jnp.where(
        before[None, None, :, :, None, None],
        ref - cum[:, :, None], -jnp.inf))               # (b, c, n, Q, h, dk)
    off = _product("bcpihd,bcpjhd->bchpij", a_ref, b_ref, dtype)
    # the blocks on the diagonal, channel by channel
    causal = jnp.tril(jnp.ones((sub, sub), bool))[:, :, None, None]
    diag = jnp.sum(
        a_blk.astype(jnp.float32)[:, :, :, :, None]
        * b_blk.astype(jnp.float32)[:, :, :, None]
        * jnp.exp(jnp.where(causal, cum_blk[:, :, :, :, None]
                            - cum_blk[:, :, :, None], -jnp.inf)),
        axis=-1)                                         # (b, c, n, i, j, h)
    diag = diag.transpose(0, 1, 5, 2, 3, 4)              # (b, c, h, n, i, j)
    eye = jnp.eye(n, dtype=jnp.float32)[:, None, :, None]
    out = off.reshape(nb, c, h, n, sub, n, sub) \
        + diag[:, :, :, :, :, None, :] * eye
    return out.reshape(nb, c, h, q, q)


def gated_delta_chunked(q, k, v, g, beta, chunk=64, compute_dtype=None):
    """The recurrence as the chunked expression in ``jax.numpy``: q and k
    (b, t, h, dk), v (b, t, h, dv), beta (b, t, h), g (b, t, h) or by
    channel (b, t, h, dk) -> o (b, t, h, dv) float32.  ``q`` comes
    scaled; nothing here normalises."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    if t % chunk:
        raise ValueError(f"the chunked delta rule takes whole chunks: T = "
                         f"{t} is no multiple of {chunk}")
    by_channel = g.ndim == 4
    c = t // chunk
    dtype = compute_dtype or q.dtype
    f32 = jnp.float32
    by_chunk = lambda a: a.reshape((b, c, chunk) + a.shape[2:])
    # a token's scalars beside its rows: (b, c, Q, h, 1); a decay by
    # channel is (b, c, Q, h, dk) as it stands
    beta = by_chunk(beta.astype(f32))[..., None]
    g = by_chunk(g.astype(f32))
    g = g if by_channel else g[..., None]
    qc, kc, vc = (by_chunk(a) for a in (q, k, v))
    cum = jnp.cumsum(g, axis=2)
    last = cum[:, :, -1:]
    with jax.named_scope("gdn_chunk"):
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        k_beta = kc.astype(f32) * beta
        if by_channel:
            a = -jnp.tril(_decayed_products(k_beta, kc, cum, dtype), -1)
        else:
            # (b, c, h, Q, Q): token i's running sum less token j's
            rows = cum[..., 0].transpose(0, 1, 3, 2)
            seg = rows[..., :, None] - rows[..., None, :]
            # masked before the exponential: above the diagonal the sum
            # is positive and may overflow, and 0 x inf is what a
            # gradient gets
            decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
            a = -jnp.tril(_product("bcihd,bcjhd->bchij", k_beta, kc, dtype)
                          * decay, -1)
        with jax.named_scope("gdn_solve"):
            inv = unit_lower_inverse(a)
        u = _product("bchij,bcjhe->bcihe", inv, vc.astype(f32) * beta, dtype)
        w = _product("bchij,bcjhd->bcihd", inv, k_beta * jnp.exp(cum), dtype)
    with jax.named_scope("gdn_carry"):
        kd = kc.astype(f32) * jnp.exp(last - cum)
        p = _product("bcihd,bcihf->bchdf", kd, w, dtype)       # Kd^T W
        n = _product("bcihd,bcihe->bchde", kd, u, dtype)       # Kd^T U
        # (b, c, h, 1): one number a head, or (b, c, h, dk): a row of
        # the state each
        over_chunk = jnp.exp(last[:, :, 0])

        def step(s, chunk_):
            p_c, n_c, d_c = chunk_
            out = d_c[..., None] * s + n_c \
                - _product("bhdf,bhfe->bhde", p_c, s, dtype)
            return out, s

        _, starts = jax.lax.scan(
            step, jnp.zeros((b, h, dk, dv), f32),
            tuple(jnp.moveaxis(x, 1, 0) for x in (p, n, over_chunk)))
        starts = jnp.moveaxis(starts, 0, 1)                    # (b, c, h, dk, dv)
    with jax.named_scope("gdn_chunk"):
        v_new = u - _product("bcihd,bchde->bcihe", w, starts, dtype)
        if by_channel:
            scores = _decayed_products(qc, kc, cum, dtype)
        else:
            scores = jnp.where(
                causal, _product("bcihd,bcjhd->bchij", qc, kc, dtype)
                * decay, 0.0)
        o = _product("bcihd,bchde->bcihe", qc.astype(f32) * jnp.exp(cum),
                     starts, dtype) \
            + _product("bchij,bcjhe->bcihe", scores, v_new, dtype)
    return o.reshape(b, t, h, dv)


def gated_delta(q, k, v, g, beta, chunk=64, compute_dtype=None):
    """``gated_delta_chunked`` keeping its inputs alone for the backward
    (the module's docstring)."""
    return jax.checkpoint(functools.partial(
        gated_delta_chunked, chunk=chunk, compute_dtype=compute_dtype))(
            q, k, v, g, beta)
