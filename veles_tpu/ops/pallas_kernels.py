"""Hand-written Pallas TPU kernels for the ops where fusion semantics or
memory movement matter beyond what XLA's automatic fusion gives.

Reference parity (each kernel names its OpenCL/CUDA counterpart):

* ``flash_attention``        — no reference counterpart (SURVEY.md §5.7: the
  reference has no attention); TPU-native blockwise-softmax kernel.  The
  long-context path (parallel/ring_attention.py ``blockwise_attention``)
  delegates to it on TPU.
* ``fused_dropout``          — reference: Znicz dropout unit backed by the
  parallel RNG kernels ``ocl/random.cl`` / ``cuda/random.cu`` (xorshift1024*
  per-state, interleaved output).  Here the RNG is a counter-based
  splitmix32 hash of (seed, linear element index) generated *inside* the
  kernel, so mask bits never touch HBM and the backward pass can regenerate
  them exactly instead of storing the mask.
* ``gather_rows``            — reference: ``ocl/fullbatch_loader.cl``
  ``fill_minibatch_data_labels`` (minibatch gather from the on-device
  dataset by shuffled indices).  TPU version: scalar-prefetched indices
  drive the BlockSpec index_map, so each minibatch row is a direct
  HBM→VMEM DMA — the dataset itself never streams through compute.

All kernels run compiled on TPU and in interpreter mode elsewhere (tests run
them on the CPU backend with ``interpret=True``; see tests/test_pallas.py).
"""

from __future__ import annotations

import functools
import math
import operator
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from . import (use_pallas_default,  # policy lives pallas-free in ops/__init__
               check_attention_window, check_gqa_heads)
from .activations import heads_per_lane_block

def _interpret(interpret: Optional[bool]) -> bool:
    """``None`` follows the backend: compiled through Mosaic on a TPU, the
    Pallas interpreter elsewhere.  Nothing else selects interpret mode, so
    a kernel the chip's compiler refuses raises there instead of running
    interpreted."""
    if interpret is None:
        return not use_pallas_default()
    return interpret


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# ---------------------------------------------------------------------------
# Flash attention (forward kernel + recompute backward)
# ---------------------------------------------------------------------------

_LANES = 128  # a vector register's lane count: row state is kept that wide


def _lanes(x, n):
    """Row state ``x`` (rows, _LANES), every lane of a row the same value,
    at width ``n``: per-row values stay lane-replicated from the reduction
    that made them to the (rows, n) tile that uses them, in scratch and in
    HBM alike.  A (rows, 1) column costs a masked store and a lane
    broadcast at every use, which on the chip was a third of the forward
    at 512-wide K blocks (PERF.md section 6, PR 28)."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return pltpu.repeat(x, n // _LANES, 1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _head_lanes(x, h, heads, head_dim):
    """Tile ``x`` (rows, heads * head_dim), ``heads`` heads side by side in
    the lanes, with every lane outside head ``h`` zeroed: a product that
    contracts the whole lane width then sees head ``h`` alone, at the MXU
    passes a ``head_dim``-wide product costs anyway (head_dim < 128 fills
    the array's depth by half).  ``x`` itself when the tile is one head."""
    if heads == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    keep = (lane >= h * head_dim) & (lane < (h + 1) * head_dim)
    return jnp.where(keep, x, jnp.zeros_like(x))


def _by_head(xs, head_dim):
    """One (rows, len(xs) * head_dim) tile whose lanes of head ``h`` are
    ``xs[h]``'s (each ``xs[h]`` that wide, valid in head ``h``'s lanes or
    lane-replicated row state)."""
    out = xs[-1]
    if len(xs) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for h in reversed(range(len(xs) - 1)):
            out = jnp.where(lane < (h + 1) * head_dim, xs[h], out)
    return out


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                      acc_ref, *,
                      scale, causal, window, block_q, block_k, tq, tk,
                      n_kb, heads, head_dim):
    """Grid = (batch, head step, n_q_blocks, n_k_blocks); the k dimension
    is minor, so VMEM holds only one (block_q, W) Q tile and one
    (block_k, W) K/V tile at a time — the m/l/acc online-softmax state
    lives in scratch that persists across the sequentially-iterated k
    steps (long T streams from HBM block-by-block instead of residing
    whole in VMEM).  A tile is ``heads`` heads of ``head_dim`` side by
    side (W = heads * head_dim; ``_flash_addressing``): each has its own
    row state, all share ``acc``.  ``scale`` is None when the caller
    folded it into Q (``_flash_scale``)."""
    qi, kj = pl.program_id(2), pl.program_id(3)
    width = acc_ref.shape[-1]

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _step(masked):
        # Operands stay in their storage dtype (bf16 inputs hit the MXU at
        # the bf16 rate); accumulation is forced to f32 via
        # preferred_element_type — casting to f32 first would silently run
        # the matmuls at the several-times-slower f32 MXU rate.
        q = q_ref[0]  # (block_q, W)
        k_blk = k_ref[0]  # (block_k, W)
        v_blk = v_ref[0]
        mask = _flash_tile_mask(
            qi, kj, causal=causal, window=window, block_q=block_q,
            block_k=block_k, tq=tq, tk=tk, rows=False) if masked else None
        alphas, pvs = [], []
        for h in range(heads):
            s = jax.lax.dot_general(
                _head_lanes(q, h, heads, head_dim), k_blk,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if scale is not None:
                s = s * scale
            if mask is not None:
                s = jnp.where(mask, s, -1e30)
            # Row state m/l is (block_q, _LANES) a head, lane-replicated
            # (_lanes); the keepdims reductions broadcast into it.
            m = m_ref[h]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - _lanes(m_new, block_k))
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            m_ref[h] = m_new
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            alphas.append(_lanes(alpha, width))
            # (block_q, W): head h's lanes are its P @ V
            pvs.append(jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        acc_ref[:] = acc_ref[:] * _by_head(alphas, head_dim) \
            + _by_head(pvs, head_dim)

    _flash_by_tile_class(_step, qi, kj, causal=causal, window=window,
                         block_q=block_q, block_k=block_k, tq=tq, tk=tk)

    @pl.when(kj == n_kb - 1)
    def _finalize():
        denoms = [jnp.maximum(l_ref[h], 1e-30) for h in range(heads)]
        o_ref[0] = (acc_ref[:] / _by_head(
            [_lanes(d, width) for d in denoms], head_dim)).astype(o_ref.dtype)
        # logsumexp per row, consumed by the Pallas backward kernels, kept
        # lane-replicated: (.., T, _LANES) is what a (.., T, 1) array
        # occupies under the (8, 128) tiling anyway.
        for h in range(heads):
            lse_ref[0, h] = m_ref[h] + jnp.log(denoms[h])


def _flash_layout(x, t_p):
    """(B, T, H, D) -> (B*H, t_p, D) with the T axis zero-padded."""
    B, T, H, D = x.shape
    return jnp.pad(x.transpose(0, 2, 1, 3).reshape(B * H, T, D),
                   ((0, 0), (0, t_p - T), (0, 0)))


def _flash_blocks(Tq, Tk, block_q, block_k):
    block_q = min(block_q, _round_up(Tq, 8))
    block_k = min(block_k, _round_up(Tk, 8))
    return block_q, block_k, _round_up(Tq, block_q), _round_up(Tk, block_k)


def _gqa_groups(q, k):
    """Grouped-query attention factor: q heads per kv head.  H == H_kv is
    plain MHA (G=1)."""
    return check_gqa_heads(q.shape[2], k.shape[2])


def flash_heads_per_step(q_shape, k_shape, block_q=256, block_k=1024):
    """THE layout rule, by the call's shapes alone: how many heads one
    grid step of the three kernels takes straight out of the projections'
    (B, T, H * D) arrays, or 0 where the call runs on transposed
    (B * H, T, D) copies.

    * D a divisor of 128 and smaller, as many kv heads as q heads, H a
      multiple of 128 / D, and lengths the blocks divide: 128 / D heads,
      side by side in one 128-lane block (D = 64: two).
    * anything else: 0.  D = 80, an odd head count at D = 64 and grouped
      queries at D < 128 do not tile the lanes; T ragged against the
      blocks needs the copies' zero padding; at D a multiple of 128 the
      copies waste no lanes and cost less than what reading (B, T, H * D)
      does to the neighbours (``ops.activations.heads_per_lane_block``)."""
    _, Tq, H, D = q_shape
    _, Tk, H_kv, _ = k_shape
    _, _, tq_p, tk_p = _flash_blocks(Tq, Tk, block_q, block_k)
    heads = heads_per_lane_block(D)
    if heads and H == H_kv and H % heads == 0 and (tq_p, tk_p) == (Tq, Tk):
        return heads
    return 0


class _FlashAddressing(NamedTuple):
    """How one call's kernels reach its operands: ``operand`` makes the
    array a kernel reads from a (B, T, heads, D) one, ``result`` the
    inverse; blocks are (1, block, ``width``) of it, ``heads`` heads
    each.  ``grid_q`` / ``grid_kv`` are the grid's (batch, head step)
    axes over q heads / kv heads, and the three maps turn those two grid
    indices into the (batch, lane block) block indices of a q-side
    operand, of K/V under a q-head grid, and of the q-side operand of
    group member ``g`` under a kv-head grid.  Row state (lse, delta) is
    (``rows`` + (T, _LANES)) float32, addressed by the same pairs."""
    heads: int
    width: int
    operand: callable
    result: callable
    grid_q: tuple
    grid_kv: tuple
    rows: tuple
    q_at: callable
    kv_at: callable
    q_of_kv: callable


def _flash_addressing(q_shape, k_shape, block_q, block_k):
    B, _, H, D = q_shape
    H_kv = k_shape[2]
    G = H // H_kv
    heads = flash_heads_per_step(q_shape, k_shape, block_q, block_k)
    if heads:
        # the projections' own layout: (B, T, H * D) is a free reshape,
        # and every kv head is a q head's own
        return _FlashAddressing(
            heads=heads, width=heads * D,
            operand=lambda x, t_p: x.reshape(*x.shape[:2], -1),
            result=lambda x, T, nh: x.reshape(B, T, nh, D),
            grid_q=(B, H // heads), grid_kv=(B, H // heads),
            rows=(B, H),
            q_at=lambda b, h: (b, h),
            kv_at=lambda b, h: (b, h),
            q_of_kv=lambda b, h, g: (b, h))
    # transposed copies, one head a row of the leading axis (any D, any
    # GQA factor, T padded to the blocks): q-head row b shares kv row
    # (b // H) * H_kv + (b % H) // G — the ONE definition the forward and
    # the dq kernel use (drift here would make them read different
    # blocks) — and kv row b's group member g is q-head row
    # (b // H_kv) * H + (b % H_kv) * G + g
    return _FlashAddressing(
        heads=1, width=D,
        operand=_flash_layout,
        result=lambda x, T, nh: x[:, :T].reshape(B, nh, T, D)
                                 .transpose(0, 2, 1, 3),
        grid_q=(B * H, 1), grid_kv=(B * H_kv, 1),
        rows=(B * H, 1),
        q_at=lambda b, h: (b, 0),
        kv_at=lambda b, h: ((b // H) * H_kv + (b % H) // G, 0),
        q_of_kv=lambda b, h, g: ((b // H_kv) * H + (b % H_kv) * G + g, 0))


def _flash_specs(at, block_q, block_k, q_where, q_block, kv_where,
                 kv_block):
    """(q-side tile, K/V tile, row state) BlockSpecs of one kernel:
    ``*_where(*grid)`` names the (batch, lane block) pair of ``at`` and
    ``*_block(*grid)`` the sequence block that a grid step reads."""
    def spec(block, where, seq, rows=False):
        def index(*grid):
            batch, lanes = where(*grid)
            if rows:
                return batch, lanes, seq(*grid), 0
            return batch, seq(*grid), lanes
        return pl.BlockSpec((1, at.heads, block, _LANES) if rows
                            else (1, block, at.width), index)
    return (spec(block_q, q_where, q_block),
            spec(block_k, kv_where, kv_block),
            spec(block_q, q_where, q_block, rows=True))


def _flash_k_sweep_specs(at, block_q, block_k, live_k):
    """``_flash_specs`` of the kernels whose grid is (batch, q head step,
    q block, k block), ``flash_fwd`` and ``flash_bwd_dq``: K/V sweep,
    clamped into the q block's live range; with grouped queries their
    index map names the shared kv head's row (arithmetic on grid
    indices)."""
    return _flash_specs(
        at, block_q, block_k,
        lambda b, h, i, j: at.q_at(b, h), lambda b, h, i, j: i,
        lambda b, h, i, j: at.kv_at(b, h), lambda b, h, i, j: live_k(i, j))


# Batch, head and block steps are independent; only the minor sweep
# carries state (the online softmax, an accumulator) — telling Mosaic lets
# it pipeline DMAs across grid steps instead of serializing.  Two heads a
# step at 1024 x 1024 tiles hold two heads' score tiles at once: 17.5 MB
# in the forward, over the 16 MiB a kernel gets unasked.
_FLASH_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=32 * 1024 * 1024)
_FLASH_STATICS = ("causal", "scale", "block_q", "block_k", "interpret",
                  "window")


@functools.partial(jax.jit, static_argnames=_FLASH_STATICS)
def _flash_fwd(q, k, v, *, causal, scale, block_q, block_k, interpret,
               window):
    """(out, lse) of one call.  A ``jax.jit`` of its own, so that the
    equal calls of a program's layers are one jaxpr, lowered once (each
    ``pl.pallas_call`` costs about 0.1 s to lower to Mosaic; XLA inlines
    the calls, so the compiled program is what it would be without).
    ``q`` carries a folded scale already and ``scale`` is what is left
    for the score tiles (``_flash_call``)."""
    _, Tq, H, D = q.shape
    Tk = k.shape[1]
    at = _flash_addressing(q.shape, k.shape, block_q, block_k)
    block_q, block_k, tq_p, tk_p = _flash_blocks(Tq, Tk, block_q, block_k)
    n_kb = tk_p // block_k
    geom = dict(causal=causal, window=window, block_q=block_q,
                block_k=block_k)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, tq=Tq, tk=Tk, n_kb=n_kb,
        heads=at.heads, head_dim=D, **geom)
    live_k = functools.partial(_flash_live_k, n_kb=n_kb, **geom)

    q_spec, kv_spec, row_spec = _flash_k_sweep_specs(
        at, block_q, block_k, live_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=(*at.grid_q, tq_p // block_q, n_kb),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct(
                (at.grid_q[0], tq_p, at.grid_q[1] * at.width), q.dtype),
            jax.ShapeDtypeStruct((*at.rows, tq_p, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((at.heads, block_q, _LANES), jnp.float32),
            pltpu.VMEM((at.heads, block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, at.width), jnp.float32),
        ],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_fwd",
    )(at.operand(q, tq_p), at.operand(k, tk_p), at.operand(v, tk_p))
    return at.result(out, Tq, H), lse


def _flash_tile_mask(qi, kj, *, causal, window, block_q, block_k, tq, tk,
                     rows):
    """Validity mask of one (block_q, block_k) edge tile, or None where
    nothing in it needs one: the causal triangle (and sliding window) as
    ONE iota difference against the tile's scalar offset, plus the
    in-range columns (and, with ``rows``, rows) only where T is padded.
    ``rows`` is the backward's: padded Q rows carry a bogus lse
    (=-1e30 + log eps), so P must be forced to zero there or they'd
    pollute dK/dV; the forward's padded rows are sliced off."""
    shape = (block_q, block_k)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    conds = []
    if tk % block_k:
        conds.append(col < tk - kj * block_k)
    if rows and tq % block_q:
        conds.append(row < tq - qi * block_q)
    if causal:
        # k_pos <= q_pos  <=>  col - row <= qi*block_q - kj*block_k
        ahead, diag = col - row, qi * block_q - kj * block_k
        conds.append(ahead <= diag)
        if window is not None:
            # key positions in (q - window, q]
            conds.append(ahead > diag - window)
    return functools.reduce(operator.and_, conds) if conds else None


def _flash_block_live(qi, kj, *, causal, window, block_q, block_k):
    """Block-level liveness: does tile (qi, kj) contain ANY unmasked pair?
    Shared by the fwd/dq kernels (k minor) and the dkv kernel (q minor).
    ``None`` when nothing can be dead (non-causal)."""
    if not causal:
        return None
    live = kj * block_k <= qi * block_q + block_q - 1
    if window is not None:
        live &= kj * block_k + block_k - 1 > qi * block_q - window
    return live


def _flash_tile_interior(qi, kj, *, causal, window, block_q, block_k, tq,
                         tk):
    """Is EVERY pair of tile (qi, kj) unmasked?  The tile then lies wholly
    on or below the diagonal, wholly inside the window where one is set,
    and holds no padded row or column, so its body needs no mask.  With
    ``_flash_block_live`` this is the one definition of the three tile
    classes: *dead* (not live), *interior*, and *edge* (live, not
    interior).  Works on grid indices in a kernel and on ints in
    ``flash_tile_classes``; the Python ``True`` when no tile of the call
    can need a mask."""
    conds = []
    if tq % block_q:
        conds.append((qi + 1) * block_q <= tq)
    if tk % block_k:
        conds.append((kj + 1) * block_k <= tk)
    if causal:
        conds.append(kj * block_k + block_k - 1 <= qi * block_q)
        if window is not None:
            conds.append(kj * block_k > qi * block_q + block_q - 1 - window)
    return functools.reduce(operator.and_, conds) if conds else True


def _flash_by_tile_class(step, qi, kj, *, causal, window, block_q, block_k,
                         tq, tk):
    """Run ``step(masked)`` as tile (qi, kj)'s class needs: not at all on a
    dead tile, mask-free on an interior one, masked on an edge.  The masked
    body is the fallback, so a predicate that is too strict costs speed
    and never correctness."""
    live = _flash_block_live(qi, kj, causal=causal, window=window,
                             block_q=block_q, block_k=block_k)
    interior = _flash_tile_interior(qi, kj, causal=causal, window=window,
                                    block_q=block_q, block_k=block_k,
                                    tq=tq, tk=tk)
    if interior is True:
        step(False)
        return
    edge = jnp.logical_not(interior)
    if live is not None:
        edge &= live
    pl.when(interior)(functools.partial(step, False))
    pl.when(edge)(functools.partial(step, True))


def _flash_live_k(qi, kj, *, causal, window, block_q, block_k, n_kb):
    """``kj`` clamped into q tile ``qi``'s live K range, for the index maps
    of the operands that sweep k (K and V in ``flash_fwd`` and
    ``flash_bwd_dq``): a dead step then names the block its live
    neighbour holds, and the pipeline issues no DMA for it."""
    if not causal:
        return kj
    hi = jnp.minimum((qi * block_q + block_q - 1) // block_k, n_kb - 1)
    if window is not None:
        kj = jnp.maximum(
            kj, jnp.maximum(qi * block_q - window + 1, 0) // block_k)
    return jnp.minimum(kj, hi)


def _flash_live_q(kj, qi, *, causal, window, block_q, block_k, n_qb):
    """``qi`` clamped into k tile ``kj``'s live Q range: ``_flash_live_k``
    for the q-minor sweep of ``flash_bwd_dkv`` (Q, dO, lse, delta)."""
    if not causal:
        return qi
    qi = jnp.maximum(qi, kj * block_k // block_q)
    hi = n_qb - 1
    if window is not None:
        hi = jnp.minimum(
            (kj * block_k + block_k + window - 2) // block_q, hi)
    return jnp.minimum(qi, hi)


def flash_tile_classes(tq, tk, block_q=256, block_k=1024, causal=False,
                       window=None):
    """How many (q-tile, k-tile) grid steps of one head are ``dead``,
    ``edge`` and ``interior`` (``flash_attention``'s docstring) at these
    lengths and requested blocks: counted with the kernels' own
    predicates, and what ``vt_flash_tiles`` reports."""
    block_q, block_k, tq_p, tk_p = _flash_blocks(tq, tk, block_q, block_k)
    geom = dict(causal=causal, window=window, block_q=block_q,
                block_k=block_k)
    # the predicates are plain arithmetic: one evaluation over the grid
    qi, kj = np.ogrid[:tq_p // block_q, :tk_p // block_k]
    grid = np.broadcast(qi, kj).shape
    live = _flash_block_live(qi, kj, **geom)
    live = np.broadcast_to(True if live is None else live, grid)
    interior = np.broadcast_to(
        _flash_tile_interior(qi, kj, tq=tq, tk=tk, **geom), grid)
    return {"dead": int((~live).sum()),
            "edge": int((live & ~interior).sum()),
            "interior": int(interior.sum())}


def _note_flash_call(kernels, q_shape, k_shape, block_q, block_k, causal,
                     window):
    """Set, while a call is traced, ``vt_flash_tiles{kernel, class}`` (how
    often the per-class bodies engage at the shapes the program runs) and
    ``vt_flash_layout{kernel, layout}`` / ``vt_flash_heads_per_step``
    (which addressing ``flash_heads_per_step`` gave the call)."""
    from ..runtime.metrics import registry
    reg = registry()
    tiles = reg.gauge(
        "vt_flash_tiles",
        "flash attention grid steps a head by tile class, as last traced",
        labels=("kernel", "class"))
    layout = reg.gauge(
        "vt_flash_layout",
        "1 on the addressing the flash kernel's last traced call took: "
        "lanes (the projections' own layout) or transposed copies",
        labels=("kernel", "layout"))
    per_step = reg.gauge(
        "vt_flash_heads_per_step",
        "heads one grid step of the flash kernel's last traced call takes",
        labels=("kernel",))
    counts = flash_tile_classes(q_shape[1], k_shape[1], block_q, block_k,
                                causal, window)
    heads = flash_heads_per_step(q_shape, k_shape, block_q, block_k)
    for kernel in kernels:
        for cls, n in counts.items():
            tiles.labels(**{"kernel": kernel, "class": cls}).set(n)
        layout.labels(kernel=kernel, layout="lanes").set(int(heads > 0))
        layout.labels(kernel=kernel, layout="transposed").set(
            int(heads == 0))
        per_step.labels(kernel=kernel).set(max(heads, 1))


def _flash_scale(scale, D):
    """(scale, folded): a power-of-two scale commutes with every rounding
    of the score products, so it is applied once to Q outside the kernels
    (``_flash_call``) and leaves the (block_q, block_k) score tile; any
    other scale stays on the scores."""
    scale = D ** -0.5 if scale is None else float(scale)
    return scale, scale > 0 and math.frexp(scale)[0] == 0.5


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                         dq_ref, delta_ref, acc_ref, *, scale, out_scale,
                         causal, window, block_q, block_k, tq, tk, n_kb,
                         heads, head_dim):
    """Grid = (batch, head step, n_q_blocks, n_k_blocks), k minor; dQ
    accumulates in scratch across the k sweep (two-pass recompute
    backward: S and P are rebuilt from Q/K and the saved row logsumexp,
    never materialized).  With the scale folded into Q (``scale`` None)
    the second ``* scale`` moves from the score tile to the accumulator
    (``out_scale``).  The sweep's first step takes delta_i =
    rowsum(dO * O) of its q rows from the tiles it holds anyway, into an
    output block that stays resident for the sweep and that
    ``flash_bwd_dkv`` reads afterwards: lane-replicated row state like
    lse (``_lanes``), written by no pass of its own."""
    qi, kj = pl.program_id(2), pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        prod = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        for h in range(heads):
            delta_ref[0, h] = jnp.broadcast_to(
                jnp.sum(_head_lanes(prod, h, heads, head_dim), axis=-1,
                        keepdims=True), (block_q, _LANES))

    def _step(masked):
        q, k_blk, v_blk, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        mask = _flash_tile_mask(
            qi, kj, causal=causal, window=window, block_q=block_q,
            block_k=block_k, tq=tq, tk=tk, rows=True) if masked else None
        dqs = []
        for h in range(heads):
            s = jax.lax.dot_general(
                _head_lanes(q, h, heads, head_dim), k_blk,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if scale is not None:
                s = s * scale
            # lse/delta blocks are lane-replicated row state (_lanes)
            p = jnp.exp(s - _lanes(lse_ref[0, h], block_k))
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            dp = jax.lax.dot_general(
                _head_lanes(do, h, heads, head_dim), v_blk,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - _lanes(delta_ref[0, h], block_k))
            if scale is not None:
                ds = ds * scale
            # (block_q, W): head h's lanes are its dS @ K
            dqs.append(jax.lax.dot_general(
                ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        acc_ref[:] += _by_head(dqs, head_dim)

    _flash_by_tile_class(_step, qi, kj, causal=causal, window=window,
                         block_q=block_q, block_k=block_k, tq=tq, tk=tk)

    @pl.when(kj == n_kb - 1)
    def _finalize():
        acc = acc_ref[:]
        if out_scale is not None:
            acc = acc * out_scale
        dq_ref[0] = acc.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                          window, block_q, block_k, tq, tk, n_qb, n_qsweep,
                          heads, head_dim):
    """Grid = (batch, kv head step, n_k_blocks, n_qsweep), q minor; dK/dV
    accumulate in scratch across the q sweep.  With GQA, n_qsweep =
    n_q_blocks * G: the minor axis enumerates (group member g, q block
    qi) — every q head of the group folds into the same kv-head
    accumulator.  With the scale folded into Q (``scale`` None) dK's
    product with that Q carries it.  The products with a head's own
    lanes of dO and Q (``_head_lanes``) are zero in the other heads'
    lanes, so the heads of a tile add up into one accumulator."""
    kj, i = pl.program_id(2), pl.program_id(3)
    qi = i % n_qb

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _step(masked):
        q, k_blk, v_blk, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        mask = _flash_tile_mask(
            qi, kj, causal=causal, window=window, block_q=block_q,
            block_k=block_k, tq=tq, tk=tk, rows=True) if masked else None
        dks, dvs = [], []
        for h in range(heads):
            q_h = _head_lanes(q, h, heads, head_dim)
            do_h = _head_lanes(do, h, heads, head_dim)
            s = jax.lax.dot_general(
                q_h, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if scale is not None:
                s = s * scale
            p = jnp.exp(s - _lanes(lse_ref[0, h], block_k))
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            dvs.append(jax.lax.dot_general(
                p.astype(do.dtype), do_h, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            dp = jax.lax.dot_general(
                do_h, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - _lanes(delta_ref[0, h], block_k))
            if scale is not None:
                ds = ds * scale
            dks.append(jax.lax.dot_general(
                ds.astype(q.dtype), q_h, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        dv_acc[:] += functools.reduce(operator.add, dvs)
        dk_acc[:] += functools.reduce(operator.add, dks)

    _flash_by_tile_class(_step, qi, kj, causal=causal, window=window,
                         block_q=block_q, block_k=block_k, tq=tq, tk=tk)

    @pl.when(i == n_qsweep - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_FLASH_STATICS + ("out_scale",))
def _flash_bwd(q, k, v, out, lse, g, *, causal, scale, out_scale, block_q,
               block_k, interpret, window):
    """(dq, dk, dv) of one call; a ``jax.jit`` of its own like
    ``_flash_fwd``.  ``q`` is the forward's (scale folded in where
    ``out_scale`` is set)."""
    _, Tq, H, D = q.shape
    Tk = k.shape[1]
    H_kv = k.shape[2]
    G = H // H_kv
    at = _flash_addressing(q.shape, k.shape, block_q, block_k)
    block_q, block_k, tq_p, tk_p = _flash_blocks(Tq, Tk, block_q, block_k)
    n_qb, n_kb = tq_p // block_q, tk_p // block_k
    qm, km, vm, dom = (at.operand(q, tq_p), at.operand(k, tk_p),
                       at.operand(v, tk_p), at.operand(g, tq_p))

    geom = dict(causal=causal, window=window, block_q=block_q,
                block_k=block_k)
    common = dict(scale=scale, tq=Tq, tk=Tk, heads=at.heads, head_dim=D,
                  **geom)
    live_k = functools.partial(_flash_live_k, n_kb=n_kb, **geom)

    q_spec, kv_spec, row_spec = _flash_k_sweep_specs(
        at, block_q, block_k, live_k)
    dq, delta = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_kb=n_kb,
                          out_scale=out_scale, **common),
        grid=(*at.grid_q, n_qb, n_kb),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, row_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(qm.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, at.width), jnp.float32)],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_dq",
    )(qm, km, vm, dom, at.operand(out, tq_p), lse)

    # dK/dV: grid over kv heads; the minor sweep covers (group member g,
    # q block) so all G q heads of a group fold into one accumulator.
    # The q block of a dead step is clamped into k tile j's live range
    # (within its group member), so it is not fetched.
    live_q = functools.partial(_flash_live_q, n_qb=n_qb, **geom)

    q_spec, kv_spec, row_spec = _flash_specs(
        at, block_q, block_k,
        lambda b, h, j, i: at.q_of_kv(b, h, i // n_qb),
        lambda b, h, j, i: live_q(j, i % n_qb),
        lambda b, h, j, i: (b, h), lambda b, h, j, i: j)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, n_qb=n_qb,
                          n_qsweep=n_qb * G, **common),
        grid=(*at.grid_kv, n_kb, n_qb * G),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(km.shape, k.dtype),
                   jax.ShapeDtypeStruct(vm.shape, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((block_k, at.width), jnp.float32),
            pltpu.VMEM((block_k, at.width), jnp.float32),
        ],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qm, km, vm, dom, lse, delta)
    return (at.result(dq, Tq, H), at.result(dk, Tk, H_kv),
            at.result(dv, Tk, H_kv))


def _flash_call(kernels, q, k, causal, scale, block_q, block_k, interpret,
                window):
    """What every trace of a call does outside the jitted kernels: the
    checks, the gauges of the path taken, and the scale's split.  Returns
    (what multiplies Q outside the kernels and dQ inside, or None; the
    static arguments of ``_flash_fwd`` / ``_flash_bwd``)."""
    window = check_attention_window(window, causal)
    _gqa_groups(q, k)
    scale, folded = _flash_scale(scale, q.shape[-1])
    _note_flash_call(kernels, q.shape, k.shape, block_q, block_k, causal,
                     window)
    return (scale if folded else None), dict(
        causal=causal, scale=None if folded else scale, block_q=block_q,
        block_k=block_k, interpret=_interpret(interpret), window=window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal=False, scale=None, block_q=256,
                    block_k=1024, interpret=None, window=None):
    """Blockwise-softmax attention, forward and backward as Pallas kernels.

    q: (B, Tq, H, D); k/v: (B, Tk, H_kv, D) -> (B, Tq, H, D).  H_kv may
    divide H (grouped-query attention): q heads share kv blocks via the
    BlockSpec index maps — the repeat is never materialized — and the
    dK/dV kernel folds all G = H/H_kv group members of the q sweep into
    one kv-head accumulator.  The backward is the standard two-pass
    recompute (dQ kernel + dK/dV kernel) driven by the forward's saved
    row logsumexp — memory stays one tile per operand, the full
    attention matrix is never materialized in either direction.

    ``window=W`` (requires ``causal=True``) restricts each query to keys
    in ``(q - W, q]`` — sliding-window local attention.

    **Layout.**  Where several heads share a 128-lane block (D = 64:
    two), the kernels read q, k, v, dO, O and write O, dQ, dK, dV in the
    layout the projections produce: (B, T, H, D) seen as (B, T, H * D),
    a free reshape, out of which a BlockSpec picks (1, block, 128) tiles
    by (batch, sequence block, head step).  No transpose, pad or copy of
    an operand surrounds a call, and a grid step does 128 / D heads,
    each product over the full lane width with the other heads' lanes
    zeroed — the MXU passes a D-wide product costs anyway.  One rule on
    the call's shapes (``flash_heads_per_step``) says which calls: D a
    divisor of 128 and smaller, plain MHA, H a multiple of 128 / D, T a
    multiple of the blocks.  Any other call (D = 80, an odd H at D = 64,
    GQA at D < 128, ragged T, and D a multiple of 128, where the copies
    waste no lanes) runs the same kernels over transposed (B * H, T, D)
    copies, T zero-padded to the blocks, one head a step; with grouped
    queries K/V's index map names the shared kv head's row.  Nothing
    selects the layout but the shapes; ``vt_flash_layout`` says which a
    traced call took.

    **Tile classes.**  Every (block_q, block_k) grid step of the three
    kernels is classed from its grid indices and the call's static sizes
    (``_flash_block_live``, ``_flash_tile_interior``), and does only what
    its class needs:

    * *dead* — no unmasked pair (above the diagonal, or wholly before the
      window): neither computed nor fetched.  The body is skipped, and
      the index maps clamp the sweeping operand's block index into the
      live range, so consecutive dead steps name the block already
      resident and no DMA is issued.  Cost is O(T·W) with a window
      instead of O(T²/2).
    * *interior* — every pair valid (wholly on or below the diagonal,
      inside the window, no padded row or column): a body without iota,
      compare or select.
    * *edge* — the rest (diagonal tiles, window borders, padded tails):
      the masked body, which is correct for any tile.

    ``flash_tile_classes`` counts them for a call; ``vt_flash_tiles``
    reports the counts of the last traced call.  A power-of-two ``scale``
    (D = 64: 0.125) is applied once to Q outside the kernels, where it is
    exact and fuses into whatever produced Q, instead of to every score
    tile.

    **Lowered once.**  The ``pl.pallas_call``s sit in two jitted
    functions (``_flash_fwd``, ``_flash_bwd``) with the geometry static,
    so a program's equal layers share one lowering of each kernel.

    The default blocks (256, 1024) are what a caller without a measured
    pick gets; no benchmark cell runs them (``MultiHeadAttention.prepare``
    measures four shapes and ``xla``; docs/autotune.md)."""
    fold, statics = _flash_call(("flash_fwd",), q, k, causal, scale,
                                block_q, block_k, interpret, window)
    return _flash_fwd(q if fold is None else q * fold, k, v, **statics)[0]


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                   window):
    fold, statics = _flash_call(("flash_fwd",), q, k, causal, scale,
                                block_q, block_k, interpret, window)
    q = q if fold is None else q * fold
    out, lse = _flash_fwd(q, k, v, **statics)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, window,
                   res, g):
    q, k, v, out, lse = res
    fold, statics = _flash_call(("flash_bwd_dq", "flash_bwd_dkv"), q, k,
                                causal, scale, block_q, block_k, interpret,
                                window)
    return _flash_bwd(q, k, v, out, lse, g, out_scale=fold, **statics)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# Grouped matrix products over ragged row groups (dropless experts)
# ---------------------------------------------------------------------------
#
# Rows arrive sorted by group, each group padded with zero rows to whole
# tiles of ``block_rows`` (``group_tiles``), so a tile of rows belongs to
# one group and the group's matrix is picked by a scalar-prefetched table
# in the BlockSpec index maps.  The grid's row dimension is dynamic: it is
# the number of tiles the groups really fill, so rows behind the last
# group cost no step, no fetch and no product (their output rows are left
# unwritten: callers never read them), and an empty group costs nothing.

def group_tiles(group_sizes, block_rows: int):
    """``(tiles (G,), row_start (G,))`` of ``group_sizes`` (G,) rows
    sorted by group: each group starts at a tile boundary and fills
    ``ceil(size / block_rows)`` tiles."""
    tiles = (group_sizes + block_rows - 1) // block_rows
    row_start = (jnp.cumsum(tiles) - tiles) * block_rows
    return tiles.astype(jnp.int32), row_start.astype(jnp.int32)


def tile_groups(group_sizes, n_rows: int, block_rows: int):
    """``(n_tiles (), tile_group (n_rows // block_rows,))``: the tiles in
    use and each tile's group (tiles past ``n_tiles`` name the last)."""
    tiles, _ = group_tiles(group_sizes, block_rows)
    tile_end = jnp.cumsum(tiles)
    t = jnp.arange(n_rows // block_rows, dtype=jnp.int32)
    # compared with every group's end at once: the default's binary search
    # is a loop of dependent gathers
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, t, side="right", method="compare_all"),
        group_sizes.shape[0] - 1)
    return tile_end[-1].astype(jnp.int32), tile_group.astype(jnp.int32)


def _gmm_kernel(tile_group_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs):
    del tile_group_ref
    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    out_ref[...] = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[0], dims,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _gmm(lhs, rhs, n_tiles, tile_group, *, block_rows, transpose_rhs,
         interpret):
    """``out[rows of tile t] = lhs[rows of tile t] @ rhs[tile_group[t]]``
    (``@ rhs[...].T`` with ``transpose_rhs``) for the first ``n_tiles``
    tiles.  One step a tile; a group's whole matrix is one block, fetched
    once while the tiles before it compute."""
    M, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((block_rows, K), lambda t, g: (t, 0)),
                pl.BlockSpec((1,) + rhs.shape[1:],
                             lambda t, g: (g[t], 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, N), lambda t, g: (t, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_GMM_VMEM),
        interpret=_interpret(interpret),
        name="grouped_matmul_t" if transpose_rhs else "grouped_matmul",
    )(tile_group, lhs, rhs)


def _gmm_dw_kernel(step_group_ref, step_tile_ref, step_flags_ref, lhs_ref,
                   g_ref, out_ref, acc_ref):
    del step_group_ref, step_tile_ref
    flags = step_flags_ref[pl.program_id(1)]

    @pl.when(flags & 1 != 0)        # the group's first step
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(flags & 4 != 0)        # the group has rows
    def _():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(flags & 2 != 0)        # the group's last step
    def _():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _gmm_dw_block_cols(n: int, block_cols: int) -> int:
    """The columns a step of ``grouped_matmul_dw`` takes: the widest
    divisor of ``n`` that is a multiple of 128 and at most ``block_cols``,
    so that the grid's ``n // block`` column blocks cover every column
    (``min(block_cols, n)`` lost columns 1536..1855 of 1856); the whole
    width where no such divisor exists."""
    fits = [c for c in range(128, min(block_cols, n) + 1, 128) if n % c == 0]
    return fits[-1] if fits else n


def _gmm_dw(lhs, g, group_sizes, *, block_rows, block_cols, interpret):
    """``out[e] = lhs[rows of e].T @ g[rows of e]`` (G, K, N): the
    products' gradient to the groups' matrices.  A group's tiles are
    consecutive steps that add into one float32 block; a group with no
    rows gets one step that writes zeros and multiplies nothing."""
    M, K = lhs.shape
    N = g.shape[1]
    G = group_sizes.shape[0]
    n_row_tiles = M // block_rows
    tiles, _ = group_tiles(group_sizes, block_rows)
    steps = jnp.maximum(tiles, 1)
    step_end = jnp.cumsum(steps)
    step_start = step_end - steps
    tile_start = jnp.cumsum(tiles) - tiles
    s = jnp.arange(n_row_tiles + G, dtype=jnp.int32)
    step_group = jnp.minimum(
        jnp.searchsorted(step_end, s, side="right"), G - 1).astype(jnp.int32)
    step_tile = jnp.clip(tile_start[step_group] + s - step_start[step_group],
                         0, n_row_tiles - 1).astype(jnp.int32)
    flags = ((s == step_start[step_group]) * 1
             + (s == step_end[step_group] - 1) * 2
             + (group_sizes[step_group] > 0) * 4).astype(jnp.int32)
    block_cols = _gmm_dw_block_cols(N, block_cols)
    return pl.pallas_call(
        _gmm_dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // block_cols, step_end[-1].astype(jnp.int32)),
            in_specs=[
                pl.BlockSpec((block_rows, K),
                             lambda j, s, sg, st, sf: (st[s], 0)),
                pl.BlockSpec((block_rows, block_cols),
                             lambda j, s, sg, st, sf: (st[s], j)),
            ],
            out_specs=pl.BlockSpec((1, K, block_cols),
                                   lambda j, s, sg, st, sf: (sg[s], 0, j)),
            scratch_shapes=[pltpu.VMEM((K, block_cols), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((G, K, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_GMM_VMEM),
        interpret=_interpret(interpret),
        name="grouped_matmul_dw",
    )(step_group, step_tile, flags, lhs, g)


#: a group's whole matrix is one block (4 MB at 2048 x 1024 bf16), double
#: buffered beside the row tiles: more than the 16 MB a kernel gets unasked
_GMM_VMEM = 64 * 1024 * 1024


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def grouped_matmul(lhs, rhs, group_sizes, block_rows=128, block_cols=512,
                   interpret=None):
    """Ragged grouped product: ``lhs`` (M, K) holds its rows sorted by
    group in the tile-aligned layout of ``group_tiles`` (zero rows pad a
    group to whole tiles of ``block_rows``; M is a multiple of it),
    ``rhs`` is (G, K, N), ``group_sizes`` (G,) int32 the rows of each
    group, zero included.  Returns (M, N): row r of group e is ``lhs[r] @
    rhs[e]``; rows past the last group's tiles are not written and hold
    anything.  Forward (``grouped_matmul``), the gradient to the rows
    (``grouped_matmul_t``) and to the matrices (``grouped_matmul_dw``,
    ``block_cols`` wide a step) are three Pallas kernels; the rows' cost
    follows ``group_sizes``, not M."""
    n_tiles, tile_group = tile_groups(group_sizes, lhs.shape[0], block_rows)
    return _gmm(lhs, rhs, n_tiles, tile_group, block_rows=block_rows,
                transpose_rhs=False, interpret=interpret)


def _gmm_vjp_fwd(lhs, rhs, group_sizes, block_rows, block_cols, interpret):
    out = grouped_matmul(lhs, rhs, group_sizes, block_rows, block_cols,
                         interpret)
    return out, (lhs, rhs, group_sizes)


def _gmm_vjp_bwd(block_rows, block_cols, interpret, res, g):
    lhs, rhs, group_sizes = res
    n_tiles, tile_group = tile_groups(group_sizes, lhs.shape[0], block_rows)
    g = g.astype(lhs.dtype)
    dlhs = _gmm(g, rhs, n_tiles, tile_group, block_rows=block_rows,
                transpose_rhs=True, interpret=interpret)
    drhs = _gmm_dw(lhs, g, group_sizes, block_rows=block_rows,
                   block_cols=block_cols, interpret=interpret)
    return dlhs, drhs.astype(rhs.dtype), None


grouped_matmul.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


# ---------------------------------------------------------------------------
# Rows summed by token (the routed experts' combine and dispatch gradient)
# ---------------------------------------------------------------------------

#: the float32 sums of a token block stay in VMEM while the rows walk past
#: (42 MiB at 4096 x 2688); more tokens than this holds go block by block
_ROW_SUM_ACC_BYTES = 48 * 1024 * 1024
#: the sums, a tile of rows in float32 and the two tiles in flight
_ROW_SUM_VMEM = 64 * 1024 * 1024


def _row_sum_kernel(token_ref, weight_ref, rows_ref, out_ref, acc_ref,
                    tile_ref, sem, *, block_rows, block_tokens):
    b, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # single rows are read at 32 bits: packed rows cannot be sliced
    tile_ref[...] = rows_ref[...].astype(jnp.float32)

    def add(r, carry):
        t = token_ref[i * block_rows + r] - b * block_tokens

        @pl.when((t >= 0) & (t < block_tokens))
        def _():
            acc_ref[pl.ds(t, 1), :] += weight_ref[i * block_rows + r] \
                * tile_ref[pl.ds(r, 1), :]
        return carry

    jax.lax.fori_loop(0, block_rows, add, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        done = pltpu.make_async_copy(
            acc_ref, out_ref.at[pl.ds(b * block_tokens, block_tokens), :],
            sem)
        done.start()
        done.wait()


@functools.partial(jax.jit, static_argnames=(
    "n_tokens", "block_rows", "interpret"))
def _row_sum(rows, token_of_row, weight_of_row, n_tiles, *, n_tokens,
             block_rows, interpret):
    """``out[t] = sum of weight_of_row[r] * rows[r] over the r with
    token_of_row[r] == t`` among the first ``n_tiles`` tiles, (n_tokens,
    D) float32.  A ``jax.jit`` of its own: a program's equal calls are
    one jaxpr, lowered once (``_flash_fwd``)."""
    M, D = rows.shape
    blocks = -(-n_tokens * D * 4 // _ROW_SUM_ACC_BYTES)
    block_tokens = n_tokens if blocks == 1 \
        else _round_up(-(-n_tokens // blocks), 8)
    out = pl.pallas_call(
        functools.partial(_row_sum_kernel, block_rows=block_rows,
                          block_tokens=block_tokens),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(blocks, n_tiles),
            in_specs=[pl.BlockSpec((block_rows, D),
                                   lambda b, i, *_: (i, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((block_tokens, D), jnp.float32),
                            pltpu.VMEM((block_rows, D), jnp.float32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((blocks * block_tokens, D),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_ROW_SUM_VMEM),
        interpret=interpret,
        name="sum_rows_by_token",
    )(token_of_row, weight_of_row, rows)
    return out[:n_tokens]


def sum_rows_by_token(rows, token_of_row, n_tokens, weight_of_row=None,
                      block_rows=128, interpret=None):
    """``out[t] = sum of weight_of_row[r] * rows[r] over the r with
    token_of_row[r] == t`` (``weight_of_row`` None: 1), (n_tokens, D)
    float32, added in ascending r; a zero row for a token that no row
    names.  ``rows`` (M, D), M a multiple of ``block_rows``;
    ``token_of_row`` (M,) int32, anything outside 0 .. n_tokens - 1 for a
    row of no token, which reaches nothing, whatever it holds.

    With ``row`` (T, K) the rows of each token's K routes (>= M: none),
    ``token_of_row[row[t, k]] = t`` and ``weight_of_row[row[t, k]] =
    weight[t, k]``, this is ``out[t] = sum over k with row[t, k] < M of
    weight[t, k] * rows[row[t, k]]``, computed from the rows' side: the
    kernel walks the rows' tiles up to the last one with a token and
    what it fetches and adds follows the rows that have one, not T * K."""
    M = rows.shape[0]
    named = (token_of_row >= 0) & (token_of_row < n_tokens)
    token_of_row = jnp.where(named, token_of_row, -1).astype(jnp.int32)
    if weight_of_row is None:
        weight_of_row = jnp.ones(M, jnp.float32)
    last = jnp.max(jnp.where(named, jnp.arange(M, dtype=jnp.int32), -1))
    # one tile at least: the sums are written, zeros if it names no token
    n_tiles = jnp.maximum(last // block_rows + 1, 1)
    # a backward pass traces under an empty abstract mesh and a forward
    # pass under none: named alike, both find the one traced ``_row_sum``
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        return _row_sum(rows, token_of_row,
                        weight_of_row.astype(jnp.float32), n_tiles,
                        n_tokens=n_tokens, block_rows=block_rows,
                        interpret=_interpret(interpret))


# ---------------------------------------------------------------------------
# Paged-attention decode (fused page gather + online softmax)
# ---------------------------------------------------------------------------

def _paged_attn_kernel(ptab_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, scale, psz, hk, g,
                       window, n_ptab):
    """Grid = (B, n_ptab): row-major sweep over each slot's logical
    pages.  The page axis is minor and ``k_ref``/``v_ref`` blocks are
    addressed THROUGH the scalar-prefetched page table (``ptab_ref`` in
    SMEM drives the BlockSpec index map), so each step is a direct
    HBM→VMEM DMA of one physical pool page — the flat ``pool[ptab]``
    logical view is never materialized.  Online-softmax state (m/l/acc)
    persists in VMEM scratch across the page sweep, exactly like the
    flash kernel's k sweep."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    pos_b = pos_ref[b]

    def _step():
        q = q_ref[0].astype(jnp.float32)          # (H, Dh)
        k_pg = k_ref[0].astype(jnp.float32)       # (psz, Hk, Dh)
        v_pg = v_ref[0].astype(jnp.float32)
        d = q.shape[-1]
        qg = q.reshape(hk, g, d)
        # grouped scores against this page: (Hk, G, psz)
        s = jnp.einsum("kgd,tkd->kgt", qg, k_pg) * scale
        k_pos = j * psz + jax.lax.broadcasted_iota(
            jnp.int32, (hk, g, psz), 2)
        mask = k_pos <= pos_b
        if window is not None:
            mask = mask & (k_pos > pos_b - window)
        s = jnp.where(mask, s, -1e30)
        m = m_ref[:].reshape(hk, g, 1)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        m_ref[:] = m_new.reshape(hk * g, 1)
        l_ref[:] = (alpha.reshape(hk * g, 1) * l_ref[:]
                    + jnp.sum(p, axis=-1).reshape(hk * g, 1))
        acc_ref[:] = (acc_ref[:] * alpha.reshape(hk * g, 1)
                      + jnp.einsum("kgt,tkd->kgd", p,
                                   v_pg).reshape(hk * g, d))

    # pages wholly past the query position (and, with a sliding window,
    # wholly before it) contribute nothing: skip their DMA'd compute —
    # page 0 is always live (pos >= 0), so m/l never finalize empty
    live = j * psz <= pos_b
    if window is not None:
        live = live & (j * psz + psz - 1 > pos_b - window)
    pl.when(live)(_step)

    @pl.when(j == n_ptab - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:]
                    / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def paged_attention_decode(q, k_pool, v_pool, ptab, pos, *, page_size,
                           n_kv_heads, scale=None, window=None,
                           interpret=None):
    """One-position paged-attention decode: softmax(q·Kᵀ)·V where K/V
    live in a flat page pool and each batch row's logical pages are
    named by its page-table row.

    q: (B, H, Dh) query at each row's own position ``pos`` (B,) int32
    (RoPE already applied); k_pool/v_pool: (rows, page_size, Hk, Dh);
    ptab: (B, n_ptab) int32 physical page per logical page (rows beyond
    a slot's span point at the scratch page — masked off by ``pos``).
    Returns the (B, H, Dh) float32 attention context (pre output
    projection — the shared `_attn_scores` tail in runtime/generate.py
    applies wo/residual so layouts cannot drift).

    This is the fused half of the paged-KV design (docs/serving.md):
    the baseline gathers ``pool[ptab]`` into a (B, l_max, Hk, Dh)
    transient before the attention math; here the page table rides SMEM
    (scalar prefetch) and pages stream HBM→VMEM block-by-block through
    the BlockSpec index map, with online softmax across the sweep —
    numerics therefore differ by summation order (bounded error, pinned
    in tests/test_pallas.py), never bitwise.  Reference idiom: the
    jax.experimental paged_attention TPU kernel (one DMA per
    non-contiguous page, scalar-prefetched page indices)."""
    B, H, Dh = q.shape
    rows, psz, Hk, _ = k_pool.shape
    if psz != page_size:
        raise ValueError(f"pool page size {psz} != page_size {page_size}")
    if n_kv_heads != Hk:
        raise ValueError(f"pool holds {Hk} kv heads, caller declared "
                         f"{n_kv_heads}")
    G = check_gqa_heads(H, Hk)
    n_ptab = ptab.shape[1]
    window = check_attention_window(window, True)
    scale_ = scale if scale is not None else Dh ** -0.5
    kernel = functools.partial(
        _paged_attn_kernel, scale=scale_, psz=psz, hk=Hk, g=G,
        window=window, n_ptab=n_ptab)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_ptab),
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda b, j, ptab, pos: (b, 0, 0)),
            pl.BlockSpec((1, psz, Hk, Dh),
                         lambda b, j, ptab, pos: (ptab[b, j], 0, 0, 0)),
            pl.BlockSpec((1, psz, Hk, Dh),
                         lambda b, j, ptab, pos: (ptab[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Dh),
                               lambda b, j, ptab, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, Dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), jnp.float32),
        # batch rows are independent; the page sweep carries the
        # online-softmax state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(interpret),
        name="paged_attention_decode",
    )(jnp.asarray(ptab, jnp.int32), jnp.asarray(pos, jnp.int32),
      q, k_pool, v_pool)


# ---------------------------------------------------------------------------
# Fused dropout with in-kernel counter-based RNG
# ---------------------------------------------------------------------------

# np scalars stay literals under tracing
_GOLDEN = np.uint32(0x9E3779B9)
_MIX1 = np.uint32(0x85EBCA6B)
_MIX2 = np.uint32(0xC2B2AE35)


def _splitmix32(z):
    z = (z + _GOLDEN).astype(jnp.uint32)
    z = (z ^ (z >> 16)) * _MIX1
    z = (z ^ (z >> 13)) * _MIX2
    return z ^ (z >> 16)


def _dropout_kernel(seed_ref, x_ref, o_ref, *, rate, block_rows, block_cols,
                    n_cols):
    # The mask bit for element (row, col) is a hash of its GLOBAL linear
    # index, so the mask is identical for any (block_rows, block_cols)
    # tiling — backward can regenerate it with different tile choices —
    # and, through the row offset in seed_ref[0, 1], for any sharding of
    # the rows over a mesh (Dropout.apply passes each shard its offset).
    pid_r, pid_c = pl.program_id(0), pl.program_id(1)
    r = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, block_cols), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, block_cols), 1)
    row = (seed_ref[0, 1] + pid_r.astype(jnp.uint32) * np.uint32(block_rows)
           + r)
    col = pid_c.astype(jnp.uint32) * np.uint32(block_cols) + c
    lin = row * np.uint32(n_cols) + col
    # One fmix32-style finalizer pass (add-xorshift-mul x2) is already a
    # full-avalanche mixer for counter inputs; u32 multiplies are the
    # VPU's slow op, and a second pass measurably lost to XLA's threefry
    # on-chip.  Seed is pre-whitened so consecutive seeds
    # don't produce correlated streams.
    bits = _splitmix32(lin ^ _splitmix32(seed_ref[0, 0]))
    # top 24 bits -> uniform in [0, 1); Mosaic lacks uint32->f32 casts, so
    # bitcast the (always-positive) value through int32 first.
    u = jax.lax.bitcast_convert_type(
        bits >> 8, jnp.int32).astype(jnp.float32) * (1.0 / 16777216.0)
    keep = (u >= rate).astype(jnp.float32) / (1.0 - rate)
    o_ref[:] = (x_ref[:].astype(jnp.float32) * keep).astype(o_ref.dtype)


# Per-block element budget: a few f32 buffers per block must fit VMEM
# (~16 MB) with headroom for Mosaic's stack.
_DROPOUT_BLOCK_ELEMS = 1 << 19


def _dropout_apply(x, seed, rate, block_rows, interpret, row_offset=0):
    orig_shape = x.shape
    flat = x.reshape(-1, orig_shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    rows, cols = flat.shape
    if cols <= 8192:
        block_cols = _round_up(cols, 128)
    else:
        # Near-equal 128-aligned column blocks keep padding under one lane
        # width (a flat 8192 cap would pad e.g. 8320 cols to 16384 —
        # nearly doubling hashed+written elements).
        n_cb = -(-cols // 8192)
        block_cols = _round_up(-(-cols // n_cb), 128)
    block_rows = max(8, min(block_rows, rows,
                            _DROPOUT_BLOCK_ELEMS // block_cols))
    rows_p = _round_up(rows, block_rows)
    cols_p = _round_up(cols, block_cols)
    flat = jnp.pad(flat, ((0, rows_p - rows), (0, cols_p - cols)))
    seed_arr = jnp.stack([jnp.asarray(seed, jnp.uint32),
                          jnp.asarray(row_offset, jnp.uint32)]).reshape(1, 2)
    kernel = functools.partial(_dropout_kernel, rate=float(rate),
                               block_rows=block_rows,
                               block_cols=block_cols, n_cols=cols)
    out = pl.pallas_call(
        kernel,
        grid=(rows_p // block_rows, cols_p // block_cols),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_cols),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows_p, cols_p), x.dtype),
        interpret=_interpret(interpret),
        name="dropout",
    )(seed_arr, flat)
    return out[:rows, :cols].reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fused_dropout(x, seed, rate, block_rows=256, interpret=None,
                  row_offset=0):
    """Dropout whose mask is a deterministic splitmix32 hash of
    (seed, element index), generated inside the kernel.  The backward pass
    re-runs the same kernel on the cotangent — the mask is never stored
    (reference stored the random state per unit: ocl/random.cl).

    ``row_offset`` is the global index of ``x``'s first row (all but the
    last axis flattened) when ``x`` is one shard of a larger array: the
    hash then sees global indices and the mask does not depend on how the
    rows are sharded."""
    return _dropout_apply(x, seed, rate, block_rows, interpret, row_offset)


def _dropout_vjp_fwd(x, seed, rate, block_rows, interpret, row_offset):
    return (_dropout_apply(x, seed, rate, block_rows, interpret, row_offset),
            (seed, row_offset))


def _dropout_vjp_bwd(rate, block_rows, interpret, res, g):
    # Same seed and offset -> same mask -> d/dx (x * keep) = g * keep.
    seed, row_offset = res
    return (_dropout_apply(g, seed, rate, block_rows, interpret, row_offset),
            None, None)


fused_dropout.defvjp(_dropout_vjp_fwd, _dropout_vjp_bwd)


# ---------------------------------------------------------------------------
# Softmax cross-entropy by rows: one sweep of the logits forward
# ---------------------------------------------------------------------------
#
# What the loss needs of a row of logits is four numbers, all functions of
# one pass over it: the maximum, the sum of exponentials (together the
# logsumexp), the label's logit and the first index of the maximum.  The
# forward kernel sweeps a row block class block by class block with the
# running maximum and sum in VMEM (the flash kernels' recurrence, without
# the products).  The backward needs no kernel: ``(exp(x - lse) - onehot)
# * g`` is elementwise in the logits and the saved logsumexp, and XLA
# computes it inside the operations that consume it (the head's two
# backward products and its bias sum read the logits and never see a
# gradient array; PERF.md section 6, PR 30).  Nothing else of the logits'
# size exists: no log-probabilities, no one-hot, no second layout.

#: requested (classes, rows) of a tile: 2 MB of float32
_XENT_BLOCK = (1024, 512)


def _xent_fwd_kernel(x_ref, lab_ref, lse_ref, pick_ref, pred_ref, m_ref,
                     l_ref, *, n_classes, block_c):
    """One (block_c, block_r) tile of the transposed logits: classes down
    the sublanes, rows along the lanes, so a row's state is one lane and
    the reductions over classes are elementwise between registers."""
    j = pl.program_id(1)
    n_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        pick_ref[:] = jnp.zeros_like(pick_ref)
        pred_ref[:] = jnp.zeros_like(pred_ref)

    def sweep(padded):
        x = x_ref[...].astype(jnp.float32)
        # classes count from the block's first: the label and the array's
        # edge are shifted once a row, not the iota once an element
        cls = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        if padded:
            x = jnp.where(cls < n_classes - j * block_c, x, -jnp.inf)
        m_blk = jnp.max(x, axis=0, keepdims=True)
        first = jnp.min(jnp.where(x == m_blk, cls, block_c), axis=0,
                        keepdims=True) + j * block_c
        m_old = m_ref[:]
        # a later block wins only with a larger maximum: ties go to the
        # first index, as jnp.argmax has it
        pred_ref[:] = jnp.where(m_blk > m_old, first, pred_ref[:])
        m_new = jnp.maximum(m_old, m_blk)
        # rows that are -inf so far (a masked vocabulary's first blocks)
        # shift by 0: exp(-inf - 0) is 0, where -inf - -inf is NaN
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        l_ref[:] = (l_ref[:] * jnp.exp(m_old - m_safe)
                    + jnp.sum(jnp.exp(x - m_safe), axis=0, keepdims=True))
        m_ref[:] = m_new
        pick_ref[:] += jnp.sum(
            jnp.where(cls == lab_ref[:] - j * block_c, x, 0.0), axis=0,
            keepdims=True)

    if n_classes % block_c:
        pl.when(j < n_j - 1)(lambda: sweep(False))
        pl.when(j == n_j - 1)(lambda: sweep(True))
    else:
        sweep(False)

    @pl.when(j == n_j - 1)
    def _():
        lse_ref[:] = m_ref[:] + jnp.log(l_ref[:])


def _xent_fwd(logits, labels, interpret):
    """(ce, pred, lse) of (rows, classes) logits, each of (rows,).

    The kernel reads the logits transposed, (classes, rows).  That is the
    layout XLA itself gives the logits of a wide head (rows minor: the
    head's three products want it, PERF.md section 6, PR 30), so the
    transpose is a change of name and no copy; a kernel over (rows,
    classes) made XLA copy the logits where the products kept theirs."""
    rows, classes = logits.shape
    # the requested tile, cut to the array where it is smaller (a block
    # may equal a whole axis whatever its size; else classes go by 8 or
    # 16 sublanes and rows by the lane width)
    block_c = min(_XENT_BLOCK[0], classes)
    block_r = min(_XENT_BLOCK[1], rows)
    row = pl.BlockSpec((1, block_r), lambda i, j: (0, i))
    lse, pick, pred = pl.pallas_call(
        functools.partial(_xent_fwd_kernel, n_classes=classes,
                          block_c=block_c),
        grid=(pl.cdiv(rows, block_r), pl.cdiv(classes, block_c)),
        in_specs=[pl.BlockSpec((block_c, block_r), lambda i, j: (j, i)),
                  row],
        out_specs=[row, row, row],
        out_shape=[jax.ShapeDtypeStruct((1, rows), jnp.float32),
                   jax.ShapeDtypeStruct((1, rows), jnp.float32),
                   jax.ShapeDtypeStruct((1, rows), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, block_r), jnp.float32),
                        pltpu.VMEM((1, block_r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(interpret),
        name="softmax_xent_fwd",
    )(logits.T, labels.reshape(1, rows).astype(jnp.int32))
    return (lse - pick)[0], pred[0], lse[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_xent_rows(logits, labels, interpret=None):
    """Per-row softmax cross-entropy and prediction of ``logits`` (rows,
    classes) against integer ``labels`` (rows,), in one sweep of the
    logits: ``(ce, pred)``, float32 and int32, ``pred`` the first index of
    the row's maximum.  All arithmetic is float32 whatever the logits'
    dtype.  The residuals are the logits and one float32 logsumexp a row;
    the logits' gradient is an elementwise expression of the two, in the
    logits' dtype, left to XLA to compute where it is consumed; ``pred``
    has no gradient."""
    ce, pred, _ = _xent_fwd(logits, labels, interpret)
    return ce, pred


def _xent_vjp_fwd(logits, labels, interpret):
    ce, pred, lse = _xent_fwd(logits, labels, interpret)
    return (ce, pred), (logits, labels, lse)


def _xent_vjp_bwd(interpret, res, g):
    logits, labels, lse = res
    with jax.named_scope("softmax_xent_bwd"):
        p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
        hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) \
            == labels[:, None]
        return (jnp.where(hit, p - 1.0, p) * g[0][:, None]).astype(
            logits.dtype), None


softmax_xent_rows.defvjp(_xent_vjp_fwd, _xent_vjp_bwd)


# ---------------------------------------------------------------------------
# Minibatch gather via scalar-prefetched indices
# ---------------------------------------------------------------------------

def _gather_kernel(idx_ref, data_ref, out_ref, sem):
    i = pl.program_id(0)
    dma = pltpu.make_async_copy(data_ref.at[idx_ref[i]], out_ref.at[i], sem)
    dma.start()
    dma.wait()


def pack_rows(data):
    """Pre-pack ``data`` (N, ...) into the (N, 8, f_p/8) tiled row layout
    ``gather_rows_packed`` DMAs from (features padded to a multiple of
    8·128 — Mosaic rejects single-row slices of a (8,128)-tiled 2-D memref,
    so the per-index DMA must slice only an untiled leading dim).  Pack once
    at dataset-upload time; gathering from the packed form then never
    touches the full dataset again (see FullBatchLoader._upload)."""
    orig_shape = data.shape
    flat = data.reshape(orig_shape[0], -1)
    n, f = flat.shape
    f_p = _round_up(f, 8 * 128)
    packed = jnp.pad(flat, ((0, 0), (0, f_p - f))).reshape(n, 8, f_p // 8)
    return packed, f, orig_shape[1:]


def unpack_rows(packed, f, sample_shape):
    m = packed.shape[0]
    return packed.reshape(m, -1)[:, :f].reshape((m,) + tuple(sample_shape))


def gather_rows_packed(packed, idx, *, interpret=None):
    """Gather pre-packed rows (see ``pack_rows``) as one direct HBM→HBM DMA
    per scalar-prefetched index."""
    m = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m,) + packed.shape[1:],
                                       packed.dtype),
        interpret=_interpret(interpret),
        name="gather_rows_packed",
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(jnp.asarray(idx, jnp.int32), packed)


def gather_rows(data, idx, *, interpret=None):
    """``data[idx]`` via per-index HBM DMA (reference:
    ocl/fullbatch_loader.cl fill_minibatch_data_labels).  Convenience
    one-shot form — packs on every call; steady-state callers should
    ``pack_rows`` once and use ``gather_rows_packed``."""
    packed, f, sample_shape = pack_rows(data)
    out = gather_rows_packed(packed, idx, interpret=interpret)
    return unpack_rows(out, f, sample_shape)
