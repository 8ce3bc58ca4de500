"""Expert parallelism: mixture-of-experts layer sharded over a mesh axis.

NOT in the reference (SURVEY.md §2.5 item 4) — new TPU-native design. The
expert FFN bank is a batched gemm with a leading expert axis sharded over
``expert``.

Two dispatch formulations, same routing semantics (GShard slot priority:
every token's slot-0 route queues before any slot-1 route; capacity
overflow drops the weakest routes):

* ``"sort"`` (default, round 3): route queue positions come from a
  stable argsort by expert id; tokens scatter into their (expert, slot)
  rows and combine gathers them back.  Peak memory is
  O(T·K + E·C·D + T·K·D) — no tensor couples T with C, so it scales to
  real token counts (the round-2 one-hot formulation's (T, K, E, C)
  slot tensor is O(T²·K/E) at fixed capacity_factor and dominated HBM).
* ``"dense"`` (round 2): one-hot einsum dispatch — kept because its
  dispatch/combine einsums are what GSPMD lowers to all_to_all over ICI
  when the expert axis is sharded, and as the cross-check reference.

Beside the capacity path, ``routed_experts_apply`` is the dropless one:
no capacity and no drops, an expert's rows are whatever the router gives
it (zero included), the layer is told which experts of the router's
width it holds and computes their part of the result, and the experts are
gated (three matrices).  Its products are ragged grouped products sized
by the routes (``ops/pallas_kernels.grouped_matmul``).  What it knows
about where routes go is read off one stable sort of the routes by expert
(``dispatch_plan``: the sort's order and the experts' sizes;
``rows_routes``: the route in each row of a buffer, a gather of the
buffer's M rows from that order), and a route's score is selected, not
gathered (``chosen_scores``): on the kernel path no scatter or gather of
T * K single elements exists, forward or backward, because on the TPU
each costs 3 to 9 ns an element where a vector pass over all of them is
microseconds.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.activations import ACTIVATIONS


def init_moe_params(key, n_experts: int, d_model: int, d_hidden: int,
                    dtype=jnp.float32) -> dict:
    kw1, kw2, kr = jax.random.split(key, 3)
    scale1 = 1.0 / jnp.sqrt(d_model)
    scale2 = 1.0 / jnp.sqrt(d_hidden)
    return {
        "router": jax.random.uniform(kr, (d_model, n_experts), dtype,
                                     -scale1, scale1),
        "w1": jax.random.uniform(kw1, (n_experts, d_model, d_hidden),
                                 dtype, -scale1, scale1),
        "w2": jax.random.uniform(kw2, (n_experts, d_hidden, d_model),
                                 dtype, -scale2, scale2),
    }


def _route_positions(topi: jnp.ndarray, E: int) -> jnp.ndarray:
    """Queue position of each (token, slot) route within its expert.

    Routes are ordered slot-major (all slot-0 routes before any slot-1
    route — GShard priority); the position equals the count of earlier
    same-expert routes, exactly what the dense formulation's masked
    cumsum computed, at O(T·K·log) sort cost and O(T·K) memory instead
    of an O(T·K·E) cumsum tensor."""
    T, K = topi.shape
    flat_e = topi.T.reshape(-1)                     # slot-major (K*T,)
    perm = jnp.argsort(flat_e, stable=True)         # groups by expert,
    seg = flat_e[perm]                              # priority-stable
    starts = jnp.searchsorted(seg, jnp.arange(E), side="left")
    pos_sorted = jnp.arange(T * K, dtype=jnp.int32) \
        - starts[seg].astype(jnp.int32)
    pos_flat = jnp.zeros(T * K, jnp.int32).at[perm].set(pos_sorted)
    return pos_flat.reshape(K, T).T                 # (T, K)


# -- shared building blocks of the "sort" formulation -----------------------
# moe_apply's local path and moe_apply_manual's expert-parallel path are
# contractually identical in routing, combine weights, and aux statistics
# (the fused-1F1B exactness tests depend on it) — so the steps live ONCE.

def _route(params: dict, x: jnp.ndarray, top_k: int):
    """Router logits -> (gates, topi, probs); Switch keeps the raw top-1
    probability (renormalizing would cut the router out of backward)."""
    logits = x @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    if top_k == 1:
        gates = topv
    else:
        gates = topv / jnp.maximum(
            jnp.sum(topv, axis=-1, keepdims=True), 1e-9)
    return gates, topi, probs


def _pack_slots(x: jnp.ndarray, topi: jnp.ndarray, E: int, C: int):
    """Scatter tokens into their (expert, slot) rows -> (slot_idx, keep,
    xe (E, C, D)); dropped routes target the out-of-bounds row E*C."""
    T, D = x.shape
    K = topi.shape[1]
    pos = _route_positions(topi, E)
    keep = pos < C
    slot_idx = jnp.where(keep, topi * C + pos, E * C)
    xk = jnp.broadcast_to(x[:, None, :], (T, K, D)).reshape(T * K, D)
    xe = jnp.zeros((E * C, D), x.dtype) \
        .at[slot_idx.reshape(-1)].add(xk, mode="drop") \
        .reshape(E, C, D)
    return slot_idx, keep, xe


def _expert_ffn(xe: jnp.ndarray, w1: jnp.ndarray, w2: jnp.ndarray,
                out_dtype) -> jnp.ndarray:
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", xe, w1,
                               preferred_element_type=jnp.float32))
    return jnp.einsum("ech,ehd->ecd", h.astype(out_dtype), w2)


def _combine_slots(ye: jnp.ndarray, slot_idx: jnp.ndarray,
                   keep: jnp.ndarray, gates: jnp.ndarray,
                   x_dtype) -> jnp.ndarray:
    E_C, D = ye.shape[0] * ye.shape[1], ye.shape[2]
    T, K = slot_idx.shape
    yk = ye.reshape(E_C, D)[
        jnp.clip(slot_idx, 0, E_C - 1).reshape(-1)].reshape(T, K, D)
    w = (gates * keep.astype(gates.dtype)).astype(x_dtype)
    return jnp.einsum("tk,tkd->td", w, yk)


def _switch_aux(topi: jnp.ndarray, probs: jnp.ndarray,
                axis_name: Optional[str] = None) -> jnp.ndarray:
    """Switch load-balance loss on the primary assignment (bincount form:
    no (T, E) one-hot materialization).

    With ``axis_name`` (the expert-parallel shard_map path) the token
    statistics are psum'd over that axis first, so ``frac_tokens`` /
    ``frac_probs`` are fractions of the dispatch group's FULL token batch
    and the aux matches ``moe_apply``'s global-batch formulation exactly
    — rank-local fractions averaged after the fact are NOT the same
    number (E·Σ mean_r(f_r)·mean_r(p_r) ≠ mean_r(E·Σ f_r·p_r))."""
    T, E = probs.shape
    counts = jnp.zeros(E, jnp.float32).at[topi[:, 0]].add(1.0)
    prob_sums = jnp.sum(probs.astype(jnp.float32), axis=0)
    n_tokens = jnp.asarray(T, jnp.float32)
    if axis_name is not None:
        counts = jax.lax.psum(counts, axis_name)
        prob_sums = jax.lax.psum(prob_sums, axis_name)
        n_tokens = n_tokens * jax.lax.psum(1, axis_name)
    frac_tokens = counts / n_tokens
    frac_probs = prob_sums / n_tokens
    return E * jnp.sum(frac_tokens * frac_probs)


def moe_apply(params: dict, x: jnp.ndarray, *,
              capacity_factor: float = 1.25, top_k: int = 1,
              dispatch_mode: str = "sort"
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k MoE FFN (round 2: k >= 1 with renormalized combine weights;
    round 1 was top-1 only).

    x: (tokens, d_model) -> (tokens, d_model), plus the load-balancing
    auxiliary loss (Switch-style: E * sum_e f_e * p_e over the primary
    assignment).  Slot priority is GShard-style: all tokens' first choices
    queue before any second choice, so capacity overflow drops the weakest
    routes first.  Tokens over capacity are dropped (0 contribution for
    that route).  ``dispatch_mode``: "sort" (scalable scatter/gather,
    default) or "dense" (one-hot einsums) — identical outputs (tests
    assert it); see the module docstring for the trade.
    """
    T, D = x.shape
    E = params["router"].shape[1]
    K = int(top_k)
    C = max(1, int(capacity_factor * T * K / E))

    gates, topi, probs = _route(params, x, K)

    if dispatch_mode == "sort":
        slot_idx, keep, xe = _pack_slots(x, topi, E, C)
        ye = _expert_ffn(xe, params["w1"], params["w2"], x.dtype)
        y = _combine_slots(ye, slot_idx, keep, gates, x.dtype)
    elif dispatch_mode == "dense":
        onehots = jax.nn.one_hot(topi, E, dtype=x.dtype)  # (T, K, E)
        # queue positions, slot-major (GShard priority).  The cumsum runs
        # in f32 regardless of activation dtype — a bf16 cumsum loses
        # integer exactness past 256 and collides capacity slots.
        oh_flat = onehots.transpose(1, 0, 2).reshape(K * T, E) \
            .astype(jnp.float32)
        pos_flat = jnp.cumsum(oh_flat, axis=0) * oh_flat - 1.0
        pos = pos_flat.reshape(K, T, E).transpose(1, 0, 2)    # (T, K, E)
        keep = (pos >= 0) & (pos < C)
        slot = jax.nn.one_hot(
            jnp.clip(pos, 0, C - 1).astype(jnp.int32), C,
            dtype=x.dtype) * keep.astype(x.dtype)[..., None]  # (T,K,E,C)
        # combine carries the gate weights; dispatch is its 0/1 support
        combine = jnp.einsum("tk,tkec->tec", gates.astype(x.dtype), slot)
        dispatch = (combine > 0).astype(x.dtype)

        # dispatch -> (E, C, D): with expert axis sharded, GSPMD lowers
        # this to an all_to_all over ICI
        xe = jnp.einsum("tec,td->ecd", dispatch, x)
        h = jax.nn.relu(jnp.einsum("ecd,edh->ech", xe, params["w1"],
                                   preferred_element_type=jnp.float32))
        ye = jnp.einsum("ech,ehd->ecd", h.astype(x.dtype), params["w2"])
        y = jnp.einsum("tec,ecd->td", combine, ye)
    else:
        raise ValueError(f"unknown dispatch_mode {dispatch_mode!r}")

    aux = _switch_aux(topi, probs)
    return y, aux


def moe_apply_manual(params: dict, x: jnp.ndarray, *, axis_name: str,
                     capacity_factor: float = 1.25, top_k: int = 1
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE for code ALREADY inside a ``shard_map`` (a
    pipeline-schedule body, Context.manual_axes): ``x`` is this rank's
    token shard, the expert partition lives on mesh axis ``axis_name``,
    and dispatch/combine are explicit ``all_to_all`` over that axis —
    the hand-written form of what GSPMD lowers the sharded einsums to
    (round-4 verdict #3: expert-parallel MoE inside fused-1F1B stages).

    Every rank routes its own tokens with the (replicated) router, packs
    them into per-expert capacity slots exactly like ``moe_apply``'s
    "sort" mode, then exchanges slots so each rank computes ONLY its
    E/n experts — on slots from all ranks — with its slice of the
    (replicated) expert bank, and a second all_to_all carries results
    home.  Parameter gradients compose with a psum over ``axis_name``:
    each rank's grad is nonzero only in its expert slice (the slice is
    a dynamic_slice of the replicated bank), so the sum reassembles the
    full bank gradient exactly once per expert.

    Semantics vs the non-distributed ``moe_apply``: identical routing
    and combine weights; capacity is enforced PER SOURCE RANK (C =
    cf·T_local·K/E slots per expert per rank) rather than globally —
    the standard expert-parallel behavior.  With capacity ample enough
    that nothing drops the outputs are exact to the global formulation;
    the load-balance aux loss psums ``frac_tokens``/``frac_probs`` over
    ``axis_name`` so it equals ``moe_apply``'s global-batch formulation
    on the dispatch group's full token set (every rank returns the same
    value — reductions that average it across ranks keep it exact).

    Registered in ``analysis/registry.py`` ``SHARD_MAP_ROOTS`` with
    axis environment ``("expert",)``: the raw ``all_to_all``/``psum``/
    ``axis_index`` here (and in :func:`_switch_aux`, which joins the
    scope through the module-local closure) are legal exactly because
    callers are already inside a schedule shard_map — veles-tpu-lint
    VS502 enforces it.
    """
    T = x.shape[0]
    E = params["router"].shape[1]
    n = jax.lax.psum(1, axis_name)           # static inside shard_map
    if E % n:
        raise ValueError(
            f"n_experts={E} must divide over the {axis_name!r} axis ({n})")
    El = E // n
    rank = jax.lax.axis_index(axis_name)
    K = int(top_k)
    C = max(1, int(capacity_factor * T * K / E))

    gates, topi, probs = _route(params, x, K)
    slot_idx, keep, xe = _pack_slots(x, topi, E, C)
    # exchange: expert-major split — rank r receives every rank's slots
    # for ITS El experts, concatenated source-major on the slot axis
    xr = jax.lax.all_to_all(xe, axis_name, split_axis=0, concat_axis=1,
                            tiled=True)       # (El, n*C, D)
    w1 = jax.lax.dynamic_slice_in_dim(params["w1"], rank * El, El, 0)
    w2 = jax.lax.dynamic_slice_in_dim(params["w2"], rank * El, El, 0)
    yr = _expert_ffn(xr, w1, w2, x.dtype)
    # inverse exchange: slot chunks go back to their source ranks
    ye = jax.lax.all_to_all(yr, axis_name, split_axis=1, concat_axis=0,
                            tiled=True)       # (E, C, D)
    y = _combine_slots(ye, slot_idx, keep, gates, x.dtype)
    aux = _switch_aux(topi, probs, axis_name=axis_name)
    return y, aux


# -- dropless routed experts --------------------------------------------------

def route_scores(x, router, *, top_k: int, bias=None,
                 route_norm: bool = True, route_scale: float = 1.0):
    """(weights (T, K) float32, expert ids (T, K)): sigmoid scores of all
    the router's experts in float32 (the product too, whatever the matmul
    default), the ``top_k`` of ``scores + bias`` (``bias`` gets no
    gradient), and their scores normalised to sum to one if
    ``route_norm``, times ``route_scale``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    choose = scores if bias is None \
        else scores + jax.lax.stop_gradient(bias)
    _, topi = jax.lax.top_k(jax.lax.stop_gradient(choose), top_k)
    w = chosen_scores(scores, topi)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * route_scale, topi


def chosen_scores(scores, topi):
    """``scores[t, topi[t, k]]`` (T, K) by selection, not by a gather: the
    sum over the router's width of the scores where the expert is the
    chosen one has one term that is not zero, so it is ``take_along_axis``
    bit for bit, and its gradient is the same selection summed over a
    token's routes (whose experts are distinct), where a gather's would
    scatter T * K single elements into (T, n_experts).  Written experts
    by tokens: with the tokens in the lanes a route's expert is spread
    over sublanes and the sum over experts adds sublanes (PERF.md section
    6, PR 37: 0.008 ms a layer where routes by experts took 0.133)."""
    experts = jnp.arange(scores.shape[-1], dtype=topi.dtype)[None, :, None]
    picked = jnp.where(topi.T[:, None, :] == experts, scores.T[None], 0.0)
    return jnp.sum(picked, axis=1).T                    # (K, E, T) summed


def dispatch_plan(topi, n_held: int, offset: int, block_rows: int):
    """The one stable sort of the routes by expert, and what is read off
    it: ``(sizes (n_held,), order (T * K,), rows_needed ())``.

    A route's key is its expert's index among the held ones, ``offset ..
    offset + n_held``, or ``n_held`` for an expert held elsewhere, so the
    held experts' routes come first in ``order``, expert by expert and by
    token within an expert, each route ``t * K + k`` once.  ``sizes`` are
    the routes each held expert got, counted by comparing the keys with
    the experts' indices (a vector pass, not a scatter-add of ones);
    ``rows_needed`` the rows up to the last expert's last tile when each
    expert's rows start at a multiple of ``block_rows``.  Which route
    sits in which row of a buffer is ``rows_routes``'s to say, from
    these alone: no array here is indexed by T * K single elements."""
    from ..ops.pallas_kernels import group_tiles
    local = topi.reshape(-1) - offset
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    held = jnp.arange(n_held, dtype=key.dtype)[:, None] == key[None, :]
    sizes = jnp.sum(held, axis=1, dtype=jnp.int32)
    tiles, _ = group_tiles(sizes, block_rows)
    return sizes, order, jnp.sum(tiles) * block_rows


def rows_routes(M: int, sizes, order, block_rows: int):
    """``route_of_row`` (M,): the route in each row of a buffer of M rows
    (a multiple of ``block_rows``), T * K where the row has none, for
    ``dispatch_plan``'s ``sizes`` and ``order``.

    Held expert e's routes are ``order[first[e] : first[e] + sizes[e]]``
    and its rows start at ``row_start[e]``, so row r of expert ``e_r``
    holds ``order[first[e_r] + r - row_start[e_r]]`` while ``r -
    row_start[e_r] < sizes[e_r]``.  A tile of rows has one expert
    (``tile_groups``), so what depends on the expert is looked up once a
    tile and ``order`` is gathered once a row: M elements, where the
    inverse of a route-to-row map is a scatter of T * K."""
    from ..ops.pallas_kernels import group_tiles, tile_groups
    R = order.shape[0]
    _, row_start = group_tiles(sizes, block_rows)
    first = jnp.cumsum(sizes) - sizes
    _, tile_group = tile_groups(sizes, M, block_rows)
    tile_row = jnp.arange(M // block_rows, dtype=jnp.int32) * block_rows
    nth = (tile_row - row_start[tile_group])[:, None] \
        + jnp.arange(block_rows, dtype=jnp.int32)
    at = jnp.minimum(first[tile_group][:, None] + nth, R - 1).reshape(M)
    has_route = (nth < sizes[tile_group][:, None]).reshape(M)
    return jnp.where(has_route, order[at], R)


def buffer_rows(n_tokens: int, top_k: int, n_held: int, n_experts: int,
                block_rows: int):
    """(small, large) row counts of the buffer the sorted rows go
    through.  ``large`` holds every route that can land here, so nothing
    is ever dropped: T * min(K, n_held) rows and a tile of padding an
    expert.  ``small`` holds three times what uniform routing sends here
    (a router that nothing balances sends a layer half or one and a half
    times its share from the first step on); it is what a step uses when
    its routes fit, since gathers and elementwise passes cost the
    buffer's rows, not the routed ones."""
    pad = n_held * block_rows
    large = _round_up(n_tokens * min(top_k, n_held), block_rows) + pad
    small = _round_up(3 * n_tokens * top_k * n_held // n_experts,
                      block_rows) + pad
    return min(small, large), large


@jax.custom_vjp
def take_rows(x, src, back):
    """``out[i] = x[src[i]]``, a zero row where ``src[i] == len(x)``, for
    a ``src`` that takes each row of ``x`` at most ``back.shape[1]``
    times: ``back[r]`` are the ``i`` with ``src[i] == r``, filled up with
    ``len(src)``.  Knowing ``back``, the gradient is a gather too
    (``dx[r] = sum of g[back[r]]``) where autodiff would scatter-add
    row by row, which on the TPU cost ten times the gather."""
    return _gather_or_zero(x, src)


def _gather_or_zero(x, idx):
    n = x.shape[0]
    rows = jnp.take(x, jnp.minimum(idx, n - 1), axis=0)
    held = (idx < n).reshape(idx.shape + (1,) * (x.ndim - 1))
    return jnp.where(held, rows, jnp.zeros((), x.dtype))


def _take_rows_fwd(x, src, back):
    return _gather_or_zero(x, src), (src, back)


def _take_rows_bwd(res, g):
    src, back = res
    dx = _gather_or_zero(g, back)               # (n, times, D)
    return jnp.sum(dx.astype(jnp.float32), axis=1).astype(g.dtype), None, \
        None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


# On the kernel path the two places that would walk all T * K routes, the
# combine and the dispatch's gradient, are sums of the buffer's rows by
# token (``ops/pallas_kernels.sum_rows_by_token``): what they fetch follows
# the routes that have a row here, an eighth or a sixteenth of all at
# uniform routing, and no array of T * K rows by D columns is formed,
# forward or backward.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def rows_by_token(block_rows, x, token_of_row):
    """``x[token_of_row]`` (M, D), a zero row where ``token_of_row ==
    len(x)``: the dispatch, for M a multiple of ``block_rows``.  Its
    gradient is the kernel's sum, ``dx[t]`` = the ``g[r]`` with
    ``token_of_row[r] == t`` added in float32."""
    return _gather_or_zero(x, token_of_row)


def _rows_by_token_fwd(block_rows, x, token_of_row):
    return _gather_or_zero(x, token_of_row), (x, token_of_row)


def _rows_by_token_bwd(block_rows, res, g):
    from ..ops.pallas_kernels import sum_rows_by_token
    x, token_of_row = res
    dx = sum_rows_by_token(g, token_of_row, x.shape[0],
                           block_rows=block_rows)
    return dx.astype(g.dtype), None


rows_by_token.defvjp(_rows_by_token_fwd, _rows_by_token_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def combine_rows(block_rows, out_rows, w, route_of_row):
    """``y[t] = sum over k with row[t, k] < M of w[t, k] *
    out_rows[row[t, k]]`` (T, D) float32, the routes' rows ``row`` given
    by their inverse ``route_of_row`` (M,), T * K where no route has the
    row.  The kernel sums the rows by token; the gradient works from the
    rows' side too: one gather of ``dy`` by the rows' tokens gives both
    ``d out_rows[r] = w_of_row[r] * dy[token_of_row[r]]`` and, as each
    row's product with it summed over D and sent to the row's route,
    ``dw``."""
    from ..ops.pallas_kernels import sum_rows_by_token
    T, K = w.shape
    return sum_rows_by_token(
        out_rows, route_of_row // K, T,
        _gather_or_zero(w.reshape(-1), route_of_row), block_rows)


def _combine_rows_fwd(block_rows, out_rows, w, route_of_row):
    return (combine_rows(block_rows, out_rows, w, route_of_row),
            (out_rows, w, route_of_row))


def _combine_rows_bwd(block_rows, res, dy):
    out_rows, w, route_of_row = res
    dy_of_row = _gather_or_zero(dy, route_of_row // w.shape[1])
    w_of_row = _gather_or_zero(w.reshape(-1), route_of_row)
    # rows no product wrote hold anything, and so do their sums: they
    # have no route, and a scatter of M sums costs a third of a gather of
    # T * K (PERF.md section 6, PR 35)
    dots = jnp.sum(out_rows.astype(jnp.float32) * dy_of_row, axis=-1)
    dw = jnp.zeros(w.size, w.dtype).at[route_of_row].set(
        dots.astype(w.dtype), mode="drop")
    return ((w_of_row[:, None] * dy_of_row).astype(out_rows.dtype),
            dw.reshape(w.shape), None)


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def grouped_products(lhs, rhs, sizes, *, block_rows: int, use_pallas: bool):
    """One ragged grouped product in the tile-aligned layout: the Pallas
    kernels, or XLA's ``ragged_dot`` over the same rows (each group as
    long as its tiles)."""
    if use_pallas:
        from ..ops.pallas_kernels import grouped_matmul
        return grouped_matmul(lhs, rhs, sizes, block_rows)
    padded = (sizes + block_rows - 1) // block_rows * block_rows
    return jax.lax.ragged_dot(lhs, rhs, padded,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)


def routed_experts_apply(params: dict, x: jnp.ndarray, *, top_k: int,
                         n_held: Optional[int] = None, offset: int = 0,
                         bias=None, route_norm: bool = True, route_scale: float = 1.0,
                         block_rows: int = 128, compute_dtype=None,
                         use_pallas: Optional[bool] = None,
                         activation: str = "silu"):
    """Dropless top-k routed experts, the held experts' part.

    ``x`` (T, D); ``params``: ``router`` (D, n_experts), and the held
    experts' ``wg``, ``wu`` (n_held, D, H) and ``wd`` (n_held, H, D),
    expert ``offset + i`` of the router at index i.  Every token is
    routed over all the router's experts; a route to an expert not held
    adds nothing.  Returns (y (T, D) float32, counters): ``y[t] = sum
    over held e in top_k(t) of w[t, e] * wd[e](act(wg[e] x[t]) * wu[e]
    x[t])``, or of ``w[t, e] * wd[e] act(wu[e] x[t])`` where ``params``
    has no ``wg`` (experts that are not gated: two grouped products, not
    three); ``counters`` are int32 scalars: ``rows_routed`` (routes
    that landed on held experts), ``rows_computed`` (rows the grouped
    products ran, tile padding included), ``experts_active`` (held
    experts with a row), ``expert_rows_max``.

    The sorted rows go through a buffer of static size.  One that holds
    every route that can land here is ``min(K, n_held) / K`` of all
    routes, eight times what uniform routing sends to 16 of 128 experts,
    and gathers and elementwise passes cost its rows, used or not.  So
    the step looks at the rows it needs and takes the small buffer when
    they fit and the large one when they do not (``buffer_rows``): one
    ``lax.cond``, both compiled, no drop either way."""
    if use_pallas is None:
        from ..ops import use_pallas_default
        use_pallas = use_pallas_default()
    T, D = x.shape
    n_held = params["wu"].shape[0] if n_held is None else int(n_held)
    wg = params.get("wg")
    dt = compute_dtype or x.dtype
    with jax.named_scope("moe_route"):
        w, topi = route_scores(x, params["router"], top_k=top_k, bias=bias,
                               route_norm=route_norm,
                               route_scale=route_scale)
    with jax.named_scope("moe_dispatch"):
        sizes, order, rows_needed = dispatch_plan(
            topi, n_held, offset, block_rows)
    small, large = buffer_rows(T, topi.shape[1], n_held,
                               params["router"].shape[1], block_rows)
    y = experts_through_buffers(
        (small, large, block_rows, bool(use_pallas), activation),
        x.astype(dt), w, None if wg is None else wg.astype(dt),
        params["wu"].astype(dt), params["wd"].astype(dt), order, sizes,
        rows_needed)
    counters = {"rows_routed": jnp.sum(sizes),
                "rows_computed": rows_needed,
                "experts_active": jnp.sum((sizes > 0).astype(jnp.int32)),
                "expert_rows_max": jnp.max(sizes)}
    return y, counters


def _through_buffer(M, block_rows, use_pallas, activation, x, w, wg, wu, wd,
                    order, sizes):
    """The held experts' part of y (T, D) float32, the sorted rows going
    through a buffer of M rows (which has to hold them).  ``wg`` None:
    experts without a gate, ``wd act(wu x)``.

    The buffer is described from its rows' side, ``rows_routes``: with
    the kernels nothing asks for a route's row, so no map of T * K
    entries is built.  ``take_rows`` wants that back-index, and gets it
    as the scatter of the M row numbers to their routes."""
    T, K = w.shape
    with jax.named_scope("moe_dispatch"):
        route_of_row = rows_routes(M, sizes, order, block_rows)
        token_of_row = route_of_row // K                # T: no route
        if use_pallas:
            rows = rows_by_token(block_rows, x, token_of_row)
        else:
            row = jnp.full(T * K, M, jnp.int32).at[route_of_row].set(
                jnp.arange(M, dtype=jnp.int32), mode="drop").reshape(T, K)
            rows = take_rows(x, token_of_row, row)      # M: no row
    with jax.named_scope("moe_experts"):
        product = functools.partial(grouped_products, sizes=sizes,
                                    block_rows=block_rows,
                                    use_pallas=use_pallas)
        act = ACTIVATIONS[activation]
        g = None if wg is None else product(rows, wg)
        u = product(rows, wu).astype(jnp.float32)
        h = act(u) if g is None else act(g.astype(jnp.float32)) * u
        out_rows = product(h.astype(x.dtype), wd)
    with jax.named_scope("moe_combine"):
        # rows no product wrote hold anything: both paths select, they do
        # not multiply
        if use_pallas:
            return combine_rows(block_rows, out_rows, w, route_of_row)
        picked = take_rows(out_rows, row.reshape(-1), route_of_row[:, None])
        return jnp.einsum("tk,tkd->td", w,
                          picked.reshape(T, K, -1).astype(jnp.float32))


def _with_the_buffer_that_fits(cfg, rows_needed, make, *operands):
    """``make(M)(*operands)`` at the small buffer when the rows fit it,
    at the large one when not: one ``lax.cond``, both compiled."""
    small, large = cfg[:2]
    if small == large:
        return make(large)(*operands)
    return jax.lax.cond(rows_needed <= small, make(small), make(large),
                        *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def experts_through_buffers(cfg, x, w, wg, wu, wd, order, sizes,
                            rows_needed):
    """``_through_buffer`` at the buffer that fits, ``cfg = (small, large,
    block_rows, use_pallas, activation)``.  Its gradient runs the forward again inside
    the branch it takes: differentiating through a ``lax.cond`` keeps
    what both branches would save, the large buffer's with the small
    one's, for every layer until its backward, which is more than the
    device has.  So nothing of the buffer outlives its branch, forward or
    backward, and a caller has nothing to gain from rematerialising this
    again."""
    return _with_the_buffer_that_fits(
        cfg, rows_needed,
        lambda M: lambda *a: _through_buffer(M, *cfg[2:], *a, order, sizes),
        x, w, wg, wu, wd)


def _experts_fwd(cfg, x, w, wg, wu, wd, order, sizes, rows_needed):
    y = experts_through_buffers(cfg, x, w, wg, wu, wd, order, sizes,
                                rows_needed)
    return y, (x, w, wg, wu, wd, order, sizes, rows_needed)


def _experts_bwd(cfg, res, dy):
    *operands, order, sizes, rows_needed = res

    def back(M):
        def run(dy, *operands):
            _, vjp = jax.vjp(
                lambda *a: _through_buffer(M, *cfg[2:], *a, order, sizes),
                *operands)
            return vjp(dy)
        return run

    grads = _with_the_buffer_that_fits(cfg, rows_needed, back, dy, *operands)
    return tuple(grads) + (None, None, None)


experts_through_buffers.defvjp(_experts_fwd, _experts_bwd)


def moe_shardings(params: dict, mesh: Mesh, axis: str = "expert") -> dict:
    """Shard the expert banks on the expert axis; router replicated."""
    return {
        "router": NamedSharding(mesh, P()),
        "w1": NamedSharding(mesh, P(axis)),
        "w2": NamedSharding(mesh, P(axis)),
    }
