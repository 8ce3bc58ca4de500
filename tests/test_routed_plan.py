"""The routed experts' plan, read off the one sort of the routes
(``parallel/moe.py`` ``dispatch_plan``, ``rows_routes``, ``chosen_scores``)
against the scatter formulation it replaced, kept here as the plain
reference; and the structure of a routed layer's program on the kernel
path: nothing in it is indexed by all T * K routes but the sort.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu.parallel import moe
from veles_tpu.units.base import Context, Spec
from veles_tpu.units.parallel_nn import RoutedExpertsFFN

N_EXPERTS, TOKENS, BLOCK = 128, 256, 128
NO_ROW = 2 ** 30


def routing(kind, K, n_held, offset, seed):
    """``topi`` (TOKENS, K), a token's experts distinct: ``uniform`` over
    the router's width; ``one_held``: every token's first route on one
    held expert and no other route on a held one; ``none_held``; and
    ``empty_middle``: uniform, but nothing on the middle held expert."""
    rng = np.random.default_rng(seed)
    held = np.arange(offset, offset + n_held)
    away = np.setdiff1d(np.arange(N_EXPERTS), held)
    allowed = {"uniform": np.arange(N_EXPERTS),
               "one_held": away, "none_held": away,
               "empty_middle": np.setdiff1d(np.arange(N_EXPERTS),
                                            held[n_held // 2])}[kind]
    topi = np.stack([rng.permutation(allowed)[:K] for _ in range(TOKENS)])
    if kind == "one_held":
        topi[:, 0] = held[3]
    return jnp.asarray(topi, jnp.int32)


def scattered_plan(topi, n_held, offset, block_rows):
    """The formulation before: sizes by a scatter-add of ones, the sorted
    keys gathered back, each route's row scattered to the route."""
    T, K = topi.shape
    R = T * K
    local = topi.reshape(-1) - offset
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sorted_key = key[order]
    sizes_all = jnp.zeros(n_held + 1, jnp.int32).at[key].add(1)
    sizes = sizes_all[:n_held]
    tiles = (sizes + block_rows - 1) // block_rows
    row_start = (jnp.cumsum(tiles) - tiles) * block_rows
    first = jnp.cumsum(sizes_all) - sizes_all
    rank = jnp.arange(R, dtype=jnp.int32) - first[sorted_key]
    row_sorted = jnp.where(
        sorted_key < n_held,
        jnp.concatenate([row_start, jnp.zeros(1, jnp.int32)])[sorted_key]
        + rank, NO_ROW)
    row_of_route = jnp.zeros(R, jnp.int32).at[order].set(row_sorted)
    return sizes, row_of_route, jnp.sum(tiles) * block_rows


def scattered_rows_routes(M, row_of_route):
    R = row_of_route.shape[0]
    return jnp.full(M, R, jnp.int32).at[jnp.minimum(row_of_route, M)].set(
        jnp.arange(R, dtype=jnp.int32), mode="drop")


@pytest.mark.parametrize("kind", ["uniform", "one_held", "none_held",
                                  "empty_middle"])
@pytest.mark.parametrize("offset", [0, 16])
@pytest.mark.parametrize("n_held", [8, 16])
@pytest.mark.parametrize("K", [6, 8])
def test_plan_from_the_sort_is_the_scattered_plan(K, n_held, offset, kind):
    """``sizes``, ``rows_needed`` and each buffer's ``route_of_row`` are
    the scatter formulation's, whichever buffer the rows fit; the selected
    scores and their gradient are ``take_along_axis``'s bit for bit."""
    topi = routing(kind, K, n_held, offset, seed=K + n_held + offset)
    want_sizes, row_of_route, want_needed = scattered_plan(
        topi, n_held, offset, BLOCK)
    sizes, order, rows_needed = jax.jit(
        moe.dispatch_plan, static_argnums=(1, 2, 3))(
            topi, n_held, offset, BLOCK)
    np.testing.assert_array_equal(sizes, want_sizes)
    assert int(rows_needed) == int(want_needed)
    assert sorted(np.asarray(order)) == list(range(TOKENS * K))
    if kind == "one_held":
        assert int(sizes[3]) == int(sizes.sum()) == TOKENS
    if kind == "none_held":
        assert int(rows_needed) == 0
    if kind == "empty_middle":
        assert int(sizes[n_held // 2]) == 0 < int(sizes[n_held // 2 + 1])
    small, large = moe.buffer_rows(TOKENS, K, n_held, N_EXPERTS, BLOCK)
    assert small < large
    for M in (small, large):
        got = jax.jit(moe.rows_routes, static_argnums=(0, 3))(
            M, sizes, order, BLOCK)
        np.testing.assert_array_equal(
            got, scattered_rows_routes(M, row_of_route), err_msg=f"M={M}")
    assert int((got < TOKENS * K).sum()) == int(sizes.sum())

    k1, k2 = jax.random.split(jax.random.key(K + offset))
    scores = jax.nn.sigmoid(jax.random.normal(k1, (TOKENS, N_EXPERTS)))
    dw = jax.random.normal(k2, (TOKENS, K))
    for f in (jax.jit(jax.vjp, static_argnums=0), jax.vjp):
        w, back = f(lambda s: moe.chosen_scores(s, topi), scores)
        want_w, want_back = f(
            lambda s: jnp.take_along_axis(s, topi, axis=-1), scores)
        np.testing.assert_array_equal(w, want_w)
        np.testing.assert_array_equal(back(dw)[0], want_back(dw)[0])


# -- the program of one routed layer ------------------------------------------

T, D, K, HELD = 64, 32, 4, 4


def layer_jaxpr(name, use_pallas):
    unit = RoutedExpertsFFN(16, 16, name=name, top_k=K, experts_held=HELD,
                            expert_offset=4, block_rows=8,
                            use_pallas=use_pallas)
    params, state = unit.init(jax.random.key(0),
                              [Spec((1, T, D), jnp.float32)])
    x = jax.random.normal(jax.random.key(1), (1, T, D))

    def loss(params, x):
        y, _ = unit.apply(params, state, [x], Context(train=True))
        return jnp.sum(jnp.sin(y))

    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)


def indexed_operations(jaxpr):
    """(primitive, how many elements or slices its indices name) of every
    gather and scatter of a jaxpr and of the jaxprs inside it; a kernel's
    body is its own."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            yield name, math.prod(eqn.invars[1].aval.shape[:-1])
        if name == "pallas_call":
            continue
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) \
                    else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from indexed_operations(inner)


def plan_path(unit):
    from veles_tpu.runtime.metrics import registry
    gauge = registry().gauge("vt_moe_plan_path", "", labels=("unit", "path"))
    return {p: gauge.labels(unit=unit, path=p).value
            for p in ("sorted", "scattered")}


def test_kernel_path_indexes_nothing_by_every_route():
    """Forward and backward of a routed layer with the kernels: a sort of
    the T * K routes, gathers and scatters of a buffer's rows, and none
    of T * K elements; the gauge says the plan came from the sort."""
    small, large = moe.buffer_rows(T, K, HELD, 16, 8)
    assert T * K not in (small, large)
    found = list(indexed_operations(layer_jaxpr("plan_sorted", True).jaxpr))
    counts = {n for _, n in found}
    assert {small, large} <= counts, found
    assert T * K not in counts, found
    assert plan_path("plan_sorted") == {"sorted": 1.0, "scattered": 0.0}


def test_back_index_path_says_scattered():
    """Off the chip ``take_rows`` wants each route's row: the rows'
    numbers scattered to their routes, and the gauge says so."""
    found = list(indexed_operations(
        layer_jaxpr("plan_scattered", False).jaxpr))
    assert any(name.startswith("scatter") for name, _ in found)
    assert plan_path("plan_scattered") == {"sorted": 0.0, "scattered": 1.0}
