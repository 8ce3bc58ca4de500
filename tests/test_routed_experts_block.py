"""The gated, QK-normed, sliding-and-full attention block with dropless
routed experts, through ``StandardWorkflow`` on the CPU in float32, against
the plain reference ``benchmarks/references/afmoe.py``; the share of a
layer that one chip holds; no drops under imbalance; the units' counters.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from references import afmoe, nemotron_h  # noqa: E402
from references.train_steps import cast_float32  # noqa: E402

from veles_tpu.models.standard import StandardWorkflow  # noqa: E402
from veles_tpu.ops import pallas_kernels as pk  # noqa: E402
from veles_tpu.parallel import moe  # noqa: E402
from veles_tpu.units.base import Context, Spec  # noqa: E402
from veles_tpu.units.parallel_nn import (MultiHeadAttention,  # noqa: E402
                                         RoutedExpertsFFN)

E, T, VOCAB = 32, 16, 64
NO_ROW = 2 ** 30           # a route without a row, in the plain references
ROUTED = dict(type="routed_experts", n_experts=8, d_hidden=16, top_k=2,
              route_scale=2.826, shared_width=16, block_rows=8,
              use_pallas=True)


def block(i, before, *, window, routed):
    attn = dict(type="attention", n_heads=4, n_kv_heads=2, head_dim=8,
                qk_norm=True, gate=True, use_flash=False, block_size=8,
                name=f"b{i}_attn")
    if window:
        attn.update(rope=True, window=window)
    # the kernels (interpreted here, slowly) in one routed layer, XLA's
    # ragged product in the others
    mlp = dict(ROUTED, name=f"b{i}_mlp", use_pallas=i == 2) if routed else \
        dict(type="gated_mlp", d_hidden=48, name=f"b{i}_mlp")
    return [
        dict(type="rms_norm", name=f"b{i}_in"), attn,
        dict(type="rms_norm", name=f"b{i}_post_attn",
             inputs=[f"b{i}_attn", before]),
        dict(type="rms_norm", name=f"b{i}_pre_mlp"), mlp,
        dict(type="rms_norm", name=f"b{i}_post_mlp",
             inputs=[f"b{i}_mlp", f"b{i}_post_attn"])]


def tiny_layers():
    """1 dense + sliding, full, sliding, sliding; window smaller than T."""
    layers = [dict(type="embedding", vocab=VOCAB, dim=E, scale=E ** 0.5,
                   name="emb")]
    before = "emb"
    for i, (window, routed) in enumerate(
            [(6, False), (6, True), (None, True), (6, True), (6, True)]):
        layers += block(i, before, window=window, routed=routed)
        before = f"b{i}_post_mlp"
    return layers + [
        dict(type="rms_norm", name="final"),
        dict(type="all2all", output_size=VOCAB, per_position=True,
             include_bias=False, name="head")]


def build(layers, batch=2):
    sw = StandardWorkflow({"name": "tiny", "loss": "softmax",
                           "optimizer": "adam", "layers": layers})
    wf = sw.workflow
    wf.build({"@input": Spec((batch, T), jnp.int32),
              "@labels": Spec((batch, T), jnp.int32),
              "@mask": Spec((batch,), jnp.float32)})
    wstate = wf.init_state(jax.random.key(3), sw.optimizer)
    return sw, wstate


def random_scales(params, key):
    """Norm scales away from one, so that a scale left out shows."""
    def leaf(path, x):
        name = str(path[-1].key)
        if name in ("scale", "q_norm", "k_norm"):
            k = jax.random.fold_in(key, hash(str(path)) % (2 ** 31))
            return 1.0 + 0.3 * jax.random.normal(k, x.shape)
        return x
    return jax.tree_util.tree_map_with_path(leaf, params)


def rows(batch=2, seed=0):
    ids = np.random.default_rng(seed).integers(0, VOCAB, (batch, T + 1))
    return {"@input": jnp.asarray(ids[:, :-1], jnp.int32),
            "@labels": jnp.asarray(ids[:, 1:], jnp.int32)}


def test_tiny_block_matches_the_plain_reference_logits_loss_gradients():
    layers = tiny_layers()
    sw, wstate = build(layers)
    wf = sw.workflow
    params = random_scales(wstate["params"], jax.random.key(5))
    batch = dict(rows(), **{"@mask": jnp.ones((2,), jnp.float32)})

    def program_loss(params):
        outs, _ = wf.forward(params, wstate["state"], batch,
                             Context(train=True, key=jax.random.key(0)))
        return outs["evaluator"], outs["head"]

    (loss, logits), grads = jax.jit(jax.value_and_grad(
        program_loss, has_aux=True))(params)
    ref_loss = afmoe.make_loss(layers)
    with jax.default_matmul_precision("highest"):
        (ce, n), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: ref_loss(p, batch, cast_float32),
            has_aux=True))(params)
        ref_logits, _ = jax.jit(
            lambda p: afmoe.make_forward(layers)(p, batch, cast_float32)
        )(params)
    np.testing.assert_allclose(logits, ref_logits, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(loss, ce / n, rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(flat) == len(ref_flat) and len(flat) > 60
    for path, g in flat:
        np.testing.assert_allclose(
            g, ref_flat[path] / n, atol=1e-5, rtol=1e-4,
            err_msg=jax.tree_util.keystr(path))


def expert_layer(**kw):
    return dict(ROUTED, name="mlp", **kw)


def expert_params(key, held=8):
    unit = RoutedExpertsFFN(**{k: v for k, v in expert_layer(
        experts_held=held).items() if k != "type"})
    return unit, unit.init(key, [Spec((2, T, E), jnp.float32)])


def test_four_shares_of_two_experts_add_up_to_the_uncut_layer():
    """Guide section 4: the parts that the shares give, with the shared
    expert counted once, are the whole layer."""
    whole_unit, (whole, state) = expert_params(jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (2, T, E))
    with jax.default_matmul_precision("highest"):
        ref, _ = afmoe._routed_experts(expert_layer(), whole, x,
                                       cast_float32, ())
        shared = afmoe._gated(x, whole["shared_wg"], whole["shared_wu"],
                              whole["shared_wd"], cast_float32)
    total = shared
    routed_rows = 0
    for share in range(4):
        unit = RoutedExpertsFFN(**{k: v for k, v in expert_layer(
            experts_held=2, expert_offset=2 * share).items() if k != "type"})
        part = {k: (v[2 * share:2 * share + 2] if k in ("wg", "wu", "wd")
                    else v) for k, v in whole.items()}
        y, new = unit.apply(part, state, [x], Context(train=False))
        total = total + (y - shared)
        routed_rows += int(new["counters"]["rows_routed"])
        # the same share of the reference
        with jax.default_matmul_precision("highest"):
            ref_part, n = afmoe._routed_experts(
                expert_layer(experts_held=2, expert_offset=2 * share), part,
                x, cast_float32, ())
        np.testing.assert_allclose(y, ref_part, atol=1e-5)
        assert int(n) == int(new["counters"]["rows_routed"])
    np.testing.assert_allclose(total, ref, atol=2e-5)
    assert routed_rows == 2 * T * 2          # every route lands somewhere


def test_no_route_is_dropped_when_every_token_picks_one_expert():
    """All tokens to held expert 3 (and 5); held expert 0 gets no row:
    output and gradients are the reference's, the empty expert's gradient
    is zero, and the counters say what happened."""
    unit, (params, state) = expert_params(jax.random.key(4))
    x = jax.random.normal(jax.random.key(6), (2, T, E))
    bias = jnp.zeros((8,)).at[3].set(50.0).at[5].set(40.0)
    router = jnp.zeros_like(params["router"])
    # scores then differ by token but the choice is the bias's
    router = router.at[:, 3].set(params["router"][:, 3]) \
        .at[:, 5].set(params["router"][:, 5])
    params = dict(params, router=router)
    # the reference has no bias: give it a router whose logits are huge
    # on experts 3 and 5, so use the program's state for the choice and
    # compare against the reference evaluated with that choice
    state = dict(state, route_bias=bias)

    def program(params, x):
        y, new = unit.apply(params, state, [x], Context(train=True))
        return jnp.sum(y * jnp.cos(y)), (y, new["counters"])

    def reference(params, x):
        s = jax.nn.sigmoid(jnp.einsum("bte,en->btn", x, params["router"],
                                      precision="highest"))
        w = s[..., jnp.array([3, 5])]
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * 2.826
        y = afmoe._gated(x, params["shared_wg"], params["shared_wu"],
                         params["shared_wd"], cast_float32)
        for j, e in enumerate((3, 5)):
            y = y + w[..., j, None] * afmoe._gated(
                x, params["wg"][e], params["wu"][e], params["wd"][e],
                cast_float32)
        return jnp.sum(y * jnp.cos(y)), y

    (_, (y, counters)), (gp, gx) = jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True)(params, x)
    with jax.default_matmul_precision("highest"):
        (_, ref_y), (rp, rx) = jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True)(params, x)
    np.testing.assert_allclose(y, ref_y, atol=1e-5)
    np.testing.assert_allclose(gx, rx, atol=1e-5, rtol=1e-4)
    for name in gp:
        np.testing.assert_allclose(gp[name], rp[name], atol=1e-5, rtol=1e-4,
                                   err_msg=name)
    assert float(jnp.abs(gp["wg"][0]).max()) == 0.0
    assert float(jnp.abs(gp["wd"][3]).max()) > 0.0
    assert int(counters["rows_routed"]) == 2 * 2 * T
    assert int(counters["expert_rows_max"]) == 2 * T
    assert int(counters["rows_computed"]) == 2 * 2 * T    # 32 = 4 tiles of 8


UNGATED = dict(gated=False, activation="relu2")


def reference_layer(gated, **cut):
    """(the reference's routed layer, its layer description): gated
    ``silu`` experts (afmoe) or ``Wd relu(Wu x)^2`` (nemotron_h)."""
    layer = expert_layer(shared_width=0, **cut, **({} if gated else UNGATED))
    return (afmoe if gated else nemotron_h)._routed_experts, layer


def unit_of(layer, **kw):
    return RoutedExpertsFFN(**{k: v for k, v in dict(layer, **kw).items()
                               if k != "type"})


def reference_counters(layer, params, x, block_rows=8):
    """The unit's four counters from the reference's own routing."""
    weights, _ = afmoe.route(layer, params, x)
    first, held = layer["expert_offset"], layer["experts_held"]
    sizes = np.asarray(
        (weights[..., first:first + held] != 0).sum(axis=(0, 1)))
    return {"rows_routed": sizes.sum(),
            "rows_computed": (-(-sizes // block_rows) * block_rows).sum(),
            "experts_active": (sizes > 0).sum(),
            "expert_rows_max": sizes.max()}


def combine_path(unit):
    from veles_tpu.runtime.metrics import registry
    gauge = registry().gauge("vt_moe_combine_path", "",
                             labels=("unit", "path"))
    return {p: gauge.labels(unit=unit, path=p).value
            for p in ("rows", "routes")}


def assert_the_reference_layer(unit, layer, reference, params, state, x,
                               out=jnp.sin):
    """``y``, the gradient to every parameter and to ``x``, and the four
    counters of ``unit`` against the reference's; returns the counters."""
    def program(params, x):
        y, new = unit.apply(params, state, [x], Context(train=True))
        return jnp.sum(out(y)), (y, new["counters"])

    def plain(params, x):
        y, _ = reference(layer, params, x, cast_float32, ())
        return jnp.sum(out(y)), y

    (_, (y, counters)), (gp, gx) = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(params, x)
    with jax.default_matmul_precision("highest"):
        (_, ref_y), (rp, rx) = jax.jit(jax.value_and_grad(
            plain, argnums=(0, 1), has_aux=True))(params, x)
    np.testing.assert_allclose(y, ref_y, atol=1e-5)
    np.testing.assert_allclose(gx, rx, atol=1e-5, rtol=1e-4)
    assert set(gp) == set(rp)
    for name in gp:
        np.testing.assert_allclose(gp[name], rp[name], atol=1e-5, rtol=1e-4,
                                   err_msg=name)
    want = reference_counters(layer, params, x)
    assert {k: int(v) for k, v in counters.items()} == want
    return counters


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("use_pallas", [True, False])
def test_kernel_and_ragged_dot_paths_agree(use_pallas, gated):
    """The grouped kernels with the row-summing kernel around them, and
    ``ragged_dot`` with ``take_rows`` and the ``einsum``: ``y``, every
    gradient and the four counters are the reference's either way, gated
    or not, and the gauge says which way the combine went."""
    cut = dict(experts_held=4, expert_offset=2)
    reference, layer = reference_layer(gated, **cut)
    name = f"paths_{use_pallas}_{gated}"
    unit = unit_of(layer, name=name, use_pallas=use_pallas)
    params, state = unit.init(jax.random.key(7),
                              [Spec((2, T, E), jnp.float32)])
    assert ("wg" in params) == gated
    x = jax.random.normal(jax.random.key(8), (2, T, E))
    counters = assert_the_reference_layer(unit, layer, reference, params,
                                          state, x)
    assert 0 < int(counters["rows_routed"]) < 2 * T * 2
    assert combine_path(name) == {"rows": float(use_pallas),
                                  "routes": float(not use_pallas)}


def test_counters_reach_the_registry_at_the_epoch_drain():
    """Through Trainer.run(): routed rows by class equal the reference's
    own routing of the same rows; computed rows hold the tile padding."""
    from veles_tpu.loader.base import TRAIN, VALID, ArrayLoader
    from veles_tpu.runtime import Decision
    from veles_tpu.runtime.metrics import registry
    layers = tiny_layers()
    sw = StandardWorkflow({"name": "tiny_counted", "loss": "softmax",
                           "optimizer": "adam",
                           "optimizer_args": {"lr": 0.0}, "layers": layers})
    ids = np.random.default_rng(1).integers(0, VOCAB, (6, T + 1))
    loader = ArrayLoader({TRAIN: ids[:4, :-1], VALID: ids[4:, :-1]},
                         {TRAIN: ids[:4, 1:], VALID: ids[4:, 1:]},
                         minibatch_size=2)
    trainer = sw.make_trainer(loader, decision=Decision(max_epochs=1))
    trainer.initialize(seed=9)
    params = jax.tree.map(jnp.copy, trainer.wstate["params"])  # donated
    rows_total = registry().counter("vt_moe_rows_total", "",
                                    labels=("unit", "klass", "kind"))

    def read(kind):
        return {(u, k): rows_total.labels(unit=u, klass=k, kind=kind).value
                for u in ("b1_mlp", "b2_mlp", "b3_mlp", "b4_mlp")
                for k in ("train", "validation")}

    before_routed, before_computed = read("routed"), read("computed")
    trainer.run()
    forward = afmoe.make_forward(layers)
    want = {}
    for klass, part in (("train", ids[:4]), ("validation", ids[4:])):
        with jax.default_matmul_precision("highest"):
            _, counts = forward(params, {"@input": jnp.asarray(part[:, :-1])},
                                cast_float32)
        for unit, n in counts.items():
            want[unit, klass] = int(n)
    got = {k: v - before_routed[k] for k, v in read("routed").items()}
    assert got == want and min(want.values()) > 0
    for k, v in read("computed").items():
        padded = v - before_computed[k]
        assert padded >= got[k] and padded % 8 == 0
    fullest = registry().gauge("vt_moe_expert_rows_max", "",
                               labels=("unit",))
    assert 0 < fullest.labels(unit="b2_mlp").value <= 2 * T * 2


def test_attention_without_qk_norm_and_gate_is_the_function_it_was():
    """Defaults off: the same parameters, the same bits as the four
    products and the core alone."""
    from veles_tpu.parallel.ring_attention import blockwise_attention
    from veles_tpu.ops import rotary_embedding
    unit = MultiHeadAttention(4, head_dim=8, n_kv_heads=2, rope=True,
                              window=6, use_flash=False, block_size=8,
                              residual=True, name="a")
    spec = Spec((2, T, E), jnp.float32)
    params, state = unit.init(jax.random.key(0), [spec])
    assert set(params) == {"wq", "wk", "wv", "wo"}
    x = jax.random.normal(jax.random.key(1), spec.shape)
    y, _ = jax.jit(lambda p, x: unit.apply(p, state, [x],
                                           Context(train=True)))(params, x)

    @jax.jit
    def before(p, x):
        q = (x @ p["wq"]).reshape(2, T, 4, -1)
        k = (x @ p["wk"]).reshape(2, T, 2, -1)
        v = (x @ p["wv"]).reshape(2, T, 2, -1)
        o = blockwise_attention(rotary_embedding(q), rotary_embedding(k), v,
                                block_size=8, causal=True, window=6,
                                use_flash=False)
        return o.reshape(2, T, -1) @ p["wo"] + x

    assert np.array_equal(np.asarray(y), np.asarray(before(params, x)))


@pytest.mark.parametrize("sizes", [
    (3, 0, 17, 8, 0), (0, 0, 0, 5), (8, 16, 24), (1, 1, 1, 1, 1, 1),
    (0, 0, 0)])
def test_grouped_matmul_kernels_on_ragged_groups(sizes):
    """Forward and both backward products against ``jnp``, interpreted:
    empty groups, sizes that are no multiple of the tile, rows behind the
    last group never computed."""
    from veles_tpu.ops import pallas_kernels as pk
    tm, K, N, G = 8, 16, 256, len(sizes)
    M = 64
    sizes_j = jnp.asarray(sizes, jnp.int32)
    _, row_start = pk.group_tiles(sizes_j, tm)
    n_tiles, tile_group = pk.tile_groups(sizes_j, M, tm)
    row_start = np.asarray(row_start)
    assert int(n_tiles) == sum(-(-s // tm) for s in sizes)
    rng = np.random.default_rng(sum(sizes))
    lhs = np.zeros((M, K), np.float32)
    valid = np.zeros(M, bool)
    for e, (n, r0) in enumerate(zip(sizes, row_start)):
        lhs[r0:r0 + n] = rng.standard_normal((n, K))
        valid[r0:r0 + n] = True
        assert all(np.asarray(tile_group)[r0 // tm:(r0 + n + tm - 1) // tm]
                   == e)
    rhs = rng.standard_normal((G, K, N)).astype(np.float32)
    weight = rng.standard_normal((M, N)).astype(np.float32)

    def kernel(lhs, rhs):
        out = pk.grouped_matmul(lhs, rhs, sizes_j, tm, 128, True)
        return jnp.sum(jnp.where(valid[:, None], out * weight, 0.0)), out

    def plain(lhs, rhs):
        out = jnp.zeros((M, N))
        for e, (n, r0) in enumerate(zip(sizes, row_start)):
            out = out.at[r0:r0 + n].set(jnp.dot(
                lhs[r0:r0 + n], rhs[e], precision="highest"))
        return jnp.sum(out * weight), out

    (_, out), (dl, dr) = jax.value_and_grad(
        kernel, argnums=(0, 1), has_aux=True)(jnp.asarray(lhs),
                                              jnp.asarray(rhs))
    (_, want), (wl, wr) = jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True)(jnp.asarray(lhs),
                                             jnp.asarray(rhs))
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(want)[valid], atol=1e-4)
    np.testing.assert_allclose(np.asarray(dl)[valid], np.asarray(wl)[valid],
                               atol=1e-4)
    np.testing.assert_allclose(dr, wr, atol=1e-4)
    for e, n in enumerate(sizes):
        if n == 0:
            assert not np.asarray(dr)[e].any()


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("crowd", [False, True])
def test_small_and_large_buffer_give_the_reference(crowd, gated):
    """2 of 8 experts held: uniform routing fits the small buffer; every
    token sent to both held experts needs the large one.  Same answer,
    gradients and counters as the reference either way, nothing dropped,
    the row-summing kernel on both sides of the grouped ones."""
    cut = dict(experts_held=2, expert_offset=4)
    reference, layer = reference_layer(gated, **cut)
    small, large = moe.buffer_rows(3 * T, 2, 2, 8, 8)
    assert (small, large) == (88, 112)
    name = f"buffers_{crowd}_{gated}"
    unit = unit_of(layer, name=name)
    params, state = unit.init(jax.random.key(9),
                              [Spec((1, 3 * T, E), jnp.float32)])
    if crowd:
        params["router"] = params["router"] * 0.01 \
            + jnp.zeros((E, 8)).at[:, 4:6].set(0.5)
    x = jnp.abs(jax.random.normal(jax.random.key(10), (1, 3 * T, E)))
    counters = assert_the_reference_layer(unit, layer, reference, params,
                                          state, x)
    assert (int(counters["rows_computed"]) > small) == crowd
    if crowd:
        assert int(counters["rows_routed"]) == 2 * 3 * T
    assert combine_path(name) == {"rows": 1.0, "routes": 0.0}


# -- the kernel that sums the buffer's rows by token ---------------------------

def rows_case(K, D, large, seed=0, tokens=24, block_rows=8):
    """A buffer of rows as a step's routes name them: token 0 has no row,
    token 1 has all K, the others some; rows that no route names, the
    whole last tile among them, hold NaN.  ``large``: every other route
    has a row (the large buffer's case); else a sixth of them."""
    rng = np.random.default_rng(seed)
    routes = tokens * K
    M = (routes + 2 * block_rows) if large else 64
    some = rng.permutation(np.arange(2 * K, routes))
    some = some[:len(some) if large else routes // 6]
    named = np.concatenate([np.arange(K, 2 * K), some])
    slots = rng.permutation(M - block_rows)[:len(named)]
    row = np.full(routes, NO_ROW, np.int32)
    row[named] = slots
    route_of_row = np.full(M, routes, np.int32)
    route_of_row[slots] = named
    rows = rng.standard_normal((M, D)).astype(np.float32)
    rows[route_of_row == routes] = np.nan
    w = rng.standard_normal((tokens, K)).astype(np.float32)
    return (jnp.asarray(rows), jnp.minimum(jnp.asarray(row), M).reshape(
        tokens, K), jnp.asarray(route_of_row), jnp.asarray(w))


def sum_over_routes(rows, row, w=None):
    """``sum_k w[t, k] * rows[row[t, k]]`` over the routes that have a
    row, in numpy float32."""
    rows, row = np.asarray(rows, np.float32), np.asarray(row)
    picked = np.where((row < len(rows))[..., None],
                      rows[np.minimum(row, len(rows) - 1)], 0.0)
    return picked.sum(axis=1) if w is None \
        else np.einsum("tk,tkd->td", np.asarray(w), picked)


@pytest.mark.parametrize("large", [False, True])
@pytest.mark.parametrize("D", [256, 384])
@pytest.mark.parametrize("K", [6, 8])
def test_combine_by_the_row_sum_is_take_rows_and_einsum(K, D, large):
    """Values and the gradients to the rows and the weights, interpreted
    (D = 384 is, like 2688, no multiple of 1024)."""
    rows, row, route_of_row, w = rows_case(K, D, large, seed=K + D)
    target = jnp.cos(jnp.arange(w.shape[0] * D, dtype=jnp.float32)
                     ).reshape(-1, D)

    def plain(rows, w):
        picked = moe.take_rows(rows, row.reshape(-1), route_of_row[:, None])
        y = jnp.einsum("tk,tkd->td", w, picked.reshape(*w.shape, -1))
        return jnp.sum(y * target), y

    def kernel(rows, w):
        y = moe.combine_rows(8, rows, w, route_of_row)
        return jnp.sum(y * target), y

    (_, y), (d_rows, d_w) = jax.value_and_grad(
        kernel, argnums=(0, 1), has_aux=True)(rows, w)
    (_, want), (want_rows, want_w) = jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True)(rows, w)
    assert y.dtype == jnp.float32 and bool(jnp.isfinite(y).all())
    assert not np.asarray(y[0]).any()               # no row: exact zeros
    assert np.asarray(y[1]).all()                   # all K of them
    np.testing.assert_allclose(y, want, atol=1e-5)
    np.testing.assert_allclose(d_rows, want_rows, atol=1e-6)
    np.testing.assert_allclose(d_w, want_w, atol=1e-4)
    assert not np.asarray(d_w)[np.asarray(row) >= rows.shape[0]].any()


@pytest.mark.parametrize("large", [False, True])
@pytest.mark.parametrize("D", [256, 384])
@pytest.mark.parametrize("K", [6, 8])
def test_dispatch_gradient_by_the_row_sum_is_take_rows_gradient(K, D, large):
    """Without weights: ``dx[t]`` is the sum of the cotangent's rows that
    token ``t``'s routes name, summed in float32 and cast; the garbage in
    the rows no route names reaches nothing."""
    g, row, route_of_row, _ = rows_case(K, D, large, seed=K * D)
    g = g.astype(jnp.bfloat16)
    x = jnp.zeros((row.shape[0], D), jnp.bfloat16)
    token_of_row = route_of_row // K
    dx, = jax.vjp(lambda x: moe.rows_by_token(8, x, token_of_row), x)[1](g)
    want, = jax.vjp(lambda x: moe.take_rows(x, token_of_row, row), x)[1](g)
    assert dx.dtype == jnp.bfloat16 and bool(jnp.isfinite(dx).all())
    assert not np.asarray(dx[0]).any()
    # both sum in float32 and round once; the orders differ (ascending
    # row against ascending k)
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               np.asarray(want, np.float32), atol=2e-2,
                               rtol=1e-2)
    exact = pk.sum_rows_by_token(g, token_of_row, row.shape[0],
                                 block_rows=8)
    np.testing.assert_allclose(exact, sum_over_routes(g, row), atol=1e-5)


def test_row_sum_with_no_named_row_writes_zeros():
    rows = jnp.full((32, 256), jnp.nan, jnp.bfloat16)
    out = pk.sum_rows_by_token(rows, jnp.full((32,), 12, jnp.int32), 12,
                               block_rows=8)
    assert out.shape == (12, 256) and not np.asarray(out).any()


def test_row_sum_goes_by_token_blocks_when_the_sums_outgrow_vmem(
        monkeypatch):
    """20 tokens x 256 float32 is 20,480 B: held to 8 KiB the sums go in
    three blocks of 8 tokens, the last one padded, with the same
    result."""
    monkeypatch.setattr(pk, "_ROW_SUM_ACC_BYTES", 8192)
    rows, row, route_of_row, w = rows_case(6, 256, True, seed=3, tokens=20)
    rows = rows.astype(jnp.bfloat16)
    w_of_row = moe._gather_or_zero(w.reshape(-1), route_of_row)
    out = pk.sum_rows_by_token(rows, route_of_row // 6, 20, w_of_row,
                               block_rows=8)
    np.testing.assert_allclose(out, sum_over_routes(rows, row, w),
                               atol=1e-5)
