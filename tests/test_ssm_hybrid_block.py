"""The state-space mixer, the block of one mixer, and routed experts that
are not gated, on the CPU in float32: the chunked scan against the
recurrence token by token (outputs and every gradient), what its
backward keeps, the convolution's causality, the
units against the plain reference ``benchmarks/references/nemotron_h.py``,
the shares' sum, and the grouped kernels at widths that their column
blocks do not divide.
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

from references import nemotron_h  # noqa: E402
from references.train_steps import cast_float32  # noqa: E402

from veles_tpu.models.standard import StandardWorkflow  # noqa: E402
from veles_tpu.ops import pallas_kernels as pk  # noqa: E402
from veles_tpu.ops import ssd as ssd_ops  # noqa: E402
from veles_tpu.units.base import Context, Spec  # noqa: E402
from veles_tpu.units.parallel_nn import RoutedExpertsFFN  # noqa: E402
from veles_tpu.units.ssm import (Mamba2Mixer,  # noqa: E402
                                 causal_conv_silu, gated_group_rms_norm)

E, T, VOCAB = 32, 16, 64


def scan_inputs(b=2, t=16, h=4, p=3, g=2, n=5, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (b, t, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, t, h))),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (b, t, g, n)),
            jax.random.normal(k[4], (b, t, g, n)),
            jax.random.normal(k[5], (h,)))


def token_by_token(x, dt, A, B, C, D):
    """The reference's own recurrence: one token a step."""
    return nemotron_h.recurrence(x, dt, A, B, C, D)


@pytest.mark.parametrize("chunk", [4, 8])
def test_chunked_scan_is_the_recurrence_outputs_and_every_gradient(chunk):
    args = scan_inputs()
    weight = jax.random.normal(jax.random.key(9), args[0].shape)
    with jax.default_matmul_precision("highest"):
        want = token_by_token(*args)
        got = ssd_ops.ssd(*args, chunk)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
        g_want = jax.grad(lambda *a: jnp.sum(token_by_token(*a) * weight),
                          argnums=range(6))(*args)
        g_got = jax.grad(lambda *a: jnp.sum(ssd_ops.ssd(*a, chunk) * weight),
                         argnums=range(6))(*args)
    for name, a, b in zip("x dt A B C D".split(), g_got, g_want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("chunk", [4, 8])
def test_a_token_of_an_earlier_chunk_reaches_every_later_chunk(chunk):
    """Only token 1 changes; the outputs of every later chunk move, by
    what the recurrence says: the carry between chunks."""
    x, dt, A, B, C, D = scan_inputs()
    dt = 0.05 * dt                       # a state that lasts the sequence
    moved = x.at[:, 1].add(1.0)
    with jax.default_matmul_precision("highest"):
        delta = ssd_ops.ssd(moved, dt, A, B, C, D, chunk) \
            - ssd_ops.ssd(x, dt, A, B, C, D, chunk)
        want = token_by_token(moved, dt, A, B, C, D) \
            - token_by_token(x, dt, A, B, C, D)
    np.testing.assert_allclose(delta, want, atol=2e-5)
    assert not np.asarray(delta[:, 0]).any()          # causal
    for c in range(1, T // chunk):                    # the later chunks
        assert np.abs(np.asarray(delta[:, c * chunk:(c + 1) * chunk])
                      ).max() > 1e-3


def test_whole_chunks_only():
    x, dt, A, B, C, D = scan_inputs(t=12)
    with pytest.raises(ValueError, match="whole chunks"):
        ssd_ops.ssd(x, dt, A, B, C, D, 8)
    with pytest.raises(ValueError, match="no multiple of the scan's chunk"):
        Mamba2Mixer(4, 8, 2, 8, chunk=8).output_spec(
            [Spec((2, 12, E), jnp.float32)])


def test_convolution_is_causal_and_depthwise():
    x = jax.random.normal(jax.random.key(0), (2, 10, 6))
    w = jax.random.normal(jax.random.key(1), (4, 6))
    b = jax.random.normal(jax.random.key(2), (6,))
    y = causal_conv_silu(x, w, b)
    want = np.zeros((2, 10, 6), np.float32)
    for t in range(10):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += np.asarray(w[k]) * np.asarray(x[:, t - 3 + k])
    np.testing.assert_allclose(y, jax.nn.silu(want + np.asarray(b)),
                               atol=1e-5)
    # a later token and another channel change nothing before or beside
    moved = causal_conv_silu(x.at[:, 5, 2].add(1.0), w, b) - y
    assert not np.asarray(moved[:, :5]).any()
    assert not np.asarray(moved[..., [0, 1, 3, 4, 5]]).any()
    assert np.asarray(moved[:, 5:9, 2]).all() and not \
        np.asarray(moved[:, 9:, 2]).any()


def plain_conv(x, w, b=None):
    """The convolution as it was written before its backward was: the
    reference that autodiff differentiates."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, i:i + t] * w[i] for i in range(k))
    return y if b is None else y + b


def plain_gated_norm(y, z, scale, n_groups, eps):
    h = y * jax.nn.silu(z)
    grouped = h.reshape(h.shape[:-1] + (n_groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return grouped.reshape(h.shape) * scale


def residual_shapes(f, *args):
    _, vjp = jax.vjp(f, *args)
    return sorted(tuple(x.shape) for x in jax.tree_util.tree_leaves(vjp)
                  if hasattr(x, "shape"))


@pytest.mark.parametrize("tokens", [10, 2])
@pytest.mark.parametrize("bias", [True, False])
@pytest.mark.parametrize("taps", [4, 2])
def test_convolutions_written_backward_is_autodiff_of_the_plain_one(
        taps, bias, tokens):
    """``dx``, ``dw``, ``db``, also where the sequence is shorter than
    the taps reach."""
    k = jax.random.split(jax.random.key(taps), 4)
    x = jax.random.normal(k[0], (2, tokens, 6))
    w = jax.random.normal(k[1], (taps, 6))
    b = jax.random.normal(k[2], (6,)) if bias else None
    weight = jax.random.normal(k[3], x.shape)

    def written(x, w, b):
        return jnp.sum(weight * causal_conv_silu(x, w, b))

    def plain(x, w, b):
        return jnp.sum(weight * jax.nn.silu(plain_conv(x, w, b)))

    argnums = (0, 1, 2) if bias else (0, 1)
    np.testing.assert_allclose(written(x, w, b), plain(x, w, b), rtol=1e-6)
    for name, got, want in zip(("dx", "dw", "db"),
                               jax.grad(written, argnums)(x, w, b),
                               jax.grad(plain, argnums)(x, w, b)):
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5,
                                   err_msg=name)


@pytest.mark.parametrize("n_groups", [1, 2, 8])
def test_gated_norms_written_backward_is_autodiff_of_the_plain_one(
        n_groups):
    k = jax.random.split(jax.random.key(n_groups), 4)
    y, z, weight = (jax.random.normal(k[i], (2, 10, 16)) for i in range(3))
    scale = jax.random.normal(k[3], (16,))

    def loss(norm):
        return lambda *a: jnp.sum(weight * norm(*a, n_groups, 1e-5))

    np.testing.assert_allclose(
        gated_group_rms_norm(y, z, scale, n_groups, 1e-5),
        plain_gated_norm(y, z, scale, n_groups, 1e-5), atol=1e-6)
    for name, got, want in zip(
            ("dy", "dz", "dscale"),
            jax.grad(loss(gated_group_rms_norm), (0, 1, 2))(y, z, scale),
            jax.grad(loss(plain_gated_norm), (0, 1, 2))(y, z, scale)):
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5,
                                   err_msg=name)


def test_the_two_stages_keep_their_inputs_alone_for_the_backward():
    """Differentiated as written the convolution keeps its pre-activation
    for ``silu`` beside ``x``, and the norm its grouped product and the
    statistic; the written ones keep what they were given."""
    x = jax.random.normal(jax.random.key(0), (1, 32, 8))
    w, b = jnp.ones((4, 8)), jnp.zeros((8,))
    assert len(residual_shapes(
        lambda *a: jax.nn.silu(plain_conv(*a)), x, w, b)) > 3
    assert residual_shapes(causal_conv_silu, x, w, b) \
        == sorted([x.shape, w.shape, b.shape])
    assert residual_shapes(causal_conv_silu, x, w) \
        == sorted([x.shape, w.shape])
    y, z, scale = x, x + 1.0, jnp.ones((8,))
    assert (1, 32, 2, 1) in residual_shapes(
        lambda *a: plain_gated_norm(*a, 2, 1e-5), y, z, scale)
    assert residual_shapes(
        lambda *a: gated_group_rms_norm(*a, 2, 1e-5), y, z, scale) \
        == sorted([y.shape, z.shape, scale.shape])


def test_gated_norm_takes_whole_groups_only():
    y = jnp.ones((1, 4, 10))
    with pytest.raises(ValueError, match="do not divide into 4 groups"):
        gated_group_rms_norm(y, y, jnp.ones((10,)), 4, 1e-5)
    with pytest.raises(ValueError, match="do not divide into 4 groups"):
        jax.grad(lambda y: jnp.sum(gated_group_rms_norm(
            y, y, jnp.ones((10,)), 4, 1e-5)))(y)


MIXER = dict(type="mamba2", n_heads=4, head_dim=8, n_groups=2, state_size=8,
             conv_kernel=4, chunk=4, dt_origin=-1.5)
EXPERTS = dict(type="routed_experts", n_experts=8, d_hidden=16, top_k=2,
               route_scale=2.5, shared_width=32, gated=False,
               activation="relu2", block_rows=8, use_pallas=True)


def hybrid_layers(pattern="ME*ME"):
    mixers = {"M": MIXER, "E": EXPERTS,
              "*": dict(type="attention", n_heads=4, n_kv_heads=2,
                        head_dim=8, use_flash=False, block_size=8)}
    layers = [dict(type="embedding", vocab=VOCAB, dim=E, name="emb")]
    stream = "emb"
    for i, c in enumerate(pattern):
        mix = dict(mixers[c], name=f"b{i}_mix")
        layers += [dict(type="rms_norm", name=f"b{i}_norm"), mix,
                   dict(type="add", name=f"b{i}",
                        inputs=[f"b{i}_mix", stream])]
        stream = f"b{i}"
    return layers + [
        dict(type="rms_norm", name="final"),
        dict(type="all2all", output_size=VOCAB, per_position=True,
             include_bias=False, name="head")]


def random_vectors(params, key):
    """Every vector away from what it starts at, so that one left out
    shows: scales, biases, the steps' bias, the decays, the skips."""
    def leaf(path, x):
        if x.ndim > 1:
            return x
        k = jax.random.fold_in(key, hash(str(path)) % (2 ** 31))
        return x + 0.3 * jax.random.normal(k, x.shape)
    return jax.tree_util.tree_map_with_path(leaf, params)


def test_hybrid_block_matches_the_plain_reference_logits_loss_gradients():
    layers = hybrid_layers()
    sw = StandardWorkflow({"name": "tiny", "loss": "softmax",
                           "optimizer": "adam", "layers": layers})
    wf = sw.workflow
    wf.build({"@input": Spec((2, T), jnp.int32),
              "@labels": Spec((2, T), jnp.int32),
              "@mask": Spec((2,), jnp.float32)})
    wstate = wf.init_state(jax.random.key(3), sw.optimizer)
    params = random_vectors(wstate["params"], jax.random.key(5))
    ids = np.random.default_rng(0).integers(0, VOCAB, (2, T + 1))
    batch = {"@input": jnp.asarray(ids[:, :-1], jnp.int32),
             "@labels": jnp.asarray(ids[:, 1:], jnp.int32),
             "@mask": jnp.ones((2,), jnp.float32)}

    def program_loss(params):
        outs, _ = wf.forward(params, wstate["state"], batch,
                             Context(train=True, key=jax.random.key(0)))
        return outs["evaluator"], outs["head"]

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(params)
        ref_loss = nemotron_h.make_loss(layers)
        (ce, n), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: ref_loss(p, batch, cast_float32),
            has_aux=True))(params)
        ref_logits, counts = jax.jit(
            lambda p: nemotron_h.make_forward(layers)(p, batch, cast_float32)
        )(params)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(loss, ce / n, rtol=1e-5)
    assert set(counts) == {"b1_mix", "b4_mix"}
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    # 2 mixers of 8 leaves, 2 expert layers of 5, attention 4, 6 norms,
    # table and head
    assert len(flat) == len(ref_flat) == 2 * 8 + 2 * 5 + 4 + 6 + 2
    for path, g in flat:
        np.testing.assert_allclose(
            g, ref_flat[path] / n, atol=2e-5, rtol=2e-4,
            err_msg=jax.tree_util.keystr(path))


def test_mixer_is_the_reference_layer_and_counts_its_chunks():
    from veles_tpu.runtime.metrics import registry
    spec = {k: v for k, v in MIXER.items() if k != "type"}
    x = jax.random.normal(jax.random.key(2), (2, T, E))
    unit = Mamba2Mixer(name="mix", **spec)
    params, state = unit.init(jax.random.key(1),
                              [Spec((2, T, E), jnp.float32)])
    # the published ranges: steps in [0.001, 0.1], decays in [1, 16]
    dt = jax.nn.softplus(params["dt_bias"] + unit.dt_origin)
    assert 1e-3 <= float(dt.min()) and float(dt.max()) <= 0.1
    assert 0.0 <= float(params["A_log"].min()) \
        and float(params["A_log"].max()) <= np.log(16.0)
    with jax.default_matmul_precision("highest"):
        y, _ = unit.apply(params, state, [x], Context(train=False))
        want = nemotron_h._mamba2(dict(MIXER), params, x, cast_float32, ())
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=1e-5)
    gauge = registry().get("vt_ssd_chunks")
    assert [child.value for key, child in gauge._snapshot()
            if "mix" in key] == [T // MIXER["chunk"]]


def test_the_written_backwards_scopes_are_in_the_compiled_program():
    from veles_tpu.runtime.metrics import registry
    unit = Mamba2Mixer(name="mix9", **{k: v for k, v in MIXER.items()
                                       if k != "type"})
    params, state = unit.init(jax.random.key(1),
                              [Spec((2, T, E), jnp.float32)])
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(unit.apply(
        p, state, [x], Context(train=True))[0]))).lower(
            params, jnp.ones((2, T, E))).as_text(debug_info=True)
    # inside the stage's own scope, on the backward side
    for scope in ("transpose(jvp(ssm_conv))/ssm_conv_bwd/",
                  "transpose(jvp(ssm_gate_norm))/ssm_gate_norm_bwd/"):
        assert scope in text, scope
    gauge = registry().get("vt_ssm_backward_path")
    assert {key: child.value for key, child in gauge._snapshot()
            if "mix9" in key} == {("mix9", "conv", "written"): 1.0,
                                  ("mix9", "gate_norm", "written"): 1.0}


def test_scan_keeps_its_inputs_alone_for_the_backward():
    """Differentiated as written the expression keeps the (chunks, heads,
    Q, Q) decay mask and the masked scores; ``ssd`` keeps neither."""
    args = scan_inputs(b=1, t=32, h=4, p=8, g=2, n=8)
    square = (1, 4, 2, 2, 8, 8)               # (b, chunks, g, r, Q, Q)
    assert square in residual_shapes(
        lambda *a: ssd_ops.ssd_chunked(*a, 8), *args)
    kept = set(residual_shapes(lambda *a: ssd_ops.ssd(*a, 8), *args))
    assert square not in kept
    assert kept <= {tuple(a.shape) for a in args}


def experts_unit(**kw):
    spec = {k: v for k, v in dict(EXPERTS, **kw).items() if k != "type"}
    return RoutedExpertsFFN(name="mlp", **spec)


def test_ungated_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts of all the shares, with the
    shared expert counted once, are the whole layer of the uncut
    reference; each share is the reference's same share."""
    whole, state = experts_unit().init(jax.random.key(1),
                                       [Spec((2, T, E), jnp.float32)])
    assert set(whole) == {"router", "wu", "wd", "shared_wu", "shared_wd"}
    x = jax.random.normal(jax.random.key(2), (2, T, E))
    with jax.default_matmul_precision("highest"):
        ref, _ = nemotron_h._routed_experts(dict(EXPERTS), whole, x,
                                            cast_float32, ())
        shared = nemotron_h._relu2_mlp(x, whole["shared_wu"],
                                       whole["shared_wd"], cast_float32)
        total, routed_rows = shared, 0
        for share in range(4):
            cut = dict(experts_held=2, expert_offset=2 * share)
            part = {k: (v[2 * share:2 * share + 2] if k in ("wu", "wd")
                        else v) for k, v in whole.items()}
            y, new = experts_unit(**cut).apply(part, state, [x],
                                               Context(train=False))
            total = total + (y - shared)
            routed_rows += int(new["counters"]["rows_routed"])
            ref_part, n = nemotron_h._routed_experts(
                dict(EXPERTS, **cut), part, x, cast_float32, ())
            np.testing.assert_allclose(y, ref_part, atol=1e-5)
            assert int(n) == int(new["counters"]["rows_routed"])
    np.testing.assert_allclose(total, ref, atol=2e-5)
    assert routed_rows == 2 * T * 2          # every route lands somewhere


@pytest.mark.parametrize("use_pallas", [True, False])
def test_ungated_experts_gradients_are_the_reference_layer(use_pallas):
    unit = experts_unit(experts_held=4, expert_offset=2,
                        use_pallas=use_pallas)
    params, state = unit.init(jax.random.key(4),
                              [Spec((2, T, E), jnp.float32)])
    x = jax.random.normal(jax.random.key(6), (2, T, E))
    layer = dict(EXPERTS, experts_held=4, expert_offset=2)

    def program(params, x):
        return jnp.sum(jnp.sin(unit.apply(params, state, [x],
                                          Context(train=True))[0]))

    def reference(params, x):
        return jnp.sum(jnp.sin(nemotron_h._routed_experts(
            layer, params, x, cast_float32, ())[0]))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(program, argnums=(0, 1))(params, x)
        want = jax.grad(reference, argnums=(0, 1))(params, x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))


def test_gated_unit_keeps_its_three_matrices_and_its_keys():
    """The flag's default is the unit it was: the same leaves from the
    same key."""
    spec = {k: v for k, v in EXPERTS.items()
            if k not in ("type", "gated", "activation")}
    gated, _ = RoutedExpertsFFN(name="mlp", **spec).init(
        jax.random.key(1), [Spec((2, T, E), jnp.float32)])
    ungated, _ = experts_unit().init(jax.random.key(1),
                                     [Spec((2, T, E), jnp.float32)])
    assert set(gated) == set(ungated) | {"wg", "shared_wg"}
    for k in ungated:
        assert np.array_equal(np.asarray(gated[k]), np.asarray(ungated[k]))


@pytest.mark.parametrize("n,block_cols,want", [
    (640, 512, 128), (192, 128, 192), (384, 256, 128), (1856, 512, 1856),
    (2688, 512, 384), (1024, 512, 512), (2048, 512, 512), (48, 512, 48)])
def test_grouped_kernels_cover_every_column(n, block_cols, want):
    """``grouped_matmul_dw`` took ``N // min(block_cols, N)`` column
    blocks and lost the columns behind the last whole one (1536..1855 of
    1856); all three kernels against ``jnp`` at such widths."""
    assert pk._gmm_dw_block_cols(n, block_cols) == want
    if n > 640:
        return                        # the rule alone at the real widths
    sizes = (5, 0, 11, 8)
    tm, K, M = 8, 16, 48
    sizes_j = jnp.asarray(sizes, jnp.int32)
    row_start = np.asarray(pk.group_tiles(sizes_j, tm)[1])
    rng = np.random.default_rng(n)
    lhs, valid = np.zeros((M, K), np.float32), np.zeros(M, bool)
    for size, r0 in zip(sizes, row_start):
        lhs[r0:r0 + size] = rng.standard_normal((size, K))
        valid[r0:r0 + size] = True
    rhs = rng.standard_normal((len(sizes), K, n)).astype(np.float32)
    weight = rng.standard_normal((M, n)).astype(np.float32)

    def kernel(lhs, rhs):
        out = pk.grouped_matmul(lhs, rhs, sizes_j, tm, block_cols, True)
        return jnp.sum(jnp.where(valid[:, None], out * weight, 0.0)), out

    def plain(lhs, rhs):
        out = jnp.zeros((M, n))
        for e, (size, r0) in enumerate(zip(sizes, row_start)):
            out = out.at[r0:r0 + size].set(jnp.dot(
                lhs[r0:r0 + size], rhs[e], precision="highest"))
        return jnp.sum(out * weight), out

    (_, out), (dl, dr) = jax.value_and_grad(
        kernel, argnums=(0, 1), has_aux=True)(jnp.asarray(lhs),
                                              jnp.asarray(rhs))
    (_, ref), (wl, wr) = jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True)(jnp.asarray(lhs),
                                             jnp.asarray(rhs))
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(ref)[valid], atol=1e-4)
    np.testing.assert_allclose(np.asarray(dl)[valid], np.asarray(wl)[valid],
                               atol=1e-4)
    np.testing.assert_allclose(dr, wr, atol=1e-4)       # every column
    assert np.asarray(wr)[:, :, -1].any()
