"""Kimi Linear's block on the CPU in float32: the delta rule with a decay
by channel against the token-by-token recurrence, ``KimiDeltaAttention``
and latent attention against the plain reference
``benchmarks/references/kimi_linear.py``, a tiny model of the family
through ``StandardWorkflow`` against it, the routed layer's shares, and
the units' scopes and gauges.
"""

import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from references import afmoe, kimi_linear  # noqa: E402
from references.olmo_hybrid import l2norm  # noqa: E402
from references.train_steps import cast_float32  # noqa: E402

from veles_tpu.models.standard import StandardWorkflow  # noqa: E402
from veles_tpu.ops import gated_delta as gd  # noqa: E402
from veles_tpu.parallel.ring_attention import blockwise_attention  # noqa: E402
from veles_tpu.runtime.metrics import registry  # noqa: E402
from veles_tpu.units.base import Context, Spec  # noqa: E402
from veles_tpu.units.linear_attention import (GatedDeltaNet,  # noqa: E402
                                              KimiDeltaAttention)
from veles_tpu.units.parallel_nn import (MultiHeadAttention,  # noqa: E402
                                         RoutedExpertsFFN)

E, T, VOCAB, CHUNK = 32, 16, 64, 4
NAMES = "q k v g beta".split()
KDA = dict(type="kimi_delta_attention", n_heads=2, head_dim=8,
           conv_kernel=4, chunk=CHUNK, norm_eps=1e-5, dt_origin=-1.2)
MLA = dict(type="attention", n_heads=4, head_dim=12, kv_latent=16,
           k_shared=4, v_head_dim=8, norm_eps=1e-5, use_flash=False,
           block_size=8)
ROUTED = dict(type="routed_experts", n_experts=16, d_hidden=16, top_k=4,
              route_scale=2.446, shared_width=16, block_rows=8,
              use_pallas=True)


def rule_inputs(t, b=2, h=3, dk=8, dv=12, seed=0):
    """Unit keys, scaled unit queries, decays by channel anywhere in
    [-20, 0] a token (a third of them steep, the rest mild), steps in
    (0, 1)."""
    k = jax.random.split(jax.random.key(seed), 6)
    q = l2norm(jax.random.normal(k[0], (b, t, h, dk))) * dk ** -0.5
    kk = l2norm(jax.random.normal(k[1], (b, t, h, dk)))
    v = jax.random.normal(k[2], (b, t, h, dv))
    steep = jax.random.uniform(k[3], (b, t, h, dk)) < 1 / 3
    g = jnp.where(steep, -20.0, -0.2) * jax.random.uniform(k[4],
                                                           (b, t, h, dk))
    beta = jax.nn.sigmoid(jax.random.normal(k[5], (b, t, h)))
    return q, kk, v, g, beta


def rel(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return np.abs(a - b).max() / np.abs(b).max()


@pytest.mark.parametrize("chunk", [16, 64])
def test_channel_decay_rule_is_the_recurrence_outputs_and_every_gradient(
        chunk):
    """float32 throughout, gates as steep as -20 a token by channel: no
    number that is no number, and outputs and every gradient within 1e-4
    of the recurrence's largest entry (read: 7.5e-6 and 5.7e-6).  Chunks of
    64 take the blocks of 16 below the diagonal; chunks of 16 only the
    diagonal one."""
    args = rule_inputs(2 * chunk)
    weight = jax.random.normal(jax.random.key(9), args[2].shape)

    def both(rule):
        return rule(*args), jax.grad(
            lambda *a: jnp.sum(rule(*a) * weight), argnums=range(5))(*args)

    with jax.default_matmul_precision("highest"):
        want, g_want = both(kimi_linear.delta_rule)
        got, g_got = both(lambda *a: gd.gated_delta(*a, chunk))
    assert np.isfinite(np.asarray(got)).all()
    assert rel(got, want) < 1e-4
    for name, a, b in zip(NAMES, g_got, g_want):
        assert np.isfinite(np.asarray(a)).all(), name
        assert rel(a, b) < 1e-4, name


def test_a_decay_alike_on_every_channel_is_the_scalar_rule():
    """The decay by channel with every channel alike is the decay a head,
    to float32's rounding, and ``Diag(exp(G_last))`` carries what
    ``exp(G_last)`` carries."""
    q, k, v, g, beta = rule_inputs(128)
    scalar = g[..., 0]
    with jax.default_matmul_precision("highest"):
        by_head = gd.gated_delta(q, k, v, scalar, beta, 64)
        by_channel = gd.gated_delta(
            q, k, v, jnp.broadcast_to(scalar[..., None], g.shape), beta, 64)
    np.testing.assert_allclose(by_channel, by_head, atol=2e-5, rtol=1e-5)


def test_the_tolerance_catches_a_decay_a_head_in_place_of_the_channels():
    """The mean of a head's channels in place of each channel's own decay
    moves the outputs by far more than the tolerance above (read: 0.70
    of the largest output)."""
    q, k, v, g, beta = rule_inputs(128)
    with jax.default_matmul_precision("highest"):
        want = kimi_linear.delta_rule(q, k, v, g, beta)
        mean = gd.gated_delta(q, k, v, g.mean(-1), beta, 64)
    assert rel(mean, want) > 100 * 1e-4


def random_vectors(params, key):
    """Every vector away from what it starts at, so that one left out
    shows: scales, the decays' bias, A_log."""
    def leaf(path, x):
        if x.ndim > 1:
            return x
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % (2 ** 31))
        return x + 0.3 * jax.random.normal(k, x.shape)
    return jax.tree_util.tree_map_with_path(leaf, params)


def unit_of(spec, klass, name="mix"):
    return klass(name=name, **{k: v for k, v in spec.items()
                                if k != "type"})


def test_kda_mixer_is_the_reference_layer_and_sets_its_gauges():
    # a name no other test file's unit takes: the gauges are the
    # process's, and a worker may run the GatedDeltaNet tests' "mix" first
    unit = unit_of(KDA, KimiDeltaAttention, "kda_mix")
    params, state = unit.init(jax.random.key(1),
                              [Spec((2, T, E), jnp.float32)])
    assert {k: v.shape for k, v in params.items()} == {
        "wq": (E, 16), "wk": (E, 16), "wv": (E, 16),
        "conv_q": (4, 16), "conv_k": (4, 16), "conv_v": (4, 16),
        "wf_a": (E, 8), "wf_b": (8, 16), "A_log": (2,), "dt_bias": (16,),
        "wb": (E, 2), "wg_a": (E, 8), "wg_b": (8, 16), "o_norm": (8,),
        "wo": (16, E)}
    # the published decays: A uniform in (1, 16)
    assert 0.0 <= float(params["A_log"].min()) <= \
        float(params["A_log"].max()) <= np.log(16.0)
    params = random_vectors(params, jax.random.key(5))
    x = jax.random.normal(jax.random.key(2), (2, T, E))
    with jax.default_matmul_precision("highest"):
        y, _ = unit.apply(params, state, [x], Context(train=False))
        want = kimi_linear._kda(dict(KDA), params, x, cast_float32, ())
        # each planted fault moves the output
        for fault in ("delta_carry", "channel_decay"):
            moved = kimi_linear._kda(dict(KDA), params, x, cast_float32,
                                     (fault,))
            assert rel(moved, want) > 1e-2, fault
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=1e-5)
    gauges = {name: {key: child.value for key, child in
                     registry().get(name)._snapshot() if "kda_mix" in key}
              for name in ("vt_kda_chunks", "vt_delta_gate")}
    assert list(gauges["vt_kda_chunks"].values()) == [T // CHUNK]
    assert gauges["vt_delta_gate"] == {("kda_mix", "channel"): 1}
    gdn = GatedDeltaNet(3, 6, 10, name="gdn", chunk=CHUNK)
    p, s = gdn.init(jax.random.key(0), [Spec((1, T, E), jnp.float32)])
    gdn.apply(p, s, [x[:1]], Context(train=False))
    assert [key for key, _ in registry().get("vt_delta_gate")._snapshot()
            if "gdn" in key] == [("gdn", "head")]


@pytest.mark.parametrize("make", [
    lambda chunk: KimiDeltaAttention(2, 8, name="solve_kda", chunk=chunk),
    lambda chunk: GatedDeltaNet(3, 6, 10, name="solve_gdn", chunk=chunk)],
    ids=["kda", "gdn"])
@pytest.mark.parametrize("chunk, block", [(8, 8), (64, 16)])
def test_solve_block_gauge_is_the_block_the_inverse_takes(make, chunk,
                                                          block):
    """Both mixers set ``vt_delta_solve_block`` when their call is traced:
    blocks of 16 at a chunk of 64, the rows loop's whole chunk at 8."""
    unit = make(chunk)
    spec = Spec((1, chunk, E), jnp.float32)
    params, state = unit.init(jax.random.key(0), [spec])
    jax.eval_shape(lambda p, x: unit.apply(p, state, [x],
                                           Context(train=False))[0],
                   params, spec)
    assert [(key, child.value) for key, child in
            registry().get("vt_delta_solve_block")._snapshot()
            if unit.name in key] == [((unit.name,), block)]


def test_the_units_scopes_are_in_the_compiled_program():
    x = jnp.zeros((2, T, E))
    for spec, klass, scopes in (
            (KDA, KimiDeltaAttention,
             ("kda_in_proj", "kda_conv", "kda_gate", "kda_scan",
              "gdn_chunk", "gdn_solve", "gdn_carry", "kda_gate_norm",
              "kda_out_proj")),
            (MLA, MultiHeadAttention, ("attn_kv_down", "attn_kv_up"))):
        unit = unit_of(spec, klass)
        params, state = unit.init(jax.random.key(1),
                                  [Spec((2, T, E), jnp.float32)])
        text = jax.jit(jax.grad(lambda p, x: jnp.sum(unit.apply(
            p, state, [x], Context(train=True))[0]))).lower(
                params, x).as_text(debug_info=True)
        for scope in scopes:
            assert scope in text, scope
    assert [child.value for key, child in
            registry().get("vt_attn_latent")._snapshot()
            if "mix" in key] == [16]


def test_latent_attention_is_plain_softmax_attention():
    unit = unit_of(MLA, MultiHeadAttention)
    params, state = unit.init(jax.random.key(3),
                              [Spec((2, T, E), jnp.float32)])
    # 4 heads x 12 for q, the 16-wide latent and a shared key of 4, the
    # up projections to 4 x 8 of each, values 4 x 8 wide
    assert {k: v.shape for k, v in params.items()} == {
        "wq": (E, 48), "w_kv_down": (E, 20), "kv_norm": (16,),
        "wk_up": (16, 32), "wv_up": (16, 32), "wo": (32, E)}
    params = random_vectors(params, jax.random.key(4))
    x = jax.random.normal(jax.random.key(5), (2, T, E))
    with jax.default_matmul_precision("highest"):
        y, _ = unit.apply(params, state, [x], Context(train=False))
        want = kimi_linear._latent_attention(dict(MLA), params, x,
                                             cast_float32)
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=1e-5)
    for bad in (dict(qk_norm=True), dict(rope=True), dict(gate=True),
                dict(n_kv_heads=2), dict(k_shared=12),
                dict(v_head_dim=16)):
        with pytest.raises(ValueError):
            MultiHeadAttention(**dict({k: v for k, v in MLA.items()
                                       if k != "type"}, **bad))
    with pytest.raises(ValueError):
        MultiHeadAttention(4, 8, v_head_dim=8)


@pytest.mark.parametrize("use_flash", [False, True])
def test_values_padded_to_the_keys_width_are_exact_to_the_bit(use_flash):
    """The core at the key's width with the values padded: the value's
    own columns come out bit for bit whatever the padding holds (no
    column of V reaches another), and zero padding gives zero columns.
    The flash kernels in interpret mode and the XLA path alike."""
    k = jax.random.split(jax.random.key(6), 4)
    q, kk = (jax.random.normal(k[i], (1, 128, 2, 12)) for i in (0, 1))
    v = jax.random.normal(k[2], (1, 128, 2, 8))
    attend = lambda v: blockwise_attention(
        q, kk, v, block_size=32, causal=True, use_flash=use_flash,
        flash_blocks=(64, 64) if use_flash else None)
    zeros = attend(jnp.pad(v, ((0, 0),) * 3 + ((0, 4),)))
    noise = attend(jnp.concatenate(
        [v, jax.random.normal(k[3], (1, 128, 2, 4))], axis=-1))
    assert np.array_equal(zeros[..., :8], noise[..., :8])
    assert not np.asarray(zeros[..., 8:]).any()


def tiny_layers():
    """Pre-norm blocks ``h + f(RMS(h))``: KDA with a dense MLP, then
    latent attention and KDA with routed experts."""
    layers = [dict(type="embedding", vocab=VOCAB, dim=E, name="emb")]
    stream = "emb"
    for i, (mixer, mlp) in enumerate(
            ((KDA, dict(type="gated_mlp", d_hidden=48)),
             (MLA, dict(ROUTED, experts_held=4, expert_offset=4)),
             (KDA, dict(ROUTED, experts_held=4)))):
        layers += [
            dict(type="rms_norm", eps=1e-5, name=f"b{i}_in",
                 inputs=[stream]),
            dict(mixer, name=f"b{i}_mix", inputs=[f"b{i}_in"]),
            dict(type="add", name=f"b{i}_a", inputs=[f"b{i}_mix", stream]),
            dict(type="rms_norm", eps=1e-5, name=f"b{i}_mlp_in"),
            dict(mlp, name=f"b{i}_mlp"),
            dict(type="add", name=f"b{i}", inputs=[f"b{i}_mlp", f"b{i}_a"])]
        stream = f"b{i}"
    return layers + [
        dict(type="rms_norm", eps=1e-5, name="final"),
        dict(type="all2all", output_size=VOCAB, per_position=True,
             include_bias=False, name="head")]


def test_tiny_block_matches_the_plain_reference_logits_loss_gradients():
    layers = tiny_layers()
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
        (ce, n), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: kimi_linear.make_loss(layers)(p, batch, cast_float32),
            has_aux=True))(params)
        ref_logits, counts = jax.jit(
            lambda p: kimi_linear.make_forward(layers)(
                p, batch, cast_float32))(params)
    np.testing.assert_allclose(logits, ref_logits, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(loss, ce / n, rtol=1e-5)
    assert set(counts) == {"b1_mlp", "b2_mlp"}
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    # 2 KDA mixers of 15 leaves, latent attention 6, the MLP 3, 2 routed
    # layers of 7, 7 norms, table and head
    assert len(flat) == len(ref_flat) == 2 * 15 + 6 + 3 + 2 * 7 + 7 + 2
    for path, g in flat:
        want = np.asarray(ref_flat[path] / n)
        np.testing.assert_allclose(
            g, want, atol=2e-5 * max(1.0, np.abs(want).max()), rtol=2e-4,
            err_msg=jax.tree_util.keystr(path))


def test_four_shares_of_the_routed_layer_add_up_to_the_uncut_layer():
    """Guide section 4 at the family's router (sigmoid, renormalised over
    the top 4 of 16, scaled by 2.446): the parts four shares of 4
    experts give, with the shared expert counted once, are the whole
    layer."""
    whole_unit = unit_of(ROUTED, RoutedExpertsFFN)
    whole, state = whole_unit.init(jax.random.key(1),
                                   [Spec((2, T, E), jnp.float32)])
    x = jax.random.normal(jax.random.key(2), (2, T, E))
    with jax.default_matmul_precision("highest"):
        ref, _ = kimi_linear._routed_experts(dict(ROUTED), whole, x,
                                             cast_float32, ())
        shared = afmoe._gated(x, whole["shared_wg"], whole["shared_wu"],
                              whole["shared_wd"], cast_float32)
    total, routed_rows = shared, 0
    for share in range(4):
        layer = dict(ROUTED, experts_held=4, expert_offset=4 * share)
        unit = unit_of(layer, RoutedExpertsFFN)
        part = {k: (v[4 * share:4 * share + 4] if k in ("wg", "wu", "wd")
                    else v) for k, v in whole.items()}
        y, new = unit.apply(part, state, [x], Context(train=False))
        total = total + (y - shared)
        routed_rows += int(new["counters"]["rows_routed"])
        with jax.default_matmul_precision("highest"):
            ref_part, n = kimi_linear._routed_experts(layer, part, x,
                                                      cast_float32, ())
        np.testing.assert_allclose(y, ref_part, atol=1e-5)
        assert int(n) == int(new["counters"]["rows_routed"])
    np.testing.assert_allclose(total, ref, atol=2e-5)
    assert routed_rows == 2 * T * 4          # every route lands somewhere
