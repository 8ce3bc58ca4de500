"""The gated delta-rule mixer, the whole-projection QK norm and the block
``h + N(f(h))``, on the CPU: the chunked program against the recurrence
token by token (outputs and every gradient, float32 and bfloat16
operands), what the tolerances catch, what its backward keeps, the
triangular inverse and its gradient, the units against the plain
reference ``benchmarks/references/olmo_hybrid.py`` through
``StandardWorkflow``, the head shares' sum, the QK norm against the
family's formula, and the spans and gauges.
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

from references import afmoe, olmo_hybrid  # noqa: E402
from references.train_steps import cast_float32  # noqa: E402

from veles_tpu.models.standard import StandardWorkflow  # noqa: E402
from veles_tpu.ops import gated_delta as gd  # noqa: E402
from veles_tpu.runtime.metrics import registry  # noqa: E402
from veles_tpu.units.base import Context, Spec  # noqa: E402
from veles_tpu.units.linear_attention import GatedDeltaNet  # noqa: E402
from veles_tpu.units.nn import GatedMLP  # noqa: E402
from veles_tpu.units.parallel_nn import MultiHeadAttention  # noqa: E402

E, T, VOCAB, CHUNK = 32, 16, 64, 4
NAMES = "q k v g beta".split()


def rule_inputs(t, neg, b=2, h=3, dk=6, dv=10, seed=0, dtype=jnp.float32):
    """Unit keys, scaled unit queries, decays that keep about half the
    state over a chunk of 64, steps in (0, 1) or, with the negative
    eigenvalues allowed, (0, 2)."""
    k = jax.random.split(jax.random.key(seed), 5)
    q = olmo_hybrid.l2norm(jax.random.normal(k[0], (b, t, h, dk))) * dk ** -.5
    kk = olmo_hybrid.l2norm(jax.random.normal(k[1], (b, t, h, dk)))
    v = jax.random.normal(k[2], (b, t, h, dv))
    g = -0.02 * jax.nn.softplus(jax.random.normal(k[3], (b, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (b, t, h))) \
        * (2.0 if neg else 1.0)
    return tuple(a.astype(dtype) for a in (q, kk, v)) + (g, beta)


def outputs_and_gradients(rule, args, weight):
    out = rule(*args)
    grads = jax.grad(
        lambda *a: jnp.sum(rule(*a).astype(jnp.float32) * weight),
        argnums=range(5))(*args)
    return out, grads


@pytest.mark.parametrize("neg", [False, True])
@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_chunked_rule_is_the_recurrence_outputs_and_every_gradient(chunks,
                                                                   neg):
    """float32 throughout: the two differ by the order of float32 sums
    (2e-5 of outputs of order 1, 2e-4 of gradients of order 10; read:
    7e-7 and 9e-6)."""
    args = rule_inputs(64 * chunks, neg)
    weight = jax.random.normal(jax.random.key(9), args[2].shape)
    with jax.default_matmul_precision("highest"):
        want, g_want = outputs_and_gradients(olmo_hybrid.delta_rule, args,
                                             weight)
        got, g_got = outputs_and_gradients(gd.gated_delta, args, weight)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    for name, a, b in zip(NAMES, g_got, g_want):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4, err_msg=name)


def rel(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return np.abs(a - b).max() / np.abs(b).max()


@pytest.mark.parametrize("neg", [False, True])
@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_bfloat16_operands_stay_within_their_rounding(chunks, neg):
    """Operands rounded to bfloat16 (8 bits: 4e-3 a product's operand),
    sums and the state float32: the outputs within 2 % and every gradient
    within 4 % of the recurrence's on the same rounded inputs (read: 0.5
    and 1.4 % at most).  What the limits catch is the next test's."""
    args = rule_inputs(64 * chunks, neg, dtype=jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    weight = jax.random.normal(jax.random.key(9), args[2].shape)
    with jax.default_matmul_precision("highest"):
        want, g_want = outputs_and_gradients(olmo_hybrid.delta_rule, exact,
                                             weight)
        # jitted: XLA's CPU runtime has no thunk for a bfloat16 product
        # with a float32 result outside a compiled program
        got, g_got = jax.jit(lambda *a: outputs_and_gradients(
            lambda *a: gd.gated_delta(*a, 64, jnp.bfloat16), a, weight))(
                *args)
    assert got.dtype == jnp.float32
    assert rel(got, want) < 0.02
    for name, a, b in zip(NAMES, g_got, g_want):
        assert rel(a, b) < 0.04, name


def test_the_tolerances_catch_a_bfloat16_state_and_a_dropped_term():
    """The float32 test's tolerances are under what a state carried in
    bfloat16 or the rule's correction left out would move: both fail
    them by more than tenfold."""
    args = rule_inputs(320, True)
    q, k, v, g, beta = args
    with jax.default_matmul_precision("highest"):
        want = olmo_hybrid.delta_rule(*args)
        plain = olmo_hybrid.delta_rule(*args, correct=False)

        def token(S, a):                  # the recurrence, S in bfloat16
            qt, kt, vt, gt, bt = a
            S = (jnp.exp(gt)[..., None, None] * S).astype(jnp.bfloat16)
            S = S.astype(jnp.float32)
            d = bt[..., None] * (vt - jnp.einsum("bhde,bhd->bhe", S, kt))
            S = (S + kt[..., :, None] * d[..., None, :]).astype(jnp.bfloat16)
            S = S.astype(jnp.float32)
            return S, jnp.einsum("bhde,bhd->bhe", S, qt)

        _, rounded = jax.lax.scan(
            token, jnp.zeros((2, 3, 6, 10)),
            tuple(jnp.moveaxis(a, 1, 0) for a in args))
    tolerance = 2e-5 + 1e-5 * np.abs(np.asarray(want))
    for other in (jnp.moveaxis(rounded, 0, 1), plain):
        assert (np.abs(np.asarray(other - want)) > 10 * tolerance).any()


@pytest.mark.parametrize("chunk", [4, 8])
def test_a_token_of_an_earlier_chunk_reaches_every_later_chunk(chunk):
    """Only token 1's value changes; the outputs of every later chunk
    move, by what the recurrence says: the carry between chunks."""
    q, k, v, g, beta = rule_inputs(16, True)
    moved = v.at[:, 1].add(1.0)
    with jax.default_matmul_precision("highest"):
        delta = gd.gated_delta(q, k, moved, g, beta, chunk) \
            - gd.gated_delta(q, k, v, g, beta, chunk)
        want = olmo_hybrid.delta_rule(q, k, moved, g, beta) \
            - olmo_hybrid.delta_rule(q, k, v, g, beta)
    np.testing.assert_allclose(delta, want, atol=2e-6)
    assert not np.asarray(delta[:, 0]).any()          # causal
    for c in range(1, 16 // chunk):                   # the later chunks
        assert np.abs(np.asarray(delta[:, c * chunk:(c + 1) * chunk])
                      ).max() > 1e-3


def test_whole_chunks_only():
    args = rule_inputs(12, True)
    with pytest.raises(ValueError, match="whole chunks"):
        gd.gated_delta(*args, 8)
    with pytest.raises(ValueError, match="no multiple of the delta rule's"):
        GatedDeltaNet(3, 6, 10, chunk=8).output_spec(
            [Spec((2, 12, E), jnp.float32)])


def rule_system(q, h=2, dk=8, seed=3):
    """``A`` (1, 1, h, Q, Q) as the rule builds it: unit keys that share
    most of one direction, steps in (0, 2), a decay by channel."""
    k = jax.random.split(jax.random.key(seed), 4)
    keys = olmo_hybrid.l2norm(
        jax.random.normal(k[0], (1, 1, 1, h, dk))
        + 0.3 * jax.random.normal(k[1], (1, 1, q, h, dk)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(k[2], (1, 1, q, h, 1)))
    cum = jnp.cumsum(-0.02 * jax.nn.softplus(
        jax.random.normal(k[3], (1, 1, q, h, dk))), axis=2)
    return -jnp.tril(gd._decayed_products(keys * beta, keys, cum,
                                          jnp.float32), -1)


@pytest.mark.parametrize("q, correlated", [(8, False), (16, False),
                                           (48, False), (64, False),
                                           (64, True)])
def test_inverse_and_its_gradient(q, correlated):
    """``(I - a)^-1``, by rows at Q of 16 or fewer and in blocks of 16
    above, against numpy's inverse in float64 and against the rows' loop,
    and its hand-written gradient against the one autodiff takes through
    the rows' loop."""
    if correlated:
        a = rule_system(q)
    else:
        a = jnp.tril(0.4 * (16 / q) ** 0.5 * jax.random.normal(
            jax.random.key(0), (3, 2, q, q)), -1)
    want = np.linalg.inv(np.eye(q) - np.asarray(a, np.float64))
    got = gd.unit_lower_inverse(a)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got, gd._inverse_rows(a), atol=1e-5,
                               rtol=1e-5)
    weight = jax.random.normal(jax.random.key(1), a.shape)
    grad = jax.grad(lambda a: jnp.sum(gd.unit_lower_inverse(a) * weight))(a)
    by_loop = jax.grad(lambda a: jnp.sum(gd._inverse_rows(a) * weight))(a)
    np.testing.assert_allclose(grad, jnp.tril(by_loop, -1), atol=1e-4,
                               rtol=1e-4)
    assert not np.asarray(jnp.triu(grad)).any()


def loop_lengths(jaxpr):
    """The trip counts of every ``scan`` in a jaxpr and the jaxprs under
    it."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) \
                    else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    out += loop_lengths(inner)
    return out


def test_inverse_at_a_chunk_of_64_loops_over_16_rows_not_64():
    a = jnp.zeros((2, 64, 64))
    assert gd.solve_block(64) == 16
    lengths = loop_lengths(jax.make_jaxpr(gd.unit_lower_inverse)(a).jaxpr)
    assert lengths == [15], lengths
    assert loop_lengths(jax.make_jaxpr(gd.unit_lower_inverse)(
        a[:, :8, :8]).jaxpr) == [7]


def test_rule_keeps_its_inputs_alone_for_the_backward():
    """Differentiated as written the expression keeps the (chunks, heads,
    Q, Q) masks and inverses and the states; ``gated_delta`` keeps none."""
    args = rule_inputs(32, True, b=1, h=2, dk=8, dv=16)

    def residual_shapes(f):
        _, vjp = jax.vjp(f, *args)
        return {tuple(x.shape) for x in jax.tree_util.tree_leaves(vjp)
                if hasattr(x, "shape")}

    square, states = (1, 4, 2, 8, 8), (1, 4, 2, 8, 16)
    written = residual_shapes(lambda *a: gd.gated_delta_chunked(*a, 8))
    assert square in written and states in written
    kept = residual_shapes(lambda *a: gd.gated_delta(*a, 8))
    assert kept <= {tuple(a.shape) for a in args}


MIXER = dict(type="gated_delta_net", n_heads=3, key_dim=6, value_dim=10,
             conv_kernel=4, chunk=CHUNK, norm_eps=1e-6,
             allow_neg_eigval=True, dt_origin=-1.5)
ATTENTION = dict(type="attention", n_heads=4, n_kv_heads=2, head_dim=8,
                 qk_norm="projection", norm_eps=1e-6, use_flash=False,
                 block_size=8)


def block_layers(pattern="LL*L", remat=()):
    """The family's blocks, ``h + N(f(h))`` twice a layer."""
    layers = [dict(type="embedding", vocab=VOCAB, dim=E, name="emb")]
    stream = "emb"
    for i, c in enumerate(pattern):
        mix = dict(MIXER if c == "L" else ATTENTION, name=f"b{i}_mix",
                   inputs=[stream])
        layers += [
            mix, dict(type="rms_norm", eps=1e-6, name=f"b{i}_mix_norm"),
            dict(type="add", name=f"b{i}_a",
                 inputs=[f"b{i}_mix_norm", stream]),
            dict(type="gated_mlp", d_hidden=48, name=f"b{i}_mlp",
                 remat=i in remat),
            dict(type="rms_norm", eps=1e-6, name=f"b{i}_mlp_norm"),
            dict(type="add", name=f"b{i}",
                 inputs=[f"b{i}_mlp_norm", f"b{i}_a"])]
        stream = f"b{i}"
    return layers + [
        dict(type="rms_norm", eps=1e-6, name="final"),
        dict(type="all2all", output_size=VOCAB, per_position=True,
             include_bias=False, name="head")]


def random_vectors(params, key):
    """Every vector away from what it starts at, so that one left out
    shows: scales, the steps' bias, the decays."""
    def leaf(path, x):
        if x.ndim > 1:
            return x
        # a checksum, not hash(): that differs from process to process
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % (2 ** 31))
        return x + 0.3 * jax.random.normal(k, x.shape)
    return jax.tree_util.tree_map_with_path(leaf, params)


def test_blocks_match_the_plain_reference_logits_loss_gradients():
    layers = block_layers(remat=(1,))
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

    reference_layers = [{k: v for k, v in l.items() if k != "remat"}
                        for l in layers]
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(params)
        ref_loss = olmo_hybrid.make_loss(reference_layers)
        (ce, n), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: ref_loss(p, batch, cast_float32),
            has_aux=True))(params)
        ref_logits, counts = jax.jit(
            lambda p: olmo_hybrid.make_forward(reference_layers)(
                p, batch, cast_float32))(params)
    # a norm after every sublayer divides by the RMS of what the sublayer
    # gave, so float32's last bits grow: the reference in float64 against
    # itself in float32 moves these logits by 4.5e-5
    np.testing.assert_allclose(logits, ref_logits, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(loss, ce / n, rtol=1e-5)
    assert counts == {}
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    # 3 delta-rule mixers of 13 leaves, attention 6, 4 MLPs of 3, 9 norms,
    # table and head
    assert len(flat) == len(ref_flat) == 3 * 13 + 6 + 4 * 3 + 9 + 2
    for path, g in flat:
        want = np.asarray(ref_flat[path] / n)
        # against the leaf's largest entry, for the norms' sake as above
        np.testing.assert_allclose(
            g, want, atol=5e-5 * max(1.0, np.abs(want).max()), rtol=2e-4,
            err_msg=jax.tree_util.keystr(path))


def test_mixer_is_the_reference_layer_its_ranges_and_its_gauges():
    spec = {k: v for k, v in MIXER.items() if k != "type"}
    x = jax.random.normal(jax.random.key(2), (2, T, E))
    unit = GatedDeltaNet(name="mix", **spec)
    params, state = unit.init(jax.random.key(1),
                              [Spec((2, T, E), jnp.float32)])
    assert {k: v.shape for k, v in params.items()} == {
        "wq": (E, 18), "wk": (E, 18), "wv": (E, 30), "wz": (E, 30),
        "wb": (E, 3), "wa": (E, 3), "conv_q": (4, 18), "conv_k": (4, 18),
        "conv_v": (4, 30), "A_log": (3,), "dt_bias": (3,),
        "gate_norm": (10,), "wo": (30, E)}
    # the published initialisation: decays in (0, 16), the steps' bias one
    np.testing.assert_allclose(params["dt_bias"] + unit.dt_origin, 1.0)
    assert float(params["A_log"].max()) <= np.log(16.0)
    with jax.default_matmul_precision("highest"):
        y, _ = unit.apply(params, state, [x], Context(train=False))
        want = olmo_hybrid._gated_delta_net(dict(MIXER), params, x,
                                            cast_float32, ())
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=1e-5)
    for gauge, value in (("vt_gdn_chunks", T // CHUNK), ("vt_gdn_heads", 3)):
        assert [child.value for key, child in
                registry().get(gauge)._snapshot() if "mix" in key] == [value]


def test_the_mixers_scopes_are_in_the_compiled_program():
    unit = GatedDeltaNet(name="mix", **{k: v for k, v in MIXER.items()
                                        if k != "type"})
    spec = [Spec((2, T, E), jnp.float32)]
    params, state = unit.init(jax.random.key(1), spec)
    text = jax.jit(lambda p, x: unit.apply(
        p, state, [x], Context(train=False))[0]).lower(
            params, jnp.zeros((2, T, E))).as_text(debug_info=True)
    for scope in ("gdn_in_proj", "gdn_conv", "gdn_scan/", "gdn_chunk",
                  "gdn_chunk/gdn_solve", "gdn_carry", "gdn_gate_norm",
                  "gdn_out_proj"):
        assert scope in text, scope


def olmo3_qk_norm(x, w, scale, eps):
    """``Olmo3Attention``: ``q_norm(q_proj(x))`` with ``Olmo3RMSNorm``
    over the whole projection (``modeling_olmo3.py:164-184``), in numpy
    float64."""
    y = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    return y / np.sqrt((y * y).mean(-1, keepdims=True) + eps) \
        * np.asarray(scale, np.float64)


def test_projection_wide_qk_norm_is_the_familys_and_a_heads_is_unchanged():
    """What reaches the attention core: the unit's q and k, read off a
    core that returns its queries (and, a second time, its keys)."""
    import importlib
    ring_attention = importlib.import_module(
        "veles_tpu.parallel.ring_attention")
    spec = [Spec((2, T, E), jnp.float32)]
    x = jax.random.normal(jax.random.key(2), (2, T, E))
    seen = {}

    def core(q, k, v, **kw):
        seen.update(q=q, k=k)
        return q

    def reach_the_core(unit, params, monkeypatch):
        monkeypatch.setattr(ring_attention, "blockwise_attention", core)
        with jax.default_matmul_precision("highest"):
            unit.apply(params, {}, [x], Context(train=False))
        return np.asarray(seen["q"]), np.asarray(seen["k"])

    with pytest.MonkeyPatch.context() as mp:
        whole = MultiHeadAttention(name="whole", **{
            k: v for k, v in ATTENTION.items() if k != "type"})
        params, _ = whole.init(jax.random.key(1), spec)
        assert params["q_norm"].shape == (32,) \
            and params["k_norm"].shape == (16,)
        params = random_vectors(params, jax.random.key(5))
        q, k = reach_the_core(whole, params, mp)
        np.testing.assert_allclose(
            q.reshape(2, T, 32), olmo3_qk_norm(x, params["wq"],
                                               params["q_norm"], 1e-6),
            atol=1e-5)
        np.testing.assert_allclose(
            k.reshape(2, T, 16), olmo3_qk_norm(x, params["wk"],
                                               params["k_norm"], 1e-6),
            atol=1e-5)
        # a head's norm, as the windowed-expert configuration runs it:
        # the same leaves, the same q and k, under either spelling
        heads = {}
        for spelling in (True, "head"):
            unit = MultiHeadAttention(4, 8, name=f"heads_{spelling}",
                                      n_kv_heads=2, qk_norm=spelling,
                                      use_flash=False, block_size=8)
            p, _ = unit.init(jax.random.key(1), spec)
            assert p["q_norm"].shape == p["k_norm"].shape == (8,)
            p = random_vectors(p, jax.random.key(5))
            heads[spelling] = reach_the_core(unit, p, mp)
            y = np.asarray(x, np.float64) @ np.asarray(p["wq"], np.float64)
            y = y.reshape(2, T, 4, 8)
            y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5) \
                * np.asarray(p["q_norm"], np.float64)
            np.testing.assert_allclose(heads[spelling][0], y, atol=1e-5)
        for a, b in zip(heads[True], heads["head"]):
            assert np.array_equal(a, b)
    kinds = {key: child.value for key, child in
             registry().get("vt_attn_qk_norm")._snapshot()}
    assert kinds[("whole", "projection")] == 1
    assert kinds[("heads_True", "head")] == kinds[("heads_head", "head")] == 1
    with pytest.raises(ValueError, match="qk_norm"):
        MultiHeadAttention(4, 8, qk_norm="rows")


def test_head_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the two head shares' mixer outputs, taken after
    ``Wo`` and before the block's norm, add up to the uncut reference's
    mixer output, for both kinds of mixer; attention's with the QK norm's
    sums of squares taken over both shares (what the pair of chips would
    exchange: the reference's ``exchanged``), and each share as it runs
    (no exchange) is the program's unit.  The MLP, computed alike on both
    chips, is counted once: the uncut block is the shares' sum through
    one norm, one MLP, one norm."""
    spec = [Spec((2, T, E), jnp.float32)]
    x = jax.random.normal(jax.random.key(2), (2, T, E))
    ctx = Context(train=False)

    def cut(params, share, by_head, by_row):
        """The leaves of head share ``share`` of two: columns of the
        input projections and of what lies on their channels, rows of
        ``wo``."""
        def half(a, axis):
            n = a.shape[axis] // 2
            return jax.lax.slice_in_dim(a, share * n, (share + 1) * n,
                                        axis=axis)
        return {k: half(v, -1) if k in by_head else
                half(v, 0) if k in by_row else v for k, v in params.items()}

    with jax.default_matmul_precision("highest"):
        # the delta-rule mixer: heads are independent, the gated norm is
        # a head's, so the program's shares add up as they are
        whole = dict(MIXER, n_heads=4)
        unit = GatedDeltaNet(name="whole", **{k: v for k, v in whole.items()
                                              if k != "type"})
        params = random_vectors(unit.init(jax.random.key(1), spec)[0],
                                jax.random.key(5))
        uncut = olmo_hybrid._gated_delta_net(whole, params, x, cast_float32,
                                             ())
        share_unit = GatedDeltaNet(name="share", **{
            k: v for k, v in dict(MIXER, n_heads=2).items() if k != "type"})
        total = 0.0
        for share in range(2):
            part = cut(params, share,
                       ("wq", "wk", "wv", "wz", "wb", "wa", "conv_q",
                        "conv_k", "conv_v", "A_log", "dt_bias"), ("wo",))
            y, _ = share_unit.apply(part, {}, [x], ctx)
            np.testing.assert_allclose(y, olmo_hybrid._gated_delta_net(
                dict(MIXER, n_heads=2), part, x, cast_float32, ()),
                atol=1e-5)
            total = total + y
        np.testing.assert_allclose(total, uncut, atol=2e-5)
        linear_total = total

        # attention: 4 query and 4 key heads, two of each a share
        whole = dict(ATTENTION, n_kv_heads=4)
        unit = MultiHeadAttention(name="whole_a", **{
            k: v for k, v in whole.items() if k != "type"})
        params = random_vectors(unit.init(jax.random.key(1), spec)[0],
                                jax.random.key(6))
        uncut = olmo_hybrid._attention(whole, params, x, cast_float32)
        held = dict(ATTENTION, n_heads=2, n_kv_heads=2)
        share_unit = MultiHeadAttention(name="share_a", **{
            k: v for k, v in held.items() if k != "type"})
        parts = [cut(params, share, ("wq", "wk", "wv", "q_norm", "k_norm"),
                     ("wo",)) for share in range(2)]
        sums = [olmo_hybrid.qk_sums(p, x, cast_float32) for p in parts]
        exchanged = (tuple(a + b for a, b in zip(*sums)), 2)
        total = 0.0
        for part in parts:
            total = total + olmo_hybrid._attention(held, part, x,
                                                   cast_float32, exchanged)
            y, _ = share_unit.apply(part, {}, [x], ctx)
            np.testing.assert_allclose(y, olmo_hybrid._attention(
                held, part, x, cast_float32), atol=1e-5)
        np.testing.assert_allclose(total, uncut, atol=2e-5)

        # the block: the summed shares through one norm, the MLP once
        mlp = GatedMLP(48, name="mlp")
        mlp_params, _ = mlp.init(jax.random.key(7), spec)
        ones = jnp.ones((E,))
        a = x + afmoe._rms(linear_total, ones, 1e-6)
        once = a + afmoe._rms(mlp.apply(mlp_params, {}, [a], ctx)[0], ones,
                              1e-6)
        layers = [l for l in block_layers("L") if l["name"].startswith("b0")]
        layers[0] = dict(layers[0], n_heads=4, inputs=["@input"])
        layers[2] = dict(layers[2], inputs=["b0_mix_norm", "@input"])
        block_params = {"b0_mix": random_vectors(
            GatedDeltaNet(name="w", **{k: v for k, v in dict(
                MIXER, n_heads=4).items() if k != "type"}).init(
                    jax.random.key(1), spec)[0], jax.random.key(5)),
            "b0_mlp": mlp_params, "b0_mix_norm": {"scale": ones},
            "b0_mlp_norm": {"scale": ones}}
        want, _ = olmo_hybrid.make_forward(
            [{k: v for k, v in l.items() if k != "remat"} for l in layers])(
                block_params, {"@input": x}, cast_float32)
        # the norms grow float32's last bits, as in the blocks' test
        np.testing.assert_allclose(once, want, atol=1e-4)
