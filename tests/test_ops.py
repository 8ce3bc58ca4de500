"""Op correctness vs numpy references (SURVEY.md §4: every op gets a numpy
reference impl — the AcceleratedTest multi-backend pattern becomes
numpy-vs-XLA parametrization)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu import ops
from veles_tpu.ops import optimizers as opt


def test_dense_matches_numpy(rng):
    x = rng.standard_normal((4, 7)).astype(np.float32)
    w = rng.standard_normal((7, 3)).astype(np.float32)
    b = rng.standard_normal(3).astype(np.float32)
    np.testing.assert_allclose(ops.dense(x, w, b), x @ w + b,
                               rtol=1e-5, atol=1e-5)


def test_dense_bf16_accumulates_f32(rng):
    x = rng.standard_normal((8, 256)).astype(np.float32)
    w = rng.standard_normal((256, 16)).astype(np.float32)
    y = ops.dense(x, w, compute_dtype=jnp.bfloat16)
    assert y.dtype == jnp.float32
    # bf16 inputs, f32 accumulation: should be within bf16 input rounding.
    np.testing.assert_allclose(np.asarray(y), x @ w, rtol=2e-2, atol=2e-1)


def _np_conv2d_valid(x, w):
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    oh, ow = h - kh + 1, wd - kw + 1
    out = np.zeros((n, oh, ow, cout), np.float32)
    for i in range(oh):
        for j in range(ow):
            patch = x[:, i:i + kh, j:j + kw, :].reshape(n, -1)
            out[:, i, j, :] = patch @ w.reshape(-1, cout)
    return out


def test_conv2d_matches_numpy(rng):
    x = rng.standard_normal((2, 8, 8, 3)).astype(np.float32)
    w = rng.standard_normal((3, 3, 3, 5)).astype(np.float32)
    got = ops.conv2d(x, w, padding="VALID")
    np.testing.assert_allclose(got, _np_conv2d_valid(x, w),
                               rtol=1e-4, atol=1e-4)


def test_deconv_shape_inverts_conv(rng):
    x = rng.standard_normal((2, 8, 8, 4)).astype(np.float32)
    w = rng.standard_normal((3, 3, 4, 6)).astype(np.float32)
    y = ops.conv2d(x, w, stride=2, padding="SAME")
    w2 = rng.standard_normal((3, 3, 6, 4)).astype(np.float32)
    z = ops.deconv2d(y, w2, stride=2, padding="SAME")
    assert z.shape == x.shape


def test_pooling(rng):
    x = rng.standard_normal((2, 6, 6, 3)).astype(np.float32)
    mp = np.asarray(ops.max_pool(x, 2))
    ap = np.asarray(ops.avg_pool(x, 2))
    ref_mp = x.reshape(2, 3, 2, 3, 2, 3).max(axis=(2, 4))
    ref_ap = x.reshape(2, 3, 2, 3, 2, 3).mean(axis=(2, 4))
    np.testing.assert_allclose(mp, ref_mp, rtol=1e-6)
    np.testing.assert_allclose(ap, ref_ap, rtol=1e-6)


def test_max_unpool_roundtrip(rng):
    x = rng.standard_normal((1, 4, 4, 1)).astype(np.float32)
    pooled, switches = ops.max_pool_with_argmax(x, 2)
    up = ops.max_unpool(pooled, switches, 2)
    # unpooled contains the max at its argmax location, zeros elsewhere
    np.testing.assert_allclose(np.asarray(up).sum(),
                               np.asarray(pooled).sum(), rtol=1e-5)


def _lrn_numpy(x, n, k=2.0, alpha=1e-4, beta=0.75):
    ref = np.empty_like(x)
    C = x.shape[-1]
    for c in range(C):
        lo, hi = max(0, c - n // 2), min(C, c - n // 2 + n)
        s = np.square(x[..., lo:hi]).sum(axis=-1)
        ref[..., c] = x[..., c] / np.power(k + alpha / n * s, beta)
    return ref


def test_lrn_reference(rng):
    x = rng.standard_normal((2, 4, 4, 8)).astype(np.float32)
    n, k, alpha, beta = 5, 2.0, 1e-4, 0.75
    got = np.asarray(ops.local_response_norm(x, n=n, k=k, alpha=alpha,
                                             beta=beta))
    np.testing.assert_allclose(got, _lrn_numpy(x, n, k, alpha, beta),
                               rtol=1e-4, atol=1e-5)


def test_softmax_ce_and_mask(rng):
    logits = rng.standard_normal((6, 4)).astype(np.float32)
    labels = rng.integers(0, 4, 6)
    loss, n_err = ops.softmax_cross_entropy(jnp.asarray(logits),
                                            jnp.asarray(labels))
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = -np.log(p[np.arange(6), labels]).mean()
    np.testing.assert_allclose(float(loss), ref, rtol=1e-5)
    ref_err = (logits.argmax(-1) != labels).sum()
    assert float(n_err) == ref_err
    # mask drops padded rows exactly
    mask = np.array([1, 1, 1, 1, 0, 0], np.float32)
    loss_m, err_m = ops.softmax_cross_entropy(
        jnp.asarray(logits), jnp.asarray(labels), mask=jnp.asarray(mask))
    ref_m = -np.log(p[np.arange(4), labels[:4]]).mean()
    np.testing.assert_allclose(float(loss_m), ref_m, rtol=1e-5)
    assert float(err_m) == (logits[:4].argmax(-1) != labels[:4]).sum()


def test_mse_rmse(rng):
    y = rng.standard_normal((5, 3)).astype(np.float32)
    t = rng.standard_normal((5, 3)).astype(np.float32)
    loss, agg = ops.mse_loss(jnp.asarray(y), jnp.asarray(t))
    ref = np.square(y - t).mean()
    np.testing.assert_allclose(float(loss), ref, rtol=1e-5)


def test_mean_disp_normalize(rng):
    x = rng.integers(0, 255, (4, 6)).astype(np.uint8)
    mean = rng.standard_normal(6).astype(np.float32)
    rdisp = rng.random(6).astype(np.float32)
    got = ops.mean_disp_normalize(jnp.asarray(x), mean, rdisp)
    np.testing.assert_allclose(got, (x.astype(np.float32) - mean) * rdisp,
                               rtol=1e-6)


def test_activations(rng):
    from veles_tpu.ops.activations import scaled_tanh, sincos
    x = rng.standard_normal((3, 4)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(scaled_tanh(x)),
                               1.7159 * np.tanh(0.6666 * x), rtol=1e-5)
    sc = np.asarray(sincos(jnp.asarray(x)))
    np.testing.assert_allclose(sc[:, 0], np.sin(x[:, 0]), rtol=1e-5)
    np.testing.assert_allclose(sc[:, 1], np.cos(x[:, 1]), rtol=1e-5)


# -- optimizers --------------------------------------------------------------

def _quad_setup():
    params = {"u": {"w": jnp.asarray([1.0, -2.0])}}
    grads = {"u": {"w": jnp.asarray([0.5, -1.0])}}
    return params, grads


def test_sgd_momentum_step():
    params, grads = _quad_setup()
    o = opt.SGD(lr=0.1, momentum=0.9)
    st = o.init(params)
    p1, st = o.update(grads, st, params, 0)
    np.testing.assert_allclose(np.asarray(p1["u"]["w"]),
                               [1 - 0.05, -2 + 0.1], rtol=1e-6)
    p2, st = o.update(grads, st, p1, 1)
    # momentum: v = 0.9*g + g = 1.9g
    np.testing.assert_allclose(np.asarray(p2["u"]["w"]),
                               [1 - 0.05 - 0.1 * 0.5 * 1.9,
                                -2 + 0.1 + 0.1 * 1.9], rtol=1e-6)


def test_adagrad_adadelta_adam_descend():
    for maker in (lambda: opt.AdaGrad(0.5), lambda: opt.AdaDelta(1.0),
                  lambda: opt.Adam(0.1)):
        o = maker()
        params = {"u": {"w": jnp.asarray([3.0])}}
        st = o.init(params)
        loss0 = float(params["u"]["w"][0]) ** 2
        for step in range(50):
            grads = {"u": {"w": 2 * params["u"]["w"]}}
            params, st = o.update(grads, st, params, step)
        assert float(params["u"]["w"][0]) ** 2 < loss0


def test_l2_and_per_unit_overrides():
    params = {"a": {"w": jnp.asarray([1.0])}, "b": {"w": jnp.asarray([1.0])}}
    grads = {"a": {"w": jnp.asarray([0.0])}, "b": {"w": jnp.asarray([0.0])}}
    o = opt.SGD(lr=0.1, l2=0.5,
                per_unit={"b": opt.HyperParams(lr_scale=2.0)})
    st = o.init(params)
    p, _ = o.update(grads, st, params, 0)
    np.testing.assert_allclose(float(p["a"]["w"][0]), 1 - 0.1 * 0.5,
                               rtol=1e-6)
    np.testing.assert_allclose(float(p["b"]["w"][0]), 1 - 0.2 * 0.5,
                               rtol=1e-6)


def test_lr_policies():
    assert float(opt.exp_decay_lr(1.0, 0.5, 10)(jnp.asarray(20))) == 0.25
    assert float(opt.inv_lr(1.0, 1.0, 1.0)(jnp.asarray(1))) == 0.5
    s = opt.step_lr(1.0, [5, 10], [0.1, 0.01])
    assert float(s(jnp.asarray(0))) == 1.0
    np.testing.assert_allclose(float(s(jnp.asarray(7))), 0.1, rtol=1e-6)
    np.testing.assert_allclose(float(s(jnp.asarray(11))), 0.01, rtol=1e-6)
    # warmup-cosine: linear ramp, peak at warmup, cosine to final_scale
    f = opt.warmup_cosine_lr(2.0, 10, 100, final_scale=0.1)
    np.testing.assert_allclose(float(f(jnp.asarray(0))), 0.0, atol=1e-7)
    np.testing.assert_allclose(float(f(jnp.asarray(5))), 1.0, rtol=1e-6)
    np.testing.assert_allclose(float(f(jnp.asarray(10))), 2.0, rtol=1e-6)
    np.testing.assert_allclose(float(f(jnp.asarray(100))), 0.2, rtol=1e-5)
    np.testing.assert_allclose(float(f(jnp.asarray(999))), 0.2, rtol=1e-5)
    assert 0.2 < float(f(jnp.asarray(55))) < 2.0


def test_adamw_decoupled_decay():
    """AdamW shrinks weights even at zero gradient (decay bypasses the
    adaptive moments); Adam does not; l2 on AdamW is rejected."""
    params = {"u": {"w": jnp.ones((4, 4))}}
    g0 = {"u": {"w": jnp.zeros((4, 4))}}
    step = jnp.zeros((), jnp.int32)
    aw = opt.AdamW(lr=0.1, weight_decay=0.5)
    p2, _ = aw.update(g0, aw.init(params), params, step)
    np.testing.assert_allclose(np.asarray(p2["u"]["w"]), 1 - 0.05,
                               rtol=1e-6)
    a = opt.Adam(lr=0.1)
    pa, _ = a.update(g0, a.init(params), params, step)
    np.testing.assert_allclose(np.asarray(pa["u"]["w"]), 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="decoupled"):
        opt.AdamW(l2=0.1)
    with pytest.raises(ValueError, match="COUPLED"):
        opt.AdamW(per_unit={"u": opt.HyperParams(l2=0.1)})
    # with a real gradient the adam part matches Adam + the decay term
    g = {"u": {"w": jnp.full((4, 4), 0.3)}}
    paw, _ = opt.AdamW(lr=0.1, weight_decay=0.0).update(
        g, aw.init(params), params, step)
    pad, _ = opt.Adam(lr=0.1).update(g, a.init(params), params, step)
    np.testing.assert_allclose(np.asarray(paw["u"]["w"]),
                               np.asarray(pad["u"]["w"]), rtol=1e-6)


def test_precision_level_config_mapping():
    """PRECISION_LEVEL parity (reference: ocl/matrix_multiplication.cl
    summation levels selected via config)."""
    import jax
    from veles_tpu.config import root
    from veles_tpu.ops.linear import config_precision, dense

    orig = getattr(root.common, "precision_level", 0)
    try:
        for level, expect in ((0, jax.lax.Precision.DEFAULT),
                              (1, jax.lax.Precision.HIGH),
                              (2, jax.lax.Precision.HIGHEST)):
            root.common.precision_level = level
            assert config_precision() == expect
        root.common.precision_level = 2
        x = jnp.ones((2, 3), jnp.float32)
        w = jnp.ones((3, 4), jnp.float32)
        np.testing.assert_allclose(np.asarray(dense(x, w)), 3.0)
    finally:
        root.common.precision_level = orig


def test_lrn_window_methods_agree():
    """The band matmul and its reduce_window guard (above
    ``_BAND_MATMUL_MAX_C`` channels) must agree with the numpy window sum
    for EVEN n (asymmetric window) as well as odd."""
    import veles_tpu.ops.lrn as lrn_mod
    rng = np.random.default_rng(5)
    x = rng.standard_normal((3, 12)).astype(np.float32)
    for n in (2, 3, 4, 5):
        ref = _lrn_numpy(x, n)
        band = lrn_mod.local_response_norm(jnp.asarray(x), n=n)
        orig = lrn_mod._BAND_MATMUL_MAX_C
        try:
            lrn_mod._BAND_MATMUL_MAX_C = 0  # force reduce_window path
            guard = lrn_mod.local_response_norm(jnp.asarray(x), n=n,
                                                method="band")
        finally:
            lrn_mod._BAND_MATMUL_MAX_C = orig
        for got, label in ((band, "band"), (guard, "reduce_window")):
            np.testing.assert_allclose(
                np.asarray(got), ref, rtol=1e-6, atol=1e-7,
                err_msg=f"n={n} {label}")


def test_lrn_auto_and_band_are_one_program():
    """``"auto"`` (what configuration files carry) and ``"band"`` name
    the one formulation: the op traces to equal jaxprs, and the unit
    keeps the concrete name for export."""
    from veles_tpu.units import nn
    x = jnp.ones((2, 5, 5, 16), jnp.float32)
    progs = {m: str(jax.make_jaxpr(
        lambda x, m=m: ops.local_response_norm(x, method=m))(x))
        for m in ("auto", "band")}
    assert progs["auto"] == progs["band"]
    assert "dot_general" in progs["band"]
    assert str(jax.make_jaxpr(ops.local_response_norm)(x)) == progs["band"]
    assert nn.LRN(method="auto").method == nn.LRN().method == "band"


@pytest.mark.parametrize("method", ["cumsum", "band_bf16"])
def test_lrn_rejects_removed_methods(method):
    from veles_tpu.units import nn
    x = jnp.ones((2, 16), jnp.float32)
    with pytest.raises(ValueError, match="'band'"):
        ops.local_response_norm(x, method=method)
    with pytest.raises(ValueError, match="'band'"):
        nn.LRN(method=method)

