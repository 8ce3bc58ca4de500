"""The softmax cross-entropy's two paths (ops/losses.py): the plain jnp
formulation, which small logits take and which is the oracle here, and the
swept one (two Pallas kernels behind a ``custom_vjp``) that large logits
take on a TPU.  The swept path is forced at small sizes by steering, in the
test, the two things the choice reads: the size constant and the backend
policy.  The kernels themselves run in interpret mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu import ops
from veles_tpu.ops import losses
from veles_tpu.ops import pallas_kernels as pk
from veles_tpu.runtime.metrics import registry
from veles_tpu.units.base import Context, Spec
from veles_tpu.units.nn import All2All, Embedding, EvaluatorSoftmax
from veles_tpu.units.workflow import Workflow

from test_numgrad import numdiff


@pytest.fixture
def on_a_tpu(monkeypatch):
    """The path choice sees a TPU; the kernels still see the CPU and run
    interpreted (``pallas_kernels`` holds its own reference)."""
    monkeypatch.setattr(ops, "use_pallas_default", lambda platform=None: True)


@pytest.fixture
def swept(monkeypatch, on_a_tpu):
    monkeypatch.setattr(losses, "SWEPT_MIN_BYTES", 0)


def _plain(logits, labels, mask=None):
    """The formulation as it stood before the swept path came (PR 29's
    ``ops.softmax_cross_entropy``, line for line): the oracle."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                              axis=-1)[..., 0]
    pred = jnp.argmax(logits, axis=-1)
    err = (pred != labels).astype(jnp.float32)
    if mask is not None:
        denom = jnp.maximum(mask.sum(), 1.0)
        return (ce * mask).sum() / denom, (err * mask).sum()
    return ce.mean(), err.sum()


def _logits(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(shape) * 4, dtype)
    labels = jnp.asarray(rng.integers(0, shape[-1], shape[:-1]), jnp.int32)
    return x, labels


def _mask(kind, labels, seed=1):
    if kind == "none":
        return None
    per_sample = jnp.zeros((labels.shape[0],)) if kind == "zero" else \
        jnp.asarray(np.random.default_rng(seed).integers(
            0, 2, labels.shape[0]), jnp.float32).at[0].set(1.0)
    # as EvaluatorSoftmax broadcasts a per-sample mask over positions
    return jnp.broadcast_to(
        per_sample.reshape((-1,) + (1,) * (labels.ndim - 1)), labels.shape)


SHAPES = {
    "2d_393": (70, 393),            # classes: no multiple of 128
    "2d_1000_rows_520": (520, 1000),  # rows: two blocks of 256 and 8 more
    "2d_4500": (24, 4500),          # three class blocks, the last partial
    "3d_393": (3, 50, 393),
}


@pytest.mark.parametrize("mask", ["none", "sample", "zero"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_swept_matches_plain(swept, shape, mask):
    x, labels = _logits(SHAPES[shape], jnp.float32)
    m = _mask(mask, labels)
    assert losses.softmax_loss_path(x.shape) == "swept"
    (loss, n_err), g = jax.value_and_grad(
        lambda a: ops.softmax_cross_entropy(a, labels, mask=m),
        has_aux=True)(x)
    (loss0, n_err0), g0 = jax.value_and_grad(
        lambda a: _plain(a, labels, m), has_aux=True)(x)
    np.testing.assert_allclose(loss, loss0, rtol=2e-6, atol=1e-6)
    assert float(n_err) == float(n_err0)
    assert g.dtype == x.dtype and g.shape == x.shape
    np.testing.assert_allclose(g, g0, rtol=1e-5, atol=1e-8)


def test_swept_bfloat16_logits(swept):
    """bfloat16 logits are widened inside the kernels: float32 arithmetic
    on the same values as the plain path's cast, a bfloat16 gradient."""
    x, labels = _logits((40, 393), jnp.bfloat16)
    (loss, n_err), g = jax.value_and_grad(
        lambda a: ops.softmax_cross_entropy(a, labels), has_aux=True)(x)
    (loss0, n_err0), g0 = jax.value_and_grad(
        lambda a: _plain(a, labels), has_aux=True)(x)
    np.testing.assert_allclose(loss, loss0, rtol=2e-6)
    assert float(n_err) == float(n_err0)
    assert g.dtype == jnp.bfloat16
    np.testing.assert_allclose(g.astype(jnp.float32),
                               g0.astype(jnp.float32), rtol=1e-2, atol=1e-6)


def test_swept_ties_go_to_the_first_index(swept):
    x, labels = _logits((16, 2600), jnp.float32)
    x = x.at[0].set(1.5)                        # a whole row tied
    x = x.at[1, 7].set(50.0).at[1, 2300].set(50.0)  # across class blocks
    x = x.at[2, 2100].set(50.0).at[2, 2101].set(50.0)  # inside one
    ce, pred = pk.softmax_xent_rows(x, labels.reshape(-1))
    np.testing.assert_array_equal(pred, jnp.argmax(x, -1))
    assert [int(p) for p in pred[:3]] == [0, 7, 2100]


@pytest.mark.parametrize("case", ["neg_inf_block", "neg_inf_label",
                                  "large"])
def test_swept_extreme_logits(swept, case):
    """No NaN where the plain path has none: a vocabulary masked to -inf
    over a whole class block, the label's own logit at -inf (an infinite
    loss, as plain), magnitudes near float32's largest."""
    x, labels = _logits((12, 2600), jnp.float32)
    if case == "neg_inf_block":
        x = x.at[:, :2200].set(-jnp.inf)
        labels = jnp.full_like(labels, 2300)
    elif case == "neg_inf_label":
        x = x.at[jnp.arange(12), labels].set(-jnp.inf)
    else:
        x = x.at[3, 5].set(3e38).at[4, 6].set(-3e38) * 1.0
    (loss, n_err), g = jax.value_and_grad(
        lambda a: ops.softmax_cross_entropy(a, labels), has_aux=True)(x)
    (loss0, n_err0), g0 = jax.value_and_grad(
        lambda a: _plain(a, labels), has_aux=True)(x)
    assert not np.isnan(np.asarray(loss0)), "the oracle itself"
    np.testing.assert_allclose(loss, loss0, rtol=2e-6)
    assert float(n_err) == float(n_err0)
    assert np.isnan(np.asarray(g)).sum() == np.isnan(np.asarray(g0)).sum()
    ok = ~np.isnan(np.asarray(g0))
    np.testing.assert_allclose(np.asarray(g)[ok], np.asarray(g0)[ok],
                               rtol=1e-5, atol=1e-8)


def test_swept_numerical_gradient(swept, rng):
    logits = rng.standard_normal((4, 5)).astype(np.float32)
    labels = jnp.asarray([0, 2, 4, 1])
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0])

    def f(a):
        return ops.softmax_cross_entropy(a, labels, mask=mask)[0]

    analytic = np.asarray(jax.grad(f)(jnp.asarray(logits)), np.float64)
    numeric = numdiff(lambda a: float(f(jnp.asarray(a, jnp.float32))),
                      logits)
    np.testing.assert_allclose(analytic, numeric, rtol=2e-3, atol=2e-4)


# -- which path a call takes ---------------------------------------------------

def test_path_by_size_backend_and_mesh(on_a_tpu, monkeypatch):
    from veles_tpu.parallel.mesh import MeshSpec, make_mesh
    lm, alexnet = (4, 2048, 50272), (512, 1000)
    assert losses.softmax_loss_path(lm) == "swept"
    assert losses.softmax_loss_path((1, 4096, 25024)) == "swept"
    assert losses.softmax_loss_path(alexnet) == "plain"
    # rows over every device: the kernels run per shard
    assert losses.softmax_loss_path(
        lm, make_mesh(MeshSpec(data=2, fsdp=2),
                      devices=jax.devices()[:4])) == "swept"
    # another axis could shard the classes; rows that do not tile
    assert losses.softmax_loss_path(
        lm, make_mesh(MeshSpec(data=2, model=2),
                      devices=jax.devices()[:4])) == "plain"
    assert losses.softmax_loss_path(
        (3, 2048, 50272), make_mesh(MeshSpec(data=2),
                                    devices=jax.devices()[:2])) == "plain"
    # off a TPU the kernels would run interpreted: plain at any size
    monkeypatch.setattr(ops, "use_pallas_default",
                        lambda platform=None: False)
    assert losses.softmax_loss_path(lm) == "plain"


def test_small_logits_lower_to_the_plain_program(on_a_tpu):
    """AlexNet-shaped logits: the function's program text is the plain
    formulation's, operation for operation."""
    x = jax.ShapeDtypeStruct((512, 1000), jnp.float32)
    labels = jax.ShapeDtypeStruct((512,), jnp.int32)
    mask = jax.ShapeDtypeStruct((512,), jnp.float32)

    def text(f):
        return jax.jit(f).lower(x, labels, mask).as_text()

    got = text(lambda a, b, c: ops.softmax_cross_entropy(a, b, mask=c))
    want = text(lambda a, b, c: _plain(a, b, c))
    assert "pallas" not in got and "custom_call" not in got
    assert got == want


def test_gauge_names_the_path(on_a_tpu):
    ev = EvaluatorSoftmax(name="ev_gauge")
    gauge = registry().gauge("vt_softmax_loss_path", "", labels=("unit",
                                                                   "path"))

    def read():
        return {p: gauge.labels(unit="ev_gauge", path=p).value
                for p in ("swept", "plain")}

    def trace(shape):
        jax.eval_shape(
            lambda x, l, m: ev.evaluate({}, {}, [x, l, m], Context()),
            jax.ShapeDtypeStruct(shape, jnp.float32),
            jax.ShapeDtypeStruct(shape[:-1], jnp.int32),
            jax.ShapeDtypeStruct(shape[:1], jnp.float32))
        return read()

    assert trace((4, 2048, 50272)) == {"swept": 1.0, "plain": 0.0}
    assert trace((512, 1000)) == {"swept": 0.0, "plain": 1.0}


# -- one sweep a step, shown on the traced program -----------------------------

V, T, B, D = 393, 16, 4, 32


def _lm_workflow():
    wf = Workflow("tiny_lm")
    wf.add(Embedding(V, D, name="emb", inputs=("@input",)))
    wf.add(All2All(V, per_position=True, name="head", inputs=("emb",)))
    wf.add(EvaluatorSoftmax(name="ev", inputs=("head", "@labels", "@mask")))
    wf.build({"@input": Spec((B, T), jnp.int32),
              "@labels": Spec((B, T), jnp.int32),
              "@mask": Spec((B,), jnp.float32)})
    return wf


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue        # a kernel's body works on tiles in VMEM
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _kernels_and_logit_arrays(fn, *args):
    """(names of the Pallas calls, how many equations outside them yield an
    array of the logits' size) in ``fn``'s jaxpr."""
    names, logit_sized = [], []
    for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        if "pallas" in eqn.primitive.name or \
                eqn.primitive.name in ("jit", "pjit", "custom_vjp_call",
                                       "custom_vjp_call_jaxpr"):
            continue    # a wrapper's outputs are its inner equations'
        for v in eqn.outvars:
            if getattr(v.aval, "size", 0) >= B * T * V:
                logit_sized.append((eqn.primitive.name, v.aval.shape,
                                    str(v.aval.dtype)))
    return names, logit_sized


def test_train_step_sweeps_the_logits_once(swept):
    """``apply`` and ``metrics`` both ask; the step holds one forward
    kernel.  Forward, the arrays of the logits' size are the head's (its
    product and the bias added); backward, the elementwise expression of
    the gradient, which XLA computes inside the operations that read it
    (tests/test_chip_compile.py shows that on the compiled step): no
    log-probabilities, no one-hot, no scatter."""
    from veles_tpu.ops.optimizers import SGD
    wf = _lm_workflow()
    opt = SGD(lr=0.1)
    ws = wf.init_state(jax.random.key(0), opt)
    batch = {"@input": jnp.zeros((B, T), jnp.int32),
             "@labels": jnp.ones((B, T), jnp.int32),
             "@mask": jnp.ones((B,), jnp.float32)}
    step = wf.make_train_step(opt, jit=False)
    names, logit_sized = _kernels_and_logit_arrays(step, ws, batch)
    assert names == ["softmax_xent_fwd"]
    made_by = [p for p, _, dtype in logit_sized
               if dtype == "float32" and p not in ("reshape", "transpose")]
    assert made_by[:2] == ["dot_general", "add"], logit_sized
    assert sorted(made_by[2:]) == ["exp", "mul", "select_n", "sub", "sub"], \
        logit_sized


def test_train_step_matches_plain_step(swept, monkeypatch):
    from veles_tpu.ops.optimizers import SGD
    rng = np.random.default_rng(3)
    batch = {"@input": jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32),
             "@labels": jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32),
             "@mask": jnp.asarray([1.0, 1.0, 0.0, 1.0])}

    def run():
        wf = _lm_workflow()
        opt = SGD(lr=0.1)
        ws = wf.init_state(jax.random.key(0), opt)
        nws, mets = wf.make_train_step(opt, donate=False)(ws, batch)
        return nws["params"], mets, wf.make_eval_step()(nws, batch)

    p1, m1, e1 = run()
    monkeypatch.setattr(losses, "SWEPT_MIN_BYTES", 1 << 60)
    p0, m0, e0 = run()
    for got, want in ((m1, m0), (e1, e0)):
        assert set(got) == set(want) >= {"loss", "n_err", "n_samples"}
        for k in want:
            np.testing.assert_allclose(got[k], want[k], rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-5, atol=1e-7), p1, p0)


def test_eval_step_has_the_forward_sweep_alone(swept):
    wf = _lm_workflow()
    ws = wf.init_state(jax.random.key(0))
    batch = {"@input": jnp.zeros((B, T), jnp.int32),
             "@labels": jnp.ones((B, T), jnp.int32),
             "@mask": jnp.ones((B,), jnp.float32)}
    names, _ = _kernels_and_logit_arrays(wf.make_eval_step(jit=False),
                                         ws, batch)
    assert names == ["softmax_xent_fwd"]


def test_swept_under_a_data_mesh(swept):
    """Rows sharded over a 4-device mesh: each device sweeps its own rows
    (``parallel.mesh.shard_batch``), the masked mean is taken over all."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from veles_tpu.parallel.mesh import MeshSpec, make_mesh
    mesh = make_mesh(MeshSpec(data=2, fsdp=2), devices=jax.devices()[:4])
    x, labels = _logits((8, 6, 393), jnp.float32)
    m = _mask("sample", labels)
    sh = NamedSharding(mesh, P(("data", "fsdp")))

    @jax.jit
    def f(a, l, m):
        return jax.value_and_grad(
            lambda a: ops.softmax_cross_entropy(a, l, mask=m, mesh=mesh),
            has_aux=True)(a)

    (loss, n_err), g = f(*(jax.device_put(a, sh) for a in (x, labels, m)))
    (loss0, n_err0), g0 = jax.value_and_grad(
        lambda a: _plain(a, labels, m), has_aux=True)(x)
    np.testing.assert_allclose(loss, loss0, rtol=2e-6)
    assert float(n_err) == float(n_err0)
    np.testing.assert_allclose(g, g0, rtol=1e-5, atol=1e-8)
