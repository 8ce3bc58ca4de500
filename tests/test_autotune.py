"""Autotune: per-device measured op picks with a persisted winner DB
(reference parity: veles/backends.py:672-731 block-size sweep persisted
to devices/device_infos.json)."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu.config import root
from veles_tpu.runtime import autotune


@pytest.fixture
def tuned(tmp_path, monkeypatch):
    monkeypatch.setattr(root.common, "autotune", True)
    monkeypatch.setattr(root.common, "cache_dir", str(tmp_path))
    autotune._memo.clear()
    yield str(tmp_path)
    autotune._memo.clear()


def test_pick_measures_and_persists(tuned):
    calls = {"fast": 0, "slow": 0}

    def fast(x):
        calls["fast"] += 1
        return x + 1.0

    def slow(x):
        calls["slow"] += 1
        # 40 chained matmuls: reliably slower than one add
        for _ in range(40):
            x = x @ x * 1e-3
        return x

    x = jnp.asarray(np.random.default_rng(0).standard_normal((128, 128)),
                    jnp.float32)
    w = autotune.pick("toy_op", {"slow": slow, "fast": fast}, [x])
    assert w == "fast"

    # persisted under the device DB with timings for both candidates
    path = os.path.join(tuned, "device_infos.json")
    db = json.load(open(path))
    (kind,) = db.keys()
    (key,) = db[kind]["autotune"].keys()
    assert key.startswith("toy_op|128x128")
    rec = db[kind]["autotune"][key]
    assert rec["winner"] == "fast"
    assert set(rec["ms"]) == {"fast", "slow"}
    assert rec["ms"]["fast"] < rec["ms"]["slow"]

    # second ask: answered from memo — no re-tracing
    calls["fast"] = calls["slow"] = 0
    assert autotune.pick("toy_op", {"slow": slow, "fast": fast}, [x]) \
        == "fast"
    assert calls == {"fast": 0, "slow": 0}

    # fresh process simulation: memo cleared, DB answers without measuring
    autotune._memo.clear()
    assert autotune.pick("toy_op", {"slow": slow, "fast": fast}, [x]) \
        == "fast"
    assert calls == {"fast": 0, "slow": 0}


def test_pick_disabled_returns_default(tuned):
    root.common.autotune = False

    def never(x):
        raise AssertionError("must not measure when disabled")

    x = jnp.ones((4, 4))
    assert autotune.pick("op2", {"a": never, "b": never}, [x],
                         default="b") == "b"


def test_pick_failure_names_the_candidate(tuned):
    """A candidate the device refuses is an error that names it — never
    a quiet fall-back to the default formulation."""
    def broken(x):
        raise RuntimeError("boom")

    def ok(x):
        return x * 2

    x = jnp.ones((4, 4))
    with pytest.raises(RuntimeError, match="op3.*'broken'.*boom"):
        autotune.pick("op3", {"ok": ok, "broken": broken}, [x],
                      default="ok")


def test_pipeline_stack_propagates_prepare(tuned):
    """Composite units must forward prepare() to sub-units: an attention
    unit inside a pipeline stage resolves its measured pick at build
    time.  The stage's LRN needs no prepare: "auto" became "band" in its
    constructor (never reaching trace or export as 'auto')."""
    import veles_tpu as vt
    from veles_tpu.units.parallel_nn import PipelineStack

    st = PipelineStack(stages=[
        [{"type": "lrn", "method": "auto"}],
        [{"type": "layer_norm"}],
    ], name="stack")
    st.prepare([vt.Spec((4, 6, 6, 32), jnp.float32)])
    assert st._stage_units[0][0].method == "band"

    st = PipelineStack(stages=[
        [{"type": "attention", "n_heads": 2, "residual": True}],
        [{"type": "layer_norm"}],
    ], name="attn_stack")
    attn = st._stage_units[0][0]
    attn._resolved_flash = "untouched"
    st.prepare([vt.Spec((2, 16, 16), jnp.float32)])
    # off-TPU prepare() resolves measurement-free to the XLA form
    assert attn._resolved_flash in (True, False)


# -- formulations chosen without a measurement --------------------------------

def _run_dropout():
    import jax
    from veles_tpu.units import nn
    from veles_tpu.units.base import Context
    u = nn.Dropout(0.3, name="drop")
    u.prepare([None])
    x = jnp.ones((64, 256), jnp.float32)
    y, _ = u.apply({}, {}, [x], Context(train=True, key=jax.random.key(0),
                                        mesh=None))
    kept = np.asarray(y) != 0
    assert abs(kept.mean() - 0.7) < 0.05
    np.testing.assert_allclose(np.asarray(y)[kept], 1 / 0.7, rtol=1e-6)
    return u.uses_kernel()


def _run_lrn():
    import veles_tpu as vt
    from veles_tpu.units import nn
    u = nn.LRN(method="auto", name="lrn")
    spec = vt.Spec((2, 4, 4, 16), jnp.float32)
    u.prepare([spec])
    x = jnp.asarray(np.random.default_rng(0).standard_normal(spec.shape),
                    jnp.float32)
    y, _ = u.apply({}, {}, [x], None)
    assert y.shape == x.shape and np.isfinite(np.asarray(y)).all()
    assert u.method == "band"
    return False  # no kernel on any platform


def _run_mean_disp():
    import jax
    import veles_tpu as vt
    from veles_tpu.units import nn
    u = nn.MeanDispNormalizer(mean=np.full((4, 4, 3), 100.0),
                              rdisp=np.full((4, 4, 3), 0.5), name="norm")
    spec = vt.Spec((8, 4, 4, 3), jnp.uint8)
    u.prepare([spec])
    _, state = u.init(jax.random.key(0), [spec])
    x = np.random.default_rng(0).integers(0, 256, spec.shape, np.uint8)
    y, _ = u.apply({}, state, [jnp.asarray(x)], None)
    np.testing.assert_allclose(np.asarray(y),
                               (x.astype(np.float32) - 100.0) * 0.5)
    return False


def _run_gather():
    """Default policy (no ``use_pallas_gather``): rows inside the static
    envelope are packed on a TPU, ``jnp.take`` elsewhere; a class smaller
    than the minibatch gathers through its own jit either way."""
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.loader.base import TRAIN, VALID
    rng = np.random.default_rng(1)
    X = {TRAIN: rng.standard_normal((300, 1024)).astype(np.float32),
         VALID: rng.standard_normal((7, 1024)).astype(np.float32)}
    ld = FullBatchLoader({k: v.copy() for k, v in X.items()},
                         minibatch_size=16)
    ld.initialize()
    assert ld.on_device
    for klass in (TRAIN, VALID):
        for i, b in enumerate(ld.iter_epoch(klass, 0)):
            perm = ld.epoch_permutation(klass, 0)[i * 16:(i + 1) * 16]
            got = np.asarray(b["@input"])[: len(perm)]
            np.testing.assert_allclose(got, X[klass][perm])
    # packed rows are stored (N, f_pad / 128, 128)-tiled, plain ones as given
    return ld._dev_data[TRAIN]["@input"].shape != X[TRAIN].shape


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("what", ["dropout", "lrn", "mean_disp", "gather"])
def test_formulation_needs_no_measurement(tuned, monkeypatch, what,
                                          platform):
    """Dropout, LRN, mean/dispersion normalize and the fullbatch gather
    choose their formulation from the platform (``use_pallas_default``)
    or have only one: with autotune ON nothing is measured and no DB
    record is written, on a TPU and off it."""
    import veles_tpu.ops as vops
    from veles_tpu.loader import fullbatch
    from veles_tpu.ops import pallas_kernels as pk

    def never(*a, **kw):
        raise AssertionError("a formulation was measured")

    on_tpu = platform == "tpu"
    monkeypatch.setattr(autotune, "measure", never)
    # the units' and the loader's policy says "TPU"; the kernels' own
    # binding keeps saying CPU, so they run in interpret mode
    monkeypatch.setattr(vops, "use_pallas_default", lambda *a: on_tpu)
    monkeypatch.setattr(fullbatch, "use_pallas_default", lambda *a: on_tpu)
    monkeypatch.setattr(pk, "use_pallas_default", lambda *a: False)
    run = {"dropout": _run_dropout, "lrn": _run_lrn,
           "mean_disp": _run_mean_disp, "gather": _run_gather}[what]
    used_kernel = run()
    assert used_kernel == (on_tpu and what in ("dropout", "gather"))
    assert not os.path.exists(os.path.join(tuned, "device_infos.json"))


def test_only_attention_measures():
    """One rule picks a formulation everywhere but in attention: the
    package has one caller of ``autotune.pick``."""
    import pathlib
    import veles_tpu
    pkg = pathlib.Path(veles_tpu.__file__).parent
    callers = sorted(
        str(f.relative_to(pkg)) for f in pkg.rglob("*.py")
        if "autotune.pick(" in f.read_text())
    assert callers == ["units/parallel_nn.py"]


def test_new_candidate_triggers_remeasure(tuned):
    """A winner persisted for an older candidate set must not suppress
    measuring a newly added formulation."""
    def a(x):
        return x + 1

    def b(x):
        y = x
        for _ in range(40):
            y = y @ y * 1e-3
        return y

    x = jnp.ones((64, 64), jnp.float32)
    assert autotune.pick("grow_op", {"b": b, "a": a}, [x]) == "a"
    autotune._memo.clear()

    def c(x):
        return x * 2.0  # new fast candidate

    w = autotune.pick("grow_op", {"b": b, "a": a, "c": c}, [x])
    path = os.path.join(tuned, "device_infos.json")
    db = json.load(open(path))
    (kind,) = db.keys()
    rec = [v for k, v in db[kind]["autotune"].items()
           if k.startswith("grow_op")][0]
    assert set(rec["ms"]) == {"a", "b", "c"}  # re-measured with all three
    assert w in ("a", "c")


def test_attention_flash_choice_via_autotune(tuned):
    """The framework's most important op follows the same measured-
    winner discipline (round-3 verdict #6): flash-vs-XLA resolves at
    build shape — measurement-free off-TPU, forced by use_flash, and
    the resolved choice actually drives apply()."""
    import jax
    import veles_tpu as vt
    from veles_tpu.units.parallel_nn import MultiHeadAttention

    on_tpu = jax.devices()[0].platform == "tpu"
    u = MultiHeadAttention(2, name="attn", rope=True, residual=True)
    u.prepare([vt.Spec((2, 16, 16), jnp.float32)])
    if on_tpu:
        assert u._resolved_flash in (True, False)
        db = json.load(open(os.path.join(tuned, "device_infos.json")))
        (kind,) = db.keys()
        assert any(k.startswith("attention_fwd_bwd")
                   for k in db[kind]["autotune"])
    else:
        # interpret-mode flash off-TPU: foregone conclusion, no probe
        assert u._resolved_flash is False

    # forced modes bypass measurement entirely
    uf = MultiHeadAttention(2, name="attn2", use_flash=False)
    uf.prepare([vt.Spec((2, 16, 16), jnp.float32)])
    assert uf._resolved_flash is False

    # autotune off -> platform default (None) at apply
    root.common.autotune = False
    ud = MultiHeadAttention(2, name="attn3")
    ud.prepare([vt.Spec((2, 16, 16), jnp.float32)])
    assert ud._resolved_flash is None

    # the unit still runs with the resolved choice
    from veles_tpu.units.base import Context
    key = jax.random.key(0)
    params, _ = u.init(key, [vt.Spec((2, 16, 16), jnp.float32)])
    x = jax.random.normal(key, (2, 16, 16))
    y, _ = u.apply(params, {}, [x], Context(train=True, key=key,
                                            mesh=None))
    assert y.shape == x.shape


@pytest.mark.slow  # block-size sweep compiles one program per
# candidate (~8s); autotune selection/persistence stays tier-1
def test_attention_block_size_sweep(tuned, monkeypatch):
    """Round-5: the attention autotune sweeps flash (block_q, block_k)
    candidates per build shape (deduped by the kernel's effective
    clamped blocks); a pre-sweep DB record fails lookup's candidate-set
    staleness check and re-measures instead of mis-parsing."""
    import jax
    import veles_tpu as vt
    from veles_tpu.runtime import autotune as at
    from veles_tpu.runtime.benchmark import update_device_info
    import veles_tpu.ops as vops
    from veles_tpu.units.parallel_nn import MultiHeadAttention

    # force the sweep path off-TPU: interpret-mode flash is measurable
    # at tiny shapes (the product gate skips it; this tests the
    # machinery, not the winner).  The gate (units' ops.use_pallas_
    # default) must say "TPU-ish" while the kernels' own binding keeps
    # saying CPU so _interpret(None) stays in interpreter mode.
    from veles_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(vops, "use_pallas_default", lambda *a: True)
    monkeypatch.setattr(pk, "use_pallas_default", lambda *a: False)

    u = MultiHeadAttention(2, name="sweep_attn", rope=True,
                           residual=True)
    u.prepare([vt.Spec((1, 16, 8), jnp.float32)])
    assert u._resolved_flash in (True, False)
    if u._resolved_flash:
        assert u._resolved_blocks is None or (
            isinstance(u._resolved_blocks, tuple)
            and len(u._resolved_blocks) == 2)
    db = json.load(open(os.path.join(tuned, "device_infos.json")))
    (kind,) = db.keys()
    entries = {k: v for k, v in db[kind]["autotune"].items()
               if k.startswith("attention_fwd_bwd")}
    assert entries
    (key, rec), = entries.items()
    # tiny T dedupes every candidate pair to ONE effective flash entry
    flash_names = [n for n in rec["ms"] if n.startswith("flash_")]
    assert len(flash_names) == 1, rec["ms"]
    assert rec["winner"] in list(rec["ms"])

    # a pre-sweep record ({flash, xla} candidate set) is STALE against
    # the swept set: lookup returns None and prepare re-measures,
    # overwriting the record with the full sweep
    def seed_legacy(infos):
        infos.setdefault("autotune", {})[key] = {
            "ms": {"flash": 0.1, "xla": 0.2}, "winner": "flash"}
    update_device_info(kind, seed_legacy)
    at._memo.clear()
    u2 = MultiHeadAttention(2, name="legacy_attn", rope=True,
                            residual=True)
    u2.prepare([vt.Spec((1, 16, 8), jnp.float32)])
    db2 = json.load(open(os.path.join(tuned, "device_infos.json")))
    rec2 = db2[kind]["autotune"][key]
    assert "flash" not in rec2["ms"]          # re-measured, not reused
    assert set(rec2["ms"]) == set(rec["ms"])


def _kernel_runs_in_chain(probe, args, monkeypatch):
    """How many times each flash kernel still runs in the program
    ``autotune.measure`` times for ``probe``: its own chain, taken as it
    hands it to ``jax.jit``, compiled, and the interpret-mode kernels
    (one ``while`` over the grid each) counted in the optimised HLO."""
    import collections
    import re
    import jax

    chain = []

    def take(fn, *a, **kw):
        chain.append(fn)
        raise StopIteration

    with monkeypatch.context() as m, pytest.raises(StopIteration):
        m.setattr(jax, "jit", take)
        autotune.measure(probe, args)
    text = jax.jit(chain[0]).lower(*args).compile().as_text()
    return collections.Counter(
        name for line in text.splitlines() if " while(" in line
        for name in re.findall(r"op_name=\"[^\"]*?/(flash_\w+?)/while",
                               line))


@pytest.mark.parametrize("probe", ["prepare", "bare_value_and_grad"])
def test_attention_probe_keeps_the_backward_alive(monkeypatch, probe):
    """``measure`` chains 4 repetitions through the first output alone.
    The probe ``prepare`` builds keeps all three kernels in every one; a
    bare ``value_and_grad`` (what it was before ISSUE 28) has a scalar
    primal first, and XLA removes both backward kernels from every
    repetition but the last, so the pick timed the forward."""
    import functools
    import jax
    from veles_tpu.parallel.ring_attention import blockwise_attention
    from veles_tpu.units.parallel_nn import MultiHeadAttention

    attend = functools.partial(
        blockwise_attention, block_size=16, causal=True, window=None,
        use_flash=True, flash_blocks=(16, 16))  # interpret mode off-TPU
    if probe == "prepare":
        fn, backward = MultiHeadAttention._probe(attend), 4
    else:
        def fn(q, k, v):
            return jax.value_and_grad(
                lambda q, k, v: jnp.sum(attend(q, k, v)),
                argnums=(0, 1, 2))(q, k, v)
        backward = 1
    args = [jnp.ones((1, 32, 1, 8), jnp.float32)] * 3
    assert _kernel_runs_in_chain(fn, args, monkeypatch) == {
        "flash_fwd": 4, "flash_bwd_dq": backward,
        "flash_bwd_dkv": backward}


def test_flash_tiles_gauge_after_a_trace():
    """``vt_flash_tiles{kernel, class}`` says how often each tile class's
    body engages at the traced shape: T = 2048 at 512 x 512 is 6 dead,
    4 edge, 6 interior a head, in all three kernels."""
    import jax
    from veles_tpu.ops import pallas_kernels as pk
    from veles_tpu.runtime.metrics import registry

    x = jax.ShapeDtypeStruct((1, 2048, 1, 64), jnp.bfloat16)
    jax.eval_shape(
        jax.grad(lambda q, k, v: jnp.sum(pk.flash_attention(
            q, k, v, True, None, 512, 512, True).astype(jnp.float32)),
            argnums=(0, 1, 2)), x, x, x)
    gauge = registry().get("vt_flash_tiles")
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert {c: gauge.labels(**{"kernel": kernel, "class": c}).value
                for c in ("dead", "edge", "interior")} \
            == {"dead": 6, "edge": 4, "interior": 6}
