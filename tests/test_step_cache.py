"""Recompile-free training lifecycle: StepCache counters, the traced
lr multiplier, persistent-cache wiring, and device-side batch prefetch.

The contract under test (ISSUE 1): a Decision rollback and a
``Trainer.restore`` with ``lr_multiplier != 1`` complete with ZERO new
step compilations, per-step math is bitwise-identical to the old
recompile-with-scaled-schedule path, and the prefetch worker's device
placement is equivalent to the synchronous fallback."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import veles_tpu as vt
from veles_tpu.config import root
from veles_tpu.loader.base import TRAIN, VALID
from veles_tpu.ops import optimizers as opt
from veles_tpu.ops.optimizers import LR_MULT_KEY
from veles_tpu.parallel import make_mesh
from veles_tpu.runtime.step_cache import StepCache, enable_persistent_cache
from veles_tpu.units.base import Spec
from veles_tpu.units.nn import (All2AllSoftmax, All2AllTanh,
                                EvaluatorSoftmax)


def _fc_wf(dim=8):
    wf = vt.Workflow("sc")
    wf.add(All2AllTanh(16, name="fc1", inputs=("@input",)))
    wf.add(All2AllSoftmax(3, name="fc2", inputs=("fc1",)))
    wf.add(EvaluatorSoftmax(name="ev", inputs=("fc2", "@labels", "@mask")))
    return wf


def _blob(dim=8, n=96):
    rng = np.random.default_rng(7)
    centers = rng.standard_normal((3, dim)) * 3
    lab = rng.integers(0, 3, n).astype(np.int32)
    d = (centers[lab] + rng.standard_normal((n, dim))).astype(np.float32)
    return d, lab


def _loader(d, lab, mb=32):
    return vt.ArrayLoader({TRAIN: d, VALID: d[:32]},
                          {TRAIN: lab, VALID: lab[:32]},
                          minibatch_size=mb)


def test_rollback_zero_recompiles():
    """lr=0 makes epoch metrics constant, so Decision(rollback_after=1)
    rolls back DETERMINISTICALLY from epoch 1 on — and every rollback
    must be a pure state write, never a recompile."""
    d, lab = _blob()
    dec = vt.Decision(max_epochs=4, fail_iterations=10, rollback_after=1)
    tr = vt.Trainer(_fc_wf(), _loader(d, lab), opt.SGD(0.0, momentum=0.9),
                    dec)
    tr.initialize(seed=0)
    assert tr.step_cache.compiles == 1  # train only; eval compiles lazily
    tr.run()
    assert tr.decision.lr_multiplier < 1.0  # rollbacks actually happened
    # train + (first-eval-epoch) eval, and ZERO compiles beyond that
    assert tr.step_cache.compiles == 2
    assert tr.step_cache.recompiles == 0
    # the traced scalar carries the cumulative drop
    assert float(jax.device_get(
        tr.wstate["opt_state"][LR_MULT_KEY])) == pytest.approx(
            tr.decision.lr_multiplier)


def test_restore_zero_recompiles(tmp_path):
    d, lab = _blob()
    snap = vt.Snapshotter("sc", str(tmp_path))
    dec = vt.Decision(max_epochs=3, fail_iterations=10, rollback_after=1)
    tr = vt.Trainer(_fc_wf(), _loader(d, lab), opt.SGD(0.0, momentum=0.9),
                    dec, snapshotter=snap)
    tr.initialize(seed=0)
    tr.run()
    assert tr.decision.lr_multiplier < 1.0

    tr2 = vt.Trainer(_fc_wf(), _loader(d, lab),
                     opt.SGD(0.0, momentum=0.9), vt.Decision(max_epochs=5))
    tr2.initialize(seed=1)
    compiles0 = tr2.step_cache.compiles
    tr2.restore(snap.last_path)
    assert tr2.step_cache.compiles == compiles0  # recompile-free restore
    base = float(opt.SGD(0.0).schedule(0))
    assert tr2.effective_lr(0) == pytest.approx(
        base * tr2.decision.lr_multiplier)
    tr2.run()  # the immortal programs keep training after the restore
    # + exactly the lazily-compiled eval program, nothing else
    assert tr2.step_cache.compiles == compiles0 + 1
    assert tr2.step_cache.recompiles == 0


def test_sharded_rollback_zero_recompiles():
    """The expensive case the lifecycle exists for: rollback under a
    mesh keeps the sharded programs AND their shardings."""
    mesh = make_mesh()
    d, lab = _blob()
    dec = vt.Decision(max_epochs=3, fail_iterations=10, rollback_after=1)
    tr = vt.Trainer(_fc_wf(), _loader(d, lab), opt.SGD(0.0, momentum=0.9),
                    dec, mesh=mesh)
    tr.initialize(seed=0)
    tr.run()
    assert tr.decision.lr_multiplier < 1.0
    assert tr.step_cache.compiles == 2
    sh = tr.wstate["params"]["fc1"]["w"].sharding
    assert getattr(sh, "mesh", None) is not None
    mult = tr.wstate["opt_state"][LR_MULT_KEY]
    assert getattr(mult, "sharding", None) is not None  # placed scalar


def test_traced_lr_multiplier_bitwise_exact():
    """The traced multiplier must reproduce the old recompile path's
    update BITWISE: lr*(mult traced) == (schedule scaled in Python)."""
    scale = 0.25
    wf = _fc_wf()
    wf.build({"@input": Spec((8, 8), jnp.float32),
              "@labels": Spec((8,), jnp.int32),
              "@mask": Spec((8,), jnp.float32)})
    rng = np.random.default_rng(3)
    batch = {"@input": rng.standard_normal((8, 8)).astype(np.float32),
             "@labels": rng.integers(0, 3, 8).astype(np.int32),
             "@mask": np.ones(8, np.float32)}

    # old path: the drop baked into a scaled Python schedule (what
    # _compile_steps used to re-trace on every rollback)
    base = opt.fixed_lr(0.05)
    opt_old = opt.SGD(lr_policy=lambda s: base(s) * scale, momentum=0.9)
    ws_old = wf.init_state(jax.random.key(0), opt_old)
    step_old = wf.make_train_step(opt_old, donate=False)

    # new path: base schedule + traced multiplier in opt_state
    opt_new = opt.SGD(lr_policy=base, momentum=0.9)
    ws_new = wf.init_state(jax.random.key(0), opt_new)
    ws_new["opt_state"][LR_MULT_KEY] = jnp.asarray(scale, jnp.float32)
    step_new = wf.make_train_step(opt_new, donate=False)

    for _ in range(3):
        ws_old, mets_old = step_old(ws_old, batch)
        ws_new, mets_new = step_new(ws_new, batch)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(ws_old["params"]),
            jax.tree_util.tree_leaves_with_path(ws_new["params"])):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=jax.tree_util.keystr(pa))
    np.testing.assert_array_equal(np.asarray(mets_old["loss"]),
                                  np.asarray(mets_new["loss"]))


def test_legacy_snapshot_without_mult_slot_restores(tmp_path):
    """Pre-change snapshots carry no __lr_mult__ leaf; restore must
    inject a neutral one instead of failing the structural tree-map."""
    d, lab = _blob()
    snap = vt.Snapshotter("legacy", str(tmp_path))
    tr = vt.Trainer(_fc_wf(), _loader(d, lab), opt.SGD(0.05),
                    vt.Decision(max_epochs=1), snapshotter=snap)
    tr.initialize(seed=0)
    tr.run()
    payload = tr._payload()
    del payload["wstate"]["opt_state"][LR_MULT_KEY]  # the old format
    path = snap.save("old", payload)

    tr2 = vt.Trainer(_fc_wf(), _loader(d, lab), opt.SGD(0.05),
                     vt.Decision(max_epochs=2))
    tr2.initialize(seed=1)
    tr2.restore(path)
    assert float(jax.device_get(
        tr2.wstate["opt_state"][LR_MULT_KEY])) == 1.0
    tr2.run()


def test_prefetch_places_on_device_and_matches_sync():
    """_batches must yield DEVICE-PLACED batches from the worker thread,
    with metrics identical to the prefetch=0 synchronous fallback."""
    mesh = make_mesh()
    d, lab = _blob()
    mets = {}
    for prefetch in (2, 0):
        tr = vt.Trainer(_fc_wf(), _loader(d, lab),
                        opt.SGD(0.05, momentum=0.9),
                        vt.Decision(max_epochs=2), mesh=mesh,
                        prefetch=prefetch)
        tr.initialize(seed=0)
        batches = list(tr._batches(TRAIN, 0))
        assert batches, "empty epoch"
        for b in batches:
            for k, v in b.items():
                assert isinstance(v, jax.Array), (prefetch, k)
                assert getattr(v.sharding, "mesh", None) is not None
        mets[prefetch] = tr._run_epoch_train(1)
    assert mets[2].keys() == mets[0].keys()
    for k in mets[2]:
        assert mets[2][k] == pytest.approx(mets[0][k]), k


def test_prefetch_worker_exception_propagates():
    d, lab = _blob()
    tr = vt.Trainer(_fc_wf(), _loader(d, lab), opt.SGD(0.05),
                    vt.Decision(max_epochs=1))
    tr.initialize(seed=0)

    orig = tr.loader.iter_epoch

    def boom(klass, epoch=None):
        yield next(orig(klass, epoch))
        raise RuntimeError("loader died")

    tr.loader.iter_epoch = boom
    with pytest.raises(RuntimeError, match="loader died"):
        list(tr._batches(TRAIN, 0))


def test_step_cache_counters_and_key_miss():
    """Same key hits; changed batch geometry misses (a stale executable
    must never serve a different signature)."""
    cache = StepCache()
    calls = []

    def build():
        calls.append(1)
        return (jax.jit(lambda s, b: (s, {"m": b.sum()})), None, None)

    args = ({"x": jax.ShapeDtypeStruct((4,), jnp.float32)},
            jax.ShapeDtypeStruct((4,), jnp.float32))
    key = ("k", 1)
    fn1, _, _ = cache.get_step("train", key, build, args)
    fn2, _, _ = cache.get_step("train", key, build, args)
    assert fn1 is fn2 and len(calls) == 1
    assert cache.compiles == 1 and cache.hits == 1
    assert cache.recompiles == 0
    args2 = ({"x": jax.ShapeDtypeStruct((8,), jnp.float32)},
             jax.ShapeDtypeStruct((8,), jnp.float32))
    cache.get_step("train", ("k", 2), build, args2)
    assert cache.compiles == 2 and len(calls) == 2
    st = cache.stats()
    assert st["programs"] == 2 and st["compile_wall_s"] >= 0.0
    # AOT executables carry cost analysis for the observability log
    ent = next(iter(cache._entries.values()))
    assert "wall_s" in ent


def test_step_cache_hits_across_reinitialize():
    """Re-initializing the SAME trainer (unchanged shapes) is a cache
    hit, not a recompile."""
    d, lab = _blob()
    tr = vt.Trainer(_fc_wf(), _loader(d, lab), opt.SGD(0.05),
                    vt.Decision(max_epochs=1))
    tr.initialize(seed=0)
    assert tr.step_cache.compiles == 1  # eval is lazy
    tr.initialize(seed=1)  # e.g. a GA re-seed of the same workflow
    assert tr.step_cache.compiles == 1
    assert tr.step_cache.hits == 1
    tr.run()  # first eval epoch compiles the second program, once
    assert tr.step_cache.compiles == 2
    assert tr.step_cache.recompiles == 0


@pytest.fixture
def persistent_cache():
    """The suite runs with jax's persistent cache switched off
    (conftest.py); the tests of the cache rule switch it on, and put jax's
    settings back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_enable_compilation_cache", True)
    yield
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_compilation_cache_dir", prev[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[2])
    compilation_cache.reset_cache()


def test_persistent_cache_writes_entries(tmp_path, monkeypatch,
                                         persistent_cache):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(root.common, "compile_cache",
                        str(tmp_path / "xlacache"))
    d, lab = _blob()
    tr = vt.Trainer(_fc_wf(), _loader(d, lab), opt.SGD(0.05),
                    vt.Decision(max_epochs=1))
    tr.initialize(seed=0)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "xlacache")
    entries = glob.glob(str(tmp_path / "xlacache" / "*"))
    assert entries, "persistent compilation cache wrote nothing"


def test_persistent_cache_placed_from_outside(tmp_path, monkeypatch,
                                              persistent_cache):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax reads it itself and the
    code sets no directory — not the default, not the configured one."""
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "outside"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    monkeypatch.setattr(root.common, "compile_cache",
                        str(tmp_path / "configured"))
    assert enable_persistent_cache() == str(tmp_path / "outside")
    assert enable_persistent_cache(str(tmp_path / "asked")) \
        == str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "outside")
    assert not (tmp_path / "configured").exists()
    assert not (tmp_path / "asked").exists()


def test_persistent_cache_default_is_one_path_in_the_checkout(
        tmp_path, monkeypatch, persistent_cache):
    """Unset, the cache resolves to the same in-checkout directory from
    any cwd: it is part of what an entry is keyed on."""
    import veles_tpu
    from veles_tpu.runtime.step_cache import DEFAULT_COMPILE_CACHE
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(root.common, "compile_cache", "")
    seen = []
    for cwd in (tmp_path, os.path.dirname(veles_tpu.__file__)):
        monkeypatch.chdir(cwd)
        seen.append(enable_persistent_cache())
        assert jax.config.jax_compilation_cache_dir == seen[-1]
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(veles_tpu.__file__)))
    assert seen[0] == seen[1] == DEFAULT_COMPILE_CACHE
    assert os.path.commonpath([checkout, seen[0]]) == checkout
    assert str(tmp_path) not in seen[0]


def test_req_int_rejects_json_booleans():
    from veles_tpu.runtime.restful import RestfulServer
    assert RestfulServer._req_int(2, "n") == 2
    assert RestfulServer._req_int(2.0, "n") == 2
    assert RestfulServer._req_int("2", "n") == 2
    for bad in (True, False, 2.5, "x", float("inf")):
        with pytest.raises(ValueError):
            RestfulServer._req_int(bad, "n")
