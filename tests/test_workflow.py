"""Workflow core: wiring checks, topo order, compiled steps, end-to-end
training on a learnable synthetic task, checkpoint resume.

Reference test analog: veles/tests/test_workflow.py (pickle roundtrip,
restored-from-snapshot semantics) + the MNIST-slice accuracy gate of
SURVEY.md §7 phase 4 (synthetic stand-in: datasets are not downloadable in
this environment; MnistLoader plugs in real files when present).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import veles_tpu as vt
from veles_tpu.loader.base import TRAIN, VALID
from veles_tpu.units import (All2AllSoftmax, All2AllTanh, EvaluatorSoftmax,
                             InputJoiner, Spec, TrivialUnit, Workflow)
from veles_tpu.units.workflow import WorkflowError


def make_blobs(rng, n, n_classes=4, dim=16, spread=3.0, centers=None):
    if centers is None:
        centers = np.random.default_rng(7).standard_normal(
            (n_classes, dim)) * spread
    labels = rng.integers(0, n_classes, n)
    data = centers[labels] + rng.standard_normal((n, dim))
    return data.astype(np.float32), labels.astype(np.int32)


def build_fc_workflow(dim=16, n_classes=4):
    wf = Workflow("fc")
    wf.add(All2AllTanh(32, name="fc1", inputs=("@input",)))
    wf.add(All2AllSoftmax(n_classes, name="out", inputs=("fc1",)))
    wf.add(EvaluatorSoftmax(name="ev", inputs=("out", "@labels", "@mask")))
    return wf


def make_loader(rng, n_train=512, n_valid=128, dim=16, mb=64):
    data_t, lab_t = make_blobs(rng, n_train, dim=dim)
    data_v, lab_v = make_blobs(rng, n_valid, dim=dim)
    return vt.ArrayLoader({TRAIN: data_t, VALID: data_v},
                          {TRAIN: lab_t, VALID: lab_v},
                          minibatch_size=mb)


def test_topo_and_cycle_detection():
    wf = Workflow("t")
    a = TrivialUnit(name="a", inputs=("b",))
    b = TrivialUnit(name="b", inputs=("a",))
    wf.add(a)
    wf.add(b)
    with pytest.raises(WorkflowError, match="cycle"):
        wf.topo_order()


def test_unknown_source_rejected():
    wf = Workflow("t")
    wf.add(TrivialUnit(name="a", inputs=("nope",)))
    with pytest.raises(WorkflowError, match="unknown source"):
        wf.topo_order()


def test_build_checks_batch_keys():
    wf = build_fc_workflow()
    with pytest.raises(WorkflowError, match="@labels"):
        wf.build({"@input": Spec((8, 16), jnp.float32)})


def test_checksum_stable_and_sensitive():
    wf1, wf2 = build_fc_workflow(), build_fc_workflow()
    assert wf1.checksum() == wf2.checksum()
    wf2.add(TrivialUnit(name="extra", inputs=("out",)))
    assert wf1.checksum() != wf2.checksum()


def test_graph_dot():
    dot = build_fc_workflow().generate_graph()
    assert "digraph" in dot and '"fc1" -> "out"' in dot


def test_input_joiner():
    wf = Workflow("j")
    wf.add(TrivialUnit(name="a"))
    wf.add(TrivialUnit(name="b"))
    wf.add(InputJoiner(name="join", inputs=("a", "b")))
    specs = wf.build({"@input": Spec((4, 3), jnp.float32)})
    assert specs["join"].shape == (4, 6)


def test_end_to_end_training_converges(rng):
    """The round-1 accuracy gate on a synthetic separable task: the full
    loader→forward→evaluator→optimizer→decision loop must reach <5% valid
    error (linearly-separable blobs)."""
    loader = make_loader(rng)
    wf = build_fc_workflow()
    trainer = vt.Trainer(
        wf, loader, vt.optimizers.SGD(0.05, momentum=0.9),
        vt.Decision(max_epochs=15, fail_iterations=15))
    trainer.initialize(seed=0)
    results = trainer.run()
    best = trainer.decision.best_value
    assert best < 5.0, f"validation error {best}% too high"
    assert results["train_samples_per_s"] > 0


def test_eval_metrics_exact_with_padding(rng):
    # 100 valid samples with minibatch 64 -> one padded batch; n_samples
    # must still count exactly 100.
    data_v, lab_v = make_blobs(rng, 100)
    data_t, lab_t = make_blobs(rng, 128)
    loader = vt.ArrayLoader({TRAIN: data_t, VALID: data_v},
                            {TRAIN: lab_t, VALID: lab_v}, minibatch_size=64)
    wf = build_fc_workflow()
    trainer = vt.Trainer(wf, loader, vt.optimizers.SGD(0.01),
                         vt.Decision(max_epochs=1))
    trainer.initialize(seed=0)
    mets = trainer._run_epoch_eval(VALID, 0)
    assert mets["n_samples"] == 100.0


def test_snapshot_resume(rng, tmp_path):
    loader = make_loader(rng)
    wf = build_fc_workflow()
    snap = vt.Snapshotter("fc", str(tmp_path), interval=1)
    trainer = vt.Trainer(wf, loader, vt.optimizers.SGD(0.05, momentum=0.9),
                         vt.Decision(max_epochs=3), snapshotter=snap)
    trainer.initialize(seed=0)
    trainer.run()
    assert snap.last_path is not None

    # Fresh trainer restores and continues.
    loader2 = make_loader(np.random.default_rng(1234))
    wf2 = build_fc_workflow()
    trainer2 = vt.Trainer(wf2, loader2,
                          vt.optimizers.SGD(0.05, momentum=0.9),
                          vt.Decision(max_epochs=6))
    trainer2.initialize(seed=1)
    trainer2.restore(snap.last_path)
    # params restored identically
    w_orig = np.asarray(trainer.wstate["params"]["fc1"]["w"])
    w_rest = np.asarray(trainer2.wstate["params"]["fc1"]["w"])
    np.testing.assert_allclose(w_orig, w_rest, rtol=1e-6)
    assert trainer2.loader.epoch_number == trainer.loader.epoch_number
    trainer2.run()
    assert trainer2.decision.best_value <= trainer.decision.best_value + 1.0


def test_fullbatch_loader_on_device_gather(rng):
    data_t, lab_t = make_blobs(rng, 256)
    loader = vt.FullBatchLoader({TRAIN: data_t}, {TRAIN: lab_t},
                                minibatch_size=64)
    loader.initialize()
    assert loader.on_device
    batch = next(loader.iter_epoch(TRAIN))
    assert isinstance(batch["@input"], jax.Array)
    assert batch["@input"].shape == (64, 16)
    # same permutation as host-side accounting
    perm = loader.epoch_permutation(TRAIN, 0)[:64]
    np.testing.assert_allclose(np.asarray(batch["@input"]), data_t[perm],
                               rtol=1e-6)


def test_loader_epoch_accounting(rng):
    """Each sample served exactly once per epoch (reference:
    veles/loader/base.py:880-898 effective_total_samples semantics)."""
    loader = make_loader(rng, n_train=130, mb=32)
    loader.initialize()
    served = []
    for batch in loader.iter_epoch(TRAIN, 0):
        m = batch["@mask"].astype(bool)
        served.extend(np.asarray(batch["@labels"])[m].tolist())
    assert len(served) == 130
    # sharded: two shards partition the epoch
    l2 = make_loader(rng, n_train=130, mb=32)
    l2.shard_count, l2.shard_index = 2, 0
    l3 = make_loader(rng, n_train=130, mb=32)
    l3.shard_count, l3.shard_index = 2, 1
    l2.initialize(), l3.initialize()
    n2 = sum(int(b["@mask"].sum()) for b in l2.iter_epoch(TRAIN, 0))
    n3 = sum(int(b["@mask"].sum()) for b in l3.iter_epoch(TRAIN, 0))
    assert n2 + n3 == 130


def test_dropout_train_vs_eval(rng):
    from veles_tpu.units import Dropout
    from veles_tpu.units.base import Context
    d = Dropout(0.5, name="drop")
    x = jnp.ones((4, 100))
    ctx_t = Context(train=True, key=jax.random.key(0))
    y, _ = d.apply({}, {}, [x], ctx_t)
    assert 0.2 < float((np.asarray(y) == 0).mean()) < 0.8
    ctx_e = Context(train=False, key=None)
    y2, _ = d.apply({}, {}, [x], ctx_e)
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(x))


def test_profile_units(rng):
    loader = make_loader(rng)
    wf = build_fc_workflow()
    trainer = vt.Trainer(wf, loader, vt.optimizers.SGD(0.01),
                         vt.Decision(max_epochs=1))
    trainer.initialize(seed=0)
    batch = next(loader.iter_epoch(TRAIN))
    rows = wf.profile_units(trainer.wstate, batch, reps=2)
    assert [r["unit"] for r in rows] == [u.name for u in wf.topo_order()]
    assert all(r["ms"] >= 0 for r in rows)
    table = vt.units.workflow.Workflow.format_profile(rows)
    assert "TOTAL" in table and rows[0]["unit"] in table


def test_decision_gauges_rmse_for_mse_workflows():
    """An MSE workflow's decision gauge is RMSE (not a mislabeled loss):
    error_pct -> rmse -> loss fallback order."""
    from veles_tpu.runtime.decision import Decision
    d = Decision(max_epochs=5)
    d.on_epoch(0, {}, {"rmse": 0.5, "loss": 0.25, "n_samples": 10.0})
    assert d.history[-1]["metric"] == "rmse"
    assert d.best_value == 0.5
    d2 = Decision(max_epochs=5)
    d2.on_epoch(0, {}, {"error_pct": 7.0, "loss": 0.1})
    assert d2.history[-1]["metric"] == "error_pct"
    assert d2.best_value == 7.0


def test_fullbatch_upload_failure_no_identical_retry(rng, monkeypatch):
    """With the default gather (plain jnp.take, no packed layout) a failed
    upload must fall straight to host gather — retrying without packing
    would re-run a byte-identical upload (round-2 review finding)."""
    data_t, lab_t = make_blobs(rng, 64)
    loader = vt.FullBatchLoader({TRAIN: data_t}, {TRAIN: lab_t},
                                minibatch_size=32)
    calls = []

    def boom(allow_pallas=True):
        calls.append(allow_pallas)
        raise RuntimeError("synthetic HBM OOM")

    monkeypatch.setattr(loader, "_upload", boom)
    loader.initialize()
    assert not loader.on_device
    assert calls == [True]

    # explicit packed gather: the unpacked retry IS meaningful
    loader2 = vt.FullBatchLoader({TRAIN: data_t}, {TRAIN: lab_t},
                                 minibatch_size=32, use_pallas_gather=True)
    calls2 = []

    def boom2(allow_pallas=True):
        calls2.append(allow_pallas)
        raise RuntimeError("synthetic HBM OOM")

    monkeypatch.setattr(loader2, "_upload", boom2)
    loader2.initialize()
    assert not loader2.on_device
    assert calls2 == [True, False]


def test_layer_norm_unit(rng):
    from veles_tpu.units import LayerNorm
    from veles_tpu.units.workflow import Workflow
    wf = Workflow("ln")
    wf.add(LayerNorm(name="norm"))
    specs = wf.build({"@input": vt.Spec((4, 6, 8), jnp.float32)})
    assert specs["norm"].shape == (4, 6, 8)
    ws = wf.init_state(jax.random.key(0), vt.optimizers.SGD(0.1))
    x = jnp.asarray(rng.standard_normal((4, 6, 8)) * 3 + 2, jnp.float32)
    fwd = wf.make_predict_step("norm")
    y = np.asarray(fwd(ws, {"@input": x}))
    np.testing.assert_allclose(y.mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(y.std(-1), 1.0, atol=1e-3)


def test_evaluator_softmax_sequence_form(rng):
    """(B, T, V) logits + (B, T) labels: per-position CE with the
    per-sample mask broadcast across positions."""
    from veles_tpu.units.nn import EvaluatorSoftmax
    B, T, V = 3, 5, 7
    logits = jnp.asarray(rng.standard_normal((B, T, V)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    mask = jnp.asarray([1.0, 1.0, 0.0])
    ev = EvaluatorSoftmax()
    mets = ev.metrics(None, None, (logits, labels, mask), None)
    assert float(mets["n_samples"]) == 2 * T
    # reference: masked mean over the first two samples' positions
    ref = 0.0
    for b in range(2):
        for t in range(T):
            lp = jax.nn.log_softmax(logits[b, t])
            ref -= float(lp[labels[b, t]])
    np.testing.assert_allclose(float(mets["loss"]), ref / (2 * T),
                               rtol=1e-5)


def test_per_position_dense_sequence_head(rng):
    """Per-position LM head path: embedding -> attention -> per-position
    softmax head -> sequence-form evaluator; loss drops on a per-position
    copy task (labels == tokens — learnable at every position, unlike
    next-token on iid noise; next-token training is the same graph with
    shifted labels)."""
    from veles_tpu.models.standard import build_workflow, build_optimizer
    layers = [
        {"type": "embedding", "vocab": 8, "dim": 16, "name": "emb"},
        {"type": "attention", "n_heads": 2, "rope": True,
         "residual": True, "name": "attn"},
        {"type": "softmax", "output_size": 8, "per_position": True,
         "name": "out"},
    ]
    wf = build_workflow("lm", layers, loss="softmax")
    B, T = 8, 12
    specs = {"@input": vt.Spec((B, T), jnp.int32),
             "@labels": vt.Spec((B, T), jnp.int32),
             "@mask": vt.Spec((B,), jnp.float32)}
    out_specs = wf.build(specs)
    assert out_specs["out"].shape == (B, T, 8)
    opt_ = build_optimizer("adam", layers, lr=3e-3)
    ws = wf.init_state(jax.random.key(2), opt_)
    step = wf.make_train_step(opt_)
    rngl = np.random.default_rng(2)
    x = rngl.integers(0, 8, (B, T)).astype(np.int32)
    # per-position copy task (emit the current token): learnable from the
    # residual stream at every position, unlike next-token on iid noise
    batch = {"@input": jnp.asarray(x), "@labels": jnp.asarray(x),
             "@mask": jnp.ones(B)}
    losses = []
    for _ in range(30):
        ws, mets = step(ws, batch)
        losses.append(float(mets["loss"]))
    assert losses[-1] < losses[0] * 0.8


def test_decision_restore_honors_new_budget():
    """Resuming a snapshot must keep the CURRENT run's epoch budget:
    restoring max_epochs/fail_iterations/complete from the payload would
    pin a curriculum fine-tune to the original run's budget."""
    from veles_tpu.runtime.decision import Decision
    d1 = Decision(max_epochs=10, fail_iterations=5)
    for ep in range(10):
        d1.on_epoch(ep, {}, {"error_pct": 50.0 - ep})
    assert d1.complete
    st = d1.state()
    d2 = Decision(max_epochs=30, fail_iterations=30)
    d2.set_state(st)
    assert d2.max_epochs == 30 and d2.fail_iterations == 30
    assert not d2.complete          # derived, not restored
    assert d2.best_value == st["best_value"]  # progress IS restored
    assert not d2.on_epoch(10, {}, {"error_pct": 39.0})  # keeps going


def test_remat_config_knob_exact_and_saves_memory(rng):
    """`remat: true` on a layer wraps it in jax.checkpoint during the
    training forward: loss and updated params are EXACTLY the AD path's
    (rematerialization changes scheduling, not math — including dropout,
    whose closed-over key makes the recompute draw the same mask), and
    the remat equations are really in the compiled step.

    Memory note: XLA:CPU's buffer analysis reports the same temp bytes
    with or without remat (it schedules the recompute adjacent to the
    original forward), so the HBM saving cannot be asserted
    here; on the chip it is not measured yet."""
    import veles_tpu as vt
    from veles_tpu.models.standard import build_workflow
    from veles_tpu.ops import optimizers as opt

    B, D, H, DEPTH = 32, 64, 256, 4

    def layers(remat):
        out = []
        for i in range(DEPTH):
            out.append({"type": "all2all_relu", "output_size": H,
                        "name": f"h{i}", "remat": remat})
            out.append({"type": "dropout", "dropout_ratio": 0.2,
                        "use_pallas": False, "name": f"d{i}",
                        "remat": remat})
        out.append({"type": "softmax", "output_size": 10, "name": "out"})
        return out

    specs = {"@input": vt.Spec((B, D), jnp.float32),
             "@labels": vt.Spec((B,), jnp.int32),
             "@mask": vt.Spec((B,), jnp.float32)}
    batch = {"@input": jnp.asarray(rng.standard_normal((B, D)),
                                   jnp.float32),
             "@labels": jnp.asarray(rng.integers(0, 10, B), jnp.int32),
             "@mask": jnp.ones((B,), jnp.float32)}

    wf_r = build_workflow("remat_on", layers(True))
    wf_n = build_workflow("remat_off", layers(False))
    wf_r.build(specs)
    wf_n.build(specs)
    o = opt.SGD(0.1)
    ws0 = wf_r.init_state(jax.random.key(7), o)

    step_r = wf_r.make_train_step(o, donate=False)
    step_n = wf_n.make_train_step(o, donate=False)
    ws_r, mets_r = step_r(jax.tree.map(jnp.copy, ws0), batch)
    ws_n, mets_n = step_n(jax.tree.map(jnp.copy, ws0), batch)
    np.testing.assert_allclose(float(mets_r["loss"]),
                               float(mets_n["loss"]), rtol=1e-6)
    for (pa, va), (pb, vb) in zip(
            jax.tree_util.tree_leaves_with_path(ws_r["params"]),
            jax.tree_util.tree_leaves_with_path(ws_n["params"])):
        np.testing.assert_allclose(np.asarray(va), np.asarray(vb),
                                   rtol=1e-6, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(pa))

    # the knob really lands in the traced program: one remat equation
    # per flagged unit, none without the flag
    step_r_tr = wf_r.make_train_step(o, jit=False, donate=False)
    step_n_tr = wf_n.make_train_step(o, jit=False, donate=False)
    jx_r = str(jax.make_jaxpr(step_r_tr)(ws0, batch))
    jx_n = str(jax.make_jaxpr(step_n_tr)(ws0, batch))
    assert jx_r.count("remat") == 2 * DEPTH, jx_r.count("remat")
    assert jx_n.count("remat") == 0

    # eval/predict ignore remat entirely (no backward to save for)
    pred_r = wf_r.make_predict_step("out")
    pred_n = wf_n.make_predict_step("out")
    np.testing.assert_allclose(
        np.asarray(pred_r(ws0, batch)), np.asarray(pred_n(ws0, batch)),
        rtol=1e-6)
