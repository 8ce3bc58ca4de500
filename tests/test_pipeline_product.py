"""The fused 1F1B schedule as the PRODUCT pipeline training path
(round-2 verdict #2): config-built workflows drive
Workflow.make_pipeline_train_step, with pre/post units folded into the
edge stages and grads matching the AD path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


import veles_tpu as vt
from veles_tpu.models.standard import StandardWorkflow, build_workflow
from veles_tpu.ops import optimizers as opt
from veles_tpu.parallel import MeshSpec, make_mesh
from veles_tpu.units.workflow import WorkflowError


def _seq_config(S=4, T=8, V=12, E=16):
    """Embedding -> S pipelined attention blocks -> seq_last -> softmax:
    the attention-stack pipeline the round-2 verdict asked for."""
    stage = [{"type": "attention", "n_heads": 2, "rope": True,
              "residual": True},
             {"type": "layer_norm"}]
    return {
        "name": "pp_lm",
        "layers": [
            {"type": "embedding", "vocab": V, "dim": E, "name": "emb"},
            {"type": "pipeline_stack", "stages": [stage] * S,
             "n_microbatches": S, "name": "stack"},
            {"type": "seq_last", "name": "last"},
            {"type": "softmax", "output_size": V, "name": "out"},
        ],
        "optimizer": "sgd",
        "optimizer_args": {"lr": 0.1},
        "pipeline_microbatches": S,
    }


def _lm_batch(rng, B, T, V):
    x = rng.integers(0, V, (B, T)).astype(np.int32)
    return {"@input": jnp.asarray(x),
            "@labels": jnp.asarray(x[:, -1].astype(np.int32)),
            "@mask": jnp.ones((B,), jnp.float32)}


def _build(config, B, T, V):
    sw = StandardWorkflow(config)
    wf = sw.workflow
    specs = {"@input": vt.Spec((B, T), jnp.int32),
             "@labels": vt.Spec((B,), jnp.int32),
             "@mask": vt.Spec((B,), jnp.float32)}
    wf.build(specs)
    return sw, wf, specs


def test_config_1f1b_matches_ad_path(rng):
    """One fused-1F1B optimizer step on the 8-dev mesh == one AD step on
    a single device, same init, same batch — loss AND updated params."""
    S, B, T, V, E = 4, 16, 8, 12, 16
    cfg = _seq_config(S, T, V, E)
    mesh = make_mesh(MeshSpec(data=2, pipe=S))

    sw, wf, specs = _build(cfg, B, T, V)
    ws0 = wf.init_state(jax.random.key(0), sw.optimizer)
    batch = _lm_batch(rng, B, T, V)

    # fused 1F1B on the mesh
    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws0, specs, n_microbatches=S, donate=False)
    ws_pp = jax.device_put(ws0, state_sh)
    ws_pp, mets_pp = step_pp(ws_pp, batch)

    # AD reference on one device (same graph; PipelineStack falls back to
    # its sequential form with no mesh)
    sw2, wf2, _ = _build(cfg, B, T, V)
    ws_ad = jax.tree.map(jnp.copy, ws0)  # identical init, fresh buffers
    step_ad = wf2.make_train_step(sw2.optimizer, donate=False)
    ws_ad, mets_ad = step_ad(ws_ad, batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    fp = {jax.tree_util.keystr(p): v for p, v in
          jax.tree_util.tree_leaves_with_path(ws_pp["params"])}
    fa = {jax.tree_util.keystr(p): v for p, v in
          jax.tree_util.tree_leaves_with_path(ws_ad["params"])}
    assert fp.keys() == fa.keys()
    for k in fp:
        np.testing.assert_allclose(np.asarray(fp[k]), np.asarray(fa[k]),
                                   rtol=2e-4, atol=2e-5, err_msg=k)


def test_config_1f1b_legacy_stack(rng):
    """The homogeneous (n_stages, d_hidden) stack trains on the fused
    path too, with the stage axis sharded over pipe."""
    S, B, D = 4, 16, 16
    mesh = make_mesh(MeshSpec(pipe=S, data=2))
    wf = build_workflow("pp_mlp", [
        {"type": "pipeline_stack", "n_stages": S, "d_hidden": 32,
         "n_microbatches": S, "name": "stack"},
        {"type": "softmax", "output_size": 5, "name": "out"},
    ])
    specs = {"@input": vt.Spec((B, D), jnp.float32),
             "@labels": vt.Spec((B,), jnp.int32),
             "@mask": vt.Spec((B,), jnp.float32)}
    wf.build(specs)
    o = opt.SGD(0.1)
    ws0 = wf.init_state(jax.random.key(1), o)
    batch = {"@input": jnp.asarray(rng.standard_normal((B, D)),
                                   jnp.float32),
             "@labels": jnp.asarray(rng.integers(0, 5, B), jnp.int32),
             "@mask": jnp.ones((B,), jnp.float32)}

    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        o, mesh, ws0, specs, n_microbatches=S, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    wf2 = build_workflow("pp_mlp", [
        {"type": "pipeline_stack", "n_stages": S, "d_hidden": 32,
         "n_microbatches": S, "name": "stack"},
        {"type": "softmax", "output_size": 5, "name": "out"},
    ])
    wf2.build(specs)
    step_ad = wf2.make_train_step(opt.SGD(0.1), donate=False)
    ws_ad, mets_ad = step_ad(jax.tree.map(jnp.copy, ws0), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    for k in ("stage_w1", "stage_w2"):
        np.testing.assert_allclose(
            np.asarray(ws_pp["params"]["stack"][k]),
            np.asarray(ws_ad["params"]["stack"][k]),
            rtol=2e-4, atol=2e-5)


def test_config_1f1b_loss_decreases(rng):
    """Product proof: repeated fused steps actually train."""
    S, B, T, V = 4, 16, 8, 12
    cfg = _seq_config(S, T, V)
    mesh = make_mesh(MeshSpec(data=2, pipe=S))
    sw, wf, specs = _build(cfg, B, T, V)
    ws = wf.init_state(jax.random.key(2), sw.optimizer)
    step, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws, specs, n_microbatches=S)
    ws = jax.device_put(ws, state_sh)
    batch = _lm_batch(rng, B, T, V)
    losses = []
    for _ in range(25):
        ws, mets = step(ws, batch)
        losses.append(float(mets["loss"]))
    assert losses[-1] < losses[0] * 0.6, losses[::6]


def test_trainer_uses_fused_pipeline(rng):
    """StandardWorkflow config switch: pipeline_microbatches routes the
    Trainer onto the fused step; a short run trains and evals."""
    from veles_tpu.loader.base import TRAIN, VALID
    S, T, V = 4, 8, 12
    cfg = dict(_seq_config(S, T, V), max_epochs=3)
    sw = StandardWorkflow(cfg)
    rng2 = np.random.default_rng(0)
    x = rng2.integers(0, V, (64, T)).astype(np.int32)
    y = x[:, -1].astype(np.int32)
    xv = rng2.integers(0, V, (32, T)).astype(np.int32)
    loader = vt.ArrayLoader({TRAIN: x, VALID: xv},
                            {TRAIN: y, VALID: xv[:, -1].astype(np.int32)},
                            minibatch_size=16)
    mesh = make_mesh(MeshSpec(data=2, pipe=S))
    trainer = sw.make_trainer(loader, mesh=mesh)
    assert trainer.pipeline_microbatches == S
    trainer.initialize(seed=0)
    res = trainer.run()
    assert res["train_samples_per_s"] > 0
    assert np.isfinite(res["best_value"])


def test_1f1b_rejects_missing_stack(rng):
    B, S = 16, 4
    mesh = make_mesh(MeshSpec(pipe=S))
    o = opt.SGD(0.1)
    # no PipelineStack at all
    wf2 = build_workflow("bad2", [
        {"type": "all2all_tanh", "output_size": 16, "name": "fc"},
        {"type": "softmax", "output_size": 5, "name": "out"},
    ])
    specs2 = {"@input": vt.Spec((B, 8), jnp.float32),
              "@labels": vt.Spec((B,), jnp.int32),
              "@mask": vt.Spec((B,), jnp.float32)}
    wf2.build(specs2)
    ws2 = wf2.init_state(jax.random.key(0), o)
    with pytest.raises(WorkflowError, match="PipelineStack"):
        wf2.make_pipeline_train_step(o, mesh, ws2, specs2,
                                     n_microbatches=S)


def test_config_stack_stage_shape_check():
    """A config stage that changes the activation spec fails at build."""
    from veles_tpu.units.parallel_nn import PipelineStack
    stack = PipelineStack(stages=[
        [{"type": "all2all_tanh", "output_size": 99}],
    ])
    with pytest.raises(ValueError, match="preserve"):
        stack.output_spec([vt.Spec((8, 16), jnp.float32)])


def test_config_stack_gpipe_forward_matches_sequential(rng):
    """Config-stage PipelineStack forwards identically pipelined (GPipe,
    pipe=4) and sequential (pipe=1) — the eval/predict path."""
    S, B, T, V, E = 4, 16, 8, 12, 16
    cfg = _seq_config(S, T, V, E)
    sw, wf, specs = _build(cfg, B, T, V)
    ws = wf.init_state(jax.random.key(3), sw.optimizer)
    batch = _lm_batch(rng, B, T, V)

    pred_seq = wf.make_predict_step("out")
    ref = np.asarray(pred_seq(ws, batch))

    mesh = make_mesh(MeshSpec(data=2, pipe=S))
    step_eval, state_sh, _ = wf.make_sharded_eval_step(
        mesh, ws, specs)
    # forward through the pipelined graph: reuse predict on the mesh
    wf.mesh = mesh
    pred_pp = wf.make_predict_step("out")
    got = np.asarray(pred_pp(jax.device_put(ws, state_sh), batch))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
    wf.mesh = None


def _dropout_config(S=4, T=8, V=12, E=16, ratio=0.25):
    """Transformer-block stages WITH dropout — the round-3 verdict's
    showcase the fused schedule previously rejected."""
    stage = [{"type": "attention", "n_heads": 2, "rope": True,
              "residual": True},
             {"type": "dropout", "dropout_ratio": ratio,
              "use_pallas": False},
             {"type": "layer_norm"}]
    return {
        "name": "pp_lm_drop",
        "layers": [
            {"type": "embedding", "vocab": V, "dim": E, "name": "emb"},
            {"type": "pipeline_stack", "stages": [stage] * S,
             "n_microbatches": S, "name": "stack"},
            {"type": "seq_last", "name": "last"},
            {"type": "softmax", "output_size": V, "name": "out"},
        ],
        "optimizer": "sgd",
        "optimizer_args": {"lr": 0.1},
        "pipeline_microbatches": S,
    }


def test_config_1f1b_dropout_matches_gpipe_ad(rng):
    """Round-4 lift: dropout INSIDE pipeline stages trains on the fused
    1F1B schedule and is grad-exact against AD-through-GPipe on the SAME
    mesh — both derive unit keys from fold_in(step_key, mb_index), so
    the masks are identical draws."""
    S, B, T, V, E = 4, 16, 8, 12, 16
    cfg = _dropout_config(S, T, V, E)
    mesh = make_mesh(MeshSpec(data=2, pipe=S))
    sw, wf, specs = _build(cfg, B, T, V)
    ws0 = wf.init_state(jax.random.key(0), sw.optimizer)
    batch = _lm_batch(rng, B, T, V)

    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws0, specs, n_microbatches=S, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    # AD reference on the SAME mesh: PipelineStack runs the keyed GPipe
    # schedule, drawing the same per-microbatch dropout masks
    sw2, wf2, _ = _build(cfg, B, T, V)
    step_ad, state_sh2, _ = wf2.make_sharded_train_step(
        sw2.optimizer, mesh, ws0, specs, donate=False)
    ws_ad, mets_ad = step_ad(
        jax.device_put(jax.tree.map(jnp.copy, ws0), state_sh2), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    fp = {jax.tree_util.keystr(p): v for p, v in
          jax.tree_util.tree_leaves_with_path(ws_pp["params"])}
    fa = {jax.tree_util.keystr(p): v for p, v in
          jax.tree_util.tree_leaves_with_path(ws_ad["params"])}
    assert fp.keys() == fa.keys()
    for k in fp:
        np.testing.assert_allclose(np.asarray(fp[k]), np.asarray(fa[k]),
                                   rtol=2e-4, atol=2e-5, err_msg=k)
    # the masks actually did something: training with ratio=0 diverges
    # from the dropout run (guards against dropout silently disabled)
    cfg0 = _dropout_config(S, T, V, E, ratio=0.0)
    sw3, wf3, _ = _build(cfg0, B, T, V)
    step0, state_sh3, _ = wf3.make_pipeline_train_step(
        sw3.optimizer, mesh, ws0, specs, n_microbatches=S, donate=False)
    _, mets0 = step0(jax.device_put(jax.tree.map(jnp.copy, ws0),
                                    state_sh3), batch)
    assert abs(float(mets0["loss"]) - float(mets_pp["loss"])) > 1e-6


def test_config_1f1b_moe_aux_matches_gpipe_ad(rng):
    """Round-4 lift: a MoE stage trains on the fused schedule with its
    load-balance aux loss included — loss and updated params exactly
    match AD-through-GPipe on the same mesh."""
    S, B, T, V, E = 2, 8, 4, 10, 8
    stage_moe = [{"type": "moe", "n_experts": 4, "d_hidden": 16,
                  "top_k": 2, "aux_weight": 0.05, "name": "moe"},
                 {"type": "layer_norm"}]
    stage_att = [{"type": "attention", "n_heads": 2, "residual": True}]
    cfg = {
        "name": "pp_moe",
        "layers": [
            {"type": "embedding", "vocab": V, "dim": E, "name": "emb"},
            {"type": "pipeline_stack", "stages": [stage_att, stage_moe],
             "n_microbatches": S, "name": "stack"},
            {"type": "seq_last", "name": "last"},
            {"type": "softmax", "output_size": V, "name": "out"},
        ],
        "optimizer": "sgd", "optimizer_args": {"lr": 0.1},
        "pipeline_microbatches": S,
    }
    mesh = make_mesh(MeshSpec(data=4, pipe=S))
    sw, wf, specs = _build(cfg, B, T, V)
    ws0 = wf.init_state(jax.random.key(0), sw.optimizer)
    batch = _lm_batch(rng, B, T, V)

    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws0, specs, n_microbatches=S, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    sw2, wf2, _ = _build(cfg, B, T, V)
    step_ad, state_sh2, _ = wf2.make_sharded_train_step(
        sw2.optimizer, mesh, ws0, specs, donate=False)
    ws_ad, mets_ad = step_ad(
        jax.device_put(jax.tree.map(jnp.copy, ws0), state_sh2), batch)

    # both paths report the main loss and the aux separately and must
    # agree on each (gradients include aux on both)
    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    np.testing.assert_allclose(float(mets_pp["aux"]),
                               float(mets_ad["aux_stack"]), rtol=2e-5)
    assert float(mets_ad["aux_stack"]) > 0.0  # the balance term is live
    fp = {jax.tree_util.keystr(p): v for p, v in
          jax.tree_util.tree_leaves_with_path(ws_pp["params"])}
    fa = {jax.tree_util.keystr(p): v for p, v in
          jax.tree_util.tree_leaves_with_path(ws_ad["params"])}
    assert fp.keys() == fa.keys()
    for k in fp:
        np.testing.assert_allclose(np.asarray(fp[k]), np.asarray(fa[k]),
                                   rtol=2e-4, atol=2e-5, err_msg=k)
    # expert params actually moved (aux + routed tokens reach them)
    moe_p = [v for k, v in fp.items() if "moe" in k]
    moe_0 = [v for p, v in jax.tree_util.tree_leaves_with_path(
        ws0["params"]) if "moe" in jax.tree_util.keystr(p)]
    assert any(float(jnp.abs(a - b).max()) > 0
               for a, b in zip(moe_p, moe_0))


def test_1f1b_ring_width_independent_of_vocab(rng):
    """Round-3 verdict #6: the activation ring must not scale with the
    output/vocab width, and dtypes ride the ring unchanged (bf16 stays
    bf16, int ids stay int)."""
    from veles_tpu.parallel.pipeline_compile import PipelinePlan
    S, B, T, E = 4, 16, 16, 8
    mesh = make_mesh(MeshSpec(pipe=S))

    def plan_for(V):
        cfg = _seq_config(S, T, V, E)
        sw, wf, specs = _build(cfg, B, T, V)
        return PipelinePlan(wf, mesh, S), sw, wf, specs

    p_small, *_ = plan_for(64)
    p_big, sw, wf, specs = plan_for(32768)
    # ring width: T*E activations, independent of V; the logits live
    # only in the last stage's local loss input
    assert p_small.act_width == p_big.act_width == T * E
    assert p_big.y_width == T * 32768 or p_big.y_width == 32768
    # input conveyor keeps token ids as int32 (no float round-trip)
    assert p_big.in_dtype == jnp.int32
    x = jnp.asarray(np.arange(B * T).reshape(B, T) % 7, jnp.int32)
    packed = p_big.pack_input(x)
    assert packed.dtype == jnp.int32

    # the fused step still compiles and trains at the 32k vocab
    ws = wf.init_state(jax.random.key(0), sw.optimizer)
    step, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws, specs, n_microbatches=S, donate=False)
    batch = _lm_batch(rng, B, T, 32768)
    _, mets = step(jax.device_put(ws, state_sh), batch)
    assert np.isfinite(float(mets["loss"]))


def test_1f1b_ring_preserves_bf16(rng):
    """bf16 activations must not be upcast to f32 on the ring (round-3
    silently carried everything as f32)."""
    from veles_tpu.parallel.pipeline_compile import PipelinePlan
    S, B, D = 4, 16, 16
    mesh = make_mesh(MeshSpec(pipe=S))
    wf = build_workflow("pp_bf16", [
        {"type": "pipeline_stack", "n_stages": S, "d_hidden": 32,
         "n_microbatches": S, "name": "stack"},
        {"type": "softmax", "output_size": 5, "name": "out"},
    ])
    specs = {"@input": vt.Spec((B, D), jnp.bfloat16),
             "@labels": vt.Spec((B,), jnp.int32),
             "@mask": vt.Spec((B,), jnp.float32)}
    wf.build(specs)
    plan = PipelinePlan(wf, mesh, S)
    assert plan.act_dtype == jnp.bfloat16
    assert plan.in_dtype == jnp.bfloat16
    o = opt.SGD(0.1)
    ws = wf.init_state(jax.random.key(1), o)
    step, state_sh, _ = wf.make_pipeline_train_step(
        o, mesh, ws, specs, n_microbatches=S, donate=False)
    batch = {"@input": jnp.asarray(rng.standard_normal((B, D)),
                                   jnp.bfloat16),
             "@labels": jnp.asarray(rng.integers(0, 5, B), jnp.int32),
             "@mask": jnp.ones((B,), jnp.float32)}
    _, mets = step(jax.device_put(ws, state_sh), batch)
    assert np.isfinite(float(mets["loss"]))


def test_trainer_accepts_padded_tail_batches(rng):
    """Round-5 lift (round-4 verdict #4): a loader whose train count
    does not divide the batch size trains through the fused 1F1B path —
    the mask-weighted loss makes the padded tail batch exact, so the
    old up-front rejection is gone."""
    from veles_tpu.loader.base import TRAIN
    S, T, V = 4, 8, 12
    cfg = dict(_seq_config(S, T, V), max_epochs=2)
    sw = StandardWorkflow(cfg)
    rng2 = np.random.default_rng(1)
    x = rng2.integers(0, V, (60, T)).astype(np.int32)  # 60 % 16 != 0
    loader = vt.ArrayLoader({TRAIN: x}, {TRAIN: x[:, -1].astype(np.int32)},
                            minibatch_size=16)
    mesh = make_mesh(MeshSpec(data=2, pipe=S))
    trainer = sw.make_trainer(loader, mesh=mesh)
    trainer.initialize(seed=0)
    res = trainer.run()
    assert np.isfinite(res["best_value"])


def test_config_1f1b_ragged_batch_matches_ad(rng):
    """Grad exactness with a NON-uniform @mask (the ragged tail batch):
    one fused step on dp2×pp4 with 5 of 16 rows padded == one AD step on
    a single device — the mask-weighted microbatch losses reassemble the
    global masked mean exactly, including an all-pad microbatch."""
    S, B, T, V, E = 4, 16, 8, 12, 16
    cfg = _seq_config(S, T, V, E)
    mesh = make_mesh(MeshSpec(data=2, pipe=S))

    sw, wf, specs = _build(cfg, B, T, V)
    ws0 = wf.init_state(jax.random.key(0), sw.optimizer)
    batch = _lm_batch(rng, B, T, V)
    # rows 11..15 are padding: microbatch 3 (rows 12-15) is ALL pad
    mask = np.ones((B,), np.float32)
    mask[11:] = 0.0
    batch["@mask"] = jnp.asarray(mask)

    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws0, specs, n_microbatches=S, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    sw2, wf2, _ = _build(cfg, B, T, V)
    step_ad = wf2.make_train_step(sw2.optimizer, donate=False)
    ws_ad, mets_ad = step_ad(jax.tree.map(jnp.copy, ws0), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    _assert_params_match(ws_pp, ws_ad)


def test_config_1f1b_ragged_with_sp_matches_ad(rng):
    """Ragged batch composed WITH sequence parallelism: the weighted
    loss's static rescale must cancel the seq-axis reduction too."""
    S, B, T, V, E = 2, 8, 8, 12, 16
    stage = [{"type": "attention", "n_heads": 2, "rope": True,
              "residual": True},
             {"type": "layer_norm"}]
    cfg = _per_position_cfg(S, V, E, stage)
    mesh = make_mesh(MeshSpec(data=2, seq=2, pipe=S))

    sw, wf, specs = _pp_build(cfg, B, T, V)
    ws0 = wf.init_state(jax.random.key(0), sw.optimizer)
    batch = _pp_lm_batch(rng, B, T, V)
    mask = np.ones((B,), np.float32)
    mask[5:] = 0.0
    batch["@mask"] = jnp.asarray(mask)

    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws0, specs, n_microbatches=S, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    sw2, wf2, _ = _pp_build(cfg, B, T, V)
    step_ad = wf2.make_train_step(sw2.optimizer, donate=False)
    ws_ad, mets_ad = step_ad(jax.tree.map(jnp.copy, ws0), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    _assert_params_match(ws_pp, ws_ad)


# ---------------------------------------------------------------------------
# round-5: collectives INSIDE fused-1F1B stages (pp×sp, pp×ep)
# ---------------------------------------------------------------------------

def _per_position_cfg(S, V, E, stage, lr=0.1):
    """Embedding -> S pipelined stages -> per-position head: the
    sp-compatible LM topology (every folded edge unit positionwise)."""
    return {
        "name": "pp_axes_lm",
        "layers": [
            {"type": "embedding", "vocab": V, "dim": E, "name": "emb"},
            {"type": "pipeline_stack", "stages": [stage] * S,
             "n_microbatches": S, "name": "stack"},
            {"type": "softmax", "output_size": V, "per_position": True,
             "name": "out"},
        ],
        "optimizer": "sgd",
        "optimizer_args": {"lr": lr},
        "pipeline_microbatches": S,
    }


def _pp_lm_batch(rng, B, T, V):
    """Next-token per-position batch: labels are (B, T)."""
    x = rng.integers(0, V, (B, T)).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    return {"@input": jnp.asarray(x), "@labels": jnp.asarray(y),
            "@mask": jnp.ones((B,), jnp.float32)}


def _pp_build(cfg, B, T, V):
    sw = StandardWorkflow(cfg)
    wf = sw.workflow
    specs = {"@input": vt.Spec((B, T), jnp.int32),
             "@labels": vt.Spec((B, T), jnp.int32),
             "@mask": vt.Spec((B,), jnp.float32)}
    wf.build(specs)
    return sw, wf, specs


def _assert_params_match(ws_a, ws_b):
    fa = {jax.tree_util.keystr(p): v for p, v in
          jax.tree_util.tree_leaves_with_path(ws_a["params"])}
    fb = {jax.tree_util.keystr(p): v for p, v in
          jax.tree_util.tree_leaves_with_path(ws_b["params"])}
    assert fa.keys() == fb.keys()
    for k in fa:
        np.testing.assert_allclose(np.asarray(fa[k]), np.asarray(fb[k]),
                                   rtol=2e-4, atol=2e-5, err_msg=k)


def test_config_1f1b_sp_inside_stages_matches_ad(rng):
    """Ring attention runs INSIDE fused-1F1B stages (round-4 verdict #3):
    pp2×sp2×dp2 on the 8-dev mesh — the transports carry T-shards, stage
    closures run raw ppermute rings over 'seq', rope rotates by global
    positions — and one optimizer step matches the single-device AD path
    to fp32 tolerance."""
    S, B, T, V, E = 2, 8, 8, 12, 16
    stage = [{"type": "attention", "n_heads": 2, "rope": True,
              "residual": True},
             {"type": "layer_norm"}]
    cfg = _per_position_cfg(S, V, E, stage)
    mesh = make_mesh(MeshSpec(data=2, seq=2, pipe=S))

    sw, wf, specs = _pp_build(cfg, B, T, V)
    ws0 = wf.init_state(jax.random.key(0), sw.optimizer)
    batch = _pp_lm_batch(rng, B, T, V)

    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws0, specs, n_microbatches=S, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    sw2, wf2, _ = _pp_build(cfg, B, T, V)
    step_ad = wf2.make_train_step(sw2.optimizer, donate=False)
    ws_ad, mets_ad = step_ad(jax.tree.map(jnp.copy, ws0), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    _assert_params_match(ws_pp, ws_ad)


def test_config_1f1b_ep_inside_stages_matches_ad(rng):
    """Expert-parallel MoE runs INSIDE fused-1F1B stages: pp2×ep2×dp2 —
    microbatch samples shard over 'expert', the stage closure's manual
    all_to_all redistributes tokens to the ranks owning each expert, and
    the full expert-bank gradient reassembles through the schedule's
    cross-shard psum.  aux_weight is NONZERO: the load-balance aux
    statistics psum over the expert axis (``_switch_aux(axis_name=)``),
    so the aux-weighted objective is exact vs the single-device AD path
    — not just the CE term (VERDICT #4; the rank-local formulation
    needed aux_weight=0 here)."""
    S, B, T, V, E = 2, 8, 8, 12, 16
    stage = [{"type": "moe", "n_experts": 4, "d_hidden": 32, "top_k": 1,
              "capacity_factor": 8.0, "aux_weight": 0.01},
             {"type": "layer_norm"}]
    cfg = _per_position_cfg(S, V, E, stage)
    mesh = make_mesh(MeshSpec(data=2, expert=2, pipe=S))

    sw, wf, specs = _pp_build(cfg, B, T, V)
    ws0 = wf.init_state(jax.random.key(0), sw.optimizer)
    batch = _pp_lm_batch(rng, B, T, V)

    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws0, specs, n_microbatches=S, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    sw2, wf2, _ = _pp_build(cfg, B, T, V)
    step_ad = wf2.make_train_step(sw2.optimizer, donate=False)
    ws_ad, mets_ad = step_ad(jax.tree.map(jnp.copy, ws0), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    _assert_params_match(ws_pp, ws_ad)


def test_config_1f1b_sp_ep_composed_trains(rng):
    """pp2×sp2×ep2 in ONE fused step (8 devices, three model axes): every
    stage is the realistic transformer-MoE block (attention + MoE — the
    uniform structure the shared SPMD dispatch requires), each stage body
    runs BOTH a seq ring and an expert all_to_all, loss decreases, aux
    flows."""
    S, B, T, V, E = 2, 8, 8, 12, 16
    block = [{"type": "attention", "n_heads": 2, "rope": True,
              "residual": True},
             {"type": "dropout", "dropout_ratio": 0.1},  # stochastic
             # draws decorrelate via the stage-index + seq-rank key fold
             {"type": "layer_norm"},
             {"type": "moe", "n_experts": 4, "d_hidden": 32,
              "top_k": 1, "capacity_factor": 4.0},
             {"type": "layer_norm"}]
    cfg = {
        "name": "pp_sp_ep_lm",
        "layers": [
            {"type": "embedding", "vocab": V, "dim": E, "name": "emb"},
            {"type": "pipeline_stack", "stages": [block, block],
             "n_microbatches": S, "name": "stack"},
            {"type": "softmax", "output_size": V, "per_position": True,
             "name": "out"},
        ],
        "optimizer": "sgd",
        "optimizer_args": {"lr": 0.3},
        "pipeline_microbatches": S,
    }
    mesh = make_mesh(MeshSpec(seq=2, expert=2, pipe=S))
    sw, wf, specs = _pp_build(cfg, B, T, V)
    ws = wf.init_state(jax.random.key(0), sw.optimizer)
    step, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws, specs, n_microbatches=S, donate=False)
    ws = jax.device_put(ws, state_sh)
    batch = _pp_lm_batch(rng, B, T, V)
    losses = []
    for _ in range(8):
        ws, mets = step(ws, batch)
        losses.append(float(mets["loss"]))
        assert np.isfinite(losses[-1])
        assert np.isfinite(float(mets["aux"]))
    assert losses[-1] < losses[0], losses


def test_1f1b_sp_rejects_heterogeneous_stages(rng):
    """Different collective sequences on different pipe ranks are not
    expressible in one SPMD program — the compiler must say so instead
    of deadlocking the runtime."""
    S, B, T, V, E = 2, 8, 8, 12, 16
    stage_att = [{"type": "attention", "n_heads": 2, "rope": True,
                  "residual": True},
                 {"type": "layer_norm"}]
    stage_ffn = [{"type": "ffn", "d_hidden": 32},
                 {"type": "layer_norm"}]
    cfg = {
        "name": "pp_het",
        "layers": [
            {"type": "embedding", "vocab": V, "dim": E, "name": "emb"},
            {"type": "pipeline_stack", "stages": [stage_att, stage_ffn],
             "n_microbatches": S, "name": "stack"},
            {"type": "softmax", "output_size": V, "per_position": True,
             "name": "out"},
        ],
        "optimizer": "sgd",
        "optimizer_args": {"lr": 0.1},
        "pipeline_microbatches": S,
    }
    mesh = make_mesh(MeshSpec(seq=2, pipe=S))
    sw, wf, specs = _pp_build(cfg, B, T, V)
    ws = wf.init_state(jax.random.key(0), sw.optimizer)
    with pytest.raises(WorkflowError, match="IDENTICAL"):
        wf.make_pipeline_train_step(sw.optimizer, mesh, ws, specs,
                                    n_microbatches=S)


def test_1f1b_sp_rejects_non_positionwise_post(rng):
    """seq_last under sequence parallelism would silently take the last
    LOCAL position — the plan must reject it with a real error."""
    S, B, T, V = 2, 8, 8, 12
    cfg = _seq_config(S, T, V)  # seq_last + sample-level softmax head
    mesh = make_mesh(MeshSpec(seq=2, pipe=S))
    sw, wf, specs = _build(cfg, B, T, V)
    ws = wf.init_state(jax.random.key(0), sw.optimizer)
    with pytest.raises(WorkflowError, match="positionwise"):
        wf.make_pipeline_train_step(sw.optimizer, mesh, ws, specs,
                                    n_microbatches=S)


def test_config_1f1b_stateful_normalizer_matches_ad(rng):
    """Round-5 lift (round-4 verdict #5): a stateful unit with READ-ONLY
    state — MeanDispNormalizer's dataset statistics — folds into the
    fused schedule's edge stage instead of being rejected; one fused
    step matches the AD path exactly."""
    S, B, D = 4, 16, 16
    mean = np.linspace(-1.0, 1.0, D).astype(np.float32)
    rdisp = np.linspace(0.5, 2.0, D).astype(np.float32)

    def build():
        wf = build_workflow("pp_statenorm", [
            {"type": "norm", "mean": mean, "rdisp": rdisp,
             "name": "norm"},
            {"type": "pipeline_stack", "n_stages": S, "d_hidden": 32,
             "n_microbatches": S, "name": "stack"},
            {"type": "softmax", "output_size": 5, "name": "out"},
        ])
        specs = {"@input": vt.Spec((B, D), jnp.float32),
                 "@labels": vt.Spec((B,), jnp.int32),
                 "@mask": vt.Spec((B,), jnp.float32)}
        wf.build(specs)
        return wf, specs

    wf, specs = build()
    o = opt.SGD(0.1)
    ws0 = wf.init_state(jax.random.key(1), o)
    assert set(ws0["state"]["norm"]) == {"mean", "rdisp"}  # real state
    batch = {"@input": jnp.asarray(rng.standard_normal((B, D)) * 2 + 1,
                                   jnp.float32),
             "@labels": jnp.asarray(rng.integers(0, 5, B), jnp.int32),
             "@mask": jnp.ones((B,), jnp.float32)}

    mesh = make_mesh(MeshSpec(data=2, pipe=S))
    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        o, mesh, ws0, specs, n_microbatches=S, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    wf2, _ = build()
    step_ad = wf2.make_train_step(opt.SGD(0.1), donate=False)
    ws_ad, mets_ad = step_ad(jax.tree.map(jnp.copy, ws0), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    _assert_params_match(ws_pp, ws_ad)
    # the statistics stayed untouched (read-only contract)
    np.testing.assert_array_equal(
        np.asarray(ws_pp["state"]["norm"]["mean"]), mean)


def test_1f1b_het_stages_with_idle_expert_axis(rng):
    """Review regression guard: an expert mesh axis on a MoE-FREE model
    must stay pure replication — heterogeneous stages keep the switch
    dispatch instead of being rejected by the shared-dispatch rule."""
    S, B, T, V, E = 2, 8, 8, 12, 16
    stage_att = [{"type": "attention", "n_heads": 2, "rope": True,
                  "residual": True},
                 {"type": "layer_norm"}]
    stage_ffn = [{"type": "ffn", "d_hidden": 32},
                 {"type": "layer_norm"}]
    cfg = {
        "name": "pp_het_idle_ep",
        "layers": [
            {"type": "embedding", "vocab": V, "dim": E, "name": "emb"},
            {"type": "pipeline_stack", "stages": [stage_att, stage_ffn],
             "n_microbatches": S, "name": "stack"},
            {"type": "seq_last", "name": "last"},
            {"type": "softmax", "output_size": V, "name": "out"},
        ],
        "optimizer": "sgd",
        "optimizer_args": {"lr": 0.1},
        "pipeline_microbatches": S,
    }
    mesh = make_mesh(MeshSpec(data=2, expert=2, pipe=S))
    sw, wf, specs = _build(cfg, B, T, V)
    ws = wf.init_state(jax.random.key(0), sw.optimizer)
    step, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws, specs, n_microbatches=S, donate=False)
    _, mets = step(jax.device_put(ws, state_sh), _lm_batch(rng, B, T, V))
    assert np.isfinite(float(mets["loss"]))


def test_config_1f1b_sp_swa_gqa_matches_ad(rng):
    """The manual ring inside fused stages carries the full attention
    feature set: sliding-window (global-position mask) + grouped-query
    (kv-head-sized ring traffic) — exact vs the AD path on pp2×sp2."""
    S, B, T, V, E = 2, 8, 16, 12, 16
    stage = [{"type": "attention", "n_heads": 4, "n_kv_heads": 2,
              "window": 8, "rope": True, "residual": True},
             {"type": "layer_norm"}]
    cfg = _per_position_cfg(S, V, E, stage)
    mesh = make_mesh(MeshSpec(data=2, seq=2, pipe=S))

    sw, wf, specs = _pp_build(cfg, B, T, V)
    ws0 = wf.init_state(jax.random.key(0), sw.optimizer)
    batch = _pp_lm_batch(rng, B, T, V)

    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws0, specs, n_microbatches=S, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    sw2, wf2, _ = _pp_build(cfg, B, T, V)
    step_ad = wf2.make_train_step(sw2.optimizer, donate=False)
    ws_ad, mets_ad = step_ad(jax.tree.map(jnp.copy, ws0), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    _assert_params_match(ws_pp, ws_ad)


def test_config_1f1b_fsdp_sharded_stage_params_matches_ad(rng):
    """pp×fsdp at rest: stage parameters (and their optimizer state)
    shard over the fsdp axis via the sharding rule; GSPMD all-gathers
    them into the schedule's P(pipe) layout at step entry and
    reduce-scatters the updates back — one fused step still matches the
    single-device AD path exactly."""
    from jax.sharding import PartitionSpec as P
    from veles_tpu.parallel.mesh import compose_rules
    from veles_tpu.units.parallel_nn import pipeline_rules
    S, B, D = 2, 16, 16
    mesh = make_mesh(MeshSpec(data=2, fsdp=2, pipe=S))
    wf = build_workflow("pp_fsdp", [
        {"type": "pipeline_stack", "n_stages": S, "d_hidden": 64,
         "n_microbatches": S, "name": "stack"},
        {"type": "softmax", "output_size": 5, "name": "out"},
    ])
    specs = {"@input": vt.Spec((B, D), jnp.float32),
             "@labels": vt.Spec((B,), jnp.int32),
             "@mask": vt.Spec((B,), jnp.float32)}
    wf.build(specs)
    o = opt.SGD(0.1)
    ws0 = wf.init_state(jax.random.key(1), o)

    def rule(path, spec):
        # stage arrays (S, d_in, d_out): stage axis on pipe, the hidden
        # dim on fsdp — persistent storage holds 1/(S·n_f) per device
        if path and path[-1].startswith("stage_"):
            return P("pipe", None, "fsdp")
        return P()

    batch = {"@input": jnp.asarray(rng.standard_normal((B, D)),
                                   jnp.float32),
             "@labels": jnp.asarray(rng.integers(0, 5, B), jnp.int32),
             "@mask": jnp.ones((B,), jnp.float32)}
    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        o, mesh, ws0, specs, n_microbatches=S, rule=rule, donate=False)
    # the rule actually sharded the stage params at rest
    sh = state_sh["params"]["stack"]["stage_w1"]
    assert "fsdp" in str(sh.spec), sh.spec
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    wf2 = build_workflow("pp_fsdp", [
        {"type": "pipeline_stack", "n_stages": S, "d_hidden": 64,
         "n_microbatches": S, "name": "stack"},
        {"type": "softmax", "output_size": 5, "name": "out"},
    ])
    wf2.build(specs)
    step_ad = wf2.make_train_step(opt.SGD(0.1), donate=False)
    ws_ad, mets_ad = step_ad(jax.tree.map(jnp.copy, ws0), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    _assert_params_match(ws_pp, ws_ad)


def test_config_1f1b_interleaved_matches_ad(rng):
    """Interleaved virtual stages through the PRODUCT path: a 4-stage
    uniform stack on pipe=2 with interleave=2 (device d hosts chunks d
    and d+2) — one fused optimizer step matches the single-device AD
    path exactly."""
    S, v, B, T, V, E = 2, 2, 8, 8, 12, 16
    stage = [{"type": "attention", "n_heads": 2, "rope": True,
              "residual": True},
             {"type": "layer_norm"}]
    cfg = {
        "name": "pp_interleaved",
        "layers": [
            {"type": "embedding", "vocab": V, "dim": E, "name": "emb"},
            {"type": "pipeline_stack", "stages": [stage] * (S * v),
             "n_microbatches": S, "name": "stack"},
            {"type": "seq_last", "name": "last"},
            {"type": "softmax", "output_size": V, "name": "out"},
        ],
        "optimizer": "sgd",
        "optimizer_args": {"lr": 0.1},
        "pipeline_microbatches": S,
    }
    mesh = make_mesh(MeshSpec(data=4, pipe=S))

    sw, wf, specs = _build(cfg, B, T, V)
    ws0 = wf.init_state(jax.random.key(0), sw.optimizer)
    batch = _lm_batch(rng, B, T, V)

    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws0, specs, n_microbatches=S,
        interleave=v, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    sw2, wf2, _ = _build(cfg, B, T, V)
    step_ad = wf2.make_train_step(sw2.optimizer, donate=False)
    ws_ad, mets_ad = step_ad(jax.tree.map(jnp.copy, ws0), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    _assert_params_match(ws_pp, ws_ad)


def test_config_1f1b_interleaved_sp_matches_ad(rng):
    """Interleave composes with in-stage ring attention: pipe=2 ×
    interleave=2 × seq=2 — T-sharded transports, four virtual chunks,
    one fused step exact vs AD."""
    S, v, B, T, V, E = 2, 2, 8, 8, 12, 16
    stage = [{"type": "attention", "n_heads": 2, "rope": True,
              "residual": True},
             {"type": "layer_norm"}]
    cfg = _per_position_cfg(S, V, E, stage)
    cfg["layers"][1]["stages"] = [stage] * (S * v)
    mesh = make_mesh(MeshSpec(data=2, seq=2, pipe=S))

    sw, wf, specs = _pp_build(cfg, B, T, V)
    ws0 = wf.init_state(jax.random.key(0), sw.optimizer)
    batch = _pp_lm_batch(rng, B, T, V)

    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws0, specs, n_microbatches=S,
        interleave=v, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    sw2, wf2, _ = _pp_build(cfg, B, T, V)
    step_ad = wf2.make_train_step(sw2.optimizer, donate=False)
    ws_ad, mets_ad = step_ad(jax.tree.map(jnp.copy, ws0), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    _assert_params_match(ws_pp, ws_ad)


def test_trainer_interleaved_config_switch(rng):
    """pipeline_interleave in the config routes the Trainer onto the
    interleaved schedule; a short run trains and evals (eval falls back
    to the sequential stack form)."""
    from veles_tpu.loader.base import TRAIN, VALID
    S, v, T, V = 2, 2, 8, 12
    stage = [{"type": "attention", "n_heads": 2, "rope": True,
              "residual": True},
             {"type": "layer_norm"}]
    cfg = {
        "name": "pp_int_trainer",
        "layers": [
            {"type": "embedding", "vocab": V, "dim": 16, "name": "emb"},
            {"type": "pipeline_stack", "stages": [stage] * (S * v),
             "n_microbatches": S, "name": "stack"},
            {"type": "seq_last", "name": "last"},
            {"type": "softmax", "output_size": V, "name": "out"},
        ],
        "optimizer": "sgd", "optimizer_args": {"lr": 0.1},
        "pipeline_microbatches": S, "pipeline_interleave": v,
        "max_epochs": 2,
    }
    sw = StandardWorkflow(cfg)
    rng2 = np.random.default_rng(0)
    x = rng2.integers(0, V, (64, T)).astype(np.int32)
    xv = rng2.integers(0, V, (32, T)).astype(np.int32)
    loader = vt.ArrayLoader({TRAIN: x, VALID: xv},
                            {TRAIN: x[:, -1].astype(np.int32),
                             VALID: xv[:, -1].astype(np.int32)},
                            minibatch_size=16)
    mesh = make_mesh(MeshSpec(data=4, pipe=S))
    trainer = sw.make_trainer(loader, mesh=mesh)
    assert trainer.pipeline_interleave == v
    trainer.initialize(seed=0)
    res = trainer.run()
    assert np.isfinite(res["best_value"])


def test_config_1f1b_interleaved_ep_matches_ad(rng):
    """Interleave composes with expert parallelism too: pp2 × v2 × ep2
    × dp2 — four virtual transformer-MoE chunks, manual all_to_all
    inside each, one fused step exact vs AD (ample capacity,
    aux_weight 0 — the rank-local aux statistic)."""
    S, v, B, T, V, E = 2, 2, 8, 8, 12, 16
    block = [{"type": "attention", "n_heads": 2, "rope": True,
              "residual": True}, {"type": "layer_norm"},
             {"type": "moe", "n_experts": 4, "d_hidden": 32, "top_k": 1,
              "capacity_factor": 8.0, "aux_weight": 0.0},
             {"type": "layer_norm"}]
    cfg = _per_position_cfg(S, V, E, block)
    cfg["layers"][1]["stages"] = [block] * (S * v)
    mesh = make_mesh(MeshSpec(data=2, expert=2, pipe=S))

    sw, wf, specs = _pp_build(cfg, B, T, V)
    ws0 = wf.init_state(jax.random.key(0), sw.optimizer)
    batch = _pp_lm_batch(rng, B, T, V)

    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws0, specs, n_microbatches=S,
        interleave=v, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    sw2, wf2, _ = _pp_build(cfg, B, T, V)
    step_ad = wf2.make_train_step(sw2.optimizer, donate=False)
    ws_ad, mets_ad = step_ad(jax.tree.map(jnp.copy, ws0), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    _assert_params_match(ws_pp, ws_ad)


def test_config_1f1b_interleaved_ragged_matches_ad(rng):
    """Ragged batches compose with the interleaved timetable: the
    mask-weighted loss's static rescale is schedule-independent — a
    non-uniform @mask (incl. an all-pad microbatch) on pipe2×v2×dp4
    matches the AD path exactly."""
    S, v, B, T, V, E = 2, 2, 8, 8, 12, 16
    stage = [{"type": "attention", "n_heads": 2, "rope": True,
              "residual": True},
             {"type": "layer_norm"}]
    cfg = _per_position_cfg(S, V, E, stage)
    cfg["layers"][1]["stages"] = [stage] * (S * v)
    mesh = make_mesh(MeshSpec(data=4, pipe=S))

    sw, wf, specs = _pp_build(cfg, B, T, V)
    ws0 = wf.init_state(jax.random.key(0), sw.optimizer)
    batch = _pp_lm_batch(rng, B, T, V)
    mask = np.ones((B,), np.float32)
    mask[5:] = 0.0
    batch["@mask"] = jnp.asarray(mask)

    step_pp, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws0, specs, n_microbatches=S,
        interleave=v, donate=False)
    ws_pp, mets_pp = step_pp(jax.device_put(ws0, state_sh), batch)

    sw2, wf2, _ = _pp_build(cfg, B, T, V)
    step_ad = wf2.make_train_step(sw2.optimizer, donate=False)
    ws_ad, mets_ad = step_ad(jax.tree.map(jnp.copy, ws0), batch)

    np.testing.assert_allclose(float(mets_pp["loss"]),
                               float(mets_ad["loss"]), rtol=2e-5)
    _assert_params_match(ws_pp, ws_ad)
