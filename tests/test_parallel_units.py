"""sp/pp/ep as trainable product features (round-1 verdict #3): attention,
pipeline stacks and MoE as Units constructible from StandardWorkflow
configs, TRAINED on the virtual 8-device mesh with loss decreasing and
gradients flowing through the parallel primitives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import veles_tpu as vt
from veles_tpu.models.standard import StandardWorkflow
from veles_tpu.parallel import (MeshSpec, compose_rules, make_mesh,
                                ring_attention)
from veles_tpu.units import expert_rules, pipeline_rules

B, T, E = 8, 16, 16
N_CLASSES = 4


def _seq_batch(rng, b=B):
    """Learnable synthetic sequence task: the label is which quarter of the
    feature space has the largest energy in the mean token."""
    x = rng.standard_normal((b, T, E)).astype(np.float32)
    mean = x.mean(1).reshape(b, N_CLASSES, E // N_CLASSES)
    labels = np.abs(mean).sum(-1).argmax(-1).astype(np.int32)
    return {"@input": jnp.asarray(x), "@labels": jnp.asarray(labels),
            "@mask": jnp.ones((b,), jnp.float32)}


def _train(config, mesh, rule, rng, steps=30):
    sw = StandardWorkflow(config)
    wf = sw.workflow
    batch = _seq_batch(rng)
    specs = {k: vt.Spec(v.shape, v.dtype) for k, v in batch.items()}
    wf.build(specs)
    ws = wf.init_state(jax.random.key(0), sw.optimizer)
    step, state_sh, batch_sh = wf.make_sharded_train_step(
        sw.optimizer, mesh, ws, specs, rule=rule)
    ws = jax.device_put(ws, state_sh)
    # fixed batch: the test verifies optimization through the parallel
    # primitives (loss must drop), not generalization
    b = jax.device_put(batch, batch_sh)
    losses = []
    for i in range(steps):
        ws, mets = step(ws, b)
        losses.append(float(mets["loss"]))
    return losses, mets, ws, wf


def _flatten_cfg():
    return {"type": "flatten", "name": "flat"}


def test_attention_unit_trains_on_seq_mesh(rng):
    """dp×sp: a MultiHeadAttention unit wired from a StandardWorkflow
    config, trained over a data=2 × seq=4 mesh (ring attention path)."""
    mesh = make_mesh(MeshSpec(data=2, seq=4))
    config = {
        "name": "sp_model",
        "layers": [
            {"type": "attention", "n_heads": 2, "name": "attn",
             "causal": False},
            _flatten_cfg(),
            {"type": "softmax", "output_size": N_CLASSES, "name": "out"},
        ],
        "optimizer": "momentum",
        "optimizer_args": {"lr": 0.05, "momentum": 0.9},
    }
    losses, mets, ws, wf = _train(config, mesh, None, rng)
    assert losses[-1] < losses[0] * 0.7, losses
    # the attention projections actually trained
    w0 = wf["attn"]  # unit exists and holds no state itself
    assert float(jnp.abs(ws["params"]["attn"]["wq"]).sum()) > 0


def test_ring_attention_gradient_matches_local(rng):
    """Gradients THROUGH ring attention equal the single-device blockwise
    gradients (the round-1 gap: forward-only verification)."""
    from veles_tpu.parallel.ring_attention import full_attention
    mesh = make_mesh(MeshSpec(data=1, seq=8))
    q, k, v = (jnp.asarray(rng.standard_normal((2, 32, 2, 8)), jnp.float32)
               for _ in range(3))

    def loss_ring(q, k, v):
        return jnp.sum(jnp.square(
            ring_attention(q, k, v, mesh, causal=True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(full_attention(q, k, v, causal=True)))

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_moe_unit_trains_with_aux_loss(rng):
    """dp×ep: MoEFFN from config; the load-balance aux loss is summed into
    the training loss automatically (round-1 weakness #7)."""
    mesh = make_mesh(MeshSpec(data=2, expert=4))
    config = {
        "name": "ep_model",
        "layers": [
            {"type": "moe", "n_experts": 4, "d_hidden": 32, "name": "moe1",
             "top_k": 2},
            _flatten_cfg(),
            {"type": "softmax", "output_size": N_CLASSES, "name": "out"},
        ],
        "optimizer": "momentum",
        "optimizer_args": {"lr": 0.05, "momentum": 0.9},
    }
    losses, mets, ws, wf = _train(config, mesh, expert_rules(), rng)
    assert losses[-1] < losses[0] * 0.8, losses
    assert "aux_moe1" in mets and np.isfinite(float(mets["aux_moe1"]))
    # expert banks actually sharded over the expert axis
    spec = ws["params"]["moe1"]["w1"].sharding.spec
    assert spec and spec[0] == "expert", spec


def test_pipeline_unit_trains_on_pipe_mesh(rng):
    """dp×pp: PipelineStack from config, trained over data=2 × pipe=4."""
    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    config = {
        "name": "pp_model",
        "layers": [
            {"type": "pipeline_stack", "n_stages": 4, "d_hidden": 32,
             "name": "stack", "n_microbatches": 4},
            _flatten_cfg(),
            {"type": "softmax", "output_size": N_CLASSES, "name": "out"},
        ],
        "optimizer": "momentum",
        "optimizer_args": {"lr": 0.05, "momentum": 0.9},
    }
    losses, mets, ws, wf = _train(config, mesh, pipeline_rules(), rng)
    assert losses[-1] < losses[0] * 0.8, losses
    spec = ws["params"]["stack"]["stage_w1"].sharding.spec
    assert spec and spec[0] == "pipe", spec


def test_composed_sp_ep_training_step(rng):
    """One config, one mesh, multiple parallel axes at once:
    data=2 × seq=2 × expert=2 with attention AND MoE units."""
    mesh = make_mesh(MeshSpec(data=2, seq=2, expert=2))
    config = {
        "name": "composed",
        "layers": [
            {"type": "attention", "n_heads": 2, "name": "attn",
             "causal": False},
            {"type": "moe", "n_experts": 2, "d_hidden": 32,
             "name": "moe1", "top_k": 2},
            _flatten_cfg(),
            {"type": "softmax", "output_size": N_CLASSES, "name": "out"},
        ],
        "optimizer": "momentum",
        "optimizer_args": {"lr": 0.05, "momentum": 0.9},
    }
    losses, mets, ws, wf = _train(config, mesh, expert_rules(), rng,
                                  steps=15)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_units_fall_back_without_mesh(rng):
    """Same configs must run single-device (portable configs)."""
    config = {
        "name": "local",
        "layers": [
            {"type": "attention", "n_heads": 2, "name": "attn"},
            {"type": "pipeline_stack", "n_stages": 2, "d_hidden": 16,
             "name": "stack"},
            {"type": "moe", "n_experts": 2, "d_hidden": 16, "name": "moe1"},
            _flatten_cfg(),
            {"type": "softmax", "output_size": N_CLASSES, "name": "out"},
        ],
        "optimizer": "sgd",
        "optimizer_args": {"lr": 0.05},
    }
    sw = StandardWorkflow(config)
    wf = sw.workflow
    batch = _seq_batch(rng)
    wf.build({k: vt.Spec(v.shape, v.dtype) for k, v in batch.items()})
    ws = wf.init_state(jax.random.key(0), sw.optimizer)
    step = wf.make_train_step(sw.optimizer)
    ws, mets = step(ws, batch)
    assert np.isfinite(float(mets["loss"]))


def test_attention_unit_gqa_trains(rng):
    """MultiHeadAttention with n_kv_heads < n_heads builds, runs and
    reduces loss through the config-driven workflow path."""
    import veles_tpu as vt
    from veles_tpu.models.standard import build_workflow, build_optimizer
    layers = [
        {"type": "attention", "n_heads": 4, "n_kv_heads": 2,
         "window": 16, "name": "attn"},
        {"type": "flatten", "name": "flat"},
        {"type": "softmax", "output_size": 8, "name": "head"},
    ]
    wf = build_workflow("gqa", layers, loss="softmax")
    B, T, E = 4, 32, 16
    specs = {"@input": vt.Spec((B, T, E), jnp.float32),
             "@labels": vt.Spec((B,), jnp.int32),
             "@mask": vt.Spec((B,), jnp.float32)}
    wf.build(specs)
    opt = build_optimizer("momentum", layers, lr=0.05)
    ws = wf.init_state(jax.random.key(0), opt)
    assert ws["params"]["attn"]["wk"].shape == (E, 2 * (E // 4))
    step = wf.make_train_step(opt)
    rngl = np.random.default_rng(0)
    x = jnp.asarray(rngl.standard_normal((B, T, E)), jnp.float32)
    yb = jnp.asarray(rngl.integers(0, 8, B), jnp.int32)
    batch = {"@input": x, "@labels": yb, "@mask": jnp.ones(B)}
    losses = []
    for _ in range(25):
        ws, mets = step(ws, batch)
        losses.append(float(mets["loss"]))
    assert losses[-1] < losses[0]


def test_rope_properties(rng):
    """RoPE preserves norms, is identity at position 0 with offset 0, and
    q.k dot products depend only on RELATIVE position."""
    from veles_tpu.ops import rotary_embedding
    x = jnp.asarray(rng.standard_normal((2, 16, 3, 8)), jnp.float32)
    r = rotary_embedding(x)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(r), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(r[:, 0]), np.asarray(x[:, 0]),
                               rtol=1e-6)
    # relative-position property: scores of (q at p+s, k at p) equal for
    # any p when the unrotated vectors are the same
    q0 = x[:, :1]
    k0 = jnp.roll(x, 1, axis=1)[:, :1]
    def score(off):
        qq = rotary_embedding(q0, offset=off + 3)
        kk = rotary_embedding(k0, offset=off)
        return np.asarray(jnp.einsum("bthd,bthd->bth", qq, kk))
    np.testing.assert_allclose(score(0), score(11), rtol=1e-4, atol=1e-5)
    # shard-offset consistency: rotating two halves with offsets equals
    # rotating the whole (the sequence-parallel contract)
    whole = rotary_embedding(x)
    lo = rotary_embedding(x[:, :8], offset=0)
    hi = rotary_embedding(x[:, 8:], offset=8)
    np.testing.assert_allclose(np.asarray(whole),
                               np.asarray(jnp.concatenate([lo, hi], 1)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("D,lanes", [(8, 16), (64, 2), (128, 0), (80, 0),
                                     (256, 0)])
def test_rope_formulations_agree(rng, D, lanes):
    """Heads that share a 128-lane block rotate on (B, T, H * D) by a lane
    roll, the others with their pairs split out: both are the rotation of
    pairs (x[2i], x[2i+1]) by pos / base^(2i/D), value and gradient."""
    import jax
    from veles_tpu.ops import rotary_embedding
    from veles_tpu.ops.activations import heads_per_lane_block
    assert heads_per_lane_block(D) == lanes
    x = jnp.asarray(rng.standard_normal((2, 12, 3, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(x.shape), jnp.float32)

    def plain(x, offset):
        half = D // 2
        ang = (offset + np.arange(12, dtype=np.float32))[:, None] \
            * (10000.0 ** (-np.arange(half, dtype=np.float32) / half))
        cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        pairs = x.reshape(2, 12, 3, half, 2)
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(x.shape)

    for offset in (0, 5):
        np.testing.assert_allclose(
            np.asarray(rotary_embedding(x, offset=offset)),
            np.asarray(plain(x, offset)), rtol=1e-5, atol=1e-5)
        got, want = (jax.grad(lambda x, f=f: jnp.sum(f(x) * w))(x)
                     for f in (lambda x: rotary_embedding(x, offset=offset),
                               lambda x: plain(x, offset)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_attention_unit_rope_trains(rng):
    import veles_tpu as vt
    from veles_tpu.models.standard import build_workflow, build_optimizer
    layers = [
        {"type": "attention", "n_heads": 2, "rope": True, "name": "attn"},
        {"type": "flatten", "name": "flat"},
        {"type": "softmax", "output_size": 4, "name": "head"},
    ]
    wf = build_workflow("rope", layers, loss="softmax")
    B, T, E = 4, 16, 8
    specs = {"@input": vt.Spec((B, T, E), jnp.float32),
             "@labels": vt.Spec((B,), jnp.int32),
             "@mask": vt.Spec((B,), jnp.float32)}
    wf.build(specs)
    opt = build_optimizer("momentum", layers, lr=0.05)
    ws = wf.init_state(jax.random.key(1), opt)
    step = wf.make_train_step(opt)
    rngl = np.random.default_rng(1)
    batch = {"@input": jnp.asarray(
                 rngl.standard_normal((B, T, E)), jnp.float32),
             "@labels": jnp.asarray(rngl.integers(0, 4, B), jnp.int32),
             "@mask": jnp.ones(B)}
    losses = []
    for _ in range(20):
        ws, mets = step(ws, batch)
        losses.append(float(mets["loss"]))
    assert losses[-1] < losses[0]


def test_induction_lm_workflow_builds_and_learns(rng):
    """The sequence model family: embedding -> residual RoPE attention x2
    -> seq_last -> softmax, config-driven; loss must drop on the
    induction task (full quality bar run: configs/induction_lm.json)."""
    from veles_tpu.models import induction_workflow
    sw = induction_workflow(
        minibatch_size=50,
        loader_args={"n_train": 500, "n_valid": 100, "seq_len": 16,
                     "vocab": 8},
        layers=[
            {"type": "embedding", "vocab": 8, "dim": 16, "name": "emb"},
            {"type": "attention", "n_heads": 2, "rope": True,
             "residual": True, "name": "attn1"},
            {"type": "attention", "n_heads": 2, "rope": True,
             "residual": True, "name": "attn2"},
            {"type": "seq_last", "name": "last"},
            {"type": "softmax", "output_size": 8, "name": "out"},
        ], max_epochs=3, fail_iterations=3)
    tr = sw.make_trainer(sw.loader)
    tr.initialize(seed=1)
    import veles_tpu as vt  # noqa: F401
    losses = []
    for ep in range(3):
        m = tr._run_epoch_train(ep)
        losses.append(float(m["loss"]) / max(float(m["n_samples"]), 1))
    assert losses[-1] < losses[0]


def test_induction_task_is_unambiguous():
    """Every trigger token must be unique before its final repeat —
    otherwise labels would carry irreducible noise."""
    from veles_tpu.models.lm import synth_induction
    xt, yt, xv, yv = synth_induction(200, 50, seq_len=24, vocab=8)
    for x, y in ((xt, yt), (xv, yv)):
        trig = x[:, -1]
        matches = (x[:, :-1] == trig[:, None]).sum(1)
        assert (matches == 1).all()  # exactly the stored occurrence
        rows = np.arange(len(x))
        p = np.argmax(x[:, :-1] == trig[:, None], axis=1)
        np.testing.assert_array_equal(x[rows, p + 1], y)
