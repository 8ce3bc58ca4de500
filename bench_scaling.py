#!/usr/bin/env python
"""Scaling-efficiency harness: AlexNet data-parallel throughput over
1..N chips (BASELINE.json north star: scaling efficiency 1→8 chips).

On a multi-chip host it measures real ICI scaling; on a single chip it
reports n/a for >1 (the sharded step itself is validated on the virtual
CPU mesh by __graft_entry__.dryrun_multichip and tests/test_parallel.py —
this harness exists so a multi-chip round can produce the BASELINE.md
scaling row unchanged).

Prints one JSON line:
  {"metric": "alexnet_scaling", "points": [{"chips": n, "samples_per_sec":
   s, "efficiency": e}, ...]}
"""

import json
import sys
import time

import numpy as np

PER_CHIP_BATCH = 256
ITERS = 20


def measure(n_chips: int, per_chip_batch: int = None,
            iters: int = None) -> float:
    import jax
    import jax.numpy as jnp
    import veles_tpu as vt
    from veles_tpu.models import alexnet_workflow
    from veles_tpu.parallel import MeshSpec, make_mesh

    batch = (per_chip_batch or PER_CHIP_BATCH) * n_chips
    sw = alexnet_workflow(minibatch_size=batch)
    wf = sw.workflow
    specs = {"@input": vt.Spec((batch, 227, 227, 3), jnp.float32),
             "@labels": vt.Spec((batch,), jnp.int32),
             "@mask": vt.Spec((batch,), jnp.float32)}
    wf.build(specs)
    wstate = wf.init_state(jax.random.key(0), sw.optimizer)
    mesh = make_mesh(MeshSpec(data=n_chips),
                     devices=jax.devices()[:n_chips])
    step, state_sh, batch_sh = wf.make_sharded_train_step(
        sw.optimizer, mesh, wstate, specs)
    wstate = jax.device_put(wstate, state_sh)
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(2):
        host = {"@input": rng.standard_normal(
                    (batch, 227, 227, 3)).astype(np.float32),
                "@labels": (np.arange(batch) % 1000).astype(np.int32),
                "@mask": np.ones(batch, np.float32)}
        batches.append(jax.device_put(host, batch_sh))
    for i in range(3):
        wstate, mets = step(wstate, batches[i % 2])
    jax.block_until_ready((wstate, mets))
    t0 = time.perf_counter()
    iters = iters or ITERS
    for i in range(iters):
        wstate, mets = step(wstate, batches[i % 2])
    jax.block_until_ready((wstate, mets))
    return batch * iters / (time.perf_counter() - t0)


def measure_fused_pp(n_chips: int, per_mb: int = 4, iters: int = 2):
    """Fused-1F1B pipeline point WITH the round-4 lifts: dropout inside
    every attention stage (per-microbatch keys) and a MoE stage (aux
    accumulated) — certifies the product pipeline path end to end on
    whatever devices are visible."""
    import jax
    import jax.numpy as jnp
    import veles_tpu as vt
    from veles_tpu.models.standard import StandardWorkflow
    from veles_tpu.parallel import MeshSpec, make_mesh

    S = n_chips
    V, T, E = 16, 16, 32
    B = per_mb * S
    stage_att = [{"type": "attention", "n_heads": 2, "rope": True,
                  "residual": True},
                 {"type": "dropout", "dropout_ratio": 0.1},
                 {"type": "layer_norm"}]
    stage_moe = [{"type": "moe", "n_experts": 2, "d_hidden": 64,
                  "top_k": 1}, {"type": "layer_norm"}]
    sw = StandardWorkflow({
        "name": "scale_pp",
        "layers": [
            {"type": "embedding", "vocab": V, "dim": E, "name": "emb"},
            {"type": "pipeline_stack",
             "stages": [stage_att] * (S - 1) + [stage_moe],
             "n_microbatches": S, "name": "stack"},
            {"type": "seq_last", "name": "last"},
            {"type": "softmax", "output_size": V, "name": "out"},
        ],
        "optimizer": "sgd", "optimizer_args": {"lr": 0.1},
        "pipeline_microbatches": S,
    })
    wf = sw.workflow
    specs = {"@input": vt.Spec((B, T), jnp.int32),
             "@labels": vt.Spec((B,), jnp.int32),
             "@mask": vt.Spec((B,), jnp.float32)}
    wf.build(specs)
    ws = wf.init_state(jax.random.key(0), sw.optimizer)
    mesh = make_mesh(MeshSpec(pipe=S), devices=jax.devices()[:S])
    step, state_sh, _ = wf.make_pipeline_train_step(
        sw.optimizer, mesh, ws, specs, n_microbatches=S)
    ws = jax.device_put(ws, state_sh)
    tok = np.random.default_rng(0).integers(0, V, (B, T))
    batch = {"@input": np.asarray(tok, np.int32),
             "@labels": np.asarray(tok[:, -1], np.int32),
             "@mask": np.ones(B, np.float32)}
    ws, mets = step(ws, batch)
    float(mets["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        ws, mets = step(ws, batch)
    float(mets["loss"])
    return B * iters / (time.perf_counter() - t0), float(mets["aux"])


def measure_augmented(n_chips: int, bs_per_chip: int = 4,
                      iters: int = 2):
    """Device-augmented loader feeding a dp-sharded conv step: the
    round-3 input-pipeline redesign under data parallelism."""
    import jax
    import jax.numpy as jnp
    import veles_tpu as vt
    from veles_tpu.loader.base import TRAIN
    from veles_tpu.loader.fullbatch import FullBatchAugmentedLoader
    from veles_tpu.models.standard import build_workflow
    from veles_tpu.parallel import MeshSpec, make_mesh

    bs = bs_per_chip * n_chips
    rng = np.random.default_rng(3)
    store = rng.integers(0, 256, (max(4 * bs, 64), 24, 24, 3)) \
        .astype(np.uint8)
    loader = FullBatchAugmentedLoader(
        {TRAIN: store},
        {TRAIN: rng.integers(0, 10, len(store)).astype(np.int32)},
        minibatch_size=bs, crop_hw=(20, 20))
    loader.initialize()
    wf = build_workflow("scale_aug", [
        {"type": "norm", "name": "norm"},
        {"type": "conv_relu", "n_kernels": 8, "kx": 3, "name": "c1"},
        {"type": "max_pooling", "window": 2, "name": "p1"},
        {"type": "softmax", "output_size": 10, "name": "out"},
    ])
    specs = {"@input": vt.Spec((bs, 20, 20, 3), jnp.uint8),
             "@labels": vt.Spec((bs,), jnp.int32),
             "@mask": vt.Spec((bs,), jnp.float32)}
    wf.build(specs)
    ws = wf.init_state(jax.random.key(1), vt.optimizers.SGD(0.01))
    mesh = make_mesh(MeshSpec(data=n_chips),
                     devices=jax.devices()[:n_chips])
    step, state_sh, batch_sh = wf.make_sharded_train_step(
        vt.optimizers.SGD(0.01), mesh, ws, specs)
    ws = jax.device_put(ws, state_sh)
    it = loader.iter_epoch(TRAIN, 0)
    ws, mets = step(ws, jax.device_put(dict(next(it)), batch_sh))
    float(mets["loss"])
    t0 = time.perf_counter()
    n = 0
    for _ in range(iters):
        b = next(it, None)
        if b is None:
            it = loader.iter_epoch(TRAIN, 1)
            b = next(it)
        ws, mets = step(ws, jax.device_put(dict(b), batch_sh))
        n += bs
    float(mets["loss"])
    return n / (time.perf_counter() - t0)


def main():
    import jax
    # --tiny: validation mode for the virtual CPU mesh (the sharded step
    # and measurement plumbing run end-to-end at toy size, so a future
    # multi-chip round can trust the harness has not bit-rotted).
    tiny = "--tiny" in sys.argv
    avail = len(jax.devices())
    points = []
    base = None
    n = 1
    while n <= avail:
        sps = measure(n, per_chip_batch=4 if tiny else None,
                      iters=2 if tiny else None)
        if base is None:
            base = sps
        points.append({"chips": n, "samples_per_sec": round(sps, 1),
                       "efficiency": round(sps / (base * n), 4)})
        n *= 2
    extras = {}
    if avail > 1:
        # round-4 certification points: fused 1F1B with dropout+MoE
        # stages, and the device-augmented loader under dp
        S = 4 if avail % 4 == 0 else 2
        pp_sps, pp_aux = measure_fused_pp(S)
        extras["fused_pp"] = {"stages": S,
                              "samples_per_sec": round(pp_sps, 1),
                              "aux": round(pp_aux, 5)}
        extras["augmented_loader_dp"] = {
            "chips": avail,
            "samples_per_sec": round(measure_augmented(avail), 1)}
    print(json.dumps({"metric": "alexnet_scaling",
                      "device": str(jax.devices()[0]),
                      "available_chips": avail,
                      "points": points,
                      **extras,
                      "tiny": tiny,
                      "note": ("VALIDATION RUN (virtual CPU mesh / tiny "
                               "shapes) — efficiencies are not hardware "
                               "numbers") if tiny or
                      jax.devices()[0].platform == "cpu" else
                      None if avail > 1 else
                      "single chip visible; >1-chip rows need multi-chip "
                      "hardware (sharded step validated on virtual mesh)"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
