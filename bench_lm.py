#!/usr/bin/env python
"""LM-family benchmark: training throughput with MFU, and KV-cached
decode tokens/sec (round-2 verdict #6: add an LM training-throughput row
with MFU; #4: a tokens/sec number for the decode path).

Model: the induction-LM topology scaled to a real size — embedding ->
4x full transformer blocks (residual RoPE attention, layer_norm,
residual 4E FFN unit, layer_norm) -> per-position softmax head, bf16
compute. Prints one JSON line per metric.

Run on the TPU host: ``python bench_lm.py [--decode-only]``; off a TPU
only ``--smoke`` runs (toy shapes, records marked ``"smoke": true``).
"""

import json
import sys
import time

import numpy as np

SHAPE = dict(B=16, T=2048, E=512, LAYERS=4, HEADS=8, VOCAB=1024)
DECODE = (8, 512, 64)  # decode batch, prompt length, new tokens
#: toy shapes for --smoke
TOY = dict(B=2, T=64, E=32, LAYERS=2, HEADS=2, VOCAB=64)
TOY_DECODE = (2, 16, 8)


def build(wstate_seed=0, *, B, T, E, LAYERS, HEADS, VOCAB, use_flash=None):
    """``use_flash``: None lets the attention units autotune flash kernel
    vs XLA at the build shape; True / False forces one."""
    import jax
    import jax.numpy as jnp
    import veles_tpu as vt
    from veles_tpu.models.standard import StandardWorkflow

    layers = [{"type": "embedding", "vocab": VOCAB, "dim": E,
               "name": "emb"}]
    for i in range(LAYERS):
        # full transformer block: attention + FFN halves (an
        # attention-only stack would understate both FLOPs and MFU)
        layers += [
            {"type": "attention", "n_heads": HEADS, "rope": True,
             "residual": True, "use_flash": use_flash,
             "name": f"attn{i}"},
            {"type": "layer_norm", "name": f"ln{i}a"},
            {"type": "ffn", "d_hidden": 4 * E, "name": f"ffn{i}"},
            {"type": "layer_norm", "name": f"ln{i}b"},
        ]
    layers += [{"type": "all2all", "output_size": VOCAB,
                "per_position": True, "name": "head"}]
    sw = StandardWorkflow({
        "name": "bench_lm", "layers": layers,
        "compute_dtype": "bfloat16",
        "optimizer": "adam", "optimizer_args": {"lr": 1e-3},
    })
    wf = sw.workflow
    specs = {"@input": vt.Spec((B, T), jnp.int32),
             "@labels": vt.Spec((B, T), jnp.int32),
             "@mask": vt.Spec((B,), jnp.float32)}
    wf.build(specs)
    ws = wf.init_state(jax.random.key(wstate_seed), sw.optimizer)
    return sw, wf, ws


def main():
    decode_only = "--decode-only" in sys.argv
    # --smoke: tiny-shape validation (CPU-runnable) of the harness itself
    smoke = "--smoke" in sys.argv
    shape = TOY if smoke else SHAPE
    DECODE_B, DECODE_P, DECODE_N = TOY_DECODE if smoke else DECODE
    import jax
    import jax.numpy as jnp

    from veles_tpu.runtime.benchmark import device_peaks

    dev = jax.devices()[0]
    # the published peak of THIS device (an unknown TPU kind raises);
    # off a TPU there is none and MFU is not measured
    peaks = device_peaks(dev)
    if peaks is None and not smoke:
        print(f"bench_lm.py measures the TPU; found {dev.platform} "
              f"({dev.device_kind}); --smoke checks the harness at toy "
              "shapes on any backend", file=sys.stderr)
        return 1
    where = {"platform": dev.platform, "device_kind": dev.device_kind,
             "device_count": len(jax.devices()), "smoke": smoke}
    rng = np.random.default_rng(0)

    sw, wf, ws = build(**shape)
    B, T, E, LAYERS, VOCAB = (shape[k] for k in
                              ("B", "T", "E", "LAYERS", "VOCAB"))

    if not decode_only:
        step = wf.make_train_step(sw.optimizer)
        batch = {
            "@input": jnp.asarray(
                rng.integers(0, VOCAB, (B, T)), jnp.int32),
            "@labels": jnp.asarray(
                rng.integers(0, VOCAB, (B, T)), jnp.int32),
            "@mask": jnp.ones((B,), jnp.float32),
        }
        cost = jax.jit(step).lower(ws, batch).compile().cost_analysis()
        flops_per_step = float(cost.get("flops", 0.0))
        for _ in range(3):
            ws, mets = step(ws, batch)
        jax.block_until_ready((ws, mets))
        iters = 2 if smoke else 20
        t0 = time.perf_counter()
        for _ in range(iters):
            ws, mets = step(ws, batch)
        jax.block_until_ready((ws, mets))
        dt = (time.perf_counter() - t0) / iters
        final = float(mets["loss"])
        tokens_s = B * T / dt
        mfu = "not measured" if peaks is None else round(
            (flops_per_step / dt) / (peaks["tflops_bf16"] * 1e12), 4)
        print(json.dumps({
            "metric": "lm_train_tokens_per_sec_per_chip",
            "value": round(tokens_s, 1), "unit": "tokens/sec/chip",
            "batch": B, "seq_len": T, "d_model": E, "layers": LAYERS,
            "step_ms": round(dt * 1e3, 2),
            "flops_per_step": flops_per_step,
            "mfu_vs_published_peak": mfu,
            "final_loss": round(final, 4), **where,
        }))

    # -- decode: KV-cached greedy generation -------------------------------
    from veles_tpu.runtime.generate import generate
    prompt = rng.integers(0, VOCAB, (DECODE_B, DECODE_P)).astype(np.int32)
    generate(wf, ws, prompt, DECODE_N).block_until_ready()  # compile + warm
    t0 = time.perf_counter()
    generate(wf, ws, prompt, DECODE_N).block_until_ready()
    dt = time.perf_counter() - t0
    n_pos = DECODE_P + DECODE_N - 1            # cached steps executed
    print(json.dumps({
        "metric": "lm_decode_tokens_per_sec",
        "value": round(DECODE_B * DECODE_N / dt, 1), "unit": "tokens/sec",
        "batch": DECODE_B, "prompt_len": DECODE_P,
        "new_tokens": DECODE_N, "d_model": E, "layers": LAYERS,
        "positions_per_sec": round(DECODE_B * n_pos / dt, 1),
        "note": "KV-cached greedy decode; value counts NEW tokens only "
                "but the wall time includes prefilling the prompt "
                "through the same cached step (positions_per_sec is the "
                "raw step rate)",
        **where,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
