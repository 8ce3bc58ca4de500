#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once on ONE TPU chip, in one
process, through the entry points a user would call, and checks what comes
out by the repo's own means:

* *device*  — JAX must find a TPU; there is no CPU carry-on.
* *kernels* — every Pallas kernel of ``veles_tpu/ops/pallas_kernels.py``
  compiled through Mosaic at the shapes the main path uses, against its
  ``jax.numpy`` form.
* *train*   — AlexNet at its published width (227x227x3, 1000 classes,
  batch 512, bf16 compute / f32 master) through
  ``alexnet_e2e_device_workflow`` -> ``make_trainer`` ->
  ``Trainer.initialize`` -> ``Trainer.run``: falling loss, dataset resident
  on the device, zero recompiles.
* *serve*   — the ``bench_lm`` language model (4 blocks, d=512, 8 heads,
  ``l_max`` 2048, bf16) behind ``DecodeEngine`` + ``RestfulServer`` on
  loopback: HTTP ``/generate`` answers bitwise equal to ``generate()``,
  zero recompiles after warm-up, ``/metrics`` answers.

``--chips 4`` runs the sharded trainer (mesh ``data=2,fsdp=2``, the rule the
CLI composes for ``--mesh``) against the same steps on a one-device mesh,
and no other phase.  ``--rehearse`` runs the same phases through the same
code at toy shapes on whatever backend is there (kernels interpreted off a
TPU) — the sandbox rehearsal; its last line never says ``"ok": true``.

One line per phase (wall seconds, compile seconds, persistent-cache hits and
misses); the LAST line of stdout is the result object.  Any failed check
raises: the run exits non-zero on the first failed phase.
"""

import argparse
import json
import os
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: Sizes of a real run: the widths are the published / bench_lm ones, only
#: the number of steps is small.
REAL = dict(
    flash=[dict(B=16, T=2048, H=8, Hk=8, D=64, window=None, slice=2),
           dict(B=1, T=4096, H=8, Hk=8, D=128, window=None, slice=1),
           dict(B=1, T=8192, H=8, Hk=2, D=64, window=1024, slice=1)],
    paged=[dict(B=32, H=16, Hk=16, D=128, psz=16, n_ptab=128, dt="float32"),
           dict(B=32, H=16, Hk=4, D=128, psz=16, n_ptab=128, dt="bfloat16"),
           # the serve phase's own geometry (slots x l_max/page pages)
           dict(B=8, H=8, Hk=8, D=64, psz=16, n_ptab=128, dt="float32")],
    dropout=[((4096, 4096), "float32", 0.3), ((512, 4096), "bfloat16", 0.5)],
    gather=(60000, 784, 512),
    train=dict(batch=512, n_train=2048, n_valid=512, epochs=3),
    lm=None,                          # bench_lm.SHAPE, in both phases
    serve=dict(slots=8, l_max=2048, steps=16, prompts=(9, 300, 1500),
               sampled_prompt=40),
    sharded=dict(steps=3, lm=None),
)
TOY = dict(
    flash=[dict(B=2, T=128, H=2, Hk=2, D=32, window=None, slice=2),
           dict(B=1, T=256, H=4, Hk=2, D=32, window=64, slice=1)],
    paged=[dict(B=3, H=4, Hk=2, D=8, psz=4, n_ptab=5, dt="float32"),
           dict(B=2, H=2, Hk=2, D=8, psz=4, n_ptab=4, dt="bfloat16")],
    dropout=[((64, 256), "float32", 0.3), ((16, 128), "bfloat16", 0.5)],
    gather=(200, 784, 16),
    train=dict(batch=4, n_train=8, n_valid=4, epochs=3),
    lm=dict(B=2, T=64, E=32, LAYERS=2, HEADS=2, VOCAB=64),
    serve=dict(slots=2, l_max=64, steps=6, prompts=(5, 20, 40),
               sampled_prompt=9),
    # wide enough that the fsdp rule (>= 2**16 elements) splits something,
    # and a batch that four devices tile
    sharded=dict(steps=3,
                 lm=dict(B=4, T=64, E=128, LAYERS=2, HEADS=2, VOCAB=512)),
)


class Meter:
    """Compile seconds and persistent-cache hits/misses of this process,
    read from jax's own monitoring events."""

    def __init__(self):
        self.compile_s, self.hits, self.misses = 0.0, 0, 0

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def __enter__(self):
        import jax.monitoring as mon
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_listener(self._event)
        mon.unregister_event_duration_listener(self._duration)

    def phase(self, name, fn):
        """Run one phase and print its line.  No exception is caught: a
        failed check ends the run."""
        c0, h0, m0 = self.compile_s, self.hits, self.misses
        t0 = time.perf_counter()
        detail = fn()
        print(f"phase {name}: ok wall_s={time.perf_counter() - t0:.1f} "
              f"compile_s={self.compile_s - c0:.1f} "
              f"cache_hits={self.hits - h0} "
              f"cache_misses={self.misses - m0} | {detail}", flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def rel_err(got, ref):
    """Largest absolute difference over the reference's largest
    magnitude."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    check(np.isfinite(got).all(), "non-finite kernel output")
    return float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-12))


def run_compiled(fn, *args, on_tpu):
    """Lower and compile ``fn`` once, check that a TPU build really holds
    a Mosaic kernel, and run it."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    if on_tpu:
        check("tpu_custom_call" in compiled.as_text(),
              "kernel did not lower to a tpu_custom_call")
    return compiled(*args)


# -- kernels -------------------------------------------------------------------

def phase_kernels(size, seed, on_tpu):
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import pallas_kernels as pk
    from veles_tpu.parallel.ring_attention import blockwise_attention

    rng = np.random.default_rng(seed)
    worst = {}

    def note(name, err, tol):
        check(err <= tol, f"{name}: rel err {err:.3g} > {tol:.3g}")
        worst[name] = max(worst.get(name, 0.0), err)

    # flash attention forward + backward against the jnp blockwise scan
    # (the repo's portable path), on the leading batch rows: rows are
    # independent, and the reference need not hold B full score matrices
    for c in size["flash"]:
        q = jnp.asarray(rng.standard_normal((c["B"], c["T"], c["H"], c["D"])),
                        jnp.bfloat16)
        k, v = (jnp.asarray(
            rng.standard_normal((c["B"], c["T"], c["Hk"], c["D"])),
            jnp.bfloat16) for _ in range(2))

        def fwd_bwd(attend):
            def f(q, k, v):
                return jax.value_and_grad(
                    lambda q, k, v: jnp.sum(
                        attend(q, k, v).astype(jnp.float32) ** 2),
                    argnums=(0, 1, 2))(q, k, v)
            return f

        out = pk.flash_attention(q, k, v, True, None, window=c["window"])
        ref = blockwise_attention(
            *(x[:c["slice"]] for x in (q, k, v)), causal=True,
            window=c["window"], use_flash=False)
        note("flash_fwd", rel_err(out[:c["slice"]], ref), 2e-2)
        _, grads = run_compiled(fwd_bwd(
            lambda q, k, v: pk.flash_attention(
                q, k, v, True, None, window=c["window"])),
            q, k, v, on_tpu=on_tpu)
        _, rgrads = jax.jit(fwd_bwd(
            lambda q, k, v: blockwise_attention(
                q, k, v, causal=True, window=c["window"],
                use_flash=False)))(*(x[:c["slice"]] for x in (q, k, v)))
        for g, r in zip(grads, rgrads):
            note("flash_bwd", rel_err(g[:c["slice"]], r), 4e-2)

    # paged-attention decode against the gather path the engine runs by
    # default.  Interpreted, the kernel holds the tolerance
    # tests/test_pallas.py pins; on the chip its float32 dots run on the
    # MXU in bfloat16 passes, so there the bound is bfloat16's
    for c in size["paged"]:
        dt = jnp.dtype(c["dt"])
        rows = c["B"] * c["n_ptab"] + 1          # + the scratch page
        pool_k, pool_v = (jnp.asarray(rng.standard_normal(
            (rows, c["psz"], c["Hk"], c["D"])), dt) for _ in range(2))
        ptab = jnp.asarray(rng.permutation(rows - 1)[:c["B"] * c["n_ptab"]]
                           .reshape(c["B"], c["n_ptab"]), jnp.int32)
        pos = jnp.asarray(rng.integers(0, c["n_ptab"] * c["psz"], c["B"]),
                          jnp.int32)
        q = jnp.asarray(rng.standard_normal((c["B"], c["H"], c["D"])),
                        jnp.float32)
        out = run_compiled(
            lambda q, pk_, pv_, ptab, pos: pk.paged_attention_decode(
                q, pk_, pv_, ptab, pos, page_size=c["psz"],
                n_kv_heads=c["Hk"]),
            q, pool_k, pool_v, ptab, pos, on_tpu=on_tpu)
        with jax.default_matmul_precision("highest"):
            ref = _paged_gather_reference(q, pool_k, pool_v, ptab, pos)
        if not on_tpu:
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)
        note("paged_decode", rel_err(out, ref), 2e-2)

    # fused dropout: deterministic per seed, keeps 1-rate of the elements
    # scaled by 1/(1-rate), and the backward pass regenerates the mask
    for shape, dt, rate in size["dropout"]:
        x = jnp.asarray(rng.standard_normal(shape), jnp.dtype(dt))
        drop = lambda x, s, rate=rate: pk.fused_dropout(x, s, rate)  # noqa: E731
        o1 = run_compiled(drop, x, jnp.uint32(7), on_tpu=on_tpu)
        o2 = drop(x, jnp.uint32(7))
        np.testing.assert_array_equal(np.asarray(o1, np.float32),
                                      np.asarray(o2, np.float32))
        kept = np.asarray(o1, np.float32) != 0
        check(abs(kept.mean() - (1 - rate)) < 0.02,
              f"dropout kept {kept.mean():.4f}, expected {1 - rate}")
        np.testing.assert_allclose(
            np.asarray(o1, np.float32)[kept],
            (np.asarray(x, np.float32) / (1 - rate))[kept], rtol=1e-2)
        g = jax.grad(lambda x: jnp.sum(
            drop(x, jnp.uint32(7)).astype(jnp.float32)))(x)
        np.testing.assert_array_equal(np.asarray(g, np.float32) != 0, kept)
        worst["fused_dropout"] = max(worst.get("fused_dropout", 0.0),
                                     abs(float(kept.mean()) - (1 - rate)))

    # the loader's per-index DMA gather against jnp.take
    n, f, m = size["gather"]
    data = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    idx = jnp.asarray(rng.permutation(n)[:m], jnp.int32)
    got = run_compiled(lambda d, i: pk.gather_rows(d, i), data, idx,
                       on_tpu=on_tpu)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.take(data, idx, axis=0)))
    worst["gather_rows"] = 0.0
    return ("compiled " if on_tpu else "interpreted ") + " ".join(
        f"{k}={v:.2e}" for k, v in worst.items())


def _paged_gather_reference(q, pool_k, pool_v, ptab, pos):
    """softmax(q·Kᵀ)·V over each row's gathered pages, in one shot: the
    math of the engine's default paged read (runtime/generate.py)."""
    import jax
    import jax.numpy as jnp
    B, H, Dh = q.shape
    _, psz, Hk, _ = pool_k.shape
    L = ptab.shape[1] * psz
    kf = pool_k[ptab].reshape(B, L, Hk, Dh).astype(jnp.float32)
    vf = pool_v[ptab].reshape(B, L, Hk, Dh).astype(jnp.float32)
    qg = q.reshape(B, Hk, H // Hk, Dh)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, kf) * (Dh ** -0.5)
    mask = jnp.arange(L)[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    return jnp.einsum("bkgt,btkd->bkgd", jax.nn.softmax(s, axis=-1),
                      vf).reshape(B, H, Dh)


# -- train ---------------------------------------------------------------------

def phase_train(size, seed, on_tpu, kind):
    from veles_tpu.models.alexnet import alexnet_e2e_device_workflow
    from veles_tpu.plotting import MetricsRecorder
    from veles_tpu.runtime import Decision

    s = size["train"]
    # ten label classes under the 1000-wide head: the pixels are noise, so
    # the label prior is what a handful of steps can learn
    sw = alexnet_e2e_device_workflow(
        minibatch_size=s["batch"], n_train=s["n_train"],
        n_valid=s["n_valid"], seed=seed, n_classes=10)
    trainer = sw.make_trainer(sw.loader,
                              decision=Decision(max_epochs=s["epochs"]))
    trainer.recorder = MetricsRecorder()
    trainer.initialize(seed=seed)
    # the loader's out-of-memory path degrades to a host gather: that must
    # fail the smoke, not time another pipeline under this phase's name
    check(sw.loader.on_device, "dataset is not resident on the device")
    wf = sw.workflow
    picks = {
        "lrn": wf["lrn1"].method,
        "dropout": "pallas" if wf["drop6"].uses_kernel() else "xla",
        # FullBatchAugmentedLoader fuses its own take+crop: no candidates
        "gather": "take+crop",
    }
    if on_tpu:
        kernel_chosen = "pallas" in picks.values()
        check(("tpu_custom_call" in trainer._train_step.as_text())
              == kernel_chosen,
              f"train step and picks disagree on kernels: {picks}")
    trainer.run()
    losses = trainer.recorder.series["train_loss"]
    check(len(losses) == s["epochs"] and np.isfinite(losses).all(),
          f"train losses {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(np.isfinite(trainer.recorder.series["valid_loss"]).all(),
          "non-finite validation loss")
    stats = trainer.step_cache.stats()
    check(stats["recompiles"] == 0, f"recompiles: {stats}")
    return (f"alexnet batch={s['batch']} steps="
            f"{s['epochs'] * s['n_train'] // s['batch']} "
            f"loss={'->'.join(f'{x:.4f}' for x in losses)} on_device=True "
            f"programs={stats['programs']} recompiles=0 "
            f"step_compile_s={stats['compile_wall_s']} "
            f"picks={json.dumps(picks, sort_keys=True)} "
            f"autotune_ms={json.dumps(autotune_records(kind))}")


def autotune_db(kind):
    """Point the autotune DB at the checkout (not the cwd) and say what it
    already holds for this device: the winners decide which formulations
    run, so a run must show whether it measured them or found them."""
    from veles_tpu.config import root
    from veles_tpu.runtime.benchmark import device_info_path
    root.common.cache_dir = os.path.join(HERE, ".veles_tpu")
    return (f"autotune_db={device_info_path()} "
            f"found={json.dumps(autotune_records(kind))}")


def autotune_records(kind):
    """{op|shapes: {candidate: ms, ...}} of this device kind, winner first."""
    from veles_tpu.runtime.benchmark import load_device_infos
    found = load_device_infos().get(kind, {}).get("autotune", {})
    return {k: dict(sorted(v["ms"].items(), key=lambda kv: kv[1]))
            for k, v in found.items()}


# -- serve ---------------------------------------------------------------------

def build_lm(shape, seed, **kw):
    """bench_lm's model (``shape`` None: at its own shape):
    (StandardWorkflow, workflow, state, shape)."""
    import bench_lm
    shape = shape or bench_lm.SHAPE
    return (*bench_lm.build(seed, **shape, **kw), shape)


def post_generate(port, body):
    """POST /generate with one prompt; the token row of a unary answer, or
    the frames of an NDJSON stream reassembled into one row."""
    rq = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(dict(body, prompt=[body["prompt"]])).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(rq, timeout=900) as r:
        check(r.status == 200, f"/generate answered {r.status}")
        if not body.get("stream"):
            return json.loads(r.read())["tokens"][0]
        frames = [json.loads(line) for line in r if line.strip()]
    check(frames[-1].get("done") and frames[-1]["finish_reason"] == "length",
          f"stream ended with {frames[-1]}")
    return body["prompt"] + [f["token"] for f in frames if not f.get("done")]


def phase_serve(size, seed, on_tpu):
    import jax
    from veles_tpu.runtime.engine import DecodeEngine
    from veles_tpu.runtime.generate import generate
    from veles_tpu.runtime.restful import RestfulServer

    s = size["serve"]
    _, wf, ws, shape = build_lm(size["lm"], seed)
    vocab = shape["VOCAB"]
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, p).tolist() for p in s["prompts"]]
    requests = [dict(prompt=p, steps=s["steps"]) for p in prompts]
    requests.append(dict(
        prompt=rng.integers(0, vocab, s["sampled_prompt"]).tolist(),
        steps=s["steps"], temperature=0.8, top_k=50, seed=seed + 1))
    # the streamed request repeats a prompt: its pages come from the
    # prefix index, and its answer is already known
    requests.append(dict(prompt=prompts[1], steps=s["steps"], stream=True))

    def reference(rq):
        return np.asarray(generate(
            wf, ws, np.asarray([rq["prompt"]], np.int32), rq["steps"],
            temperature=rq.get("temperature", 0.0), top_k=rq.get("top_k"),
            key=jax.random.key(rq.get("seed", 0))))[0].tolist()

    refs = [reference(rq) for rq in requests[:-1]]
    refs.append(refs[1])
    predict = wf.make_predict_step(wf.default_output())
    ties = []

    def same_tokens(rq, got, ref):
        """The repo's contract is tokens bitwise equal to generate()'s.  On
        the chip it holds up to a tie: engine and generate() are different
        compiled programs over bfloat16 activations, XLA fuses (and rounds)
        them differently, and where the reference's own top two logits lie
        within one bfloat16 ulp the choice falls either way.  So: equal, or
        equal up to a greedy token at which the teacher-forced logits of
        the reference are tied and the engine took the other of the two;
        past it the sequences are different continuations and are not
        compared."""
        if got == ref:
            return
        p, i = len(rq["prompt"]), next(
            j for j, (a, b) in enumerate(zip(got, ref)) if a != b)
        check(len(got) == len(ref) and i >= p and "temperature" not in rq,
              f"prompt of {p}: answer differs from generate() at {i}")
        x = np.zeros((shape["B"], shape["T"]), np.int32)
        x[0, :i] = ref[:i]
        logits = np.asarray(predict(ws, {"@input": x}), np.float32)[0, i - 1]
        first, second = np.argsort(logits)[:-3:-1]
        margin = float(logits[first] - logits[second])
        ulp = float(abs(logits[first])) * 2.0 ** -8
        check({got[i], ref[i]} == {int(first), int(second)} and margin <= ulp,
              f"prompt of {p}: token {i - p} differs from generate() and is "
              f"no tie (top-2 margin {margin:.4g}, bfloat16 ulp {ulp:.4g})")
        ties.append(f"P{p}/token{i - p}/margin{margin:.2g}")

    eng = DecodeEngine(wf, dict(ws), slots=s["slots"],
                       l_max=s["l_max"]).start()
    srv = RestfulServer(wf.make_predict_step(wf.default_output()), dict(ws),
                        shape["B"], (shape["T"],), port=0,
                        workflow=wf, engine=eng,
                        input_dtype=np.int32).start()
    kern = None
    try:
        # warm-up pass, one request at a time: every prefill bucket the
        # prompts need compiles here
        answers = [post_generate(srv.port, rq) for rq in requests]
        for rq, got, ref in zip(requests, answers, refs):
            same_tokens(rq, got, ref)
        warm = eng.stats()["compile"]
        # the same requests at once: continuous batching, prefix hits,
        # not one more compile, and bit for bit the answers of before
        with ThreadPoolExecutor(len(requests)) as pool:
            futs = [pool.submit(post_generate, srv.port, rq)
                    for rq in requests]
            for rq, got, fut in zip(requests, answers, futs):
                check(fut.result() == got,
                      f"prompt of {len(rq['prompt'])}: the engine answered "
                      "differently under concurrency")
        st = eng.stats()
        check(st["compile"]["compiles"] == warm["compiles"]
              and st["compile"]["recompiles"] == 0,
              f"compiles after warm-up: {warm} -> {st['compile']}")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=60) as r:
            check(r.status == 200 and b"vt_compile_total" in r.read(),
                  "/metrics did not answer")

        # the fused paged-attention read: bounded error, not bitwise (the
        # kernels phase holds the kernel to its tolerance), so the tokens'
        # agreement with the reference is reported, not required
        kern = DecodeEngine(wf, dict(ws), slots=s["slots"], l_max=s["l_max"],
                            paged_kernel=True).start()
        if on_tpu:
            check("tpu_custom_call" in kern._decode.as_text(),
                  "paged_kernel decode program holds no Mosaic kernel")
        agree = total = 0
        for rq, ref in zip(requests[:2], refs[:2]):
            p = len(rq["prompt"])
            got = kern.generate(np.asarray([rq["prompt"]], np.int32),
                                rq["steps"], timeout=900)[0].tolist()
            check(got[:p] == ref[:p] and len(got) == len(ref)
                  and all(0 <= t < vocab for t in got),
                  "paged_kernel engine: bad tokens")
            agree += sum(a == b for a, b in zip(got[p:], ref[p:]))
            total += rq["steps"]
        check(kern.stats()["compile"]["recompiles"] == 0,
              "paged_kernel engine recompiled")
    finally:
        srv.stop()
        eng.stop()
        if kern is not None:
            kern.stop()
    return (f"lm d={shape['E']} blocks={shape['LAYERS']} "
            f"vocab={vocab} l_max={s['l_max']} slots={s['slots']} "
            f"http_requests={2 * len(requests)} "
            f"bitwise={len(requests) - len(ties)}/{len(requests)} "
            f"ties={ties} "
            f"prompts={[len(r['prompt']) for r in requests]} "
            f"programs={st['compile']['programs']} recompiles=0 "
            f"engine_compile_s={st['compile']['compile_wall_s']} "
            f"prefix_hit_pages={st.get('pages', {}).get('prefix_hit_pages')} "
            f"paged_kernel_token_agreement={agree}/{total}")


# -- four chips ----------------------------------------------------------------

def phase_sharded(size, seed, on_tpu):
    import jax
    import veles_tpu as vt
    from veles_tpu.__main__ import mesh_rule
    from veles_tpu.loader.base import TRAIN
    from veles_tpu.parallel import MeshSpec, make_mesh
    from veles_tpu.plotting import MetricsRecorder
    from veles_tpu.runtime import Decision

    steps = size["sharded"]["steps"]
    devices = jax.devices()[:4]

    def run(mesh_spec, devs):
        # the flash kernel forced on: the path under test is the Mosaic
        # kernel inside the GSPMD-partitioned step
        sw, wf, _, shape = build_lm(size["sharded"]["lm"], seed,
                                    use_flash=True)
        b, t = shape["B"], shape["T"]
        tok = np.random.default_rng(seed).integers(
            0, shape["VOCAB"], (b, t + 1))
        # one batch an epoch, so the recorder's epoch loss is the step's
        loader = vt.ArrayLoader({TRAIN: tok[:, :-1].astype(np.int32)},
                                {TRAIN: tok[:, 1:].astype(np.int32)},
                                minibatch_size=b)
        mesh = make_mesh(mesh_spec, devices=devs)
        trainer = sw.make_trainer(loader, decision=Decision(max_epochs=steps),
                                  mesh=mesh, rule=mesh_rule(wf, mesh))
        trainer.recorder = MetricsRecorder()
        trainer.initialize(seed=seed)
        batch = trainer._place_batch(next(loader.iter_epoch(TRAIN, 0)))
        placed = jax.tree.leaves((trainer.wstate["params"], batch))
        text = trainer._train_step.as_text()
        trainer.run()
        return trainer.recorder.series["train_loss"], placed, text, trainer

    sharded, placed, text, trainer = run(MeshSpec(data=2, fsdp=2), devices)
    check(all(len(x.sharding.device_set) == 4 for x in placed),
          "a parameter or a batch array does not live on four devices")
    split = sum(not x.sharding.is_fully_replicated for x in placed)
    check(split > 0, "nothing is partitioned: every array is replicated")
    check(any(op in text for op in ("all-reduce", "all-gather",
                                    "reduce-scatter")),
          "no collective in the sharded train step")
    if on_tpu:
        check("tpu_custom_call" in text,
              "sharded train step holds no Mosaic kernel")
    check(trainer.step_cache.stats()["recompiles"] == 0, "recompiles")
    per_device = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
    single, _, _, _ = run(MeshSpec(data=1), devices[:1])
    check(np.isfinite(sharded).all() and np.isfinite(single).all(),
          f"non-finite loss: {sharded} {single}")
    # bf16 compute: 8 bits of mantissa through four blocks
    np.testing.assert_allclose(sharded, single, rtol=2e-2)
    return (f"mesh data=2,fsdp=2 steps={steps} "
            f"loss_sharded={[round(x, 4) for x in sharded]} "
            f"loss_one_device={[round(x, 4) for x in single]} "
            f"arrays={len(placed)} partitioned={split} on_4_devices=all "
            f"per_device_bytes_in_use={per_device}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, data and prompts")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded trainer and what it is compared "
                         "with, and no other phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy shapes on whatever backend is there; the "
                         "result never says ok")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not args.rehearse and not (on_tpu and device["count"] >= args.chips):
        print(f"chip_smoke.py needs {args.chips} TPU chip(s); JAX found "
              f"{device}.  --rehearse runs the toy-shape rehearsal on any "
              "backend.", file=sys.stderr)
        return 2
    size = TOY if args.rehearse else REAL

    from veles_tpu.runtime.benchmark import device_peaks
    from veles_tpu.runtime.step_cache import enable_persistent_cache

    with Meter() as meter:
        meter.phase("device", lambda: (
            f"{json.dumps(device)} jax={jax.__version__} "
            f"peaks={json.dumps(device_peaks(dev)) if on_tpu else 'not measured'} "
            f"compile_cache={enable_persistent_cache()} "
            f"{autotune_db(dev.device_kind)}"))
        if args.chips == 4:
            meter.phase("sharded",
                        lambda: phase_sharded(size, args.seed, on_tpu))
        else:
            meter.phase("kernels",
                        lambda: phase_kernels(size, args.seed, on_tpu))
            meter.phase("train", lambda: phase_train(
                size, args.seed, on_tpu, dev.device_kind))
            meter.phase("serve", lambda: phase_serve(size, args.seed, on_tpu))
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": "passed",
                          "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
