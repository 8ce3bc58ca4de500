"""The one span API and the names the compiled programs carry.

``runtime.metrics.span`` puts one interval on the span ring, on the
profiler's host plane and (when ``root.common.trace_file`` is set) on the
JSONL timeline; ``Trainer.run()`` leaves a ``train_run`` tree whose phases
partition it; every unit (the evaluator with its metrics), the optimizer
and each Pallas kernel carry their names into the lowered programs.  Nothing
the program emits is called ``epoch_boundary``: the benchmark counts marks
of that name to find whole epochs.
"""

import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import veles_tpu as vt
from veles_tpu.config import root
from veles_tpu.loader.base import TRAIN, VALID
from veles_tpu.logger import event_tracer
from veles_tpu.ops import pallas_kernels as pk
from veles_tpu.runtime.metrics import registry, span, span_ring
from veles_tpu.units import (All2AllSoftmax, All2AllTanh, EvaluatorSoftmax,
                             Workflow)

PARTITION = ("train_epoch", "eval", "boundary", "snapshot")


def spans_since(mark):
    """The ring's complete spans whose ids the test opened after ``mark``
    (a span id: ids only grow)."""
    return [e for e in span_ring().snapshot()
            if e.get("ph") == "X" and e.get("args", {}).get("id", 0) > mark]


@pytest.fixture
def mark():
    with span("mark") as sp:
        pass
    return sp.id


@pytest.fixture
def trace_file(tmp_path):
    """``root.common.trace_file`` pointed at a file for one test."""
    saved = root.common.value("trace_file", "")
    root.common.trace_file = str(tmp_path / "events.jsonl")
    yield root.common.trace_file
    event_tracer().close()
    root.common.trace_file = saved


def test_span_nests_with_parent_and_trace_ids(mark):
    with span("outer", cat="train", epoch=3) as outer:
        with span("inner") as inner:
            with span("leaf") as leaf:
                pass
        with span("second") as second:
            pass
    with span("other_root") as other:
        pass
    assert outer.parent is None and other.parent is None
    assert inner.parent == outer.id and leaf.parent == inner.id
    assert second.parent == outer.id
    assert inner.trace == leaf.trace == second.trace == outer.trace
    assert other.trace != outer.trace
    by_name = {e["name"]: e for e in spans_since(mark)}
    assert set(by_name) == {"outer", "inner", "leaf", "second",
                            "other_root"}
    ev = by_name["outer"]
    assert ev["cat"] == "train" and ev["tid"] == outer.trace
    assert ev["args"] == {"epoch": 3, "id": outer.id, "parent": None,
                          "trace": outer.trace}
    assert by_name["leaf"]["args"]["parent"] == inner.id
    # a child lies inside its parent on the ring's clock
    assert ev["ts"] <= by_name["inner"]["ts"]
    assert by_name["inner"]["ts"] + by_name["inner"]["dur"] \
        <= ev["ts"] + ev["dur"] + 0.2


def test_span_survives_an_exception_in_its_body(mark):
    with pytest.raises(KeyError):
        with span("outer"):
            with span("failing", step=1):
                raise KeyError("x")
    with span("after") as after:
        pass
    assert after.parent is None, "a failed span stayed on the stack"
    by_name = {e["name"]: e for e in spans_since(mark)}
    assert by_name["failing"]["args"]["error"] == "KeyError"
    assert by_name["outer"]["args"]["error"] == "KeyError"
    assert "error" not in by_name["after"]["args"]


def test_span_args_added_in_the_body_reach_the_ring(mark):
    with span("totals") as sp:
        sp.args.update(steps=7)
    assert sp.seconds >= 0.0
    (ev,) = spans_since(mark)
    assert ev["args"]["steps"] == 7


@pytest.mark.parametrize("to_file", [False, True])
def test_span_writes_jsonl_only_when_trace_file_is_set(
        to_file, tmp_path, request):
    if to_file:
        path = request.getfixturevalue("trace_file")
    else:
        path = str(tmp_path / "events.jsonl")
        assert not root.common.value("trace_file", "")
    with span("compile", program="train") as sp:
        pass
    if not to_file:
        assert not glob.glob(str(tmp_path / "*"))
        return
    recs = [json.loads(l) for l in open(path)]
    assert [(r["name"], r["kind"]) for r in recs] == [
        ("compile", "begin"), ("compile", "end")]
    assert all(r["program"] == "train" and r["id"] == sp.id for r in recs)
    assert recs[1]["seconds"] == pytest.approx(sp.seconds)


# -- Trainer.run() ------------------------------------------------------------

def fc_workflow():
    wf = Workflow("fc")
    wf.add(All2AllTanh(32, name="fc1", inputs=("@input",)))
    wf.add(All2AllSoftmax(4, name="out", inputs=("fc1",)))
    wf.add(EvaluatorSoftmax(name="ev", inputs=("out", "@labels", "@mask")))
    return wf


def tiny_trainer(tmp_path=None, epochs=3):
    rng = np.random.default_rng(0)
    data = {k: rng.standard_normal((n, 16)).astype(np.float32)
            for k, n in ((TRAIN, 256), (VALID, 128))}
    labels = {k: rng.integers(0, 4, len(v)).astype(np.int32)
              for k, v in data.items()}
    loader = vt.ArrayLoader(data, labels, minibatch_size=64)
    snap = vt.Snapshotter("fc", str(tmp_path), interval=1) \
        if tmp_path is not None else None
    trainer = vt.Trainer(fc_workflow(), loader, vt.optimizers.SGD(0.05),
                         vt.Decision(max_epochs=epochs, fail_iterations=99),
                         snapshotter=snap)
    trainer.initialize(seed=0)
    return trainer


@pytest.fixture(scope="module")
def one_run(tmp_path_factory):
    """One tiny ``Trainer.run()`` of three epochs with a snapshot each:
    (the ring's spans of the run, the steps counter's change)."""
    trainer = tiny_trainer(tmp_path_factory.mktemp("snap"))
    with span("mark") as m:
        pass
    steps = registry().get("vt_train_steps_total")
    before = {k: steps.labels(klass=k).value
              for k in ("train", "validation", "test")}
    trainer.run()
    counted = {k: steps.labels(klass=k).value - v
               for k, v in before.items()}
    return spans_since(m.id), counted


def test_run_leaves_one_train_run_tree(one_run):
    spans, _ = one_run
    (run,) = [e for e in spans if e["name"] == "train_run"]
    rid, trace = run["args"]["id"], run["args"]["trace"]
    assert run["args"]["parent"] is None
    assert run["args"]["epochs"] == 3 and run["args"]["steps"] == 12
    children = [e for e in spans if e["args"]["parent"] == rid]
    names = [e["name"] for e in children
             if e["name"] != "step_compile"]     # the lazy eval program
    assert names == ["train_epoch", "epoch_decision", "eval",
                     "epoch_decision", "snapshot"] * 3
    assert all(e["args"]["trace"] == trace and e["tid"] == trace
               for e in spans if e["name"] != "mark")
    assert [e["args"]["epoch"] for e in children
            if e["name"] == "train_epoch"] == [0, 1, 2]
    epochs = {e["args"]["id"]: e for e in children
              if e["name"] == "train_epoch"}
    drains = [e for e in spans if e["name"] == "train_drain"]
    assert sorted(e["args"]["parent"] for e in drains) == sorted(epochs)
    evals = [e for e in children if e["name"] == "eval"]
    assert {e["args"]["klass"] for e in evals} == {"validation"}
    # the epoch's metrics ride its span, as they did before it had a parent
    assert all("loss" in e["args"] for e in epochs.values())


def test_run_phases_partition_the_run(one_run):
    spans, _ = one_run
    (run,) = [e for e in spans if e["name"] == "train_run"]
    args = run["args"]
    phases = sum(args[f"{p}_s"] for p in PARTITION)
    assert args["self_s"] >= 0.0
    assert phases + args["self_s"] == pytest.approx(run["dur"] * 1e-6,
                                                    abs=2e-3)
    # and the args are the children's own durations
    for phase, names in (("train_epoch", {"train_epoch"}), ("eval", {"eval"}),
                         ("boundary", {"epoch_decision"}),
                         ("snapshot", {"snapshot"})):
        dur = sum(e["dur"] for e in spans if e["name"] in names
                  and e["args"]["parent"] == args["id"]) * 1e-6
        assert args[f"{phase}_s"] == pytest.approx(dur, abs=1e-4), phase
        assert dur > 0.0, phase


def test_steps_counter_equals_the_batches_driven(one_run):
    _, counted = one_run
    # 256 / 64 train and 128 / 64 validation batches an epoch, 3 epochs
    assert counted == {"train": 12.0, "validation": 6.0, "test": 0.0}


def test_phase_histogram_has_the_epoch_phases(one_run):
    hist = registry().get("vt_train_phase_seconds")
    for phase in ("train_epoch", "train_drain", "eval", "boundary",
                  "snapshot", "data_wait", "step"):
        assert hist.labels(phase=phase).count > 0, phase


def test_no_ring_event_is_named_epoch_boundary(one_run):
    spans, _ = one_run
    assert spans and all(e["name"] != "epoch_boundary"
                         for e in span_ring().snapshot())


def test_profiler_sees_the_spans_and_no_epoch_boundary(tmp_path):
    """Under a capture the spans and the per-step annotation lie on the
    profiler's host plane; none of them is the benchmark's own mark."""
    from jax.profiler import ProfileData
    trainer = tiny_trainer(epochs=2)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        trainer.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    names = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    names[ev.name] = names.get(ev.name, 0) + 1
    assert names.get("train_run") == 1
    assert names.get("train_epoch") == 2 and names.get("train_drain") == 2
    assert names.get("eval") == 2 and names.get("epoch_decision") == 4
    assert names.get("train_step") == 8
    assert "epoch_boundary" not in names


# -- names inside the compiled programs ---------------------------------------

@pytest.fixture(scope="module")
def lowered_steps():
    trainer = tiny_trainer()
    wf = trainer.workflow
    args = (wf.state_struct(trainer.wstate), dict(trainer._batch_spec))
    return {
        "train": wf.make_train_step(trainer.optimizer).lower(
            *args).as_text(debug_info=True),
        "eval": wf.make_eval_step().lower(*args).as_text(debug_info=True)}


@pytest.mark.parametrize("program,scope", [
    ("train", "jvp(fc1)"), ("train", "jvp(out)"), ("train", "jvp(ev)"),
    ("train", "transpose(jvp(fc1))"), ("train", "transpose(jvp(out))"),
    ("train", "/optimizer/"), ("train", "transpose(jvp(ev))"),
    ("eval", "/fc1/"), ("eval", "/out/"), ("eval", "/ev/")])
def test_lowered_step_names_units_and_phases(lowered_steps, program, scope):
    assert scope in lowered_steps[program]


def test_remat_unit_keeps_its_name():
    """The scope sits inside the ``jax.checkpoint`` too."""
    trainer = tiny_trainer()
    wf = trainer.workflow
    wf["fc1"].remat = True
    text = wf.make_train_step(trainer.optimizer).lower(
        wf.state_struct(trainer.wstate),
        dict(trainer._batch_spec)).as_text(debug_info=True)
    assert re.search(r"checkpoint/(rematted_computation/)?fc1", text)


@pytest.mark.parametrize("klass", ["gather", "aug"])
def test_loader_programs_are_scoped(klass):
    from veles_tpu.loader.fullbatch import (FullBatchAugmentedLoader,
                                            FullBatchLoader)
    rng = np.random.default_rng(0)
    data = {TRAIN: rng.integers(0, 255, (16, 8, 8, 3)).astype(np.uint8)}
    labels = {TRAIN: rng.integers(0, 4, 16).astype(np.int32)}
    if klass == "gather":
        loader = FullBatchLoader(data, labels, minibatch_size=4)
        loader.initialize()
        text = loader._gather[TRAIN].lower(
            loader._dev_data[TRAIN], jnp.zeros((4,), jnp.int32)
        ).as_text(debug_info=True)
        assert "loader_gather" in text
    else:
        loader = FullBatchAugmentedLoader(
            data, labels, minibatch_size=4, crop_hw=(6, 6))
        loader.initialize()
        text = loader._aug.lower(
            loader._dev_data[TRAIN], jnp.zeros((4,), jnp.int32),
            jnp.zeros((4, 2), jnp.int32), jnp.zeros((4,), bool)
        ).as_text(debug_info=True)
        assert "loader_aug" in text


def _flash(q, k, v):
    return pk.flash_attention(q, k, v, causal=True, block_q=128,
                              block_k=128, interpret=True)


def _flash_grad(q, k, v):
    return jax.grad(lambda *a: _flash(*a).sum(), argnums=(0, 1, 2))(q, k, v)


def _kernel_case(name):
    """(wrapper, arguments) that trace the kernel called ``name``."""
    f32 = jnp.float32
    qkv = (jnp.zeros((1, 128, 2, 64), f32),) * 3
    if name == "flash_fwd":
        return _flash, qkv
    if name in ("flash_bwd_dq", "flash_bwd_dkv"):
        return _flash_grad, qkv
    if name == "paged_attention_decode":
        pool = jnp.zeros((7, 4, 2, 8), f32)
        return (lambda q, k, v, ptab, pos: pk.paged_attention_decode(
            q, k, v, ptab, pos, page_size=4, n_kv_heads=2, interpret=True),
            (jnp.zeros((2, 4, 8), f32), pool, pool,
             jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32)))
    if name == "dropout":
        return (lambda x: pk.fused_dropout(x, 3, 0.5, interpret=True),
                (jnp.ones((256, 128), f32),))
    if name == "gather_rows_packed":
        packed, _, _ = pk.pack_rows(jnp.ones((16, 1024), f32))
        return (lambda p, i: pk.gather_rows_packed(p, i, interpret=True),
                (packed, jnp.zeros((4,), jnp.int32)))
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "paged_attention_decode",
    "dropout", "gather_rows_packed"])
def test_each_pallas_kernel_carries_its_name(name):
    fn, args = _kernel_case(name)
    text = str(jax.make_jaxpr(fn)(*args))
    assert re.search(rf"\bname={name}\b", text), text[:2000]
