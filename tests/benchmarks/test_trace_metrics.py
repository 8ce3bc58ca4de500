"""The readers of the metrics that read the program's names and spans:
``attn_kernel_roofline`` on hand arithmetic and on hand-made ``run``
dicts, ``eval_share`` and ``boundary_host_share`` on a hand-filled ring,
and all three behind the tiny cells driven end to end on the CPU.

No chip, no child process, no topology call.
"""

import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import config_io  # noqa: E402
import counts  # noqa: E402
from metrics import (attn_kernel_roofline, boundary_host_share,  # noqa: E402
                     eval_share, train_run_spans)
from test_perf_benchmark import program_state, tiny  # noqa: E402,F401

LM = "opt350m_train_t2048"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
#: raw operation names as the chip's trace gives them (cut short)
FWD = ('%flash_fwd.7 = (bf16[64,2048,64]{2,1,0:T(8,128)(2,1)}, f32[64,2048,1]'
       '{2,1,0:T(8,128)}) custom-call(bf16[64,2048,64]{2,1,0} %bitcast.13), '
       'custom_call_target="tpu_custom_call"')
DQ = ('%flash_bwd_dq.3 = bf16[64,2048,64]{2,1,0:T(8,128)(2,1)} custom-call('
      'bf16[64,2048,64]{2,1,0} %bitcast.15, f32[64,2048,1]{2,1,0} '
      '%pallas_call.9), custom_call_target="tpu_custom_call"')
DKV = ('%flash_bwd_dkv.3 = (bf16[64,2048,64]{2,1,0}, bf16[64,2048,64]{2,1,0})'
       ' custom-call(bf16[64,2048,64]{2,1,0} %bitcast.14), '
       'custom_call_target="tpu_custom_call"')
#: an operation that only consumes a kernel's result is not the kernel
CONSUMER = ('%fusion.88 = f32[4,2048,1024]{2,1,0} fusion(bf16[64,2048,64]'
            '{2,1,0} %flash_bwd_dq.3, f32[1024,1024]{1,0} %copy-done.3), '
            'kind=kOutput, calls=%fused_computation.40')
PARENT = ('%jvp__.21 = (bf16[64,2048,64]{2,1,0}, f32[64,2048,1]{2,1,0}) '
          'custom-call(bf16[64,2048,64]{2,1,0} %bitcast.13), '
          'custom_call_target="tpu_custom_call"')


def lm_measured():
    cell = config_io.load_cell(LM)
    cfg = config_io.load_config(cell["config"])
    c = counts.model_counts(cfg, cell["traffic"])
    return {"items_per_epoch": cell["traffic"]["n_train"]
            * cell["traffic"]["seq_len"],
            "train_flops_per_item": c["train_flops_per_item"]}


def lm_run(seconds_by_op, epochs=1, measured=None):
    return {"measured": measured or lm_measured(), "peaks": PEAKS,
            "chips": 1,
            "trace": {"epochs_in_window": epochs, "window_s": 2.4,
                      "busy_s": 2.3, "seconds_by_op": seconds_by_op}}


def hand_seconds(epochs=1):
    """An epoch of the LM cell as PERF.md's hand reading has the kernels:
    a forward call on 4 sequences (64 x 2048 x 64) takes 1.30 ms, the two
    backward kernels together twice that; 16 training and 1 validation
    batch an epoch, 12 layers."""
    return {FWD: epochs * 12 * (16 + 1) * 1.30e-3,
            DQ: epochs * 12 * 16 * 1.00e-3,
            DKV: epochs * 12 * 16 * 1.60e-3}


def test_attention_core_flops_are_counts_walks_own():
    cell = config_io.load_cell(LM)
    cfg = config_io.load_config(cell["config"])
    # 12 layers x 2 x T^2 x E: two products, the causal half
    assert attn_kernel_roofline.core_flops_per_sequence(
        cfg, cell["traffic"]) == 12 * 2 * 2048 ** 2 * 1024


@pytest.mark.parametrize("epochs", [1, 2])
def test_attn_kernel_roofline_on_hand_arithmetic(epochs):
    """4 x 2 x 2048^2 x 1024 FLOP forward in 1.30 ms is 26.4 TFLOP/s, 13.4 %
    of 197; backward is twice the work in twice the time."""
    value = attn_kernel_roofline.read(
        lm_run(hand_seconds(epochs), epochs=epochs))
    forward_rate = 4 * 2 * 2048 ** 2 * 1024 / 1.30e-3
    assert value == pytest.approx(100 * forward_rate / 197e12, rel=1e-9)
    assert value == pytest.approx(13.4, abs=0.05)


def test_attn_kernel_roofline_counts_the_kernels_own_operations_only():
    secs = hand_seconds()
    noisy = {**secs, CONSUMER: 5.0, PARENT: 3.0, "%fusion.1 = f32[] x": 1.0}
    assert attn_kernel_roofline.kernel_seconds(noisy) \
        == pytest.approx(sum(secs.values()))
    assert attn_kernel_roofline.read(lm_run(noisy)) \
        == pytest.approx(attn_kernel_roofline.read(lm_run(secs)))


@pytest.mark.parametrize("case", [
    "no_kernel_named", "no_epochs_marked", "no_trace", "unknown_cell",
    "two_cells_match"])
def test_attn_kernel_roofline_gives_nothing(case, monkeypatch):
    bench = config_io.load_benchmark()
    run = lm_run(hand_seconds())
    if case == "no_kernel_named":        # the parent: %jvp__.21
        run = lm_run({PARENT: 0.3, CONSUMER: 0.1})
    elif case == "no_epochs_marked":
        run["trace"]["epochs_in_window"] = 0
    elif case == "no_trace":
        run["trace"] = None
    elif case == "unknown_cell":
        run["measured"] = {**run["measured"], "items_per_epoch": 12345}
    elif case == "two_cells_match":
        twin = next(w for w in bench["workloads"] if w["name"] == LM)
        bench["workloads"].append(dict(twin))
        monkeypatch.setattr(config_io, "load_benchmark", lambda: bench)
    assert attn_kernel_roofline.read(run) is None


# -- the program's spans ------------------------------------------------------

def span_event(name, ts, dur, **args):
    return {"name": name, "cat": "train", "ph": "X", "ts": ts * 1e6,
            "dur": dur * 1e6, "pid": 0, "tid": 1, "args": args}


def ring(epochs=4, run_id=10, slow_boundary=()):
    """A ``train_run`` of ``epochs`` epochs: 0.96 s of training, 0.02 s of
    validation, 0.003 + 0.007 s of decisions, 0.01 s of snapshot an epoch;
    the epochs in ``slow_boundary`` hold the profiler's start or stop (1 s
    inside the second decision).  Before it, the warm-up's run."""
    events = [span_event("train_run", 0.0, 2.0, id=1, parent=None, trace=1),
              span_event("train_epoch", 0.0, 1.9, id=2, parent=1, trace=1,
                         epoch=0)]
    at, n = 10.0, run_id
    for epoch in range(1, epochs + 1):
        second = 1.007 if epoch in slow_boundary else 0.007
        for name, dur in (("train_epoch", 0.96), ("epoch_decision", 0.003),
                          ("eval", 0.02), ("epoch_decision", second),
                          ("snapshot", 0.01)):
            n += 1
            events.append(span_event(name, at, dur, id=n, parent=run_id,
                                     trace=2, epoch=epoch))
            if name == "train_epoch":
                events.append(span_event("train_drain", at + 0.9, 0.06,
                                         id=n + 100, parent=n, trace=2,
                                         epoch=epoch))
            at += dur
    # a TEST pass after the last epoch belongs to no epoch's cycle
    events.append(span_event("eval", at, 0.5, id=999, parent=run_id,
                             trace=2, epoch=epochs + 1, klass="test"))
    events.append({"name": "status", "ph": "i", "ts": 0.0, "pid": 0,
                   "tid": 0})
    total = at + 0.5 - 10.0
    events.append(span_event("train_run", 10.0, total, id=run_id,
                             parent=None, trace=2, epochs=epochs))
    return events, total


def fill_ring(monkeypatch, events):
    """Put ``events`` where the readers look: the program's span ring."""
    from veles_tpu.runtime import metrics as program_metrics

    class Ring:
        def snapshot(self):
            return events
    monkeypatch.setattr(program_metrics, "span_ring", Ring)


READERS = {"eval_share": (eval_share, 2.0),
           "boundary_host_share": (boundary_host_share, 2.0)}


@pytest.mark.parametrize("reader", sorted(READERS))
@pytest.mark.parametrize("case", [
    "match", "profiler_in_two_epochs", "no_train_run", "parent_program",
    "window_mismatch", "two_epochs"])
def test_span_readers_on_a_hand_filled_ring(reader, case, monkeypatch):
    module, want = READERS[reader]
    events, total = ring(slow_boundary=(2, 4)
                         if case == "profiler_in_two_epochs" else ())
    if case == "no_train_run":
        events = [e for e in events if e["name"] != "train_run"]
    elif case == "parent_program":       # one train_epoch an epoch, no ids
        events = [{**span_event("train_epoch", float(i), 1.0, epoch=i),
                   "tid": 0} for i in range(5)]
    elif case == "two_epochs":
        events, total = ring(epochs=2)
    run = {"measured": {"window_s": total * (1.02 if case ==
                                             "window_mismatch" else 1.001)}}
    fill_ring(monkeypatch, events)
    got = module.read(run)
    if case in ("match", "profiler_in_two_epochs"):
        # 0.02 and 0.003 + 0.007 + 0.01 of a 1.0 s cycle: the typical epoch
        # is one the profiler neither started nor stopped in, though half
        # of the window's four epochs are
        assert got == pytest.approx(want, rel=1e-6)
    else:
        assert got is None


def test_epoch_cycles_sum_both_decisions_and_skip_the_test_pass(monkeypatch):
    events, total = ring(epochs=3)
    fill_ring(monkeypatch, events)
    cycles = train_run_spans.epoch_cycles({"measured": {"window_s": total}})
    assert len(cycles) == 3
    assert cycles[0] == pytest.approx({"train_epoch": 0.96, "eval": 0.02,
                                       "epoch_decision": 0.01,
                                       "snapshot": 0.01})


@pytest.mark.parametrize("name", ["tiny_image", "tiny_lm"])
def test_span_readers_read_a_driven_tiny_cell(
        program_state, name):  # noqa: F811
    """Through the driver and ``Trainer.run()``: the newest ``train_run``
    is the window the driver timed, and both shares come out."""
    from veles_tpu.runtime.metrics import span_ring
    cell, cfg = tiny(name)
    # a window long enough that 1 % of it is scheduling noise's size
    args = types.SimpleNamespace(seed=7, seconds=1.0, trace=0)
    out = program_state.run(cell, cfg, args, time.perf_counter())
    cycles = train_run_spans.epoch_cycles(out)
    # (the ring holds 512 spans: of a window of more epochs, the newest)
    assert cycles is not None
    assert 3 <= len(cycles) <= out["measured"]["epochs"]
    for module in (eval_share, boundary_host_share):
        assert 0.0 < module.read(out) < 100.0
    # the driver's own mark is an annotation, never a ring event
    assert all(e["name"] != "epoch_boundary" for e in span_ring().snapshot())
    # tiny cells are no cell of BENCHMARK.json
    assert attn_kernel_roofline.find_cell(out["measured"]) is None
