"""The benchmark's tests of the hybrid configuration (state-space mixers,
attention, experts that are not gated; one mixer a block): a tiny cell of
its own runs through ``train_counted`` end to end on the CPU and agrees
with its reference; the float8 control and each of the reference's three
planted faults come out not correct; the new counts are pinned; the new
reader on hand arithmetic and on runs that have nothing for it.

No chip, no child process, no topology call.
"""

import json
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

import compare  # noqa: E402
import config_io  # noqa: E402
import counts_hybrid  # noqa: E402
from metrics import (moe_kernel_roofline,  # noqa: E402
                     moe_ungated_kernel_roofline)
from test_perf_benchmark import program_state, tiny  # noqa: E402,F401

CONFIG = "nemotron-3-nano-30b-a3b"
CELL = "nemotron3_nano_train_t4096"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def driver(program_state):
    from drivers import train_counted
    return train_counted


def test_driver_runs_the_tiny_hybrid_cell_and_agrees_with_the_reference(
        driver, capsys):
    import run
    cell, cfg = tiny("tiny_hybrid")
    args = types.SimpleNamespace(seed=3000000019, seconds=0.2, trace=0)
    out = driver.run(cell, cfg, args, time.perf_counter())
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    gaps = {k: v["value"] for k, v in out["compared"].items()}
    assert max(gaps.values()) < 1e-4, gaps
    assert gaps["routed_rows_gap"] == 0.0
    m = out["measured"]
    assert m["routed_layers"] == [
        {"d_model": 32, "d_hidden": 16, "experts_held": 4,
         "products_forward": 2}]
    rows = m["routed_rows"]
    assert rows["train"]["computed"] >= rows["train"]["routed"] > 0
    bench = {
        "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": f"{stem}.tokens", "unit": "%",
                       "moves": "train_tokens_per_s"}
                      for stem in ("step_mfu", "data_wait_share",
                                   "device_idle_share", "eval_share",
                                   "moe_padded_rows_share",
                                   "moe_ungated_kernel_roofline")]}
    entry = {"name": cell["name"], "chips": 1}
    devices = [types.SimpleNamespace(platform="cpu", device_kind="cpu")]
    out.update(peaks=PEAKS, chips=1)
    assert run.report(bench, entry, out, devices, 1) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # the readers of a trace find none and give nothing, never 0
    assert set(line["metrics"]) == {
        "step_mfu.tokens", "data_wait_share.tokens", "eval_share.tokens",
        "moe_padded_rows_share.tokens"}
    assert line["correct"] is True


@pytest.mark.parametrize("control", [
    "control_float8", "fault_ssm_carry", "fault_conv",
    "fault_routed_left_out"])
def test_control_and_each_planted_fault_come_out_not_correct(driver,
                                                              control):
    import calibrate_counted
    import calibrate_hybrid
    cell, cfg = tiny("tiny_hybrid")
    driver.configure_program()
    saved = dict(calibrate_counted.CONTROLS)
    calibrate_counted.CONTROLS.update(calibrate_hybrid.CONTROLS)
    try:
        out = calibrate_counted.one_seed(cell, cfg, 11, controls=(control,))
    finally:
        calibrate_counted.CONTROLS.clear()
        calibrate_counted.CONTROLS.update(saved)
    limits = cell["check"]["limits"]
    assert compare.verdict(out["program"], limits)[1] is True
    assert compare.verdict(out[control], limits)[1] is False
    # by the norms, not by a number that is no number
    assert out[control]["grad_norm_gap"] > 100 * limits["grad_norm_gap"]


def test_counts_of_the_hybrid_configuration_are_pinned():
    cfg = config_io.load_config(CONFIG)
    traffic = config_io.load_cell(CELL)["traffic"]
    c = counts_hybrid.model_counts(cfg, traffic)
    assert c["params"] == 666962944
    assert c["forward_flops_per_item"] == pytest.approx(678.8e6, rel=0.001)
    assert c["train_flops_per_item"] == pytest.approx(2.036e9, rel=0.001)
    # the cut is a cut of the published model: 31.6B
    assert counts_hybrid.whole_model_params(cfg) == 31577937344
    by_kind = {}
    for _, kind, params, flops, _ in counts_hybrid.walk(cfg, traffic):
        p, f = by_kind.get(kind, (0, 0))
        by_kind[kind] = (p + params, f + flops / traffic["seq_len"])
    assert by_kind["mamba2"][0] == 4 * 38742208
    # a mixer: its two products 77.4 MFLOP a token, the recurrence 2.1
    assert by_kind["mamba2"][1] == pytest.approx(
        4 * (2 * (2688 * 10304 + 4096 * 2688) + 4 * 64 * 64 * 128))
    assert by_kind["attention"] == (
        23396352, pytest.approx(2 * 23396352 + 2 * 2 * 2048.5 * 4096))
    assert by_kind["routed_experts"][0] == 4 * (
        2688 * 128 + 2 * 2688 * (8 * 1856 + 3712))
    # router 0.7, shared 39.9, 6 x 8 / 128 of a held expert a token 7.5
    assert by_kind["routed_experts"][1] == pytest.approx(192.3e6, rel=0.001)
    assert by_kind["all2all"] == (16384 * 2688, 2 * 16384 * 2688)
    share = by_kind["mamba2"][1] / c["forward_flops_per_item"]
    assert share == pytest.approx(0.47, abs=0.005)    # most of the work
    assert counts_hybrid.routed_layers(cfg) == 4 * [
        {"d_model": 2688, "d_hidden": 1856, "experts_held": 8,
         "products_forward": 2}]
    with pytest.raises(ValueError):
        counts_hybrid.model_counts(config_io.load_config("trinity-mini"),
                                   traffic)


def test_configuration_keeps_every_published_width_and_states_its_cut():
    cfg = config_io.load_config(CONFIG)
    row = next(json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in l) \
        if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is not None:
        differing = sorted(k for k, v in row["config"].items()
                           if cfg.get(k) != v)
        assert differing == sorted(cfg["reduced"])
        assert cfg["source"] == row["source_url"]
        assert cfg["published"]["vocab_size"] == row["config"]["vocab_size"]
    assert (cfg["hidden_size"], cfg["mamba_num_heads"],
            cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"],
            cfg["conv_kernel"], cfg["chunk_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"], cfg["router_width"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["layer_norm_epsilon"]) == (
                2688, 64, 64, 8, 128, 4, 128, 32, 2, 128, 1856, 3712, 128,
                6, 2.5, 1e-5)
    assert sorted(cfg["reduced"]) == [
        "hybrid_override_pattern", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    assert cfg["hybrid_override_pattern"] == "MEMEM*EME" == \
        cfg["published"]["hybrid_override_pattern"][:9]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 16 == \
        cfg["published"]["n_routed_experts"]
    kinds = [l["type"] for l in config_io.expand_layers(cfg)
             if l["name"].endswith("_mix")]
    assert kinds == [{"M": "mamba2", "E": "routed_experts",
                      "*": "attention"}[c] for c in "MEMEM*EME"]
    cell = config_io.load_cell(CELL)
    assert cell["traffic"] == {"batch": 1, "seq_len": 4096, "n_train": 16,
                               "n_valid": 1}
    assert cell["driver"] == "train_counted" and cell["chips"] == 1
    bench = config_io.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"].split(".")[0] in ("moe_kernel_roofline",
                                       "attn_kernel_roofline"):
            assert CELL not in m["workloads"]


GMM = ('%grouped_matmul.3 = bf16[5632,1856]{1,0:T(8,128)(2,1)} custom-call('
       's32[44]{0} %fusion.9, bf16[5632,2688]{1,0} %gather.4), '
       'custom_call_target="tpu_custom_call"')
CONSUMER = ('%fusion.88 = f32[5632,1856]{1,0} fusion(bf16[5632,1856]{1,0} '
            '%grouped_matmul.3), kind=kLoop, calls=%fused_computation.40')


def hybrid_run(seconds_by_op, rows=None, epochs_traced=2, epochs=4):
    cfg = config_io.load_config(CONFIG)
    traffic = config_io.load_cell(CELL)["traffic"]
    c = counts_hybrid.model_counts(cfg, traffic)
    return {"measured": {"epochs": epochs, "routed_rows": rows,
                         "routed_layers": counts_hybrid.routed_layers(cfg),
                         "batches_per_epoch": {"train": 16, "validation": 1},
                         "items_per_epoch": 16 * 4096,
                         "train_flops_per_item": c["train_flops_per_item"]},
            "peaks": PEAKS, "chips": 1,
            "trace": {"epochs_in_window": epochs_traced,
                      "seconds_by_op": seconds_by_op}}


def test_ungated_reader_counts_two_products_where_the_gated_counts_three():
    # window of 4 epochs, 4 layers: 192 rows an expert, 7 of 8 active
    rows = {"train": {"routed": 4 * 4 * 16 * 1536, "computed": 0,
                      "experts_active": 4 * 4 * 16 * 7},
            "validation": {"routed": 4 * 4 * 1536, "computed": 0,
                           "experts_active": 4 * 4 * 7}}
    run = hybrid_run({GMM: 0.4, CONSUMER: 9.0}, rows)
    matrix = 2688 * 1856 * 2
    # 2 traced epochs, a layer: the matrices read forward and for the rows'
    # gradient by the experts with rows, written as gradient for all 8;
    # rows in and out of each of 6 (training) or 2 (validation) products
    moved = (matrix * 2 * (2 * 2 * 16 * 7 + 8 * 2 * 16 + 2 * 7)
             + 2 * (2688 + 1856) * 2 * (3 * 2 * 16 * 1536 + 2 * 1536)
             ) / 819e9
    work = 2 * 2 * 2688 * 1856 * (3 * 2 * 16 * 1536 + 2 * 1536) / 197e12
    assert moved > work                   # 192 rows an expert: memory-bound
    layer = run["measured"]["routed_layers"][0]
    assert counts_hybrid.grouped_products_roof_seconds(
        layer, 2 * 16 * 1536, 2 * 1536, 2 * 16 * 7, 2 * 7, 32, PEAKS) == (
            pytest.approx(moved), "memory")
    got = moe_ungated_kernel_roofline.read(run)
    assert got == pytest.approx(100.0 * 4 * moved / 0.4)
    # the accepted reader would set the roof half too high
    assert moe_kernel_roofline.read(run) == pytest.approx(1.5 * got)


def test_new_reader_gives_nothing_where_there_is_nothing_to_read():
    rows = {"train": {"routed": 900, "computed": 1000, "experts_active": 8},
            "validation": {"routed": 90, "computed": 100,
                           "experts_active": 8}}
    run = hybrid_run({GMM: 0.1}, rows)
    read = moe_ungated_kernel_roofline.read
    assert read(run) > 0
    assert read(hybrid_run({CONSUMER: 0.1}, rows)) is None
    assert read(dict(run, trace=None)) is None
    assert read(dict(run, trace={"epochs_in_window": 0,
                                 "seconds_by_op": {}})) is None
    # no counters, or a driver that states no products a row
    assert read(hybrid_run({GMM: 0.1}, None)) is None
    gated = hybrid_run({GMM: 0.1}, rows)
    for layer in gated["measured"]["routed_layers"]:
        layer.pop("products_forward")
    assert read(gated) is None
