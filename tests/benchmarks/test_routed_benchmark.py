"""The benchmark's tests of the routed-experts configuration: the new
driver runs a tiny cell of its own end to end on the CPU and agrees with
its reference; its check fails the float8 control and the planted fault
(routed experts left out); the new counts are pinned; both new readers on
hand arithmetic; the attention reader still finds its cell.

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

import compare  # noqa: E402
import config_io  # noqa: E402
import counts_routed  # noqa: E402
from metrics import (attn_kernel_roofline, moe_kernel_roofline,  # noqa: E402
                     moe_padded_rows_share)
from test_perf_benchmark import program_state, tiny  # noqa: E402,F401

CELL = "trinity_mini_train_t4096"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def driver(program_state):
    from drivers import train_counted
    return train_counted


def drive(driver, seed=3000000019):
    cell, cfg = tiny("tiny_routed")
    args = types.SimpleNamespace(seed=seed, seconds=0.2, trace=0)
    return cell, cfg, driver.run(cell, cfg, args, time.perf_counter())


def test_new_driver_runs_its_tiny_cell_and_agrees_with_the_reference(
        driver, capsys):
    import json
    import run
    cell, cfg, out = drive(driver)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    gaps = {k: v["value"] for k, v in out["compared"].items()}
    assert max(gaps.values()) < 1e-4, gaps
    assert gaps["routed_rows_gap"] == 0.0
    m = out["measured"]
    # the keys the accepted readers use, and the routed readers'
    assert {"window_s", "items_per_s", "items_per_epoch",
            "train_flops_per_item", "routed_rows", "routed_layers",
            "batches_per_epoch"} <= set(m)
    rows = m["routed_rows"]
    assert rows["train"]["computed"] >= rows["train"]["routed"] > 0
    assert rows["validation"]["routed"] > 0
    bench = {
        "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": f"{stem}.tokens", "unit": "%",
                       "moves": "train_tokens_per_s"}
                      for stem in ("step_mfu", "data_wait_share",
                                   "device_idle_share", "eval_share",
                                   "moe_kernel_roofline",
                                   "moe_padded_rows_share")]}
    entry = {"name": cell["name"], "chips": 1}
    devices = [types.SimpleNamespace(platform="cpu", device_kind="cpu")]
    out.update(peaks=PEAKS, chips=1)
    for traced, want in ((0, {"train_tokens_per_s", "setup_s"}),
                         (1, {"step_mfu.tokens", "data_wait_share.tokens",
                              "eval_share.tokens",
                              "moe_padded_rows_share.tokens"})):
        assert run.report(bench, entry, out, devices, traced) == 0
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        # a reader that finds no trace returns nothing, never 0
        assert set(line["metrics"]) == want
        assert all(v["value"] > 0 for v in line["metrics"].values())
        assert line["correct"] is True


def test_float8_control_and_routed_experts_left_out_come_out_not_correct(
        driver):
    import calibrate_counted
    cell, cfg = tiny("tiny_routed")
    driver.configure_program()
    out = calibrate_counted.one_seed(cell, cfg, 11, controls=(
        "control_float8", "fault_routed_left_out", "window_ignored"))
    limits = cell["check"]["limits"]
    assert compare.verdict(out["program"], limits)[1] is True
    assert compare.verdict(out["control_float8"], limits)[1] is False
    assert compare.verdict(out["fault_routed_left_out"], limits)[1] is False
    # read and reported, not judged; at this size the norms do see it
    assert out["window_ignored"]["grad_norm_gap"] > 10 * \
        out["program"]["grad_norm_gap"]


def test_an_unchanged_state_comes_out_not_correct(driver, monkeypatch):
    from test_perf_benchmark import _break_train_step
    _break_train_step(monkeypatch, "state_unchanged")
    _, _, out = drive(driver)
    assert out["correct"] is False, out["compared"]


def test_counts_of_the_routed_configuration_are_pinned():
    cfg = config_io.load_config("trinity-mini")
    traffic = config_io.load_cell(CELL)["traffic"]
    c = counts_routed.model_counts(cfg, traffic)
    assert c["params"] == pytest.approx(705.4e6, rel=0.001)
    assert c["forward_flops_per_item"] == pytest.approx(687.5e6, rel=0.01)
    assert c["train_flops_per_item"] == pytest.approx(2.06e9, rel=0.01)
    by_kind = {}
    for _, kind, params, flops, _ in counts_routed.walk(cfg, traffic):
        p, f = by_kind.get(kind, (0, 0))
        by_kind[kind] = (p + params, f + flops / traffic["seq_len"])
    assert by_kind["gated_mlp"] == (37748736, pytest.approx(75.5e6, rel=0.01))
    assert by_kind["routed_experts"][0] == 4 * (
        2048 * 128 + 17 * 3 * 2048 * 1024)
    # routers 2.1, shared 50.3, one held expert a token on average 50.3
    assert by_kind["routed_experts"][1] == pytest.approx(102.8e6, rel=0.01)
    # 5 x projections 54.5; cores: full 33.6, four sliding 25.2 each
    assert by_kind["attention"][1] == pytest.approx(272.6e6 + 134.2e6,
                                                    rel=0.01)
    assert counts_routed.visible_pairs(4096) == 4096 * 4097 // 2
    assert counts_routed.visible_pairs(8, 3) == 1 + 2 + 3 * 6
    assert counts_routed.routed_layers(cfg) == 4 * [
        {"d_model": 2048, "d_hidden": 1024, "experts_held": 16}]
    # the published widths, untouched
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["router_width"],
            cfg["sliding_window"]) == (2048, 32, 4, 128, 6144, 1024, 8, 128,
                                       2048)
    assert sorted(cfg["reduced"]) == ["num_dense_layers", "num_experts",
                                      "num_hidden_layers", "vocab_size"]


GMM = ('%grouped_matmul.3 = bf16[34816,1024]{1,0:T(8,128)(2,1)} custom-call('
       's32[272]{0} %fusion.9, bf16[34816,2048]{1,0} %gather.4), '
       'custom_call_target="tpu_custom_call"')
GMM_T = GMM.replace("%grouped_matmul.3", "%grouped_matmul_t.5")
GMM_DW = GMM.replace("%grouped_matmul.3", "%grouped_matmul_dw.7")
CONSUMER = ('%fusion.88 = f32[34816,1024]{1,0} fusion(bf16[34816,1024]{1,0} '
            '%grouped_matmul.3), kind=kLoop, calls=%fused_computation.40')


def routed_run(seconds_by_op, rows, epochs_traced=2, epochs=4):
    layer = {"d_model": 2048, "d_hidden": 1024, "experts_held": 16}
    return {"measured": {"epochs": epochs, "routed_rows": rows,
                         "routed_layers": [layer, layer],
                         "batches_per_epoch": {"train": 16,
                                               "validation": 1}},
            "peaks": PEAKS, "chips": 1,
            "trace": {"epochs_in_window": epochs_traced,
                      "seconds_by_op": seconds_by_op}}


def test_moe_kernel_roofline_on_hand_arithmetic():
    # window of 4 epochs, 2 layers: 256 rows an expert, 12 of 16 active
    rows = {"train": {"routed": 4 * 2 * 16 * 4096, "computed": 0,
                      "experts_active": 4 * 2 * 16 * 12},
            "validation": {"routed": 4 * 2 * 4096, "computed": 0,
                           "experts_active": 4 * 2 * 12}}
    run = routed_run({GMM: 0.1, GMM_T: 0.1, GMM_DW: 0.2, CONSUMER: 9.0},
                     rows)
    assert moe_kernel_roofline.kernel_seconds(
        run["trace"]["seconds_by_op"]) == pytest.approx(0.4)
    # 2 traced epochs, a layer: 2 x 16 x 4096 training rows, 2 x 4096
    # validation rows; a row's forward is 3 x 2 x 2048 x 1024 FLOP
    fwd = 3 * 2 * 2048 * 1024
    work = fwd * (3 * 2 * 16 * 4096 + 2 * 4096) / 197e12
    matrix = 2048 * 1024 * 2
    # read forward and for the rows' gradient by the experts with rows,
    # written as the matrices' gradient for all 16; validation reads once
    moved = (matrix * 3 * (2 * 2 * 16 * 12 + 16 * 2 * 16 + 2 * 12)
             + 2 * 3072 * (9 * 2 * 16 * 4096 + 3 * 2 * 4096)) / 819e9
    assert moved > work                   # 256 rows an expert: memory-bound
    layer = run["measured"]["routed_layers"][0]
    roof, bound = counts_routed.grouped_products_roof_seconds(
        layer, 2 * 16 * 4096, 2 * 4096, 2 * 16 * 12, 2 * 12, 32, PEAKS)
    assert (roof, bound) == (pytest.approx(moved), "memory")
    assert moe_kernel_roofline.read(run) == pytest.approx(
        100.0 * 2 * moved / 0.4)
    # ten times the rows an expert: compute-bound
    assert counts_routed.grouped_products_roof_seconds(
        layer, 20 * 16 * 4096, 0, 32 * 16, 0, 32, PEAKS)[1] == "compute"


def test_routed_readers_give_nothing_without_a_trace_or_a_counter():
    rows = {"train": {"routed": 900, "computed": 1000},
            "validation": {"routed": 90, "computed": 100}}
    run = routed_run({GMM: 0.1}, rows)
    assert moe_padded_rows_share.read(run) == pytest.approx(10.0)
    assert moe_kernel_roofline.read(run) > 0
    assert moe_kernel_roofline.read(routed_run({CONSUMER: 1.0}, rows)) is None
    no_trace = dict(run, trace=None)
    assert moe_kernel_roofline.read(no_trace) is None
    assert moe_padded_rows_share.read(no_trace) == pytest.approx(10.0)
    # a program without the counter: the driver's reading is empty
    for empty in ({}, None):
        bare = routed_run({GMM: 0.1}, empty)
        assert moe_kernel_roofline.read(bare) is None
        assert moe_padded_rows_share.read(bare) is None
    bare["measured"].pop("routed_rows")
    assert moe_kernel_roofline.read(bare) is None
    assert moe_padded_rows_share.read(bare) is None


def test_attention_reader_still_finds_its_cell_and_only_its_cell():
    """The new cells are not listed under ``attn_kernel_roofline``: its
    ``find_cell`` walks every listed cell with ``counts.py``, which does
    not know the routed configuration's layers."""
    import counts
    bench = config_io.load_benchmark()
    listed = [m["workloads"] for m in bench["per_layer"]
              if m["name"].startswith("attn_kernel_roofline")]
    assert listed == [["opt350m_train_t2048"]]
    cell = config_io.load_cell("opt350m_train_t2048")
    cfg = config_io.load_config(cell["config"])
    c = counts.model_counts(cfg, cell["traffic"])
    measured = {"items_per_epoch": 64 * 2048,
                "train_flops_per_item": c["train_flops_per_item"]}
    found = attn_kernel_roofline.find_cell(measured)
    assert found is not None and found[0]["name"] == "opt350m_train_t2048"
    with pytest.raises(ValueError):
        counts.model_counts(config_io.load_config("trinity-mini"),
                            config_io.load_cell(CELL)["traffic"])
    # same tokens a step and steps an epoch as the t2048 cell
    short = config_io.load_cell("opt350m_train_t512")["traffic"]
    assert short["batch"] * short["seq_len"] == 4 * 2048
    assert short["n_train"] // short["batch"] == 16
