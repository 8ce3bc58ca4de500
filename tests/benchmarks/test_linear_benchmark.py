"""The benchmark's tests of the linear-attention configuration (gated
delta-rule mixers three layers in four beside full attention with a norm
over the whole q and k projections, post-norm blocks): a tiny cell of its
own runs through ``train_counted`` end to end on the CPU and agrees with
its reference; the float8 control and each of the reference's three
planted faults come out not correct; the new counts are pinned; the
configuration keeps every published width and states its cut.

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
import counts_linear  # noqa: E402
from test_perf_benchmark import program_state, tiny  # noqa: E402,F401

CONFIG = "olmo-hybrid-7b"
CELL = "olmo_hybrid_train_t4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
#: the per-layer metrics the cell is listed under
LISTED = ("device_idle_share", "step_mfu", "data_wait_share", "eval_share",
          "boundary_host_share")


@pytest.fixture
def driver(program_state):
    from drivers import train_counted
    return train_counted


def test_driver_runs_the_tiny_linear_cell_and_agrees_with_the_reference(
        driver, capsys):
    import run
    cell, cfg = tiny("tiny_linear")
    args = types.SimpleNamespace(seed=3000000019, seconds=0.2, trace=0)
    out = driver.run(cell, cfg, args, time.perf_counter())
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    gaps = {k: v["value"] for k, v in out["compared"].items()}
    assert max(gaps.values()) < 1e-4, gaps
    # nothing routes: no layer, no rows, a gap of nothing
    assert gaps["routed_rows_gap"] == 0.0
    m = out["measured"]
    # (counters another cell's units registered in this process read zero)
    assert m["routed_layers"] == [] and not any(
        n for kinds in m["routed_rows"].values() for n in kinds.values())
    assert m["train_flops_per_item"] == counts_linear.model_counts(
        cfg, cell["traffic"])["train_flops_per_item"]
    bench = {
        "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": f"{stem}.tokens", "unit": "%",
                       "moves": "train_tokens_per_s"} for stem in LISTED]}
    entry = {"name": cell["name"], "chips": 1}
    devices = [types.SimpleNamespace(platform="cpu", device_kind="cpu")]
    out.update(peaks=PEAKS, chips=1)
    assert run.report(bench, entry, out, devices, 1) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # the readers of a trace find none and give nothing, never 0
    assert set(line["metrics"]) == {
        "step_mfu.tokens", "data_wait_share.tokens", "eval_share.tokens",
        "boundary_host_share.tokens"}
    assert line["correct"] is True


@pytest.mark.parametrize("control", [
    "control_float8", "fault_delta_carry", "fault_delta_term", "fault_conv"])
def test_control_and_each_planted_fault_come_out_not_correct(driver,
                                                              control):
    import calibrate_counted
    import calibrate_linear
    cell, cfg = tiny("tiny_linear")
    driver.configure_program()
    saved = dict(calibrate_counted.CONTROLS)
    calibrate_counted.CONTROLS.update(calibrate_linear.CONTROLS)
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


def test_counts_of_the_linear_configuration_are_pinned():
    cfg = config_io.load_config(CONFIG)
    traffic = config_io.load_cell(CELL)["traffic"]
    c = counts_linear.model_counts(cfg, traffic)
    assert c["params"] == 766241946
    assert c["forward_flops_per_item"] == 1454979840
    assert c["train_flops_per_item"] == pytest.approx(4.365e9, rel=0.001)
    # the cut is a cut of the published model: 7.43 B
    assert counts_linear.whole_model_params(cfg) == 7430870688
    by_kind = {}
    for _, kind, params, flops, _ in counts_linear.walk(cfg, traffic):
        p, f = by_kind.get(kind, (0, 0))
        by_kind[kind] = (p + params, f + flops / traffic["seq_len"])
    # a mixer of 15 heads: seven matrices, the taps, A_log, dt_bias, the
    # gated norm's scale; its products 88.7 MFLOP a token, the recurrence
    # 4 x 15 x 96 x 192 = 1.1
    matrices = 3840 * (2 * 1440 + 2 * 2880 + 2 * 15) + 2880 * 3840
    assert by_kind["gated_delta_net"] == (
        3 * (matrices + 4 * 5760 + 30 + 192),
        pytest.approx(3 * (2 * matrices + 4 * 15 * 96 * 192)))
    assert by_kind["attention"] == (
        4 * 3840 * 1920 + 2 * 1920,
        pytest.approx(2 * 4 * 3840 * 1920 + 2 * 2 * 2048.5 * 1920))
    assert by_kind["gated_mlp"] == (4 * 3 * 3840 * 11008,
                                    4 * 2 * 3 * 3840 * 11008)
    assert by_kind["all2all"] == (12544 * 3840, 2 * 12544 * 3840)
    assert by_kind["rms_norm"][0] == 9 * 3840
    fwd = c["forward_flops_per_item"]
    # three layers of four, 18.5 % of the work with the heads halved; the
    # whole MLPs 70 %
    assert by_kind["gated_delta_net"][1] / fwd == pytest.approx(0.185,
                                                                abs=0.001)
    assert by_kind["gated_mlp"][1] / fwd == pytest.approx(0.697, abs=0.001)
    assert counts_linear.routed_layers(cfg) == []
    for other in ("trinity-mini", "nemotron-3-nano-30b-a3b"):
        with pytest.raises(ValueError):
            counts_linear.model_counts(config_io.load_config(other), traffic)


def test_configuration_keeps_every_published_width_and_states_its_cut():
    cfg = config_io.load_config(CONFIG)
    row = next(json.loads(l) for l in open(CATALOG)
               if '"Olmo-Hybrid-7B"' in l) \
        if os.path.exists(CATALOG) else None
    if row is not None:
        differing = sorted(k for k, v in row["config"].items()
                           if cfg.get(k) != v)
        assert differing == sorted(cfg["reduced"])
        assert cfg["source"] == row["source_url"]
        for key in cfg["reduced"]:
            assert cfg["published"][key] == row["config"][key]
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"], cfg["linear_allow_neg_eigval"],
            cfg["head_dim"], cfg["rms_norm_eps"], cfg["hidden_act"],
            cfg["chunk_size"]) == (3840, 11008, 96, 192, 4, True, 128, 1e-6,
                                   "silu", 64)
    assert sorted(cfg["reduced"]) == [
        "layer_types", "linear_num_key_heads", "linear_num_value_heads",
        "num_attention_heads", "num_hidden_layers", "num_key_value_heads",
        "vocab_size"]
    # no width among them
    assert not [k for k in cfg["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and
                k != "vocab_size"]
    published = cfg["published"]
    assert cfg["layer_types"] == published["layer_types"][:4] == \
        3 * ["linear_attention"] + ["full_attention"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 4
    deployment = cfg["deployment"]
    assert deployment["chips_sharing_a_layer"] == 8
    for key in ("linear_num_key_heads", "linear_num_value_heads",
                "num_attention_heads", "num_key_value_heads"):
        assert cfg[key] * 2 == published[key] == 30
    assert cfg["vocab_size"] * 8 == published["vocab_size"] == 100352
    assert "766,241,946" in deployment["size"]
    for said in ("heads", "vocabulary", "whole_on_every_chip", "depth",
                 "exchange", "qk_norm"):
        assert deployment[said]
    for said in ("block", "qk_norm", "positions", "projections", "chunk",
                 "dt_origin", "A_log_and_dt_bias", "residual_stream",
                 "optimizer", "data", "sequence", "remat", "use_flash"):
        assert cfg["assumed"][said]
    layers = config_io.expand_layers(cfg)
    kinds = [l["type"] for l in layers if l["name"].endswith("_mix")]
    assert kinds == 3 * ["gated_delta_net"] + ["attention"]
    mixer = next(l for l in layers if l["type"] == "gated_delta_net")
    assert (mixer["n_heads"], mixer["key_dim"], mixer["value_dim"],
            mixer["conv_kernel"], mixer["chunk"],
            mixer["allow_neg_eigval"]) == (15, 96, 192, 4, 64, True)
    # softplus(dt_origin) = 0.01 a token: exp(-0.64) of the state is left
    # at a chunk's end
    import math
    assert math.log1p(math.exp(mixer["dt_origin"])) == pytest.approx(0.01)
    full = next(l for l in layers if l["type"] == "attention")
    assert (full["n_heads"], full["n_kv_heads"], full["head_dim"],
            full["qk_norm"]) == (15, 15, 128, "projection")
    assert not full.get("rope") and full["use_flash"] is None
    # the block: the norm after the sublayer, then the residual's sum
    by_name = {l["name"]: l for l in layers}
    assert by_name["b0_mix"]["inputs"] == ["emb"]
    assert by_name["b0_a"]["inputs"] == ["b0_mix_norm", "emb"]
    assert by_name["b0"]["inputs"] == ["b0_mlp_norm", "b0_a"]
    assert by_name["b1_mix"]["inputs"] == ["b0"]
    cell = config_io.load_cell(CELL)
    assert cell["traffic"] == {"batch": 1, "seq_len": 4096, "n_train": 16,
                               "n_valid": 1}
    assert cell["driver"] == "train_counted" and cell["chips"] == 1
    assert cell["check"]["limits"]["routed_rows_gap"] == 0.0
    bench = config_io.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {"train_tokens_per_s"} | {f"{stem}.tokens"
                                               for stem in LISTED}
