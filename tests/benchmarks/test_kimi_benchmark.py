"""The benchmark's tests of the Kimi Linear configuration (Kimi delta
attention three layers in four beside latent attention without
positions, top-8 of 256 routed experts beside a shared one, pre-norm
blocks): a tiny cell of its own runs through ``train_counted`` end to
end on the CPU and agrees with its reference; the float8 control and
each of the reference's planted faults come out not correct; the counts
are pinned; the configuration keeps every published width and states its
cut; the cell is listed where its metrics are read.

No chip, no child process, no topology call.
"""

import json
import math
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
import counts_kimi  # noqa: E402
from test_perf_benchmark import program_state, tiny  # noqa: E402,F401

CONFIG = "kimi-linear-48b-a3b"
CELL = "kimi_linear_train_t4096"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
#: the per-layer metrics the cell is listed under, at least
LISTED = ("device_idle_share", "step_mfu", "data_wait_share",
          "moe_device_ms", "moe_padded_rows_share", "moe_kernel_roofline",
          "attn_device_ms", "head_device_ms", "optimizer_device_ms",
          "scoped_device_share", "kda_device_ms")
#: the span shares the cell is not listed under: an epoch of the cell
#: takes over 5 s on the chip, so the window of a run holds two epochs,
#: and train_run_spans.py reads three at least
UNREAD = ("eval_share", "boundary_host_share")
#: the reference's own planted faults, beside calibrate_counted.py's
FAULTS = {"fault_delta_carry": {"leave_out": ("delta_carry",)},
          "fault_channel_decay": {"leave_out": ("channel_decay",)}}


@pytest.fixture
def driver(program_state):
    from drivers import train_counted
    return train_counted


def test_driver_runs_the_tiny_kimi_cell_and_agrees_with_the_reference(
        driver, capsys):
    import run
    cell, cfg = tiny("tiny_kimi")
    args = types.SimpleNamespace(seed=3000000019, seconds=0.2, trace=0)
    out = driver.run(cell, cfg, args, time.perf_counter())
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    gaps = {k: v["value"] for k, v in out["compared"].items()}
    assert max(gaps.values()) < 1e-4, gaps
    m = out["measured"]
    assert m["routed_layers"] == [
        {"d_model": 32, "d_hidden": 16, "experts_held": 4}] * 4
    for kind in ("train", "validation"):
        rows = m["routed_rows"][kind]
        assert rows["routed"] > 0 and rows["experts_active"] > 0
    assert m["train_flops_per_item"] == counts_kimi.model_counts(
        cfg, cell["traffic"])["train_flops_per_item"]
    bench = {
        "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": f"{stem}.tokens", "unit": "%",
                       "moves": "train_tokens_per_s"}
                      for stem in LISTED + UNREAD]}
    entry = {"name": cell["name"], "chips": 1}
    devices = [types.SimpleNamespace(platform="cpu", device_kind="cpu")]
    out.update(peaks=PEAKS, chips=1)
    assert run.report(bench, entry, out, devices, 1) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # the readers of a trace find none and give nothing, never 0; the
    # spans' readers need three epochs in the 0.2 s window, which a
    # loaded CPU can miss with this model's scans
    assert {"step_mfu.tokens", "data_wait_share.tokens",
            "moe_padded_rows_share.tokens"} <= set(line["metrics"]) <= {
        "step_mfu.tokens", "data_wait_share.tokens", "eval_share.tokens",
        "boundary_host_share.tokens", "moe_padded_rows_share.tokens"}
    assert line["correct"] is True


@pytest.mark.parametrize("control", [
    "control_float8", "fault_routed_left_out", "fault_delta_carry",
    "fault_channel_decay"])
def test_control_and_each_planted_fault_come_out_not_correct(driver,
                                                              control):
    import calibrate_counted
    cell, cfg = tiny("tiny_kimi")
    driver.configure_program()
    saved = dict(calibrate_counted.CONTROLS)
    calibrate_counted.CONTROLS.update(FAULTS)
    try:
        out = calibrate_counted.one_seed(cell, cfg, 11, controls=(control,))
    finally:
        calibrate_counted.CONTROLS.clear()
        calibrate_counted.CONTROLS.update(saved)
    limits = cell["check"]["limits"]
    assert compare.verdict(out["program"], limits)[1] is True
    assert compare.verdict(out[control], limits)[1] is False
    # by the norms, not by a number that is no number
    assert out[control]["grad_norm_gap"] > 10 * limits["grad_norm_gap"]


def test_counts_of_the_kimi_configuration_are_pinned():
    cfg = config_io.load_config(CONFIG)
    traffic = config_io.load_cell(CELL)["traffic"]
    c = counts_kimi.model_counts(cfg, traffic)
    # the share: 9.64 GB at 16 B a parameter
    assert c["params"] == 602433408
    assert c["forward_flops_per_item"] == 721528832
    assert c["train_flops_per_item"] == pytest.approx(2.1646e9, rel=1e-4)
    # the cut is a cut of the published model: 49.1 B for the published
    # 48 B
    assert counts_kimi.whole_model_params(cfg) == 49122675072
    by_kind = {}
    for _, kind, params, flops, _ in counts_kimi.walk(cfg, traffic):
        p, f = by_kind.get(kind, (0, 0))
        by_kind[kind] = (p + params, f + flops / traffic["seq_len"])
    # a KDA mixer: q, k, v, two low-rank pairs of rank 128, Wb, Wo; the
    # taps, A_log, dt_bias, o_norm; the recurrence 4 x 32 x 128 x 128 a
    # token
    matrices = 3 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) \
        + 2304 * 32 + 4096 * 2304
    assert by_kind["kimi_delta_attention"] == (
        4 * 39514272, pytest.approx(4 * (2 * matrices + 4 * 32 * 128 * 128)))
    assert matrices + 3 * 4 * 4096 + 32 + 4096 + 128 == 39514272
    # latent attention: q 2304 x 32 x 192, down 2304 x 576, the latent's
    # norm, up 512 x 32 x (128 + 128), out 4096 x 2304; the core over the
    # visible pairs at 192 + 128
    assert by_kind["attention"] == (29114880, pytest.approx(
        2 * 29114368 + 2 * 2048.5 * 32 * (192 + 128)))
    assert by_kind["gated_mlp"] == (63700992, 2 * 63700992)
    one = 3 * 2304 * 1024
    assert by_kind["routed_experts"] == (
        4 * (2304 * 256 + 9 * one),
        pytest.approx(4 * 2 * (2304 * 256 + 8 * 8 / 256 * one + one)))
    assert by_kind["all2all"] == (20480 * 2304, 2 * 20480 * 2304)
    assert by_kind["rms_norm"][0] == 11 * 2304
    fwd = c["forward_flops_per_item"]
    # the four KDA mixers are the largest part of the work, the experts
    # the smallest
    assert by_kind["kimi_delta_attention"][1] / fwd == pytest.approx(
        0.449, abs=0.001)
    assert by_kind["routed_experts"][1] / fwd == pytest.approx(0.105,
                                                               abs=0.001)
    assert counts_kimi.routed_layers(cfg) == [
        {"d_model": 2304, "d_hidden": 1024, "experts_held": 8}] * 4
    for other in ("trinity-mini", "olmo-hybrid-7b"):
        with pytest.raises(ValueError):
            counts_kimi.model_counts(config_io.load_config(other), traffic)


def test_configuration_keeps_every_published_width_and_states_its_cut():
    cfg = config_io.load_config(CONFIG)
    assert cfg["source"] == ("https://huggingface.co/moonshotai/"
                             "Kimi-Linear-48B-A3B-Instruct/blob/main/"
                             "config.json")
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["num_experts_per_token"], cfg["num_shared_experts"],
            cfg["routed_scaling_factor"], cfg["rms_norm_eps"],
            cfg["mla_use_nope"], cfg["q_lora_rank"]) == (
        2304, 9216, 1024, 32, 512, 128, 64, 128, 8, 1, 2.446, 1e-5, True,
        None)
    assert sorted(cfg["reduced"]) == [
        "linear_attn_config", "num_experts", "num_hidden_layers",
        "vocab_size"]
    published = cfg["published"]
    lac, pub_lac = cfg["linear_attn_config"], published["linear_attn_config"]
    # the group's widths as published, its layers the first five
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert lac[key] == pub_lac[key]
    assert lac["kda_layers"] == [i for i in pub_lac["kda_layers"] if i <= 5]
    assert lac["full_attn_layers"] == [
        i for i in pub_lac["full_attn_layers"] if i <= 5] == [4]
    assert cfg["num_hidden_layers"] == 5
    deployment = cfg["deployment"]
    assert deployment["chips_sharing_a_layer"] == 32
    assert cfg["num_experts"] * 32 == published["num_experts"] == 256
    assert cfg["router_width"] == 256 and cfg["expert_offset"] == 0
    assert cfg["vocab_size"] * 8 == published["vocab_size"] == 163840
    assert "602,433,408" in deployment["size"]
    for said in ("experts", "vocabulary", "whole_on_every_chip", "depth",
                 "exchange", "load"):
        assert deployment[said]
    for said in ("block", "kda", "kda_low_rank", "kda_biases", "kda_eps",
                 "dt_origin", "A_log_and_dt_bias", "chunk", "mla",
                 "positions", "router", "route_bias", "auxiliary_loss",
                 "router_precision", "residual_stream", "optimizer", "init",
                 "data", "sequence", "remat", "use_flash"):
        assert cfg["assumed"][said]
    layers = config_io.expand_layers(cfg)
    kinds = [l["type"] for l in layers if l["name"].endswith("_mix")]
    assert kinds == 3 * ["kimi_delta_attention"] + ["attention",
                                                    "kimi_delta_attention"]
    assert [l["type"] for l in layers if l["name"].endswith("_mlp")] == \
        ["gated_mlp"] + 4 * ["routed_experts"]
    mixer = next(l for l in layers if l["type"] == "kimi_delta_attention")
    assert (mixer["n_heads"], mixer["head_dim"], mixer["conv_kernel"],
            mixer["chunk"], mixer["norm_eps"]) == (
        lac["num_heads"], lac["head_dim"], lac["short_conv_kernel_size"],
        64, 1e-5)
    # softplus(dt_origin) = 0.01 a token: exp(-0.64) of the state is left
    # at a chunk's end
    assert math.log1p(math.exp(mixer["dt_origin"])) == pytest.approx(0.01)
    mla = next(l for l in layers if l["type"] == "attention")
    assert (mla["n_heads"], mla["head_dim"], mla["kv_latent"],
            mla["k_shared"], mla["v_head_dim"]) == (
        32, cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], 512, 64, 128)
    assert not mla.get("rope") and mla["use_flash"] is None
    routed = next(l for l in layers if l["type"] == "routed_experts")
    assert (routed["n_experts"], routed["top_k"], routed["experts_held"],
            routed["d_hidden"], routed["shared_width"], routed["route_norm"],
            routed["route_scale"]) == (256, 8, 8, 1024, 1024, True, 2.446)
    # the block: the norm before the sublayer, the residual's sum after
    by_name = {l["name"]: l for l in layers}
    assert by_name["b0_in"]["inputs"] == ["emb"]
    assert by_name["b0_mix"]["inputs"] == ["b0_in"]
    assert by_name["b0_a"]["inputs"] == ["b0_mix", "emb"]
    assert by_name["b0"]["inputs"] == ["b0_mlp", "b0_a"]
    assert by_name["b1_in"]["inputs"] == ["b0"]
    cell = config_io.load_cell(CELL)
    assert cell["traffic"] == {"batch": 1, "seq_len": 4096, "n_train": 16,
                               "n_valid": 1}
    assert cell["driver"] == "train_counted" and cell["chips"] == 1
    bench = config_io.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    assert next(c for c in bench["configs"]
                if c["name"] == CONFIG)["reduced"] == cfg["reduced"]
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"train_tokens_per_s"} | {f"{stem}.tokens" for stem in LISTED} \
        <= listed
    assert not {f"{stem}.tokens" for stem in UNREAD} & listed
    kda = next(m for m in bench["per_layer"]
               if m["name"] == "kda_device_ms.tokens")
    assert kda["workloads"] == [CELL] and kda["source"] == "device_trace"
