"""The benchmark's own tests: its files hang together, its counts and its
trace arithmetic are right, its driver runs a tiny cell end to end on the
CPU, its references agree with the program in float32, and its check
fails the lower-precision control and each fault a training cell can have.

No chip, no child process, no topology call.
"""

import json
import os
import re
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402
import config_io  # noqa: E402
import counts  # noqa: E402
import trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture
def program_state(tmp_path, monkeypatch):
    """The driver points the program's caches and its loader stream
    somewhere of its own; put them back for the tests that follow."""
    import jax
    from veles_tpu import prng
    from veles_tpu.config import root
    from drivers import train
    saved = (root.common.cache_dir, root.common.compile_cache,
             jax.config.jax_compilation_cache_dir, prng.streams.state())
    monkeypatch.setattr(train, "CACHE", str(tmp_path / "cache"))
    yield train
    root.common.cache_dir, root.common.compile_cache = saved[:2]
    jax.config.update("jax_compilation_cache_dir", saved[2])
    prng.streams.reset()
    prng.streams.set_state(saved[3])


def tiny(name):
    cell = config_io.load_cell(name + "_cell", DATA)
    return cell, config_io.load_config(cell["config"], DATA)


def drive(train, name, seed=7):
    cell, cfg = tiny(name)
    args = types.SimpleNamespace(seed=seed, seconds=0.2, trace=0)
    return cell, cfg, train.run(cell, cfg, args, time.perf_counter())


def test_benchmark_json_names_files_that_exist_and_load():
    bench = config_io.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][-1].startswith(bench["paths"][0] + "/")
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/")
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["source"] == c["source"] and "assumed" in cfg
        assert config_io.expand_layers(cfg)
        assert os.path.exists(os.path.join(
            BENCH, "references", cfg["reference"] + ".py"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(m["bound"] <= 0.1 for m in e2e.values())
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = config_io.load_cell(w["name"])
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert os.path.exists(os.path.join(
            BENCH, "drivers", cell["driver"] + ".py"))
        assert cell["check"]["limits"], "a cell without limits checks nothing"
    import run
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert callable(run.metric_reader(m["name"]))
        for w in m["workloads"]:   # the cell reports what the metric moves
            assert w in e2e[m["moves"]].get("workloads", cells)


def test_counts_match_the_published_figures():
    cfg = config_io.load_config("alexnet")
    traffic = config_io.load_cell("alexnet_train_b512")["traffic"]
    c = counts.model_counts(cfg, traffic)
    assert c["params"] == cfg["published"]["parameters"] == 62378344
    assert c["forward_flops_per_item"] == pytest.approx(2.27e9, rel=0.01)
    # three products a layer but conv1, whose input needs no gradient
    assert c["train_flops_per_item"] == pytest.approx(6.6e9, rel=0.01)

    cfg = config_io.load_config("opt-350m-rope")
    traffic = config_io.load_cell("opt350m_train_t2048")["traffic"]
    c = counts.model_counts(cfg, traffic)
    assert c["params"] == pytest.approx(254e6, rel=0.01)
    assert c["train_flops_per_item"] == pytest.approx(1.37e9, rel=0.01)


def test_trace_reduce_union_sums_and_gaps_on_hand_made_intervals():
    ev = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("a", 3.0, 4.0), ("c", 6.0, 7.0)]
    assert trace_reduce.busy_seconds(ev) == pytest.approx(4.0)
    assert trace_reduce.seconds_by_name(ev) == pytest.approx(
        {"a": 2.0, "b": 1.5, "c": 1.0})
    marks = [("epoch_boundary", 4.5, 5.0)]
    gaps = trace_reduce.idle_gaps(ev, 0.0, 8.0, marks)
    assert gaps == [("epoch_boundary", 2.0), ("unattributed", 1.0),
                    ("unattributed", 1.0)]
    out = trace_reduce.reduce(
        [ev], [("epoch_boundary", 0.4, 0.5), ("epoch_boundary", 4.5, 5.0),
               ("epoch_boundary", 7.4, 7.5)], chips=1)
    assert out["window_s"] == pytest.approx(7.0)       # 0.5 .. 7.5
    assert out["busy_s"] == pytest.approx(3.5)         # a is clipped at 0.5
    assert out["epochs_in_window"] == 2
    assert dict(map(tuple, out["device_ops"])) == pytest.approx(
        {"a": 1.5, "b": 1.5, "c": 1.0})
    with pytest.raises(RuntimeError):
        trace_reduce.reduce([ev], [], chips=4)
    assert trace_reduce.reduce([], [], chips=1) is None


def test_run_refuses_to_measure_without_the_chip(capsys):
    import run
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "alexnet_train_b512", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code == 2
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", ["tiny_image", "tiny_lm"])
def test_driver_runs_a_tiny_cell_and_agrees_with_its_reference(
        program_state, name, capsys):
    """End to end through Trainer.run() with the look for a chip lifted:
    every declared metric comes out, nothing compiles in the window, and
    in float32 the program and the plain reference agree to rounding."""
    import run
    cell, cfg, out = drive(program_state, name)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    gaps = {k: v["value"] for k, v in out["compared"].items()}
    assert max(v for k, v in gaps.items() if "keep" not in k) < 1e-4, gaps
    item = cfg["item"]
    bench = {
        "end_to_end": [{"name": f"train_{item}_per_s", "unit": f"{item}/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": f"{stem}.{item}", "unit": "%",
             "moves": f"train_{item}_per_s"}
            for stem in ("step_mfu", "data_wait_share",
                         "device_idle_share")]}
    entry = {"name": cell["name"], "chips": 1}
    devices = [types.SimpleNamespace(platform="cpu", device_kind="cpu")]
    out.update(peaks={"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}, chips=1)
    for traced, want in ((0, {f"train_{item}_per_s", "setup_s"}),
                         (1, {f"step_mfu.{item}",
                              f"data_wait_share.{item}"})):
        assert run.report(bench, entry, out, devices, traced) == 0
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        # a reader that finds no trace returns nothing, never 0
        assert set(line["metrics"]) == want
        assert all(m["value"] > 0 for m in line["metrics"].values())
        assert list(line)[-1] == "compared" and line["correct"] is True


@pytest.mark.parametrize("name", ["tiny_image", "tiny_lm"])
def test_control_in_float8_comes_out_not_correct(program_state, name):
    """The reference with float8 operands, put in the program's place,
    fails the cell's limits; with float32 operands it passes them."""
    import calibrate_train
    cell, cfg = tiny(name)
    program_state.configure_program()
    out = calibrate_train.one_seed(cell, cfg, 11, controls=True)
    limits = {k: v for k, v in cell["check"]["limits"].items()
              if "keep" not in k}
    assert compare.verdict(out["control_float8"], limits)[1] is False
    sound = {k: v for k, v in out["program"].items() if "keep" not in k}
    assert compare.verdict(sound, limits)[1] is True
    assert compare.verdict(out["fault_half_batch"], limits)[1] is False


def _break_train_step(monkeypatch, fault):
    """Plant a fault under the timed path: the program's own train step,
    with its state returned unchanged or half of its batch masked out."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.units.workflow import Workflow
    make = Workflow.make_train_step

    def broken(self, optimizer, **kw):
        pure = make(self, optimizer, jit=False)

        def step(wstate, batch):
            if fault == "half_batch":
                n = batch["@mask"].shape[0]
                batch = {**batch, "@mask": batch["@mask"]
                         * (jnp.arange(n) < n // 2)}
            new, mets = pure(wstate, batch)
            return (wstate if fault == "state_unchanged" else new), mets

        return jax.jit(step, donate_argnums=(0,))

    monkeypatch.setattr(Workflow, "make_train_step", broken)


@pytest.mark.parametrize("name,fault", [
    ("tiny_image", "state_unchanged"), ("tiny_image", "half_batch"),
    ("tiny_lm", "state_unchanged"), ("tiny_lm", "half_batch")])
def test_a_broken_timed_path_comes_out_not_correct(
        program_state, monkeypatch, name, fault):
    _break_train_step(monkeypatch, fault)
    _, _, out = drive(program_state, name)
    assert out["correct"] is False, out["compared"]
