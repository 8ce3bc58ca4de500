"""The per-layer metrics that read device time by unit
(``benchmarks/metrics/unit_device_ms.py`` and its thin readers): the
scope tables of the tiny cells driven on the CPU, each reader on a
hand-filled ``run``, and every ``per_layer`` entry of ``BENCHMARK.json``
behind ``run.metric_reader``.

No chip, no child process, no topology call.
"""

import gc
import importlib
import io
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import config_io  # noqa: E402
from metrics import unit_device_ms  # noqa: E402
from test_perf_benchmark import program_state, tiny  # noqa: E402,F401
from veles_tpu.runtime import program_scopes as ps  # noqa: E402

NEW = ("ssm_device_ms", "gdn_device_ms", "moe_device_ms", "attn_device_ms",
       "head_device_ms", "lrn_device_ms", "optimizer_device_ms",
       "scoped_device_share")


@pytest.fixture(autouse=True)
def empty_store():
    ps.clear()
    yield
    ps.clear()


@pytest.mark.parametrize("name,paths", [
    ("tiny_hybrid", {"ssm_in_proj", "ssm_conv", "ssm_scan/ssd_intra",
                     "ssm_scan/ssd_carry", "ssm_gate_norm", "ssm_out_proj",
                     "moe_route", "moe_dispatch", "moe_experts",
                     "moe_shared", "moe_combine"}),
    ("tiny_linear", {"gdn_in_proj", "gdn_conv", "gdn_scan/gdn_chunk",
                     "gdn_scan/gdn_carry", "gdn_gate_norm", "gdn_out_proj"}),
    ("tiny_routed", {"moe_route", "moe_dispatch", "moe_experts",
                     "moe_shared", "moe_combine"}),
    ("tiny_image", set()),
    ("tiny_lm", set())])
def test_tables_of_a_driven_tiny_cell_name_units_directions_and_scopes(
        program_state, name, paths):  # noqa: F811
    """Through the driver's own set-up (``make_trainer``, the warm-up
    epoch that compiles both step programs): the train step's table names
    every unit and ``optimizer``, both directions for a unit with
    parameters, the units' sub-scopes, and outlives the trainer."""
    cell, cfg = tiny(name)
    driver = importlib.import_module("drivers." + cell["driver"])
    driver.configure_program()
    s = driver.setup(cell, cfg, 7)
    workflow = s["trainer"].workflow
    classes = {u.name: type(u).__name__ for u in workflow.units}
    with_params = set(s["trainer"].wstate["params"])
    evaluator = workflow.evaluator
    del s, workflow
    gc.collect()

    tables = {t.program: t for t in ps.noted()}
    # (the image cell's loader crops on the device: its programs too)
    assert set(tables) == {"train", "eval"} | (
        {"loader_aug.train", "loader_aug.validation"}
        if name == "tiny_image" else set())
    train = tables["train"]
    assert train.units == classes and tables["eval"].units == classes
    assert train.evaluator == evaluator.name
    assert train.head == evaluator.inputs[0]
    directions = {}
    for ins in train.instructions:
        if ins.unit is not None:
            directions.setdefault(ins.unit, set()).add(ins.direction)
    assert set(directions) == set(classes) | {"optimizer"}
    assert directions["optimizer"] == {"forward"}
    for unit in with_params:
        assert directions[unit] == {"forward", "backward"}, unit
    assert paths <= {i.path for i in train.instructions}
    assert {i.direction for i in tables["eval"].instructions} \
        == {"", "forward"}
    # the readers pick by class: every class of the cell is asked for
    # somewhere in its table
    assert {train.klass(u) for u in directions} \
        == set(classes.values()) | {"optimizer"}
    # plain data: nothing of the executable is held
    for ins in train.instructions[:50]:
        assert all(isinstance(f, (str, bool, tuple, type(None)))
                   for f in ins)


# -- the readers on a hand-filled run ----------------------------------------

UNITS = {"emb": "Embedding", "b0_mix": "Mamba2Mixer",
         "b1_mix": "RoutedExpertsFFN", "b2_mix": "MultiHeadAttention",
         "b3_mix": "GatedDeltaNet", "b3_ssm": "Mamba2Mixer", "lrn1": "LRN",
         "head": "All2All", "evaluator": "EvaluatorSoftmax"}


def hand_table():
    def ins(n, unit, path="", direction="forward", calls=(), where="main"):
        name = f"%fusion.{n}"
        return ps.Instruction(name, f"{name} = f32[8]{{0}} fusion()", where,
                              "fusion", calls, unit, path, direction, False)
    return ps.ScopeTable("train", "jit_step", [
        ins(1, "b0_mix", "ssm_scan"), ins(2, "b0_mix", "", "backward"),
        ins(3, "b3_ssm", "ssm_conv"),
        ins(4, "b1_mix", "moe_experts", "backward", ("branch",)),
        ins(5, "b1_mix", "moe_experts", "backward", where="branch"),
        ins(6, "b2_mix"), ins(7, "b3_mix", "gdn_scan/gdn_chunk"),
        ins(8, "lrn1", "", "backward"), ins(9, "head"),
        ins(10, "evaluator", "", "backward"), ins(11, "optimizer"),
        ins(12, None, "", ""), ins(13, "emb")],
        UNITS, "evaluator", "head")


def text(n):
    return f"%fusion.{n} = f32[8]{{0}} fusion()"


#: seconds over 2 traced epochs of 16 train steps: 32 steps
SECONDS = {text(1): 0.32, text(2): 0.64, text(3): 0.16,
           text(4): 0.96, text(5): 0.64,      # a conditional and its branch
           text(6): 1.28, text(7): 0.48, text(8): 0.08, text(9): 0.24,
           text(10): 0.08, text(11): 0.8, text(12): 0.16, text(13): 0.08,
           "%fusion.99 = u8[4]{0} fusion()": 0.04}
WANT = {"ssm_device_ms": (0.32 + 0.64 + 0.16) * 1000 / 32,
        "gdn_device_ms": 0.48 * 1000 / 32,
        "moe_device_ms": 0.96 * 1000 / 32,     # self time 0.32 + 0.64
        "attn_device_ms": 1.28 * 1000 / 32,
        "head_device_ms": (0.24 + 0.08) * 1000 / 32,
        "lrn_device_ms": 0.08 * 1000 / 32,
        "optimizer_device_ms": 0.8 * 1000 / 32,
        # busy 5.32 s: all but the unscoped 0.16 and the unmatched 0.04
        "scoped_device_share": 100.0 * 5.12 / 5.32}


def hand_run(seconds=SECONDS, epochs_traced=2):
    return {"measured": {"steps": 64, "epochs": 4},
            "trace": {"epochs_in_window": epochs_traced, "busy_s": 5.32,
                      "window_s": 5.4, "seconds_by_op": dict(seconds)}}


def reader(stem):
    return importlib.import_module("metrics." + stem).read


@pytest.mark.parametrize("stem", NEW)
def test_reader_gives_the_hand_arithmetic(stem, capsys):
    ps._NOTED[("train", "jit_step")] = hand_table()
    run = hand_run()
    assert reader(stem)(run) == pytest.approx(WANT[stem], rel=1e-9)
    # the table is printed once a run, whichever reader comes first
    printed = capsys.readouterr().err
    assert printed.count("device time by unit") == 1
    reader("optimizer_device_ms")(run)
    assert "device time by unit" not in capsys.readouterr().err
    assert run["unit_device"]["total_s"] == pytest.approx(5.32)


@pytest.mark.parametrize("stem", NEW)
@pytest.mark.parametrize("case", ["no_trace", "no_table", "no_unit",
                                  "no_whole_epoch"])
def test_reader_gives_nothing_where_there_is_nothing_to_read(stem, case,
                                                             capsys):
    run = hand_run()
    if case != "no_table":
        ps._NOTED[("train", "jit_step")] = hand_table()
    if case == "no_trace":
        run = {"measured": run["measured"]}
    elif case == "no_whole_epoch":
        run["trace"]["epochs_in_window"] = 0
    elif case == "no_unit":
        # a program whose units are of other classes
        table = hand_table()
        ps._NOTED[("train", "jit_step")] = ps.ScopeTable(
            "train", "jit_step", table.instructions,
            dict.fromkeys(UNITS, "FFN"), None, None)
        if stem in ("optimizer_device_ms", "scoped_device_share"):
            pytest.skip("the optimizer's scope is of no class")
    assert reader(stem)(run) is None


def test_table_print_names_rows_and_the_three_totals():
    ps._NOTED[("train", "jit_step")] = hand_table()
    run = hand_run()
    scopes = unit_device_ms.joined(run)
    out = io.StringIO()
    unit_device_ms.print_table(run, scopes, out)
    lines = out.getvalue().splitlines()
    assert "busy 166.250" in lines[0] and "unmatched 166.250" in lines[0]
    assert "  b2_mix | MultiHeadAttention | 40.000 | 0.000 (0.000) | " \
        "40.000" in lines
    assert "  train | b1_mix | RoutedExpertsFFN | moe_experts | backward" \
        " | 30.0000" in lines
    assert "  unscoped: 5.0000" in lines and "  ambiguous: 0.0000" in lines
    assert "  unmatched: 1.2500" in lines
    assert "    1.2500  %fusion.99 = u8[4]{0} fusion()" in lines


def test_every_per_layer_entry_finds_its_reader_and_its_cells():
    import run
    bench = config_io.load_benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["per_layer"]:
        assert callable(run.metric_reader(m["name"])), m["name"]
        assert set(m["workloads"]) <= cells
        # a cell a metric lists reports the end-to-end metric it moves
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"].split(".")[0] in NEW}
    # no entry lists olmo_hybrid_train_t4096 (test_linear_benchmark.py
    # pins that cell's metrics, a benchmark PR's to extend): so
    # gdn_device_ms has a reader and no entry, and unit_table.py prints
    # that cell's table
    assert sorted(new) == [
        "attn_device_ms.tokens", "head_device_ms.tokens",
        "lrn_device_ms.images", "moe_device_ms.tokens",
        "optimizer_device_ms.images", "optimizer_device_ms.tokens",
        "scoped_device_share.images", "scoped_device_share.tokens",
        "ssm_device_ms.tokens"]
    assert callable(run.metric_reader("gdn_device_ms.tokens"))
    others = e2e["train_tokens_per_s"] - {"olmo_hybrid_train_t4096"}
    for stem in ("attn_device_ms", "head_device_ms", "optimizer_device_ms",
                 "scoped_device_share"):
        assert set(new[stem + ".tokens"]["workloads"]) == others
    for m in new.values():
        assert m["source"] == "device_trace"
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if m["name"].startswith("scoped_device_share")
            else ("ms", "lower"))


def test_unit_table_prints_table_and_readings_before_the_result_line(capsys):
    import unit_table
    ps._NOTED[("train", "jit_step")] = hand_table()
    seen = []

    def then(bench, entry, out, devices, traced):
        seen.append((entry, traced,
                     (out["unit_device"] or {}).get("total_s")))
        return 0
    assert unit_table.report({}, "cell", hand_run(), [], 1, then) == 0
    assert seen == [("cell", 1, pytest.approx(5.32))]
    err = capsys.readouterr().err
    assert err.count("device time by unit") == 1
    got = dict(line.split(" = ") for line in err.splitlines()
               if " = " in line and line.split(" = ")[0] in NEW)
    assert {k: float(v) for k, v in got.items()} == {
        k: pytest.approx(v) for k, v in WANT.items()}
    # without a trace: nothing printed, the line all the same
    assert unit_table.report({}, "cell", {"measured": {}}, [], 1, then) == 0
    assert capsys.readouterr().err == "" and seen[-1] == ("cell", 1, None)
