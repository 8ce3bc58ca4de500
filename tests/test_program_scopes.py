"""The scope tables of compiled step programs and the join of a device
trace's seconds to them (``veles_tpu.runtime.program_scopes``): an
``op_name`` to its scope, a module's text to a table, the bounded store,
what ``StepCache.get_step`` notes, and ``seconds_by_scope`` on hand-made
tables and seconds.  No chip."""

import gc
import json

import jax
import jax.numpy as jnp
import pytest

from veles_tpu.runtime import program_scopes as ps
from veles_tpu.runtime.metrics import span_ring
from veles_tpu.runtime.step_cache import StepCache

UNITS = {"b0_mix": "Mamba2Mixer", "b1_mix": "RoutedExpertsFFN",
         "attn": "MultiHeadAttention", "head": "All2All",
         "evaluator": "EvaluatorSoftmax"}


@pytest.fixture(autouse=True)
def empty_store():
    ps.clear()
    yield
    ps.clear()


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(b0_mix)/ssm_in_proj/dot_general",
     ("b0_mix", "ssm_in_proj", "forward", False)),
    ("jit(step)/b0_mix/ssm_scan/ssd_carry/mul",
     ("b0_mix", "ssm_scan/ssd_carry", "forward", False)),
    ("jit(step)/transpose(jvp(b0_mix))/ssm_gate_norm/mul",
     ("b0_mix", "ssm_gate_norm", "backward", False)),
    # the backward of a checkpointed scan names unit and scope twice
    ("jit(step)/transpose(jvp(b0_mix))/ssm_scan/jvp(b0_mix)/ssm_scan/"
     "checkpoint/ssd_carry/while",
     ("b0_mix", "ssm_scan/ssd_carry", "backward", False)),
    ("jit(step)/transpose(jvp(b0_mix))/ssm_scan/jvp(b0_mix)/ssm_scan/"
     "checkpoint/rematted_computation/ssd_intra/mul",
     ("b0_mix", "ssm_scan/ssd_intra", "backward", True)),
    # a function, jax's control flow and an einsum's formula are no scope
    ("jit(step)/jvp(b0_mix)/ssm_conv/jit(silu)/mul",
     ("b0_mix", "ssm_conv", "forward", False)),
    ("jit(step)/jvp(b1_mix)/moe_experts/cond/branch_1_fun/moe_dispatch/"
     "gather", ("b1_mix", "moe_experts/moe_dispatch", "forward", False)),
    ("jit(step)/jvp(b0_mix)/ssm_scan/ssd_intra/bcgrij,bcjgrp->bcigrp/"
     "transpose", ("b0_mix", "ssm_scan/ssd_intra", "forward", False)),
    ("jit(step)/transpose(jvp(attn))/while/body/closed_call/remat2",
     ("attn", "", "backward", False)),
    ("jit(step)/jvp(attn)/jit(flash)/pallas_call",
     ("attn", "", "forward", False)),
    ("jit(step)/optimizer/jit(_where)/select_n",
     ("optimizer", "", "forward", False)),
    ("jit(aug)/loader_aug/jit(_take)/gather",
     ("loader_aug", "", "forward", False)),
    # XLA joins merged instructions' names: the first decides
    ("jit(step)/jvp(head)/dot_general;jit(step)/jvp(attn)/add",
     ("head", "", "forward", False)),
    # a function called as a unit is no unit; nothing names one
    ("jit(head)/mul", (None, "", "", False)),
    ("jit(step)/jit(_threefry_split)/while/body/add",
     (None, "", "", False)),
    ("reduce_sum", (None, "", "", False)),
])
def test_scope_of_an_op_name(op_name, want):
    assert ps.scope_of(op_name, UNITS) == want


#: a module as ``Compiled.as_text()`` prints one, cut to what matters: a
#: fusion with its computation, a ``conditional`` whose second branch
#: holds a ``while``, an instruction without ``op_name``
MODULE = '''HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "/root/repo/veles_tpu/units/workflow.py"

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(head)/mul"}
}

%add_f32 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%body.1 (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %fusion.7 = f32[8]{0} fusion(%t), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(b1_mix))/moe_experts/while/body/mul"}
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(%t, %fusion.7)
}

%cond.1 (t: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%t.1, %t.1), direction=LT
}

%branch_small (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %grouped_matmul.3 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(b1_mix))/moe_experts/cond/branch_0_fun/pallas_call"}, backend_config={"custom_call_config": {"body": "AAAA"}}
}

%branch_large (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  %while.2 = (s32[], f32[8]{0}) while(%x.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/transpose(jvp(b1_mix))/moe_experts/cond/branch_1_fun/while"}, backend_config={"known_trip_count":{"n":"4"}}
  ROOT %sum_rows_by_token.2 = f32[8]{0} custom-call(%while.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(b1_mix))/moe_combine/pallas_call"}
}

ENTRY %main.1 (w: f32[8]) -> f32[8] {
  %w = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%w), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(head)/mul" source_file="a {b}.py"}, backend_config={"flag":{"x":"1"}}
  %copy.5 = f32[8]{0} copy(%fusion.1)
  %reduce.1 = f32[] reduce(%copy.5, %w), dimensions={0}, to_apply=%add_f32, metadata={op_name="jit(step)/optimizer/reduce_sum"}
  ROOT %conditional.1 = f32[8]{0} conditional(%reduce.1, %copy.5, %copy.5), branch_computations={%branch_small, %branch_large}, metadata={op_name="jit(step)/transpose(jvp(b1_mix))/moe_experts/cond"}
}
'''

#: the events as a trace names them: every operand with its type before
FUSION_1 = ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %w), kind=kLoop, "
            "calls=%fused_computation.1")
CONDITIONAL = ("%conditional.1 = f32[8]{0} conditional(f32[] %reduce.1, "
               "f32[8]{0} %copy.5, f32[8]{0} %copy.5), "
               "branch_computations={%branch_small, %branch_large}")
WHILE = ("%while.2 = (s32[], f32[8]{0}) while(f32[8]{0} %x.1), "
         "condition=%cond.1, body=%body.1")
GROUPED = ('%grouped_matmul.3 = f32[8]{0} custom-call(f32[8]{0} %x), '
           'custom_call_target="tpu_custom_call"')
SUM_ROWS = ('%sum_rows_by_token.2 = f32[8]{0} custom-call((s32[], f32[8]{0})'
            ' %while.2), custom_call_target="tpu_custom_call"')
FUSION_7 = ("%fusion.7 = f32[8]{0} fusion((s32[], f32[8]{0}) %t), "
            "kind=kLoop, calls=%fused_computation.1")
COPY = "%copy.5 = f32[8]{0} copy(f32[8]{0} %fusion.1)"


def table(program="train", text=MODULE, units=UNITS):
    return ps.parse(program, text, units, "evaluator", "head")


def test_parse_keeps_what_can_be_an_event_as_the_trace_names_it():
    t = table()
    assert t.module == "jit_step"
    by_name = {i.name: i for i in t.instructions}
    # a fusion's computation is not entered, nor what a reduce applies
    assert "%mul.1" not in by_name and "%add.9" not in by_name
    assert {"%fusion.1", "%copy.5", "%reduce.1", "%conditional.1",
            "%while.2", "%grouped_matmul.3", "%sum_rows_by_token.2",
            "%fusion.7", "%lt.1"} <= set(by_name)
    # the text is the event's name: no ROOT, metadata or backend_config,
    # each operand with its type
    assert by_name["%fusion.1"].text == FUSION_1
    assert by_name["%conditional.1"].text == CONDITIONAL
    assert by_name["%grouped_matmul.3"].text == GROUPED
    assert by_name["%while.2"].text == WHILE
    assert by_name["%fusion.1"].calls == ()
    assert by_name["%conditional.1"].calls == ("branch_small",
                                               "branch_large")
    assert by_name["%while.2"].calls == ("cond.1", "body.1")
    assert by_name["%while.2"].computation == "branch_large"
    assert by_name["%reduce.1"].calls == ()
    assert by_name["%fusion.1"][5:] == ("head", "", "forward", False)
    assert by_name["%fusion.7"][5:] == ("b1_mix", "moe_experts",
                                        "backward", False)
    assert by_name["%sum_rows_by_token.2"].path == "moe_combine"
    assert by_name["%reduce.1"].unit == "optimizer"
    assert by_name["%copy.5"].unit is None
    # what XLA left unnamed inside a called computation is its caller's
    assert by_name["%lt.1"][5:] == by_name["%while.2"][5:] == (
        "b1_mix", "moe_experts", "backward", False)
    assert by_name["%x.1"].unit == "b1_mix"
    assert t.klass("b1_mix") == "RoutedExpertsFFN"
    assert t.klass("optimizer") == "optimizer"
    assert t.role("head") == "head" and t.role("evaluator") == "evaluator"
    assert t.role("b1_mix") is None and t.role(None) is None
    assert t.scoped == sum(i.unit is not None for i in t.instructions)


def test_a_table_is_plain_data_and_survives_json():
    t = table()
    again = ps.ScopeTable.from_json(json.loads(json.dumps(t.to_json())))
    assert again.instructions == t.instructions
    assert (again.program, again.module, again.units, again.evaluator,
            again.head) == (t.program, t.module, t.units, t.evaluator,
                            t.head)
    for ins in t.instructions:
        for field in ins:
            assert isinstance(field, (str, bool, tuple, type(None)))


def test_parse_numbers_no_operand_and_names_an_unnamed_caller():
    text = MODULE.replace(
        "fusion(%w), kind=kLoop, calls=%fused_computation.1, metadata",
        "fusion(%w, %w, %w, %w, %w, /*index=5*/%w), kind=kLoop, "
        "calls=%fused_computation.1, metadata").replace(
        ', metadata={op_name="jit(step)/transpose(jvp(b1_mix))/moe_experts/'
        'cond"}', "")
    t = table(text=text)
    assert t.by_name["%fusion.1"].text == FUSION_1.replace(
        "(f32[8]{0} %w)", "(" + ", ".join(["f32[8]{0} %w"] * 6) + ")")
    # the conditional has no op_name: its branches agree on a unit
    assert t.by_name["%conditional.1"][5:] == ("b1_mix", "", "backward",
                                               False)
    # branches of two units: the caller stays unscoped
    mixed = table(text=text.replace(
        "transpose(jvp(b1_mix))/moe_combine", "jvp(attn)"))
    assert mixed.by_name["%conditional.1"].unit is None


def test_an_unnamed_fusion_takes_what_its_instructions_agree_on():
    """XLA rewrites a ``concatenate`` to updates in place and gives all
    but the last no metadata: such a fusion is its instructions'."""
    bare = MODULE.replace(
        ', metadata={op_name="jit(step)/jvp(head)/mul" '
        'source_file="a {b}.py"}', "")
    assert table(text=bare).by_name["%fusion.1"][5:] == (
        "head", "", "forward", False)
    root = ('ROOT %mul.1 = f32[8]{0} multiply(%p, %p), metadata='
            '{op_name="jit(step)/jvp(head)/mul"}')
    mixer = 'metadata={op_name="jit(step)/transpose(jvp(b1_mix))/'

    def fused(*lines):
        return table(text=bare.replace(root, "\n  ".join(lines))
                     ).by_name["%fusion.1"]

    last = (f'ROOT %mul.1 = f32[8]{{0}} multiply(%mul.0, %p), {mixer}'
            'ssm_conv/ssm_conv_bwd/mul"}')
    # one unit, one of its instructions under a path: the path's
    assert fused(f'%mul.0 = f32[8]{{0}} multiply(%p, %p), {mixer}'
                 'concatenate"}', last)[5:] == (
        "b1_mix", "ssm_conv/ssm_conv_bwd", "backward", False)
    # working instructions of two units, however many of each: nobody's
    other = ('%mul.0 = f32[8]{0} multiply(%p, %p), metadata={op_name='
             '"jit(step)/jvp(attn)/mul"}')
    assert fused(other, last).unit is None
    assert fused(other, last.replace("ROOT %mul.1", "%mul.2"),
                 last).unit is None
    # XLA shares a constant between units' fusions under the first
    # unit's name: it and what spreads it have no vote
    assert fused(
        '%c.0 = f32[] constant(1), metadata={op_name="jit(step)/jvp(attn)/'
        'mul"}', '%mul.0 = f32[8]{0} broadcast(%c.0), dimensions={}, '
        'metadata={op_name="jit(step)/jvp(attn)/mul"}', last)[5:] == (
        "b1_mix", "ssm_conv/ssm_conv_bwd", "backward", False)
    # a fusion XLA did name keeps its name, whatever is fused into it
    assert table(text=MODULE.replace(root, root.replace("head", "attn"))
                 ).by_name["%fusion.7"].unit == "b1_mix"


def eval_module():
    """The validation step: the same instruction name as the train
    step's kernel, under another operand and another unit."""
    return MODULE.replace(
        "%x = f32[8]{0} parameter(0)",
        "%x = f32[8]{0:T(128)} parameter(0)").replace(
        "transpose(jvp(b1_mix))/moe_experts/cond/branch_0_fun",
        "attn/cond/branch_0_fun")


def rows(out):
    return {(r["program"], r["unit"], r["path"], r["direction"]):
            r["seconds"] for r in out["rows"]}


@pytest.mark.parametrize("case", [
    "whole_text", "same_name_two_programs", "name_in_one_program",
    "ambiguous", "unmatched", "conditional_self_time",
    "while_in_conditional", "no_op_name"])
def test_seconds_by_scope_on_hand_made_tables(case):
    train, valid = table("train"), table("eval", eval_module())
    if case == "whole_text":
        out = ps.seconds_by_scope({FUSION_1: 0.25}, [train])
        assert rows(out) == {("train", "head", "", "forward"): 0.25}
        assert out["rows"][0]["class"] == "All2All"
        assert out["rows"][0]["role"] == "head"
        assert out["total_s"] == out["scoped_s"] == 0.25
    elif case == "same_name_two_programs":
        # %grouped_matmul.3 is in both programs: the whole text decides
        other = GROUPED.replace("{0} %x)", "{0:T(128)} %x)")
        out = ps.seconds_by_scope({GROUPED: 0.5, other: 0.03},
                                  [train, valid])
        assert rows(out) == {
            ("train", "b1_mix", "moe_experts", "backward"): 0.5,
            ("eval", "attn", "", "forward"): 0.03}
        assert out["ambiguous"]["seconds"] == 0
    elif case == "name_in_one_program":
        # the trace prints an operand another way: the text fails, the
        # name of a ``while`` of that type is in one noted program
        typed = WHILE.replace("{0} %x.1)", "{0:T(8)} %x.1)")
        out = ps.seconds_by_scope({typed: 0.125}, [train])
        assert rows(out) == {
            ("train", "b1_mix", "moe_experts", "backward"): 0.125}
    elif case == "ambiguous":
        # by name alone, two programs claim it under different units
        typed = GROUPED.replace("%x)", "%z)")
        out = ps.seconds_by_scope({typed: 0.5}, [train, valid])
        assert out["rows"] == [] and out["ambiguous"] == {
            "seconds": 0.5, "heaviest": [[typed, 0.5]]}
        # under one unit, the first noted program takes it
        both = ps.seconds_by_scope({FUSION_1: 0.5}, [train, valid])
        assert rows(both) == {("train", "head", "", "forward"): 0.5}
    elif case == "unmatched":
        event = "%fusion.99 = u8[4]{0} fusion(%p), kind=kLoop, calls=%f"
        out = ps.seconds_by_scope({event: 0.0625, FUSION_1: 0.25}, [train])
        assert out["unmatched"] == {"seconds": 0.0625,
                                    "heaviest": [[event, 0.0625]]}
        assert out["total_s"] == 0.3125 and out["scoped_s"] == 0.25
        assert ps.seconds_by_scope({event: 1.0}, [])["unmatched"][
            "seconds"] == 1.0
        # a program that was not noted numbers its fusions like one that
        # was: a name alone, under another type, claims nothing
        alien = "%fusion.1 = u8[4]{0} fusion(u8[4]{0} %q), kind=kLoop"
        assert ps.seconds_by_scope({alien: 1.0}, [train])["unmatched"][
            "seconds"] == 1.0
    elif case == "conditional_self_time":
        # the device was busy 1.0 s: the conditional's event covers its
        # branch's two operations, which are events of their own
        seconds = {CONDITIONAL: 0.75, GROUPED: 0.5, SUM_ROWS: 0.125,
                   FUSION_1: 0.25}
        out = ps.seconds_by_scope(seconds, [train])
        assert rows(out) == {
            ("train", "b1_mix", "moe_experts", "backward"): 0.5 + 0.125,
            ("train", "b1_mix", "moe_combine", "backward"): 0.125,
            ("train", "head", "", "forward"): 0.25}
        assert out["total_s"] == pytest.approx(1.0)
        assert sum(seconds.values()) == 1.625      # what a plain sum gives
    elif case == "while_in_conditional":
        seconds = {CONDITIONAL: 0.75, WHILE: 0.5, FUSION_7: 0.375,
                   SUM_ROWS: 0.125, COPY: 0.25}
        out = ps.seconds_by_scope(seconds, [train])
        # conditional 0.75 - (0.5 + 0.125); while 0.5 - 0.375; fusion
        assert rows(out) == {
            ("train", "b1_mix", "moe_experts", "backward"):
                0.125 + 0.125 + 0.375,
            ("train", "b1_mix", "moe_combine", "backward"): 0.125}
        assert out["unscoped"] == {"seconds": 0.25,
                                   "heaviest": [[COPY, 0.25]]}
        assert out["total_s"] == pytest.approx(1.0)
    else:
        out = ps.seconds_by_scope({COPY: 0.5}, [train])
        assert out["rows"] == [] and out["scoped_s"] == 0
        assert out["unscoped"]["seconds"] == out["total_s"] == 0.5


def test_recomputed_seconds_are_kept_beside_the_rows():
    text = MODULE.replace("moe_experts/while/body/mul",
                          "moe_experts/checkpoint/rematted_computation/mul")
    out = ps.seconds_by_scope({FUSION_7: 0.5, SUM_ROWS: 0.25},
                              [table(text=text)])
    got = {r["path"]: (r["seconds"], r["recomputed_s"]) for r in out["rows"]}
    assert got == {"moe_experts": (0.5, 0.5), "moe_combine": (0.25, 0.0)}


def test_store_keeps_the_newest_of_a_program_and_is_bounded():
    first = ps.note("train", MODULE, {"classes": UNITS})
    second = ps.note("train", MODULE, {"classes": UNITS,
                                       "evaluator": "evaluator"})
    ps.note("eval", MODULE)
    assert [t.program for t in ps.noted()] == ["train", "eval"]
    assert ps.noted()[0] is second and second is not first
    assert ps.noted()[1].units == {}
    for n in range(ps.MAX_NOTED + 3):
        ps.note("prefill", MODULE.replace("jit_step", f"jit_prefill_{n}"))
    noted = ps.noted()
    assert len(noted) == ps.MAX_NOTED
    assert noted[-1].module == f"jit_prefill_{ps.MAX_NOTED + 2}"
    assert all(t.program == "prefill" for t in noted)
    # with no tables given, the join reads the store
    assert ps.seconds_by_scope({FUSION_1: 1.0})["unscoped"]["seconds"] == 1.0


def test_get_step_notes_the_compiled_program_and_the_span_says_so():
    def scoped(x):
        with jax.named_scope("head"):
            y = x @ x
        with jax.named_scope("optimizer"):
            return y, jnp.cumsum(x, axis=0)

    cache = StepCache()
    units = {"classes": {"head": "All2All"}, "evaluator": None,
             "head": "head"}
    args = (jax.ShapeDtypeStruct((8, 8), jnp.float32),)
    fn, _, _ = cache.get_step("train", ("k",),
                              lambda: (jax.jit(scoped), None, None), args,
                              units=units)
    compiled = [e for e in span_ring().snapshot()
                if e["name"] == "step_compile"][-1]["args"]
    noted = ps.noted()
    assert [t.program for t in noted] == ["train"]
    assert noted[0].module == "jit_scoped"
    assert compiled["instructions"] == len(noted[0].instructions) > 0
    assert compiled["scoped"] == noted[0].scoped > 0
    assert compiled["noting_s"] >= 0
    assert {i.unit for i in noted[0].instructions} >= {"head", "optimizer"}
    # a hit notes nothing anew; the table outlives cache and executable
    cache.get_step("train", ("k",), None, args)
    assert ps.noted()[0] is noted[0]
    del cache, fn
    gc.collect()
    assert ps.noted()[0].instructions == noted[0].instructions


def test_a_capture_describes_itself(tmp_path, monkeypatch):
    """``POST /debug/profile``'s capture writes the noted tables beside
    the trace files, and they join like the store's own."""
    from veles_tpu.runtime.profiler import ProfilerCapture
    monkeypatch.setattr(jax.profiler, "start_trace", lambda path: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    ps.note("train", MODULE, {"classes": UNITS, "evaluator": "evaluator",
                              "head": "head"})
    out = ProfilerCapture().capture(0.01, out_dir=str(tmp_path))
    path = tmp_path / out["path"] / "program_scopes.json"
    assert out["files"] >= 1 and path.exists()
    tables = [ps.ScopeTable.from_json(d) for d in json.loads(path.read_text())]
    assert [(t.program, t.module) for t in tables] == [("train", "jit_step")]
    seconds = {CONDITIONAL: 0.75, GROUPED: 0.5, SUM_ROWS: 0.125}
    assert ps.seconds_by_scope(seconds, tables)["rows"] \
        == ps.seconds_by_scope(seconds)["rows"]


@pytest.mark.parametrize("scope", ["loader_gather", "loader_aug"])
def test_loader_programs_are_noted_by_their_one_compile(scope):
    import numpy as np
    from veles_tpu.loader.base import TRAIN, VALID
    from veles_tpu.loader.fullbatch import (FullBatchAugmentedLoader,
                                            FullBatchLoader)
    rng = np.random.default_rng(0)
    data = {TRAIN: rng.integers(0, 255, (16, 8, 8, 3)).astype(np.uint8),
            VALID: rng.integers(0, 255, (8, 8, 8, 3)).astype(np.uint8)}
    labels = {TRAIN: rng.integers(0, 4, 16).astype(np.int32),
              VALID: rng.integers(0, 4, 8).astype(np.int32)}
    if scope == "loader_gather":
        loader = FullBatchLoader(data, labels, minibatch_size=4)
        jitted = lambda: loader._gather[TRAIN]  # noqa: E731
    else:
        loader = FullBatchAugmentedLoader(data, labels, minibatch_size=4,
                                          crop_hw=(6, 6))
        jitted = lambda: loader._aug  # noqa: E731
    loader.initialize()
    assert loader.on_device
    first = loader.make_batch(np.arange(4), TRAIN)
    again = loader.make_batch(np.arange(4), TRAIN)
    loader.make_batch(np.arange(3), VALID)
    np.testing.assert_array_equal(first["@input"], again["@input"])
    assert first["@input"].shape[0] == 4
    # one compile a class, ahead of time: the jitted function's own
    # cache never filled
    assert sorted(loader._compiled) == [(scope, VALID), (scope, TRAIN)]
    assert jitted()._cache_size() == 0
    tables = {t.program: t for t in ps.noted()}
    assert set(tables) == {f"{scope}.train", f"{scope}.validation"}
    for t in tables.values():
        assert {i.unit for i in t.instructions} == {None, scope}
        assert t.klass(scope) == scope
    # uploading again drops what was compiled for the old arrays
    loader._upload()
    assert loader._compiled == {}
