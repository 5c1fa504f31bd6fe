"""The split of a traced round by the program's phase scopes, on a small
synthetic compiled program and trace; and the by-hand script's traced run
of the tiny fed cell, which keeps the names of the round's instructions."""
import pytest

import phase_split as PS
import tiny
from lib import phases as PH
from lib import trace as TR

E = TR.Event
SEED = 2 ** 31 + 11

HLO = """HloModule jit_round_fn, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.9 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_type="mul" op_name="jit(round_fn)/heron_cohort/mul"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %negate.1 = f32[8]{0} negate(%param_0.1), metadata={op_name="jit(round_fn)/heron_cohort/neg"}
  ROOT %add.3 = f32[8]{0} add(%negate.1, %param_0.1), metadata={op_name="jit(round_fn)/heron_replay/add"}
}

%fused_computation.3 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  ROOT %negate.2 = f32[8]{0} negate(%param_0.2)
}

%body.2 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %dot.3 = f32[8]{0} dot(%x, %y), lhs_contracting_dims={0}, metadata={op_name="jit(round_fn)/heron_server_fo/while/body/transpose(jvp(heron_server_fo))/dot_general" source_line=7}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%i, %dot.3)
}

ENTRY %main.5 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %zo_dual_matmul.1 = (bf16[4,8]{1,0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_fn)/heron_cohort/while/body/vmap(heron_aux_head)/jit(zo_dual_matmul)/pallas_call"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(round_fn)/heron_cohort/heron_aux_head/add"}
  %while.4 = (s32[], f32[8]{0}) while(%t), condition=%cond.1, body=%body.2, metadata={op_name="jit(round_fn)/heron_server_fo/while"}
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(round_fn)/heron_replay/mul"}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(round_fn)/heron_cohort_x/mul"}
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)
  %fusion.10 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.2
  %fusion.11 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.3
  ROOT %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
}
"""


def _record():
    ops = [("%make.1 = u32[2]{0} fusion(%k)", 0.2, 0.3),      # feed
           ("%fusion.2 = f32[8]{0} fusion(%k)", 0.5, 0.2),    # feed too
           ("%zo_dual_matmul.1 = (bf16[4,8]) custom-call(%a)", 1.0, 1.0),
           ("%fusion.2 = f32[8]{0} fusion(%a)", 2.0, 0.5),
           ("%while.4 = (s32[], f32[8]) while(%t)", 3.0, 2.0),
           ("%dot.3 = f32[8]{0} dot(%x, %y)", 3.5, 1.0),      # in while.4
           ("%fusion.7 = f32[8]{0} fusion(%a)", 5.5, 0.5),
           ("%fusion.8 = f32[8]{0} fusion(%a)", 6.0, 0.25),
           ("%copy-start.1 = (f32[8]) copy-start(%a)", 6.5, 0.25),
           ("%fusion.10 = f32[8]{0} fusion(%a)", 6.75, 0.05),  # bare
           ("%make.1 = u32[2]{0} fusion(%k)", 7.0, 0.5),      # next feed
           ("%zo_dual_matmul.1 = (bf16[4,8]) custom-call(%a)", 9.5, 1.0)]
    mods = [("jit__make_inputs(1)", 0.2, 0.6), ("jit_round_fn(2)", 1.0, 5.8),
            ("jit__make_inputs(1)", 7.0, 0.5), ("jit_round_fn(2)", 9.5, 1.0)]
    spans = [E(TR.WINDOW_SPAN, 0.0, 10.0),
             E("bench.make_inputs", 0.1, 0.05), E("bench.round", 0.16, 0.1),
             E("bench.block", 0.9, 5.95),         # ends 0.05 after 6.8
             E("bench.make_inputs", 6.9, 0.05),
             E("bench.block", 9.4, 0.55)]
    tr = TR.Trace({"/device:TPU:0": TR.device_events(ops, mods)}, spans)
    return {"kind": "fed_round", "steps": 2, "trace": tr,
            "op_scopes": {"jit_round_fn": PH.op_names(HLO)},
            "programs": {"jit_round_fn": {"zo_dual_matmul.1": {
                "wrapper": "zo_dual_matmul"}}}}


def test_op_names_cover_every_computation():
    names = PH.op_names(HLO)
    assert names["dot.3"].endswith("transpose(jvp(heron_server_fo))"
                                   "/dot_general")            # while body
    assert names["multiply.9"] == "jit(round_fn)/heron_cohort/mul"  # fused
    assert names["copy-start.1"] == ""                          # none
    # bare fusions: the fused root's name, or none where the body has none
    assert names["fusion.10"] == "jit(round_fn)/heron_replay/add"
    assert names["fusion.11"] == ""
    assert "main.5" not in names and "fused_computation.1" not in names


@pytest.mark.parametrize("op_name,scope,held", [
    ("jit(round_fn)/heron_server_fo/while", "heron_server_fo", True),
    ("a/vmap(heron_aux_head)/b", "heron_aux_head", True),
    ("a/transpose(jvp(heron_server_fo))/dot", "heron_server_fo", True),
    ("jit(round_fn)/heron_cohort/heron_aux_head/add", "heron_cohort", True),
    ("jit(round_fn)/heron_cohort_x/mul", "heron_cohort", False),
    ("jit(round_fn)/xheron_cohort/mul", "heron_cohort", False),
    ("jit(heron_cohort_fn)/mul", "heron_cohort", False),
    ("", "heron_cohort", False),
])
def test_a_scope_matches_whole_path_components(op_name, scope, held):
    assert PH.holds(op_name, scope) is held


def test_phase_self_times_split_the_round_program():
    """The feed's ops are another program's; the aux head is counted
    inside the cohort; a bare fusion is its fused root's; the last call
    is clipped at the window's end."""
    r = _record()
    assert PH.phase_seconds(r, "heron_cohort") == pytest.approx(2.0)
    assert PH.phase_seconds(r, "heron_aux_head") == pytest.approx(2.0)
    assert PH.phase_seconds(r, "heron_server_fo") == pytest.approx(2.0)
    assert PH.phase_seconds(r, "heron_replay") == pytest.approx(0.55)
    # the look-alike scope and the copy with no metadata
    assert PH.unattributed_seconds(r) == pytest.approx(0.5)
    assert sum(s for _, s in PH.op_seconds(r)) == pytest.approx(5.05)


def test_exclusive_shares_add_up_to_the_busy_union():
    """A nested op keeps its self time; an op that starts inside another
    and outlasts it takes the overlap, where ``Event.own`` counts more
    than the busy time; the window clips."""
    evs = [E("a", 0.0, 10.0), E("b", 1.0, 2.0),   # b nested in a
           E("c", 2.0, 4.0),                      # c outlasts b
           E("d", 12.5, 1.0),                     # clipped at 13
           E("e", -1.0, 2.0)]                     # a started later
    share = PH.exclusive(evs, 0.0, 13.0)
    assert share == pytest.approx([5.0, 1.0, 4.0, 0.5, 0.0])
    assert sum(share) == pytest.approx(
        sum(b - a for a, b in TR.merged(evs, 0.0, 13.0)))
    raw = [(f"%{e.name}.1 = f32[] add()", e.start, e.dur) for e in evs[:3]]
    own = sum(e.own for e in TR.device_events(raw, []))
    assert own == pytest.approx(12.0)     # 10 busy


def test_a_program_without_scopes_or_a_trace_reads_nothing():
    r = _record()
    r["op_scopes"] = {"jit_round_fn": {n: op.replace("heron_", "x_")
                                       for n, op in PH.op_names(HLO).items()}}
    assert PH.phase_seconds(r, "heron_cohort") is None
    assert PH.unattributed_seconds(r) is None
    r = _record()
    r["trace"] = TR.Trace({}, r["trace"].spans)
    assert PH.phase_seconds(r, "heron_replay") is None
    assert PH.phase_seconds({"steps": 1}, "heron_replay") is None


def test_split_accounts_for_the_round_and_the_gap_between_rounds(tmp_path):
    reg = tiny.registry(tmp_path)
    out = PS.split(reg, "fed-tiny", _record())
    ms = out["ms_per_round"]
    assert ms["round_busy"] == pytest.approx(
        ms["heron_cohort"] + ms["heron_server_fo"] + ms["heron_replay"]
        + ms["unattributed"])
    assert ms["round_own"] == pytest.approx(ms["round_busy"])
    # 1-2, 2-2.5, 3-5, 5.5-6, 6-6.25, 6.5-6.8, 9.5-10 over two rounds
    assert ms["round_union"] == pytest.approx(1000 * 5.05 / 2)
    assert ms["kernels"] == pytest.approx(1000 * 1.0 / 2)
    assert out["tflop_per_s"]["client"] > 0
    assert [row[0] for row in out["top_unattributed"]] == [
        "fusion.8", "copy-start.1"]
    gap = out["between_rounds_ms"]
    assert gap["block_lag"]["median"] == pytest.approx(50.0)
    assert gap["host"]["median"] == pytest.approx(50.0)
    assert gap["dispatch"]["median"] == pytest.approx(100.0)
    assert gap["idle"]["median"] == pytest.approx(200.0)
    assert gap["block_lag"]["min"] == pytest.approx(50.0)
    assert gap["idle"]["n"] == 1


def test_traced_run_keeps_the_round_programs_instruction_names(tmp_path):
    """On the CPU the trace has no device ops, so there is no split; the
    names of the round's instructions carry every phase."""
    reg = tiny.registry(tmp_path)
    res = PS.traced_run(reg, "fed-tiny", SEED, 0.5, "TPU v5 lite")
    names = res.record["op_scopes"]["jit_round_fn"]
    for scope in (*PH.PHASES, PH.AUX_HEAD):
        assert any(PH.holds(op, scope) for op in names.values()), scope
    assert PS.split(reg, "fed-tiny", res.record) is None
    assert res.correct
