"""The reduction from a trace to busy time, idle gaps and kernel time, on
a small synthetic event list."""
import pytest

from lib import roofline
from lib import trace as TR

E = TR.Event


def _trace():
    ops = {"/device:TPU:0": [
        E("fusion.1", 1.0, 2.0, "jit_round"),       # 1-3
        E("k_matmul.1", 2.5, 1.0, "jit_round"),     # 2.5-3.5 overlaps
        E("k_matmul.1", 5.0, 1.0, "jit_round"),     # 5-6
        E("k_flash.1", 8.0, 0.5, "jit_round"),      # 8-8.5
        E("fusion.2", 9.5, 2.0, "jit_round"),       # 9.5-11.5, clipped
        E("k_matmul.1", 12.0, 1.0, "jit_round"),    # after the window
    ]}
    spans = [E(TR.WINDOW_SPAN, 0.0, 10.0),
             E("bench.make_inputs", 3.6, 1.0),      # covers gap 3.5-5
             E("bench.round", 6.2, 1.5),            # covers gap 6-8
             E("bench.block", 6.3, 0.5)]            # inner, ends before 7.0
    return TR.Trace(ops, spans)


def test_busy_union_clips_to_window():
    assert TR.busy_seconds(_trace()) == pytest.approx(
        2.5 + 1.0 + 0.5 + 0.5)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    gaps = TR.idle_gaps(_trace())
    assert [g[0] for g in gaps] == [
        "bench.round", "bench.make_inputs",
        "host outside any bench span", "host outside any bench span"]
    assert [g[1] for g in gaps] == pytest.approx([2.0, 1.5, 1.0, 1.0])


def test_op_seconds_sum_per_op_inside_the_window():
    ops = dict(TR.op_seconds(_trace()))
    assert ops["jit_round/k_matmul.1"] == pytest.approx(2.0)
    assert ops["jit_round/fusion.2"] == pytest.approx(0.5)


def test_kernel_share_sums_least_time_over_kernel_time():
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    work = {"zo_dual_matmul": {"flops": 3e11, "bytes": 1e10},
            "zo_dual_flash_attention": {"flops": 1e10, "bytes": 2e10}}
    record = {"trace": _trace(), "peak": peak, "programs": {
        "jit_round": {"k_matmul.1": {"wrapper": "zo_dual_matmul"},
                      "k_flash.1": {"wrapper": "zo_dual_flash_attention"}},
        # a program the window did not run: its calls find no events
        "jit_other": {"k_matmul.1": {"wrapper": "zo_dual_matmul"}}}}

    def share(wrapper):
        return roofline.share(record, lambda c: c["wrapper"] == wrapper,
                              lambda c: work[c["wrapper"]])
    # two matmul calls in the window, 2 s of device time, each needing
    # max(0.3 s compute, 0.1 s memory)
    assert share("zo_dual_matmul") == pytest.approx(100 * 0.6 / 2.0)
    # the flash call is memory-bound: 0.2 s of 0.5 s
    assert share("zo_dual_flash_attention") == pytest.approx(40.0)
    assert share("flash_attention") is None


def test_device_events_name_nest_and_attribute_ops():
    ops = [("%while.3 = (s32[], bf16[4]) while(%t), body=%b", 1.0, 4.0),
           ("%zo_dual_matmul.1 = (bf16[4,8]{1,0}) custom-call(%x)", 1.5, 1.0),
           ("%fusion.2 = f32[8]{0} fusion(%p), kind=kLoop", 3.0, 0.5),
           ("%copy.1 = f32[8]{0} copy(%q)", 7.0, 0.5)]
    mods = [("jit_round_fn(123)", 0.5, 5.0), ("jit__make_inputs(9)", 6.5, 1.0)]
    evs = {e.name: e for e in TR.device_events(ops, mods)}
    assert set(evs) == {"while.3", "zo_dual_matmul.1", "fusion.2", "copy.1"}
    assert evs["while.3"].own == pytest.approx(2.5)
    assert evs["zo_dual_matmul.1"].own == pytest.approx(1.0)
    assert evs["zo_dual_matmul.1"].module == "jit_round_fn"
    assert evs["copy.1"].module == "jit__make_inputs"
    tr = TR.Trace({"/device:TPU:0": list(evs.values())},
                  [E(TR.WINDOW_SPAN, 0.0, 10.0)])
    assert TR.busy_seconds(tr) == pytest.approx(4.5)
    assert dict(TR.op_seconds(tr))["jit_round_fn/while.3"] == \
        pytest.approx(2.5)
    assert [e.name for e in TR.kernel_events(tr, ["zo_dual_matmul.1"],
                                             "jit_round_fn")] == \
        ["zo_dual_matmul.1"]
    assert TR.kernel_events(tr, ["zo_dual_matmul.1"], "jit_other") == []
