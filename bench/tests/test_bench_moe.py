"""The Moonlight cell's pieces at a size the CPU runs: its model FLOPs
against a count by hand, a sound run of the tiny MoE round against the
plain reference (``references/deepseek_v3.py``), the broken timed paths
it must refuse, the ``moe_rows`` counter a traced run records, and the
work its two kernel readers count, and the by-hand split by scope."""
import json

import jax
import jax.numpy as jnp
import pytest

import run
import tiny_moe
from lib import kernels as K
from lib import trace as TR
from lib.registry import BENCH, Registry

SEED = 2 ** 31 + 11
F = Registry().model("deepseek_v3")


def _json(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def test_moonlight_round_by_hand():
    cfg, t = _json("configs", "moonlight-16b-a3b"), _json("traffic",
                                                          "fed-s8192-b1")
    d, H, S, V = 2048, 16, 8192, 20480
    mla = d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d
    attn = 2 * H * (192 + 128) * S * (S + 1) // 2       # per sequence
    dense = S * 2 * (mla + 3 * d * 11264) + attn
    moe = S * 2 * (mla + d * 64 + 3 * d * 2816 + 0.75 * 3 * d * 1408) + attn
    vocab = S * 2 * d * V
    n_seqs = 4
    client = 2 * n_seqs * (dense + 2 * moe + vocab)   # 2 blocks + aux
    server = 3 * n_seqs * (4 * moe + vocab)
    got = F.fed_round_flops(cfg, t)
    assert got["client"] == pytest.approx(client, rel=1e-12)
    assert got["server"] == pytest.approx(server, rel=1e-12)
    assert got["total"] == pytest.approx(88.87e12, rel=1e-3)
    # per token: client forward 526 MFLOP, the dense layer 208
    assert (dense + 2 * moe + vocab) / S == pytest.approx(526.4e6, rel=1e-3)
    assert dense / S == pytest.approx(207.9e6, rel=1e-3)


def _run(tmp_path, wrap_step=None, trace=False):
    reg = tiny_moe.registry(tmp_path)
    res, per_layer = run.run_cell(reg, "fed-moe-tiny", SEED, 0.5, trace,
                                  "TPU v5 lite", wrap_step=wrap_step)
    return reg, res, per_layer


def test_sound_run_is_correct_and_counts_rows(tmp_path):
    reg, res, per_layer = _run(tmp_path, trace=True)
    assert res.correct, [(c.name, c.value, c.limit) for c in res.checks]
    assert res.failed == 0 and res.metrics["round_s"] > 0
    # 2 clients x 2 streams x 2 MoE layers x about 4 x 32 x 2 / 8 rows
    rounds = res.record["steps"]
    assert 0 < res.record["moe_rows"] <= rounds * 2 * 2 * 2 * 4 * 32 * 2
    # no Pallas call runs on the CPU: the kernel readers find nothing
    for name in ("zo_dual_grouped_matmul_roofline",
                 "zo_dual_flash_attention_mla_roofline"):
        assert reg.metric(name).read(res.record) is None
    assert set(per_layer) == {"round_mfu", "device_idle_share.train",
                              "client_peak_bytes"}


def _control(step, cell):
    return Registry().reference(cell.cfg_json["reference"]).Round(
        cell.cfg_json, cell.traffic, "fp8")


def _state_unchanged(step, cell):
    def f(state, batch, key):
        _, m = step(jax.tree.map(jnp.copy, state), batch, key)
        return state, m
    return f


def _half_batch(step, cell):
    half = jax.jit(cell.round_fn)

    def f(state, batch, key):
        b = batch["inputs"].shape[2] // 2
        return half(state, jax.tree.map(lambda x: x[:, :, :b], batch), key)
    return f


@pytest.mark.parametrize("broken", [_control, _state_unchanged, _half_batch],
                         ids=["control_fp8", "state_unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct(tmp_path, broken):
    _, res, _ = _run(tmp_path, wrap_step=broken)
    assert not res.correct, [(c.name, c.value, c.limit) for c in res.checks]


GROUPED = (
    '  %grouped.1 = (bf16[1536,1408]{1,0}, bf16[1536,1408]{1,0}) '
    'custom-call(%a, %b, %c, %d, %e, %f, %g, %h, %x, %y, %w), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{s32[24]{0}, s32[24]{0}, s32[24]{0}, s32[24]{0}, s32[24]{0}, '
    's32[1,1]{1,0}, f32[1,2]{1,0}, s32[1,1]{1,0}, bf16[1536,2048]{1,0}, '
    'bf16[1536,2048]{1,0}, bf16[8,2048,1408]{2,1,0}}, metadata={op_name='
    '"jit(round_fn)/heron_moe_experts/jit(zo_dual_grouped_matmul)/while/'
    'body/pallas_call"}')
MLA_FLASH = (
    '  %fa.2 = (bf16[16,8192,128]{2,1,0}, bf16[16,8192,128]{2,1,0}) '
    'custom-call(%a, %b, %c, %d, %e, %f, %g, %h, %i), custom_call_target='
    '"tpu_custom_call", operand_layout_constraints={s32[1,1]{1,0}, '
    'f32[1,2]{1,0}, s32[1,1]{1,0}, bf16[16,8192,192]{2,1,0}, '
    'bf16[16,8192,192]{2,1,0}, bf16[16,8192,192]{2,1,0}, '
    'bf16[16,8192,128]{2,1,0}, bf16[16,8192,192]{2,1,0}, '
    'bf16[16,8192,128]{2,1,0}}, metadata={op_name="jit(round_fn)/heron_mla/'
    'jit(zo_dual_flash_attention)/pallas_call"}')


def _record(hlo, events, rows=None):
    spans = [TR.Event(TR.WINDOW_SPAN, 0.0, 10.0)]
    rec = {"programs": {"jit_round_fn": K.parse_custom_calls(hlo)},
           "trace": TR.Trace({"/device:TPU:0": [
               TR.Event(n, s, d, "jit_round_fn") for n, s, d in events]},
               spans),
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    if rows is not None:
        rec["moe_rows"] = rows
    return rec


def test_grouped_roofline_counts_work_from_the_rows_counter():
    """Three projections of 2 K N FLOPs per counted row, whatever the
    padded buffers hold; bytes: each call's expert weights plus the rows'
    inputs and outputs; the larger bound over the calls' time."""
    rows, dur = 12_288, 2e-3
    rec = _record(GROUPED, [("grouped.1", 1.0, dur), ("grouped.1", 2.0, dur),
                            ("grouped.1", 3.0, dur)], rows)
    flops = rows * 3 * 2 * 2048 * 1408
    moved = 3 * 8 * 2048 * 1408 * 2 + rows * 3 * (2048 + 1408) * 2
    want = max(flops / 197e12, moved / 819e9) / (3 * dur) * 100
    got = Registry().metric("zo_dual_grouped_matmul_roofline").read(rec)
    assert got == pytest.approx(want)
    assert Registry().metric("zo_dual_grouped_matmul_roofline").read(
        _record(GROUPED, [("grouped.1", 1.0, dur)])) is None


def test_mla_flash_work_counts_both_head_dims():
    call = K.parse_custom_calls(MLA_FLASH)["fa.2"]
    m = Registry().metric("zo_dual_flash_attention_mla_roofline")
    assert m.match(call)
    w = m.work(call)
    assert w["flops"] == 2 * 2 * 16 * (8192 * 8193 // 2) * (192 + 128)
    assert w["bytes"] == K.io_bytes(call)


SCOPED = """HloModule jit_round_fn, is_scheduled=true

ENTRY %main.5 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %dot.1 = f32[8]{0} dot(%a, %a), metadata={op_name="jit(round_fn)/heron_cohort/while/body/heron_mla/dot_general"}
  %dot.2 = f32[8]{0} dot(%a, %a), metadata={op_name="jit(round_fn)/heron_server_fo/while/body/transpose(jvp(heron_moe_experts))/ragged_dot"}
  %grouped.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_fn)/heron_cohort/vmap(heron_moe_experts)/jit(zo_dual_grouped_matmul)/pallas_call"}
  ROOT %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(round_fn)/heron_server_fo/heron_moe_route/top_k"}
}
"""


def test_scope_split_reads_each_scope_within_each_phase():
    import scope_split as SS
    from lib import phases as PH
    ops = [("%dot.1 = f32[8]{0} dot(%a, %a)", 1.0, 0.5),
           ("%grouped.1 = f32[8]{0} custom-call(%a)", 2.0, 1.0),
           ("%dot.2 = f32[8]{0} dot(%a, %a)", 3.5, 0.25),
           ("%fusion.3 = f32[8]{0} fusion(%a)", 4.0, 0.125)]
    tr = TR.Trace({"/device:TPU:0": TR.device_events(
        ops, [("jit_round_fn(2)", 1.0, 4.0)])},
        [TR.Event(TR.WINDOW_SPAN, 0.0, 10.0)])
    rec = {"kind": "fed_round", "steps": 2, "trace": tr, "moe_rows": None,
           "op_scopes": {"jit_round_fn": PH.op_names(SCOPED)},
           "programs": {"jit_round_fn": {"grouped.1": {
               "wrapper": "zo_dual_grouped_matmul"}}},
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    got = SS.scopes_ms(Registry(), rec, SS.SCOPES,
                       ["zo_dual_grouped_matmul_roofline"])
    assert got == {
        "heron_cohort/heron_mla": 250.0,
        "heron_server_fo/heron_mla": 0.0,
        "heron_cohort/heron_moe_route": 0.0,
        "heron_server_fo/heron_moe_route": 62.5,
        "heron_cohort/heron_moe_experts": 500.0,
        "heron_server_fo/heron_moe_experts": 125.0,
        "kernel/zo_dual_grouped_matmul_roofline": 500.0,
        "read/zo_dual_grouped_matmul_roofline": None}
