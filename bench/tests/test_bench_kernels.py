"""The work of each Pallas call, counted by its roofline metric's file from
the shapes a compiled program gives it."""
from lib import kernels as K
from lib.registry import Registry

MATMUL = (
    '  %vmap_jit_zo_dual_matmul__.1 = (bf16[4,256,512]{2,1,0:T(8,128)(2,1)}, '
    'bf16[4,256,512]{2,1,0:T(8,128)(2,1)}) custom-call(%copy, %c.11, %c.0, '
    '%x.1, %x.1, /*index=5*/%w.1), custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={s32[4,1,1]{2,1,0}, f32[1,2]{1,0}, '
    's32[1,1]{1,0}, bf16[4,256,1024]{2,1,0}, bf16[4,256,1024]{2,1,0}, '
    'bf16[4,1024,512]{2,1,0}}, frontend_attributes={kernel_metadata={}}, '
    'metadata={op_name="jit(f)/vmap(jit(zo_dual_matmul))/pallas_call" '
    'stack_frame_id=6}, backend_config={"custom_call_config":{"body":"x"}}')
FLASH = (
    '  %zo_dual_flash_attention.1 = (bf16[8,256,64]{2,1,0}, '
    'bf16[8,256,64]{2,1,0}) custom-call(%a, %b, %c, %d, %e, %f, %g, %h, %i), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{s32[1,1]{1,0}, f32[1,2]{1,0}, s32[1,1]{1,0}, bf16[8,256,64]{2,1,0}, '
    'bf16[8,256,64]{2,1,0}, bf16[8,256,64]{2,1,0}, bf16[8,256,64]{2,1,0}, '
    'bf16[8,256,64]{2,1,0}, bf16[8,256,64]{2,1,0}}, metadata={op_name='
    '"jit(round_fn)/while/body/checkpoint/jit(zo_dual_flash_attention)/'
    'pallas_call"}')
OTHER = '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop'
HLO = "HloModule jit_round_fn, entry_computation_layout={}\n" + "\n".join(
    [MATMUL, OTHER, FLASH])


def _metric(name):
    return Registry().metric(name)


def test_parse_names_families_and_shapes():
    assert K.module_name(HLO) == "jit_round_fn"
    calls = K.parse_custom_calls(HLO)
    assert set(calls) == {"vmap_jit_zo_dual_matmul__.1",
                          "zo_dual_flash_attention.1"}
    mm = calls["vmap_jit_zo_dual_matmul__.1"]
    assert mm["wrapper"] == "zo_dual_matmul"
    assert calls["zo_dual_flash_attention.1"]["wrapper"] == \
        "zo_dual_flash_attention"
    assert mm["operands"][-1] == ("bf16", (4, 1024, 512))
    assert mm["results"] == [("bf16", (4, 256, 512))] * 2
    # each roofline metric picks its own kernel's calls and no other
    for name, call in (("zo_dual_matmul_roofline", mm),
                       ("zo_dual_flash_attention_roofline",
                        calls["zo_dual_flash_attention.1"])):
        assert [n for n, c in calls.items() if _metric(name).match(c)] == \
            [n for n, c in calls.items() if c is call]


def test_dual_matmul_work_from_operand_shapes():
    w = _metric("zo_dual_matmul_roofline").work(
        K.parse_custom_calls(MATMUL)["vmap_jit_zo_dual_matmul__.1"])
    # 4 vmapped clients x 2 streams x 2 x M K N
    assert w["flops"] == 4 * 2 * 2 * 256 * 1024 * 512
    # scalars s32[4,1,1] f32[1,2] s32[1,1]; bf16 xa, xb, w; bf16 ya, yb
    operands = 16 + 8 + 4 + 2 * 2 * (4 * 256 * 1024) + 2 * (4 * 1024 * 512)
    assert w["bytes"] == operands + 2 * 2 * (4 * 256 * 512)


def test_dual_flash_work_is_causal():
    w = _metric("zo_dual_flash_attention_roofline").work(
        K.parse_custom_calls(FLASH)["zo_dual_flash_attention.1"])
    assert w["flops"] == 2 * 4 * 8 * (256 * 257 // 2) * 64
    assert w["bytes"] == 4 + 8 + 4 + 8 * 2 * (8 * 256 * 64)


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert K.least_seconds({"flops": 300, "bytes": 10}, peak) == 3.0
    assert K.least_seconds({"flops": 100, "bytes": 50}, peak) == 5.0
