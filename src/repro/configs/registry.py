"""Architecture registry: ``--arch <id>`` resolution."""
from __future__ import annotations

from repro.configs import (command_r_35b, gemma2_27b, kimi_k2_1t_a32b,
                           moonlight_16b_a3b, qwen2_1_5b, qwen2_5_32b,
                           qwen2_vl_2b, qwen3_moe_30b_a3b,
                           recurrentgemma_9b, seamless_m4t_medium,
                           xlstm_1_3b)

_MODULES = {
    "qwen2-1.5b": qwen2_1_5b,
    "command-r-35b": command_r_35b,
    "qwen2.5-32b": qwen2_5_32b,
    "gemma2-27b": gemma2_27b,
    "kimi-k2-1t-a32b": kimi_k2_1t_a32b,
    "moonlight-16b-a3b": moonlight_16b_a3b,
    "qwen3-moe-30b-a3b": qwen3_moe_30b_a3b,
    "seamless-m4t-medium": seamless_m4t_medium,
    "xlstm-1.3b": xlstm_1_3b,
    "recurrentgemma-9b": recurrentgemma_9b,
    "qwen2-vl-2b": qwen2_vl_2b,
}

ARCH_IDS = tuple(_MODULES)


def get_config(arch: str, smoke: bool = False):
    mod = _MODULES[arch]
    return mod.smoke_config() if smoke else mod.full_config()
