"""A temporary benchmark registry at a size the CPU runs in seconds: a
Moonlight-shaped model (MLA, a leading dense layer, dropless sigmoid-
routed MoE layers holding 4 of 8 experts, 2 shared; width 64, 4 heads,
vocab 257, bf16), with the benchmark's own drivers, references and
metric readers, under the fed mix ``fed-moe-tiny``."""
from __future__ import annotations

import json
import os
import pathlib

from lib.registry import BENCH, Registry

LIMITS = {"loss_gap": 6e-5, "grad_gap": 5.5e-3, "change_gap": 0.25}


def registry(root: pathlib.Path, limits=None) -> Registry:
    b = root / "bench"
    for d in ("configs", "traffic", "cells"):
        (b / d).mkdir(parents=True, exist_ok=True)
    for d in ("drivers", "models", "references", "metrics"):
        os.symlink(BENCH / d, b / d)
    cfg = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json")
                     .read_text())
    cfg.update(name="moe-tiny",
               constructor="repro.configs.moonlight_16b_a3b:smoke_config",
               overrides={"forward_impl": "kernel",
                          "param_dtype": "bfloat16",
                          "compute_dtype": "bfloat16"},
               num_hidden_layers=4, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, intermediate_size=96, vocab_size=257,
               kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
               v_head_dim=8, n_routed_experts=4, num_experts_per_tok=2,
               moe_intermediate_size=32)
    cfg["published"] = dict(cfg["published"], n_routed_experts=8)
    (b / "configs" / "moe-tiny.json").write_text(json.dumps(cfg))
    fed = json.loads((BENCH / "traffic" / "fed-s8192-b1.json").read_text())
    fed.update(clients=2, micro_batch=4, seq=32)
    (b / "traffic" / "fed-moe-tiny.json").write_text(json.dumps(fed))
    (b / "cells" / "fed-moe-tiny.json").write_text(json.dumps(
        {"limits": limits or LIMITS}))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec.update(
        run_seconds=1,
        configs=[{"name": "moe-tiny", "source": "test",
                  "file": "bench/configs/moe-tiny.json",
                  "reduced": [], "why": "test"}],
        workloads=[{"name": "fed-moe-tiny", "config": "moe-tiny",
                    "traffic": "fed-moe-tiny", "chips": 1, "why": "t"}])
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["fed-moe-tiny"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return Registry(b)
