"""A temporary benchmark registry at a size the CPU runs in seconds: a
GPT-2-shaped model of 4 blocks (width 64, 4 heads, vocab 211, bf16), with
the benchmark's own drivers, references and metric readers."""
from __future__ import annotations

import json
import os
import pathlib

from lib.registry import BENCH, Registry

FED_LIMITS = {"loss_gap": 1.5e-4, "grad_gap": 0.01, "change_gap": 0.1}
SERVE_LIMITS = {"logit_gap": 0.005}


def registry(root: pathlib.Path, fed_limits=None, serve_limits=None,
             modules=("drivers", "models", "references", "metrics")
             ) -> Registry:
    b = root / "bench"
    for d in ("configs", "traffic", "cells"):
        (b / d).mkdir(parents=True, exist_ok=True)
    for d in modules:
        os.symlink(BENCH / d, b / d)
    cfg = json.loads((BENCH / "configs" / "gpt2-medium.json").read_text())
    cfg.update(name="tiny", constructor="repro.configs.gpt2:gpt2_tiny",
               overrides={"forward_impl": "kernel",
                          "param_dtype": "bfloat16",
                          "compute_dtype": "bfloat16"},
               n_layer=4, n_embd=64, n_head=4, n_inner=256, vocab_size=211)
    cfg["assumed"] = dict(cfg["assumed"], cut_layers=1, aux_layers=1)
    (b / "configs" / "tiny.json").write_text(json.dumps(cfg))
    fed = json.loads((BENCH / "traffic" / "fed-s1024-b4.json").read_text())
    fed.update(clients=2, micro_batch=4, seq=16)
    (b / "traffic" / "fed-tiny.json").write_text(json.dumps(fed))
    serve = {"driver": "serve_open_loop", "slots": 4, "capacity": 64,
             "segment_len": 4, "rate_per_s": 20.0, "size_seed": 7,
             "prompt_buckets": [8, 16, 24], "prompt_weights": [0.5, 0.3, 0.2],
             "out_median": 8, "out_sigma": 0.6, "out_min": 2, "out_max": 32,
             "drain_s": 30, "check_tokens": 40, "calibrate_s": 1.0}
    (b / "traffic" / "serve-tiny.json").write_text(json.dumps(serve))
    (b / "cells" / "fed-tiny.json").write_text(json.dumps(
        {"limits": fed_limits or FED_LIMITS}))
    (b / "cells" / "serve-tiny.json").write_text(json.dumps(
        {"limits": serve_limits or SERVE_LIMITS}))
    e2e = [{"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
            "source": "host_clock"},
           {"name": "round_s", "unit": "s", "better": "lower", "bound": 0.01,
            "source": "host_clock", "workloads": ["fed-tiny"]},
           {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
            "bound": 0.1, "source": "host_clock",
            "workloads": ["serve-tiny"]}]
    spec = {"command": ["python3", "bench/run.py"], "paths": ["bench"],
            "run_seconds": 1,
            "configs": [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}],
            "workloads": [{"name": "fed-tiny", "config": "tiny",
                           "traffic": "fed-tiny", "chips": 1, "why": "t"},
                          {"name": "serve-tiny", "config": "tiny",
                           "traffic": "serve-tiny", "chips": 1, "why": "t"}],
            "end_to_end": e2e, "per_layer": []}
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return Registry(b)


def reference_round(cell, precision: str):
    """The plain reference's round for a fed cell, in ``precision``."""
    ref = Registry().reference(cell.cfg_json["reference"])
    return ref.Round(cell.cfg_json, cell.traffic, precision)
