"""Roofline derivation from a compiled dry-run artifact.

Three terms (seconds, per chip), against the peaks of the chip the
program targets (:data:`PEAKS`, keyed by ``jax.Device.device_kind``):

    compute    = HLO_FLOPs            / peak_FLOP/s
    memory     = HLO_bytes_accessed   / HBM_bw
    collective = collective_bytes     / link_bw

``cost_analysis()`` of an SPMD-partitioned executable reports the
*per-device* program, so no further division by chip count is applied.
Collective bytes are parsed from the post-SPMD HLO text with a
ring-model traffic estimate per op kind.
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float             # bf16 FLOP/s
    hbm_bw: float            # HBM bytes/s
    ici_bw: float            # interconnect bytes/s per link


# Published per-chip peaks.  TPU v5e: Google Cloud documentation, "TPU
# v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of interconnect
# (200 GB/s over 4 links).
PEAKS = {"TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9)}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a kind without published peaks is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|all-reduce-start|all-gather-start|"
    r"reduce-scatter-start|collective-permute-start)\b")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))           # [num_groups, group_size]
    m = _GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return 2


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_op: dict
    total_bytes: float
    count: int


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Per-chip ring-model traffic summed over all collective ops."""
    by_op: dict[str, float] = {}
    count = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op").replace("-start", "")
        out_bytes = _shape_bytes(m.group("shape"))
        g = max(_group_size(line), 2)
        if op == "all-reduce":
            traffic = 2.0 * out_bytes * (g - 1) / g
        elif op == "all-gather":
            traffic = out_bytes * (g - 1) / g       # output is the full buf
        elif op == "reduce-scatter":
            traffic = out_bytes * (g - 1)           # output is the shard
        elif op == "all-to-all":
            traffic = out_bytes * (g - 1) / g
        else:  # collective-permute
            traffic = float(out_bytes)
        by_op[op] = by_op.get(op, 0.0) + traffic
        count += 1
    return CollectiveStats(by_op, sum(by_op.values()), count)


def roofline_terms(compiled, lowered_text: str | None = None, *,
                   device_kind: str):
    """Returns dict with the three terms + raw inputs, against the peaks
    of ``device_kind`` (the chip the program was compiled for).

    FLOPs/bytes/collectives come from the scan-aware HLO analyzer
    (launch/hlo_costs.py) because ``cost_analysis()`` counts while-loop
    bodies once; the raw cost_analysis numbers are kept for reference.
    """
    from repro.launch import hlo_costs as HC
    peaks = peaks_for(device_kind)
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    text = lowered_text if lowered_text is not None else compiled.as_text()
    tc = HC.total_costs(text)
    flops = float(tc["flops"])
    bytes_accessed = float(tc["bytes"])
    terms = {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "collective_bytes": tc["collective_bytes"],
        "collective_by_op": tc["collectives"],
        "raw_cost_analysis": {"flops": float(ca.get("flops", 0.0)),
                              "bytes": float(ca.get("bytes accessed",
                                                    0.0))},
        "compute_s": flops / peaks.flops,
        "memory_s": bytes_accessed / peaks.hbm_bw,
        "collective_s": tc["collective_bytes"] / peaks.ici_bw,
    }
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    terms["bottleneck"] = dom.replace("_s", "")
    step_time = max(terms["compute_s"], terms["memory_s"],
                    terms["collective_s"])
    terms["roofline_step_s"] = step_time
    terms["compute_fraction"] = (terms["compute_s"] / step_time
                                 if step_time > 0 else 0.0)
    return terms


def memory_summary(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        out["total_hbm_bytes"] = (out.get("argument_size_in_bytes", 0)
                                  + out.get("temp_size_in_bytes", 0)
                                  + out.get("output_size_in_bytes", 0)
                                  - out.get("alias_size_in_bytes", 0))
    return out


def model_flops(cfg, n_tokens: int, n_params_active: int) -> float:
    """6·N_active·D — the useful-compute yardstick."""
    return 6.0 * n_params_active * n_tokens
