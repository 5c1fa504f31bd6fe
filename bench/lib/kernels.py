"""The Pallas calls of a compiled program, and the roofline of their work.

A compiled TPU program names each Pallas call after the jitted wrapper
that made it (``%vmap_jit_zo_dual_matmul__.1``), and prints the call's
operand shapes (``operand_layout_constraints``), its result shapes and
the wrapper in ``metadata.op_name``.  The device trace names the call's
events after the same instruction.  The harness parses the calls of
every program a window drives into the record (``programs``); a roofline
metric under ``metrics/`` picks its kernel's calls and counts the work
each needs from the shapes it receives.  So a change that moves work
into or out of a kernel changes the counted work and the kernel time
together, and a new kernel's roofline is one new metric file.
"""
from __future__ import annotations

import math
import re

DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s32": 4, "u32": 4,
               "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s64": 8, "u64": 8,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*(?P<result>.*?)"
                    r"\s*custom-call\(")
_SHAPE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_JIT = re.compile(r"jit\((\w+)\)")


def shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    out = []
    for dt, dims in _SHAPE.findall(text):
        if dt in DTYPE_BYTES:
            out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def nbytes(shapes_) -> int:
    return sum(DTYPE_BYTES[dt] * math.prod(dims) for dt, dims in shapes_)


def module_name(hlo_text: str) -> str:
    """The program's name, as the trace's ``XLA Modules`` line gives it."""
    return hlo_text.split(None, 2)[1].rstrip(",")


def parse_custom_calls(hlo_text: str) -> dict[str, dict]:
    """``{instruction name: {op_name, wrapper, operands, results}}`` for
    every ``tpu_custom_call`` of a compiled program's HLO text."""
    calls = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        # the operand list runs up to the next attribute; its layouts
        # ('{1,0}') hold no '[', so the shape regex sees only dtype[dims]
        ops_txt = ""
        ops_full = line.split("operand_layout_constraints={", 1)
        if len(ops_full) > 1:
            ops_txt = ops_full[1].split("frontend_attributes=", 1)[0]
            ops_txt = ops_txt.split("metadata=", 1)[0]
        op = _OP_NAME.search(line)
        op_name = op.group(1) if op else ""
        jits = _JIT.findall(op_name)
        calls[m.group("name")] = {
            "op_name": op_name,
            # the innermost jitted function around the call
            "wrapper": jits[-1] if jits else m.group("name"),
            "operands": shapes(ops_txt),
            "results": shapes(m.group("result")),
        }
    return calls


def lead(dims, core: int) -> int:
    """Product of the leading (vmapped) axes in front of ``core`` axes."""
    return math.prod(dims[:len(dims) - core]) if len(dims) > core else 1


def arrays(call: dict) -> list[tuple[str, tuple[int, ...]]]:
    """The call's array operands, in order: the scalar ones (seed, mu,
    offset; SMEM) are (.., 1, 1) or (1, 2) and left out."""
    return [o for o in call["operands"]
            if len(o[1]) >= 2 and min(o[1][-2:]) > 2]


def io_bytes(call: dict) -> int:
    """One read of every operand and one write of every result."""
    return nbytes(call["operands"]) + nbytes(call["results"])


def least_seconds(w: dict, peak: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(w["flops"] / peak["bf16_flops_per_s"],
               w["bytes"] / peak["hbm_bytes_per_s"])
