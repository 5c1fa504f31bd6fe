"""The phase scopes of a compiled HERON round, read from its HLO text.

The round names its phases with ``jax.named_scope``: ``heron_cohort``,
``heron_server_fo`` and ``heron_replay``, with ``heron_aux_head`` inside
the cohort.  A scope shows in an instruction's ``metadata.op_name`` as a
path component, bare or wrapped in transform names (``vmap(...)``,
``transpose(jvp(...))``)."""
import re

PHASES = ("heron_cohort", "heron_server_fo", "heron_replay")
AUX_HEAD = "heron_aux_head"

_HEADER = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=(.*)$")
_COMPUTE = re.compile(r"\s(dot|convolution|custom-call)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
# scalar bodies of reductions, sorts and scatters: their op_name is
# relative to the caller's, and they run as part of it
_APPLIED = re.compile(r"\b(?:to_apply|comparator)=%([\w.\-]+)")
_WRAP = re.compile(r"^(?:[\w.\-]+\()*|\)*$")


def scopes(op_name: str) -> set[str]:
    """The bare names of an ``op_name``'s path components."""
    return {_WRAP.sub("", c) for c in op_name.split("/")}


def instructions(hlo_text: str):
    """``(name, text after '=', op_name)`` of every instruction outside
    the scalar bodies of reductions, sorts and scatters."""
    applied = set(_APPLIED.findall(hlo_text))
    comp = None
    for line in hlo_text.splitlines():
        h = _HEADER.match(line)
        if h is not None:
            comp = h.group(1)
            continue
        m = _INSTR.match(line)
        if m is not None and comp not in applied:
            op = _OP_NAME.search(line)
            yield m.group(1), m.group(2), op.group(1) if op else ""


def phase_faults(hlo_text: str, targets=None) -> list[tuple[str, str]]:
    """``(instruction, op_name)`` of each instruction that breaks the
    contract: a dot, a convolution or a custom-call (of a target in
    ``targets``, where given) under not exactly one phase, or an
    instruction of the aux head outside the cohort."""
    faults = []
    for name, rest, op_name in instructions(hlo_text):
        s = scopes(op_name)
        kind = _COMPUTE.search(rest.split(", metadata=")[0])
        if kind and kind.group(1) == "custom-call" and targets is not None:
            kind = kind if _TARGET.search(rest).group(1) in targets else None
        if (kind and len(s & set(PHASES)) != 1) or \
                (AUX_HEAD in s and "heron_cohort" not in s):
            faults.append((name, op_name))
    return faults


def named(hlo_text: str) -> set[str]:
    """The phases and the aux head, where some instruction carries them."""
    found = set()
    for _, _, op_name in instructions(hlo_text):
        found |= scopes(op_name) & {*PHASES, AUX_HEAD}
    return found
