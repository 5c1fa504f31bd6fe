"""Device time of the HERON round's phases, from the trace.

The program names each phase of the round with a ``jax.named_scope``:
``heron_cohort`` (the client cohort's ZO dual probes), ``heron_server_fo``
(the server's sequential AdamW steps) and ``heron_replay`` (the
Fed-Server's seed replay); ``heron_aux_head`` (the clients' auxiliary
network) sits inside ``heron_cohort``.  A scope reaches every HLO
instruction made under it through ``metadata.op_name``, and the device
trace names each op event after its instruction, so a phase's time is the
summed busy time of the events whose instruction's ``op_name`` holds the
scope.  ``op_names`` maps a compiled program's instructions to their
``op_name``; a record that holds those maps as ``op_scopes`` (program
name -> map) can be read by ``phase_seconds``.

Each instant of the busy time goes to one event: the latest started of
those running then (``exclusive``).  For nested events that is the self
time ``trace.device_events`` gives (``Event.own``).  Events that overlap
without nesting (async copies and slices beside a fusion) make the sum
of ``Event.own`` exceed the busy time; here each overlap counts once, so
the phases and the ops no phase claims add up to the busy union.
"""
from __future__ import annotations

import heapq
import re

from lib import trace as TR

PHASES = ("heron_cohort", "heron_server_fo", "heron_replay")
AUX_HEAD = "heron_aux_head"

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")
# a path component: a name, bare or wrapped in transforms: 'vmap(x)'
_COMPONENT = re.compile(r"(?:[\w.\-]+\()*([\w.\-]*)\)*")


def op_names(hlo_text: str) -> dict[str, str]:
    """``{instruction: op_name}`` for every instruction of every
    computation of a compiled program's HLO text ('' where it has no
    ``op_name``).  A fusion without an ``op_name`` of its own (the
    compiler leaves some of the fusions it forms bare) takes its fused
    computation's: its root's, else its first instruction's that has
    one."""
    out, fused, comp_name = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        h = _HEADER.match(line)
        if h is not None:
            comp = h.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        name = out[m.group(2)] = op.group(1) if op else ""
        if name and (m.group(1) or not comp_name.get(comp)):
            comp_name[comp] = name
        calls = _CALLS.search(line)
        if not name and calls is not None:
            fused[m.group(2)] = calls.group(1)
    for instr, comp in fused.items():
        out[instr] = comp_name.get(comp, "")
    return out


def holds(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is a whole component of the ``op_name`` path,
    bare or wrapped in transform names (``transpose(jvp(scope))``)."""
    for c in op_name.split("/"):
        m = _COMPONENT.fullmatch(c)
        if m is not None and m.group(1) == scope:
            return True
    return False


def exclusive(events, lo: float, hi: float) -> list[float]:
    """Each event's share of the busy time in [lo, hi]: every instant goes
    to the latest started of the events running then (the shorter first
    where two start together)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].dur))
    share = [0.0] * len(events)
    running: list[tuple[int, float]] = []     # (-rank, end): latest on top
    t = lo

    def run_until(stop):
        nonlocal t
        while running and t < stop:
            neg_rank, end = running[0]
            if end <= t:
                heapq.heappop(running)
                continue
            step = min(end, stop) - t
            share[order[-neg_rank]] += step
            t += step
        t = max(t, stop)

    for rank, i in enumerate(order):
        run_until(min(max(events[i].start, lo), hi))
        heapq.heappush(running, (-rank, min(events[i].end, hi)))
    run_until(hi)
    return share


def op_seconds(record) -> list[tuple[str, float]] | None:
    """``(op_name, seconds)`` of each event of the first device in the
    traced window that belongs to a program in ``op_scopes``: its
    ``exclusive`` share of the busy time."""
    scopes = record.get("op_scopes")
    tr = record.get("trace")
    if not scopes or tr is None or not tr.ops:
        return None
    lo, hi = TR.window(tr)
    events = next(iter(tr.ops.values()))
    return [(scopes[e.module].get(e.name, ""), s)
            for e, s in zip(events, exclusive(events, lo, hi))
            if e.module in scopes and s > 0]


def _has_scope(record, scope: str) -> bool:
    return any(holds(op, scope) for names in record["op_scopes"].values()
               for op in names.values())


def phase_seconds(record, scope: str) -> float | None:
    """Device time in the traced window of the ops under ``scope``; None
    where there is no trace, or no instruction carries the scope (a
    program that names no phases)."""
    ops = op_seconds(record)
    if ops is None or not _has_scope(record, scope):
        return None
    return sum(s for op, s in ops if holds(op, scope))


def unattributed_seconds(record) -> float | None:
    """Device time of the driven programs' ops that no phase of
    ``PHASES`` claims; None as for ``phase_seconds``."""
    ops = op_seconds(record)
    if ops is None or not any(_has_scope(record, p) for p in PHASES):
        return None
    return sum(s for op, s in ops if not any(holds(op, p) for p in PHASES))

