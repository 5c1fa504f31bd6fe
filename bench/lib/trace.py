"""Reduction of one profiler trace to busy time, idle gaps and kernel time.

The traced window is the host span ``bench.window``.  Device busy time is
the union of the intervals in which an operation ran on a device (the
``XLA Ops`` line of each ``/device:`` plane), clipped to the window and
averaged over the devices.  An idle gap is a stretch of the window with
no operation on the device; it is labelled with the innermost ``bench.*``
host span that covers its midpoint, which says what the host was doing.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
# a TPU op event is named by its HLO text: '%name = (shape) op(...)'
_OP_NAME = re.compile(r"^%?([^\s=]+)")
# a program event by its module and fingerprint: 'jit_round_fn(4123...)'
_MODULE_NAME = re.compile(r"^([^(]+)")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float       # seconds, on the trace's clock
    dur: float
    module: str = ""   # the compiled program a device op belongs to
    self_dur: float | None = None   # dur less the ops nested inside it

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def own(self) -> float:
        return self.dur if self.self_dur is None else self.self_dur


def device_events(ops, modules) -> list[Event]:
    """Device op events from ``(name, start, dur)`` of an ``XLA Ops`` line
    and ``(name, start, dur)`` of its ``XLA Modules`` line: each op named
    by its HLO instruction, attributed to the program running at its
    start, and given its self time (a ``while`` holds its body's ops on
    the same line)."""
    mods = sorted((s, s + d, _MODULE_NAME.match(n).group(1))
                  for n, s, d in modules)
    starts = [m[0] for m in mods]
    raw = sorted(ops, key=lambda e: (e[1], -e[2]))
    child = [0.0] * len(raw)
    stack: list[int] = []
    for i, (_, st, du) in enumerate(raw):
        while stack and raw[stack[-1]][1] + raw[stack[-1]][2] <= st:
            stack.pop()
        if stack:
            child[stack[-1]] += du
        stack.append(i)
    out = []
    for (n, st, du), c in zip(raw, child):
        j = bisect.bisect_right(starts, st) - 1
        mod = mods[j][2] if j >= 0 and st < mods[j][1] else ""
        out.append(Event(_OP_NAME.match(n).group(1), st, du, mod,
                         max(du - c, 0.0)))
    return out


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]     # device plane name -> its op events
    spans: list[Event]              # host spans named bench.*


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: [(ev.name, ev.start_ns * 1e-9,
                                  ev.duration_ns * 1e-9)
                                 for ev in line.events]
                     for line in plane.lines
                     if line.name in ("XLA Ops", "XLA Modules")}
            if lines.get("XLA Ops"):
                ops[plane.name] = device_events(lines["XLA Ops"],
                                                lines.get("XLA Modules", []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append(Event(ev.name, ev.start_ns * 1e-9,
                                           ev.duration_ns * 1e-9))
    return Trace(ops, spans)


def window(tr: Trace) -> tuple[float, float]:
    ws = [s for s in tr.spans if s.name == WINDOW_SPAN]
    if len(ws) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(ws)}")
    return ws[0].start, ws[0].end


def merged(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the events' intervals, clipped to [lo, hi]."""
    iv = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                if e.end > lo and e.start < hi)
    out: list[list[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(tr: Trace) -> float:
    """Device busy time in the window, averaged over the devices."""
    lo, hi = window(tr)
    per = [sum(b - a for a, b in merged(evs, lo, hi))
           for evs in tr.ops.values()]
    return sum(per) / len(per) if per else 0.0


def idle_gaps(tr: Trace, top: int = 10) -> list[list]:
    """The ``top`` longest idle stretches of the first device, each as
    ``[label, seconds]``."""
    lo, hi = window(tr)
    if not tr.ops:
        return []
    busy = merged(next(iter(tr.ops.values())), lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    inner = [s for s in tr.spans if s.name != WINDOW_SPAN]

    def label(a, b):
        mid = (a + b) / 2
        cover = [s for s in inner if s.start <= mid <= s.end]
        return min(cover, key=lambda s: s.dur).name if cover \
            else "host outside any bench span"

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return [[label(a, b), b - a] for a, b in gaps[:top]]


def op_seconds(tr: Trace, top: int = 10) -> list[list]:
    """The ``top`` device operations by summed self time in the window,
    each as ``[module/op, seconds]`` (first device)."""
    lo, hi = window(tr)
    if not tr.ops:
        return []
    tot: dict[str, float] = {}
    for e in next(iter(tr.ops.values())):
        if e.end > lo and e.start < hi:
            key = f"{e.module}/{e.name}" if e.module else e.name
            tot[key] = tot.get(key, 0.0) + e.own * (
                min(e.end, hi) - max(e.start, lo)) / max(e.dur, 1e-12)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:top]]


def kernel_events(tr: Trace, names, module: str | None = None):
    """Events in the window of the instructions ``names``, on every
    device, optionally only those of program ``module``."""
    lo, hi = window(tr)
    names = set(names)
    return [e for evs in tr.ops.values() for e in evs
            if e.name in names and e.start >= lo and e.end <= hi
            and (module is None or e.module == module)]
