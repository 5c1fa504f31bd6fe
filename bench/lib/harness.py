"""What every driver shares: keys from the seed, host spans, the compile
counter, results and the comparison that decides ``correct``."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import statistics
from typing import Any

import numpy as np

REL_FLOOR = 1e-3     # leaves under this share of the median are excluded


def log(tag: str, **fields):
    """An earlier line of standard output (the last one is the result)."""
    print(f"[{tag}] " + json.dumps(fields, default=float), flush=True)


def leaf_paths(tree) -> list[str]:
    """'/'-joined dict keys and sequence indices of every leaf."""
    import jax
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
            for kp, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def root_key(seed: int):
    """A raw (2,) uint32 PRNG key from any non-negative whole number."""
    import jax.numpy as jnp
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """The profiler over the first ``until`` seconds of a window (all of
    it when ``until`` is None), marked by the host span ``bench.window``;
    off unless ``on``."""

    def __init__(self, on: bool, until: float | None):
        import tempfile
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if on else None
        self.until = float("inf") if until is None else until
        self.span = None
        self.closed_at = None

    def start(self):
        import jax
        if self.dir:
            jax.profiler.start_trace(self.dir)
            self.span = span("bench.window")
            self.span.__enter__()

    def tick(self, t: float) -> bool:
        """Called with the seconds the window has run; stops at ``until``
        and then returns True (once)."""
        if self.span is not None and t >= self.until:
            self.stop(t)
            return True
        return False

    def stop(self, t: float):
        import jax
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None
            self.closed_at = t
            jax.profiler.stop_trace()

    def load(self):
        import shutil

        from lib import trace as TR
        tr = TR.load(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        return tr


class CompileCounter:
    """Counts the programs traced, lowered and compiled while armed."""
    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traced",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        import jax
        self.armed = False
        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    @contextlib.contextmanager
    def window(self):
        self.armed = True
        try:
            yield self
        finally:
            self.armed = False


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float
    note: str = ""

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class Result:
    attempted: int
    failed: int
    metrics: dict[str, float]            # end-to-end, by name
    checks: list[Check]
    record: dict[str, Any]                # what the per-layer readers read
    memory_peak_bytes: int

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def checks_from(numbers: dict[str, float], limits: dict[str, float],
                notes: dict[str, str] | None = None) -> list[Check]:
    missing = set(limits) - set(numbers)
    if missing:
        raise KeyError(f"no reading for the limits {sorted(missing)}")
    notes = notes or {}
    return [Check(k, float(numbers[k]), float(limits[k]), notes.get(k, ""))
            for k in limits]


# ---------------------------------------------------------------------------
# the comparison of a training cell
# ---------------------------------------------------------------------------

def worst_leaf_gap(prog: dict[str, float], ref: dict[str, float],
                   keep: set[str]) -> tuple[float, str]:
    """The worst leaf's ``|prog - ref| / max(ref, median ref)`` over the
    leaves ``keep``; the median is over the kept leaves."""
    med = statistics.median(ref[k] for k in keep)
    worst, where = 0.0, ""
    for k in sorted(keep):
        g = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not np.isfinite(prog[k]):
            g = float("inf")
        if g > worst:
            worst, where = g, k
    return worst, where


def kept_leaves(ref_grad: dict[str, float]) -> set[str]:
    """Leaves whose reference gradient is not nought to rounding: at least
    ``REL_FLOOR`` of the median leaf's, within each group (the group is
    the first path element, e.g. client / server)."""
    keep = set()
    groups: dict[str, list[str]] = {}
    for k in ref_grad:
        groups.setdefault(k.split("/", 1)[0], []).append(k)
    for ks in groups.values():
        med = statistics.median(ref_grad[k] for k in ks)
        keep |= {k for k in ks if ref_grad[k] >= REL_FLOOR * med}
    return keep


def training_numbers(prog: dict, ref: dict) -> tuple[dict, dict]:
    """The numbers a training cell compares, from readings
    ``{"loss": [[client, server], ...], "grad": {leaf: norm},
    "change": {leaf: norm}}`` of the program (or a stand-in) and of the
    reference:

    * ``loss_gap``: the worst relative gap of a loss over the first steps;
    * ``grad_gap``: the worst leaf of the first gradient as the optimizer
      gets it, read from its state after one step (the server's AdamW
      first moment; the client has no optimizer state: its step is the
      seed replay, which ``change_gap`` covers);
    * ``change_gap``: the worst leaf of the parameters' change over the
      first steps, client and server.

    A leaf gap is ``|prog - ref| / max(ref, median ref)``.  Leaves whose
    reference first gradient is nought to rounding are left out.
    """
    steps = min(len(prog["loss"]), len(ref["loss"]))
    loss_gap = max(abs(p - r) / abs(r)
                   for s in range(steps)
                   for p, r in zip(prog["loss"][s], ref["loss"][s]))
    if not all(np.isfinite(v) for s in prog["loss"] for v in s):
        loss_gap = float("inf")
    keep = kept_leaves(ref["grad"])
    server = {k for k in keep if k.startswith("server/")}
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad"], ref["grad"], server)
    client_gap, client_leaf = worst_leaf_gap(prog["grad"], ref["grad"],
                                             keep - server)
    change_gap, change_leaf = worst_leaf_gap(prog["change"], ref["change"],
                                             keep)
    numbers = {"loss_gap": loss_gap, "grad_gap": grad_gap,
               "change_gap": change_gap}
    notes = {"grad_gap": grad_leaf, "change_gap": change_leaf,
             "loss_gap": f"{steps} steps",
             "client_step_gap": f"{client_gap:.6g} at {client_leaf} "
                                "(not compared)"}
    excluded = sorted(set(ref["grad"]) - keep)
    if excluded:
        notes["excluded"] = ",".join(excluded)
    return numbers, notes
