"""Fault tolerance & elasticity utilities.

Three layers, all exercised by tests:

* **step-level resilience** — :func:`run_resilient` wraps a training loop
  with checkpoint/restart: any step that raises (device loss, preemption,
  injected fault) rolls back to the last checkpoint and replays; the
  deterministic data streams (data/synthetic.py are pure functions of
  (seed, step)) make the replay exact.
* **cluster-level elasticity** — :func:`remesh` rebuilds the mesh from
  the devices currently visible; FedAvg aggregation is count-weighted,
  so a changed data-parallel width between rounds is mathematically
  benign (DESIGN.md §5).
* **client-level straggler handling** — deadline-based over-sampling
  lives in core/aggregate.py (straggler_mask); this module adds the
  failure *injector* used to test it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import numpy as np

from repro.checkpoint import checkpoint as CKPT
from repro.distributed.sharding import auto_mesh


@dataclasses.dataclass
class FaultInjector:
    """Deterministic fault schedule for tests/drills: raises on the
    configured step numbers (once each)."""
    fail_at: tuple[int, ...] = ()
    _fired: set = dataclasses.field(default_factory=set)

    def check(self, step: int):
        if step in self.fail_at and step not in self._fired:
            self._fired.add(step)
            raise RuntimeError(f"injected fault at step {step}")


def remesh(model_parallel: int = 1):
    """Elastic mesh from the currently-visible devices."""
    n = jax.device_count()
    mp = model_parallel if model_parallel > 0 and n % model_parallel == 0 \
        else 1
    return auto_mesh((n // mp, mp), ("data", "model"))


def backoff_s(attempt: int, base: float = 0.05, cap: float = 1.0) -> float:
    """Bounded exponential backoff: base·2^(attempt-1), capped.  Shared
    by :func:`run_resilient` and the fleet controller's retry loop."""
    return min(cap, base * (2.0 ** max(attempt - 1, 0)))


@dataclasses.dataclass
class RestartTelemetry:
    """What the resilience loop did: how often it restarted, where it
    resumed from, and how long it backed off in total."""
    restarts: int = 0
    from_checkpoint: int = 0
    from_start: int = 0
    backoff_total_s: float = 0.0
    resumed_at: list = dataclasses.field(default_factory=list)


def run_resilient(step_fn: Callable, state, batch_fn: Callable,
                  n_steps: int, ckpt_dir: str, ckpt_every: int = 10,
                  injector: FaultInjector | None = None,
                  max_retries: int = 5, start_step: int = 0,
                  backoff_base_s: float = 0.05, backoff_cap_s: float = 1.0,
                  sleep: Callable = time.sleep):
    """Run ``n_steps`` of ``state, metrics = step_fn(state, batch)`` with
    checkpoint/replay on failure.

    ``batch_fn(step) -> batch`` must be deterministic in ``step`` (replay
    exactness).  On failure the loop backs off exponentially
    (``backoff_s(attempt, backoff_base_s, backoff_cap_s)``) and resumes
    from the latest checkpoint — or, when none exists yet, resets to the
    initial ``(state, start_step)`` and replays from the start against
    the same deterministic streams.  Returns
    ``(state, last_metrics, RestartTelemetry)``.
    """
    step = start_step
    state0 = state                   # replay anchor before any checkpoint
    restored = CKPT.latest_step(ckpt_dir)
    if restored is not None:
        state, step = CKPT.restore(ckpt_dir, state)
    tel = RestartTelemetry()
    metrics = {}
    while step < n_steps:
        try:
            if injector is not None:
                injector.check(step)
            state, metrics = step_fn(state, batch_fn(step))
            step += 1
            if step % ckpt_every == 0:
                CKPT.save(ckpt_dir, step, state)
        except Exception:
            tel.restarts += 1
            if tel.restarts > max_retries:
                raise
            wait = backoff_s(tel.restarts, backoff_base_s, backoff_cap_s)
            tel.backoff_total_s += wait
            sleep(wait)
            last = CKPT.latest_step(ckpt_dir)
            if last is not None:
                state, step = CKPT.restore(ckpt_dir, state)
                tel.from_checkpoint += 1
            else:
                # no checkpoint yet: replay from start_step for real —
                # both the state AND the step counter reset
                state, step = state0, start_step
                tel.from_start += 1
            tel.resumed_at.append(step)
    CKPT.save(ckpt_dir, step, state)
    return state, metrics, tel
