"""Driver ``fed_round_moe``: the ``fed_round`` driver's rounds, unchanged,
for a configuration with a dropless MoE, keeping the round's ``moe_rows``
counter: the (token, held expert) rows the grouped dual-probe kernel
computed, summed over clients, streams and layers.

The step is wrapped (``ctx.wrap_step``) to keep each round's counter as
a device scalar; it is read after the window, so the timed loop gains no
readback.  A traced run's record gets the traced rounds' sum under
``moe_rows``, which the grouped kernel's roofline reader counts its work
from.

After the check rounds, in set-up, the wrapper collects Python's garbage
and freezes what is left: a full collection walks every object of the
traced and compiled programs, and one inside the window stalled a round
by seconds in some runs.  The collections the window still makes are
logged (``[gc]``).
"""
from __future__ import annotations

import gc
import importlib.util
import pathlib
import time

_spec = importlib.util.spec_from_file_location(
    "bench_driver_fed_round_for_moe",
    pathlib.Path(__file__).with_name("fed_round.py"))
fed_round = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(fed_round)

H = fed_round.H


def calibrate(ctx, seeds, control_seeds):
    """``fed_round.calibrate``'s readings, with the program's rounds of
    every seed run before any reference (the round's program and the
    reference's server step do not both fit on one chip), and no
    half-batch fault where a micro-batch of one has no half to keep."""
    cell = fed_round.FedCell(ctx.model, ctx.cfg_json, ctx.traffic)
    progs = {}
    if seeds:
        compiled = cell.compile(H.root_key(seeds[0]))
        for seed in seeds:
            root = H.root_key(seed)
            _, progs[seed] = cell.readings(compiled, cell.init_state(root),
                                           root, ctx.traffic["check_steps"])
        del compiled
    for seed, prog in progs.items():
        ref = cell.reference(ctx.registry, H.root_key(seed))
        numbers, notes = H.training_numbers(prog, ref)
        yield {"seed": seed, "what": "program", **numbers, "notes": notes,
               "loss": prog["loss"], "ref_loss": ref["loss"]}
    faults = [("control_fp8", {"precision": "fp8"})]
    if ctx.traffic["micro_batch"] >= 2:
        faults.append(("fault_half_batch", {"half_batch": True}))
    for seed in control_seeds:
        root = H.root_key(seed)
        ref = cell.reference(ctx.registry, root)
        for what, kw in faults:
            numbers, notes = H.training_numbers(
                cell.reference(ctx.registry, root, **kw), ref)
            yield {"seed": seed, "what": what, **numbers, "notes": notes}


def run(ctx):
    rows, pauses, calls, started = [], [], [], {}
    inner = ctx.wrap_step
    check_steps = ctx.traffic["check_steps"]

    def keep(step, owner):
        step = inner(step, owner)

        def f(state, batch, key):
            t = time.perf_counter()
            state, m = step(state, batch, key)
            if "moe_rows" in m:
                rows.append(m["moe_rows"])
            if len(rows) == check_steps:
                gc.collect()
                gc.freeze()
            elif len(rows) > check_steps:
                calls.append(t)
            return state, m
        return f

    def on_gc(phase, info):
        if phase == "start":
            started["t"] = time.perf_counter()
        elif "t" in started:
            t = started.pop("t")
            pauses.append((t, time.perf_counter() - t))

    ctx.wrap_step = keep
    gc.callbacks.append(on_gc)
    try:
        res = fed_round.run(ctx)
    finally:
        ctx.wrap_step = inner
        gc.callbacks.remove(on_gc)
        gc.unfreeze()
    # the window's collections: from its first round to the readings after
    # its last (each round blocks before the next starts)
    window = [d for t, d in pauses if calls and calls[0] <= t
              and (len(calls) < 2 or t <= 2 * calls[-1] - calls[-2])]
    H.log("gc", collections=len(window),
          longest_ms=1000 * max(window, default=0.0))
    if "steps" in res.record:
        # the window follows the check rounds; its first rounds are traced
        first = ctx.traffic["check_steps"]
        traced = rows[first:first + res.record["steps"]]
        res.record["moe_rows"] = int(sum(int(r) for r in traced))
    return res
