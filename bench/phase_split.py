"""The HERON round's device time by phase, read by hand from one traced
run of a fed cell, with the checks that the phases account for the round
and that the host spans and the device ops share one clock.

    python3 bench/phase_split.py --workload fed-gpt2m-s1024 --seed <n>

Runs the cell once as ``run.py --trace 1`` does, keeps the HLO text of
the programs its window drives (``op_scopes``, read by ``lib/phases.py``)
and prints one JSON line:

* ``ms_per_round``: device time per traced round of each phase scope,
  of the aux head (inside the cohort) and of the ops that no phase
  claims, each op's ``exclusive`` share of the busy time; over the round
  program, ``round_busy`` is their sum, ``round_union`` the union of its
  ops' intervals and ``round_own`` the sum of their ``Event.own``;
  ``kernels`` is the device time of the calls the two roofline readers
  select;
* ``tflop_per_s``: the model FLOPs of the client and of the server
  (``fed_round_flops``) over their phase's time;
* ``top_unattributed``: the instructions no phase claims, by self time;
* ``between_rounds_ms``: how the device's idle stretch from a round's
  last op to the next feed's first op (``idle``) is spent, over the
  rounds: the end of ``bench.block`` after the last op (``block_lag``),
  the host outside any span up to the next ``bench.make_inputs``
  (``host``), and that span's start to the feed's first op
  (``dispatch``).  Host spans and device ops are on two clocks: a
  negative ``dispatch``, an op before the call that made it, bounds how
  far apart they are.

The benchmark's own runs never call it.
"""
from __future__ import annotations

import argparse
import bisect
import json
import statistics

import run as R


def traced_run(reg, workload: str, seed: int, seconds: float, kind: str):
    """One traced run of the cell; returns its Result, whose record holds
    ``op_scopes``."""
    from lib import kernels as K
    from lib import phases as PH

    texts = []

    def keep(step, owner):
        texts.append(step.as_text())
        return step

    res, _ = R.run_cell(reg, workload, seed, seconds, True, kind,
                        wrap_step=keep)
    res.record["op_scopes"] = {K.module_name(t): PH.op_names(t)
                               for t in texts}
    return res


def split(reg, workload: str, record) -> dict | None:
    """The phase split and the clock check of a traced fed record; None
    where the record has no device ops or its program names no phase."""
    from lib import phases as PH
    from lib import trace as TR

    ms = {s: PH.phase_seconds(record, s) for s in (*PH.PHASES, PH.AUX_HEAD)}
    ms["unattributed"] = PH.unattributed_seconds(record)
    if any(v is None for v in ms.values()):
        return None
    tr, per_round = record["trace"], 1000.0 / record["steps"]
    lo, hi = TR.window(tr)
    (module,) = record["op_scopes"]
    names = record["op_scopes"][module]
    events = next(iter(tr.ops.values()))
    mine = [(e, s) for e, s in zip(events, PH.exclusive(events, lo, hi))
            if e.module == module and e.end > lo and e.start < hi]
    ms["round_busy"] = sum(s for _, s in mine)
    ms["round_union"] = sum(b - a for a, b in
                            TR.merged([e for e, _ in mine], lo, hi))
    ms["round_own"] = sum(e.own * (min(e.end, hi) - max(e.start, lo))
                          / max(e.dur, 1e-12) for e, _ in mine)
    ms["kernels"] = 0.0
    for metric in ("zo_dual_matmul_roofline",
                   "zo_dual_flash_attention_roofline"):
        match = reg.metric(metric).match
        picked = {n for n, c in record["programs"][module].items()
                  if match(c)}
        ms["kernels"] += sum(e.dur for e in
                             TR.kernel_events(tr, picked, module))
    ms = {k: v * per_round for k, v in ms.items()}

    w = reg.workload(workload)
    cfg = reg.config(w["config"])
    fl = reg.model(cfg["model"]).fed_round_flops(cfg,
                                                 reg.traffic(w["traffic"]))
    tflops = {"client": fl["client"] / ms["heron_cohort"] / 1e9,
              "server": fl["server"] / ms["heron_server_fo"] / 1e9}
    loose: dict[str, float] = {}
    for e, s in mine:
        if not any(PH.holds(names.get(e.name, ""), p) for p in PH.PHASES):
            loose[e.name] = loose.get(e.name, 0.0) + s * per_round
    top = sorted(loose.items(), key=lambda kv: -kv[1])[:10]
    return {"ms_per_round": ms, "tflop_per_s": tflops,
            "top_unattributed": [[n, names.get(n, ""), v] for n, v in top],
            "between_rounds_ms": between_rounds(tr, module),
            "steps": record["steps"], "module": module}


def between_rounds(tr, module: str) -> dict:
    """Medians, minima and maxima (ms) over the traced rounds of the parts
    of the device's idle stretch from each round's last op to the next op
    of another program (the next round's feed)."""
    from lib import trace as TR

    lo, hi = TR.window(tr)
    dev = sorted(next(iter(tr.ops.values())), key=lambda e: e.start)
    starts = [e.start for e in dev]
    ends = [e.end if e.module == module else lo for e in dev]
    feeds = sorted(s.start for s in tr.spans
                   if s.name == "bench.make_inputs")
    parts = {"block_lag": [], "host": [], "dispatch": [], "idle": []}
    for b in (s for s in tr.spans if s.name == "bench.block"):
        i = bisect.bisect_left(starts, b.end)
        j = bisect.bisect_left(feeds, b.end)
        if i == 0 or j == len(feeds):
            continue
        last = max(ends[:i])
        nxt = next((e for e in dev[bisect.bisect_left(starts, last):]
                    if e.module != module), None)
        if nxt is None or nxt.end > hi:
            continue
        parts["block_lag"].append(b.end - last)
        parts["host"].append(feeds[j] - b.end)
        parts["dispatch"].append(nxt.start - feeds[j])
        parts["idle"].append(nxt.start - last)
    return {k: {"median": 1000 * statistics.median(v), "min": 1000 * min(v),
                "max": 1000 * max(v), "n": len(v)}
            for k, v in parts.items() if v}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    R._paths()
    from lib.registry import Registry

    reg = Registry(R.BENCH)
    R.enable_cache()
    devs = R.require_chips(reg.workload(args.workload)["chips"])
    res = traced_run(reg, args.workload, args.seed, args.seconds,
                     devs[0].device_kind)
    out = {"workload": args.workload, "seed": args.seed,
           "device": devs[0].device_kind, "correct": res.correct,
           "split": split(reg, args.workload, res.record)}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
