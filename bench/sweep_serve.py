"""Sweep of the serving cell's arrival rate, to find the highest rate the
engine sustains without a growing backlog.

    python3 bench/sweep_serve.py --workload <serving cell> \
        --rates 10,20,30,40 --seconds 20 --seed 1

One process: the engine is built and warmed once, then the open loop of
the cell's traffic runs at each rate in turn.  Per rate it prints
completed/attempted, the requests waiting for a slot at the middle and
at the end of the window (a backlog that grows between the two is
beyond capacity), the drain time after the window and the latency
percentiles.  The benchmark's own runs never call it.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

import run as R


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    R._paths()
    from lib import harness as H
    from lib.registry import Registry

    reg = Registry(R.BENCH)
    w = reg.workload(args.workload)
    R.enable_cache()
    R.require_chips(w["chips"])
    cfg, t = reg.config(w["config"]), reg.traffic(w["traffic"])
    drv = reg.driver(t["driver"])
    srv = drv.Server(reg.model(cfg["model"]), cfg, t,
                     H.root_key(args.seed))
    srv.warm_up()
    for rate in (float(r) for r in args.rates.split(",")):
        reqs = drv.schedule(t, args.seconds, args.seed, cfg["vocab_size"],
                            rate=rate)
        loop = drv.open_loop(srv.engine, reqs, args.seconds, t["drain_s"],
                             cfg, srv.model)
        while srv.engine.pending:     # what the drain limit left behind
            srv.engine.step()
        ttft, tpot = drv.latencies(reqs, args.seconds + t["drain_s"])
        done = sum(r.tokens is not None for r in reqs)
        print(json.dumps({
            "rate_per_s": rate, "completed": done, "attempted": len(reqs),
            "backlog_half": loop["backlog_half"],
            "backlog_end": loop["backlog_end"],
            "drain_s": loop["elapsed"] - args.seconds,
            "ttft_p50_ms": 1e3 * float(np.median(ttft)),
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "tpot_p50_ms": 1e3 * float(np.median(tpot)),
            "tpot_p95_ms": 1e3 * float(np.percentile(tpot, 95)),
            "engine_busy_share": loop["step_s"] / loop["elapsed"],
            "submit_late_p95_ms": 1e3 * float(np.percentile(
                loop["late_s"], 95))}), flush=True)


if __name__ == "__main__":
    main()
