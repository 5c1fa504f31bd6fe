"""Readings from which a cell's limits are set, on the accelerator.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        --control-seeds 4,5,6

Runs the cell's timed program (without a window) against the plain
reference on ``--seeds``, and the control and the planted faults the
driver knows on ``--control-seeds``, all in one process at the cell's
own size.  Prints one JSON line per reading; the benchmark's own runs
never call it.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as R


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    R._paths()
    from lib import harness as H
    from lib.registry import Registry

    reg = Registry(R.BENCH)
    w = reg.workload(args.workload)
    R.enable_cache()
    devs = R.require_chips(w["chips"])
    traffic = reg.traffic(w["traffic"])
    ctx = R.Context(registry=reg, workload=w, cfg_json=reg.config(w["config"]),
                    traffic=traffic, cell=reg.cell(args.workload), seed=0,
                    seconds=0.0, trace=False,
                    device_kind=devs[0].device_kind,
                    counter=H.CompileCounter())
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    for line in reg.driver(traffic["driver"]).calibrate(ctx, seeds,
                                                        controls):
        print(json.dumps(line, default=float), flush=True)
    print(json.dumps({"device": devs[0].device_kind, "done": True}),
          file=sys.stderr)


if __name__ == "__main__":
    main()
