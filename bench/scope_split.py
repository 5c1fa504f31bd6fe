"""The device time per round under the scopes the program names inside
the round's phases, read by hand from one traced run of a fed cell.

    python3 bench/scope_split.py --workload fed-moonlight-s8192 --seed <n>

Runs ``phase_split.py``'s traced run and prints one JSON line: its phase
split (``split``), and ``ms_per_round`` of each scope in ``--scopes``
(``heron_mla``, ``heron_moe_route``, ``heron_moe_experts`` by default)
within the client cohort and within the server's FO steps, each op's
exclusive share of the busy time as ``lib/phases.py`` counts it, beside
the device time of the calls each kernel roofline reader in
``--readers`` selects and the share it reads.  The benchmark's own runs
never call it.
"""
from __future__ import annotations

import argparse
import json

import phase_split as PS
import run as R

SCOPES = ("heron_mla", "heron_moe_route", "heron_moe_experts")
READERS = ("zo_dual_grouped_matmul_roofline",
           "zo_dual_flash_attention_mla_roofline", "zo_dual_matmul_roofline")


def scopes_ms(reg, record, scopes, readers) -> dict:
    from lib import phases as PH
    from lib import trace as TR

    per_round = 1000.0 / record["steps"]
    ops = PH.op_seconds(record)
    out = {}
    for scope in scopes:
        for phase in ("heron_cohort", "heron_server_fo"):
            out[f"{phase}/{scope}"] = per_round * sum(
                s for op, s in ops
                if PH.holds(op, scope) and PH.holds(op, phase))
    (module,) = record["programs"]
    for name in readers:
        m = reg.metric(name)
        picked = {n for n, c in record["programs"][module].items()
                  if m.match(c)}
        out[f"kernel/{name}"] = per_round * sum(
            e.dur for e in TR.kernel_events(record["trace"], picked, module))
        out[f"read/{name}"] = m.read(record)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--scopes", default=",".join(SCOPES))
    ap.add_argument("--readers", default=",".join(READERS))
    args = ap.parse_args(argv)
    R._paths()
    from lib.registry import Registry

    reg = Registry(R.BENCH)
    R.enable_cache()
    devs = R.require_chips(reg.workload(args.workload)["chips"])
    res = PS.traced_run(reg, args.workload, args.seed, args.seconds,
                        devs[0].device_kind)
    rec = res.record
    out = {"workload": args.workload, "seed": args.seed,
           "device": devs[0].device_kind, "correct": res.correct,
           "steps": rec["steps"], "moe_rows": rec.get("moe_rows"),
           "split": PS.split(reg, args.workload, rec),
           "ms_per_round": scopes_ms(reg, rec, args.scopes.split(","),
                                     args.readers.split(","))}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
