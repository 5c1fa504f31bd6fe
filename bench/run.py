"""Runs one benchmark cell once, on the accelerator of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It loads, warms up, measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON object as the
last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its limit.
The same numbers end standard error.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
CACHE_DIR = CHECKOUT / ".jax_cache"


def _paths():
    for p in (str(BENCH), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's data, the run's arguments and the
    harness's instruments."""
    registry: object
    workload: dict
    cfg_json: dict
    traffic: dict
    cell: dict
    seed: int
    seconds: float
    trace: bool
    device_kind: str
    counter: object
    t0: float = T0
    # the timed path's step, as built; a test may break it underneath
    wrap_step: object = staticmethod(lambda step, owner: step)
    # the compiled programs the window drives (``drives``)
    programs: list = dataclasses.field(default_factory=list)

    @property
    def model(self):
        """The configuration's model family (``models/<model>.py``)."""
        return self.registry.model(self.cfg_json["model"])

    def drives(self, compiled):
        """Marks a compiled program as one the window drives, and returns
        it: a traced run's record gets the Pallas calls of each."""
        self.programs.append(compiled)
        return compiled

    def memory_peak(self) -> int:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        return int(max(peaks))


def enable_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the environment says, so only a cell's first run compiles."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench/run.py needs a TPU; JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < n:
        raise SystemExit(f"the cell asks for {n} chips; JAX found "
                         f"{len(devs)}")
    return devs


def run_cell(registry, workload: str, seed: int, seconds: float,
             trace: bool, device_kind: str, wrap_step=None):
    """Everything after the look for a chip: returns (Result, per-layer
    metrics or None)."""
    from lib import harness as H
    from lib import kernels as K
    from lib import peaks as PK

    w = registry.workload(workload)
    traffic = registry.traffic(w["traffic"])
    ctx = Context(registry=registry, workload=w,
                  cfg_json=registry.config(w["config"]), traffic=traffic,
                  cell=registry.cell(workload), seed=seed, seconds=seconds,
                  trace=trace, device_kind=device_kind,
                  counter=H.CompileCounter())
    if wrap_step is not None:
        ctx.wrap_step = wrap_step
    res = registry.driver(traffic["driver"]).run(ctx)
    H.log("compiles_in_window", **ctx.counter.counts)
    res.record["peak"] = PK.peak(device_kind)
    per_layer = None
    if trace:
        texts = [p.as_text() for p in ctx.programs]
        res.record["programs"] = {K.module_name(t): K.parse_custom_calls(t)
                                  for t in texts}
        per_layer = {}
        for m in registry.per_layer(workload):
            v = registry.metric(m["name"]).read(res.record)
            if v is not None:
                per_layer[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return res, per_layer


def result_line(res, per_layer, registry, workload, devs) -> dict:
    from lib import trace as TR

    units = {m["name"]: m["unit"] for m in registry.end_to_end(workload)}
    if per_layer is None:
        metrics = {k: {"value": float(res.metrics[k]), "unit": u}
                   for k, u in units.items()}
    else:
        metrics = per_layer
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": res.memory_peak_bytes}
    out = {"correct": res.correct, "attempted": res.attempted,
           "failed": res.failed, "metrics": metrics, "device": device}
    tr = res.record.get("trace")
    if tr is not None:
        lo, hi = TR.window(tr)
        device["busy_s"] = TR.busy_seconds(tr)
        device["window_s"] = hi - lo
        out["breakdown"] = {"device_ops": TR.op_seconds(tr),
                            "idle_gaps": TR.idle_gaps(tr)}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in res.checks}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative whole number")
    _paths()
    from lib import harness as H
    from lib.registry import Registry

    reg = Registry(BENCH)
    chips = reg.workload(args.workload)["chips"]
    enable_cache()
    devs = require_chips(chips)
    H.log("setup", devices=len(devs), kind=devs[0].device_kind,
          cache=str(CACHE_DIR), workload=args.workload, seed=args.seed)
    res, per_layer = run_cell(reg, args.workload, args.seed, args.seconds,
                              bool(args.trace), devs[0].device_kind)
    out = result_line(res, per_layer, reg, args.workload, devs)
    for c in res.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r}"
              + (f" ({c.note})" if c.note else ""), file=sys.stderr,
              flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
