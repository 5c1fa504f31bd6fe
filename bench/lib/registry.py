"""Finds every piece of the benchmark by the name `BENCHMARK.json` gives it.

A cell (``workloads`` entry) names a configuration and a traffic mix.  The
configuration's file is given by its entry; everything else is found by
name under the benchmark directory:

* ``traffic/<traffic>.json``  — one traffic mix, naming its ``driver``;
* ``drivers/<driver>.py``     — the driver kind that runs such a mix;
* ``cells/<workload>.json``   — the limits of the cell's correctness check;
* ``metrics/<metric>.py``     — one per-layer metric, a ``read(record)``;
* ``models/<model>.py``       — the program's side of a model family, and
  its model FLOPs (the configuration's ``model``);
* ``references/<name>.py``    — a configuration's plain reference (its
  ``reference``).

New pieces are new files and new entries: nothing here lists them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]


def _load_module(path: pathlib.Path, tag: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {tag} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{tag}_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _load_json(path: pathlib.Path, tag: str) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no {tag} at {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Registry:
    """The benchmark rooted at ``bench`` (its parent holds BENCHMARK.json)."""
    bench: pathlib.Path = BENCH

    @property
    def spec(self) -> dict:
        return _load_json(self.bench.parent / "BENCHMARK.json",
                          "BENCHMARK.json")

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _load_json(self.bench.parent / c["file"],
                                  f"config {name}")
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _load_json(self.bench / "traffic" / f"{name}.json",
                          f"traffic {name}")

    def cell(self, workload: str) -> dict:
        return _load_json(self.bench / "cells" / f"{workload}.json",
                          f"cell {workload}")

    def driver(self, kind: str):
        return _load_module(self.bench / "drivers" / f"{kind}.py", "driver")

    def model(self, name: str):
        return _load_module(self.bench / "models" / f"{name}.py", "model")

    def reference(self, name: str):
        return _load_module(self.bench / "references" / f"{name}.py",
                            "reference")

    def metric(self, name: str):
        return _load_module(self.bench / "metrics" / f"{name}.py", "metric")

    def per_layer(self, workload: str) -> list[dict]:
        """The per-layer metrics whose ``workloads`` list this cell (or
        that name no cells, and so read every cell)."""
        return [m for m in self.spec["per_layer"]
                if workload in m.get("workloads", [workload])]

    def end_to_end(self, workload: str) -> list[dict]:
        return [m for m in self.spec["end_to_end"]
                if workload in m.get("workloads", [workload])]
