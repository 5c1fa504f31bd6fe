"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state.  Single pod: 16x16 = 256 chips ("data","model").
Multi-pod: 2x16x16 = 512 chips ("pod","data","model").
"""
from __future__ import annotations

import jax

from repro.distributed.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    """Elastic: build a mesh from whatever devices are visible."""
    n = jax.device_count()
    mp = model_parallel if n % model_parallel == 0 else 1
    return auto_mesh((n // mp, mp), ("data", "model"))


def make_replay_mesh(n_devices: int | None = None):
    """1-D cohort mesh for mesh-sharded seed-replay aggregation: the
    ``"clients"`` axis spans all (or the first ``n_devices``) local
    devices, so the Fed-Server replays N clients as N/n_devices
    per-device sub-streams."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return auto_mesh((len(devs),), ("clients",), devices=devs)
