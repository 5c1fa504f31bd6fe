"""Placement of JAX's persistent compilation cache for the entry points
(``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache goes to one fixed directory in
the checkout, so a second run of any entry point finds what the first
one compiled.  Only entry points call :func:`enable`; importing the
library writes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
