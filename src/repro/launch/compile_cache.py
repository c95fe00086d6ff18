"""Where the chip entry points keep JAX's persistent compilation cache.

A cold run compiles every program again (the ``bvh`` engine compiles once
per calibration probe), so ``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.cluster`` and ``examples/serve_clusters.py`` call
:func:`enable` before their first compile. ``JAX_COMPILATION_CACHE_DIR``,
when set, wins: JAX reads that variable itself and nothing is set here.
Otherwise the cache lives at the fixed ``<repo>/.jax_cache`` (git-ignored);
the directory is part of the cache key, so it is never derived from a
temporary directory, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
