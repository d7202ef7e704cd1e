"""JAX's persistent compilation cache for the repo's entry points.

Called by the scripts a user runs (``chip_smoke.py``, ``examples/``,
``benchmarks/run.py``); importing this module changes nothing, and the
tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and nothing is overridden. Otherwise the cache lives at
    ``<repo>/.jax_cache``: a fixed path, so runs of the same checkout
    find each other's programs (a per-run directory would never hit).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
