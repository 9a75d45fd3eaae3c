"""Where JAX keeps its persistent compilation cache, for every entry point.

A restarted server (or a second smoke run) otherwise pays every jit compile
again — on the serving path that lands in the first requests' tail latency.
The cache key includes the directory, so the directory must not move between
runs: it is never derived from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: The checkout-local cache directory (git-ignored).
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and nothing is changed. Otherwise the cache goes to the fixed
    checkout path :data:`CHECKOUT_CACHE_DIR`. Call before the first compile:
    JAX reads the setting when it first uses the cache.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
