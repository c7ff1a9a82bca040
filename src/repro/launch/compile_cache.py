"""JAX's persistent compilation cache for the repo's entry points.

Called from ``main()`` of chip_smoke.py, launch/serve.py and
benchmarks/bench_engine_throughput.py — never at import time, so importing
a module changes no JAX state.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# A fixed directory at the checkout root (listed in .gitignore): the path is
# part of the cache key, so it must not vary from run to run.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    else is set; otherwise the cache goes to the checkout's ``.jax_cache/``."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
