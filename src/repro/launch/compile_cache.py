"""JAX's persistent compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_compile_cache` before their
first compile, so a process started after another one on the same
machine reads the programs it compiled instead of compiling them
again. Nothing enables the cache at package import.
"""
from __future__ import annotations

import os
from pathlib import Path

# the repo root: src/repro/launch/ -> three levels up. The cache path
# is part of each entry's key, so it must not move between runs (no
# temp dir, pid or time in it).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the
    directory from it, and nothing is set here; otherwise the cache
    goes to ``.jax_cache/`` at the repo root."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
