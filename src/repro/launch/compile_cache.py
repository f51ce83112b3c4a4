"""JAX's persistent compilation cache, kept at one fixed directory.

Entry points (``chip_smoke.py``, ``python -m repro.launch.train``, the
``benchmarks/bench_*.py`` scripts) call :func:`enable_compile_cache`
before their first compile, so a second run in the same checkout reuses
the programs the first one compiled.  Library code never calls it.
"""

from __future__ import annotations

import os
import pathlib

import jax
from jax.experimental.compilation_cache import compilation_cache

#: ``.jax_cache/`` at the repository root.  The directory is part of the
#: cache key, so it must not move between runs.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and that directory wins; otherwise the cache goes to
    :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # JAX decides once, at its first compile, whether a cache is in use;
    # a compile made while importing must not leave it off
    compilation_cache.reset_cache()
    return str(CACHE_DIR)
