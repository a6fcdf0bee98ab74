"""JAX's persistent compilation cache, at a path that does not move.

The cache key includes the directory, so a cache kept under a temporary
name never hits.  Entry points call :func:`enable_compile_cache` once,
before they compile anything; library code and tests never do.
"""

from __future__ import annotations

import os
import pathlib

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    no path is set here.  Otherwise the cache lives in ``.jax_cache`` at the
    root of this checkout.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
