"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once at start-up;
importing the package never does. The cache key includes the directory, so
the directory must not move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

_REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (jax reads the
    variable itself; this sets no other). Otherwise the cache lives in
    ``<repo>/.jax_cache``, which git ignores.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_REPO_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
