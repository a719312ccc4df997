"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.vision``,
``repro.launch.serve``) call :func:`enable_compile_cache` once at start-up;
library code and tests never do. The cache key includes the directory, so
the directory must not move between runs: ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself), and otherwise the cache lives
at the fixed, git-ignored ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
