"""Persistent XLA compile cache for the entry points.

Every launcher calls :func:`enable_compile_cache` before its first compile,
so a second run of the same program on the same machine reads its compiled
executables back instead of compiling again.  The cache path is part of the
cache key, so it is fixed: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads
the variable itself), else ``.jax_cache/`` at the checkout root.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
