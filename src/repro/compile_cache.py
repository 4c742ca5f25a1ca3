"""Persistent JAX compilation cache for the repository's entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and
``benchmarks/serve_closed_loop.py`` call :func:`enable_compile_cache`
once at start-up; nothing calls it at import time. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the directory from the
environment and this module sets none. Otherwise the cache lives at
``<checkout>/.jax_cache`` (git-ignored): a fixed path, resolved from
this file, so a later run from the same checkout finds its entries.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# The Pallas kernels compile in about a second, under JAX's default
# threshold; cache everything that takes longer than this.
MIN_COMPILE_SECS = 0.25


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return path
