"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/serving.py``, the examples)
call ``place_compile_cache()`` once before compiling anything; library
imports and tests never do. A cold start then compiles each serving program
once per cache directory instead of once per process.
"""
from __future__ import annotations

import os
import pathlib

import jax

# fixed, git-ignored, inside the checkout: the cache key includes the
# path, so a directory that moved between runs would never hit
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Use ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself; nothing else is configured), else ``<checkout>/.jax_cache``.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
