"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/quickstart.py``,
``repro.launch.serve_recs``) call :func:`use_compile_cache` once at start-up
so a second run skips the compiles the first one paid for. The cache key
includes the directory, so the directory never moves between runs: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself and nothing is set here), and the fixed ``<checkout>/.jax_cache``
otherwise.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/utils/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
