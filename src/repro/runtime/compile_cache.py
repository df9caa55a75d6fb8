"""Where JAX's persistent compilation cache lives: one rule for every entry
point (``chip_smoke.py``, the benchmark runners, the examples).

``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing else is touched
(JAX reads that variable itself). Otherwise the cache sits at one fixed
directory inside the checkout, ``.jax_cache/`` (git-ignored): the path is
part of what a cache entry is found by, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
