"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` at start, never at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and this module sets nothing.  Otherwise the cache goes to one fixed
directory inside the checkout: a temporary or pid-named directory would
never be found again by the next run.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on the persistent cache; returns its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
