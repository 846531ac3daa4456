"""Where JAX's persistent compilation cache lives for this checkout.

The entry points a user runs (``chip_smoke.py``, ``benchmarks/run.py``,
the examples) call :func:`enable_compile_cache` once before compiling;
the tests never do.  The cache key includes its directory, so the
directory is fixed: a second run in the same checkout hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_ENV", "CHECKOUT_CACHE", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache is ``.jax_cache/``
    at the checkout root (listed in ``.gitignore``)."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
