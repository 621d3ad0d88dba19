"""Persistent XLA compilation cache for the entry points.

A compiled step program is keyed by its HLO, the backend and the cache's
own directory, so the directory must not move between runs: it is either
the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable
itself) or ``.jax_cache/`` at the root of this checkout.  Only entry points
call :func:`enable_compile_cache`; importing the library never turns the
cache on, and neither do the tests.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
