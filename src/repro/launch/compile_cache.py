"""Persistent XLA compilation cache for the entry points.

A compiled step program is keyed by its HLO, the backend and the cache's
own directory, so the directory must not move between runs: it is either
the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable
itself) or ``.jax_cache/`` at the root of this checkout.  Only entry points
call :func:`enable_compile_cache`; importing the library never turns the
cache on, and neither do the tests.

:func:`log_compiles` starts a log of the backend compiles (a cache hit
included), so that a run can tell whether one fell inside a timed window:
:func:`compiles`.  ``bench/diagnose.py`` reads it.
"""

from __future__ import annotations

import os
import time
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
# JAX's event around each backend compile (jax/_src/dispatch.py)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compiles: list[tuple[str, float, float]] = []
_logging = False


def _on_span(event: str, start: float, end: float, **kwargs) -> None:
    if event == BACKEND_COMPILE_EVENT:
        shift = time.perf_counter() - time.time()
        _compiles.append((str(kwargs.get("fun_name", "")), start + shift,
                          end + shift))


def log_compiles() -> None:
    """Start the compile log (once per process): one listener on JAX's
    backend-compile event, called only when a program compiles."""
    global _logging
    if not _logging:
        jax.monitoring.register_event_time_span_listener(_on_span)
        _logging = True


def compiles() -> list[tuple[str, float, float]]:
    """``(function name, start, end)`` of each backend compile since
    :func:`log_compiles`, on the clock of ``time.perf_counter``."""
    return list(_compiles)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
