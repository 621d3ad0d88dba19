"""What every driver shares, whatever the family: host spans, the
profiler around the window, the device's peak memory and the comparison
that decides ``correct``.  What one family needs is in the modules its
configuration file names (``bench/family.py``)."""

from __future__ import annotations

import contextlib
import math
import shutil
import tempfile

import jax

from bench import trace as trace_lib


def span(on: bool, name: str):
    """A profiler span around one call into the program, when tracing."""
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


@contextlib.contextmanager
def profiled(on: bool, span_names, out: dict):
    """Trace the body when ``on``; the reduced trace lands in
    ``out['trace']``.  The raw trace is written under TMPDIR and removed
    once read."""
    if not on:
        yield
        return
    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(logdir)
        try:
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()
        out["trace"] = trace_lib.read(trace_lib.newest_xplane(logdir),
                                      span_names)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else int(stats.get("peak_bytes_in_use", 0))


def judge(numbers: dict, limits: dict):
    """``correct`` and the checks: each number with its limit.  A number
    that is missing or not finite fails."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and math.isfinite(value) and value <= limit
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
