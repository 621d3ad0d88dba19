"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, idle
gaps labelled by what the host was doing, and the top device operations.

The harness wraps each of its calls into the program in a
``jax.profiler.TraceAnnotation`` (one ``window`` span around the whole
measured window, and one span per call inside it).  Device operations come
from the device planes (``/device:TPU:<n>``, line ``XLA Ops``); host spans
from the host planes, on the profiler's clock up to an offset of the
device's that is removed as below.

- busy: the union of the intervals in which an operation ran on a device,
  inside the window, averaged over the devices;
- idle gaps: the rest of the window, each gap charged to the innermost
  host span open at its midpoint (``window`` when no call was open);
- ``busy_in(name)``: device busy time inside the spans of that name.

The device's clock is aligned to the host's before anything is reduced:
a program cannot start on the device before the host asked for it, so
where a device's executions (line ``XLA Modules``) pair one to one with
the host's ``PJRT_LoadedExecutable_Execute`` calls and some execution
reads as starting before its call, the device's events are shifted by the
largest such lead.

A trace with no device operation raises: a missing plane must never read
as an idle device.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "window"
HOST_EXECUTE = "PJRT_LoadedExecutable_Execute"


def is_tpu_op_line(plane: str, line: str) -> bool:
    return plane.startswith("/device:TPU:") and line == "XLA Ops"


def newest_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def union(intervals):
    """Merge (start, end) intervals; returns sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals):
    return sum(e - s for s, e in intervals)


@dataclass
class Trace:
    """Device operations per device and host spans, in nanoseconds."""
    ops: dict = field(default_factory=dict)        # device -> [(name, s, e)]
    spans: list = field(default_factory=list)      # [(name, s, e)]
    shift: dict = field(default_factory=dict)      # device -> ns added

    @property
    def window(self):
        w = [(s, e) for n, s, e in self.spans if n == WINDOW]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW!r} span, found {len(w)}")
        return w[0]

    def busy_intervals(self, device):
        lo, hi = self.window
        return clip(union((s, e) for _, s, e in self.ops[device]), lo, hi)

    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over devices."""
        return sum(length(self.busy_intervals(d)) for d in self.ops) / len(self.ops) / 1e9

    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    def busy_in(self, name: str) -> float:
        """Device busy seconds inside the spans called ``name``, averaged
        over devices."""
        spans = union((s, e) for n, s, e in self.spans if n == name)
        total = 0
        for d in self.ops:
            busy = self.busy_intervals(d)
            total += sum(length(clip(busy, s, e)) for s, e in spans)
        return total / len(self.ops) / 1e9

    def idle_gaps(self, top: int = 10):
        """[[label, seconds]]: idle time of the first device in the window,
        summed by the innermost host span open at each gap's midpoint."""
        lo, hi = self.window
        busy = self.busy_intervals(sorted(self.ops)[0])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        inner = sorted((s, e, n) for n, s, e in self.spans if n != WINDOW)
        by = defaultdict(int)
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            label = WINDOW
            for ss, se, n in inner:          # the latest-starting open span
                if ss > mid:
                    break
                if se >= mid:
                    label = n
            by[label] += e - s
        ranked = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        return [[n, ns / 1e9] for n, ns in ranked]

    def device_ops(self, top: int = 10):
        """[[op name, seconds]]: the operations that took the most device
        time in the window, averaged over devices."""
        lo, hi = self.window
        by = defaultdict(int)
        for d in self.ops:
            for n, s, e in self.ops[d]:
                if e > lo and s < hi:
                    by[n] += min(e, hi) - max(s, lo)
        ranked = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        return [[n, ns / len(self.ops) / 1e9] for n, ns in ranked]


def read(path: str, span_names, device_line=is_tpu_op_line) -> Trace:
    """Read the device operations and the host spans named in
    ``span_names`` (plus ``window``) from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    names = set(span_names) | {WINDOW}
    trace = Trace()
    modules, executes = {}, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if device_line(plane.name, line.name):
                trace.ops.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif line.name == "XLA Modules":
                modules[plane.name] = [e.start_ns for e in line.events]
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name in names:
                        trace.spans.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name == HOST_EXECUTE:
                        executes.append(e.start_ns)
    trace.ops = {k: v for k, v in trace.ops.items() if v}
    if not trace.ops:
        raise ValueError(f"{path}: no device operations in the trace; the "
                         f"idle share cannot be read")
    for dev in trace.ops:
        shift = trace.shift[dev] = device_lead(modules.get(dev, []), executes)
        trace.ops[dev] = [(n, s + shift, e + shift) for n, s, e in trace.ops[dev]]
    return trace


def device_lead(module_starts, execute_starts) -> int:
    """Nanoseconds to add to a device's clock: the largest lead of an
    execution over the host call that launched it, when the two pair one
    to one in order; 0 when they do not pair or no execution leads."""
    if not module_starts or len(module_starts) != len(execute_starts):
        return 0
    lag = min(m - h for m, h in zip(sorted(module_starts),
                                    sorted(execute_starts)))
    return max(0, -lag)
