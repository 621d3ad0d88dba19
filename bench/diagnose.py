"""A diagnostic run of one serving cell: what a traced result line leaves
out, on the same clock as the line.

    python3 bench/diagnose.py --workload <cell> --seed <n> --seconds <s>

It drives the cell as ``bench/run.py --trace 1`` does, on the chip only,
and prints to standard output:

- ``scopes <span>``: device seconds by label inside each span
  (``bench/scopes.py``), largest first, then the operations that took the
  most of that span, each with its label;
- ``idle``: the window's idle device time, each gap charged to the
  innermost host event of any name open at its midpoint: the benchmark's
  spans, and also the runtime's own events (transfers, launches) and
  Python's, so that time under ``token_fetch`` splits into the wait, the
  copy and Python;
- ``compiles``: every backend compile of the run (the program's log,
  ``repro.launch.compile_cache``), and those that fell between the
  window's first submission and its last token;
- the harness's result line, last.
"""

from __future__ import annotations

import argparse
import bisect
import contextlib
import json
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run as harness  # noqa: E402
from bench import scopes  # noqa: E402
from bench import trace as trace_lib  # noqa: E402


def host_events(path: str, device_line=trace_lib.is_tpu_op_line):
    """[(name, start, end)] of every event on the host planes of one
    ``.xplane.pb``, in nanoseconds; lines that ``device_line`` takes for
    the device's are left out."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if not device_line(plane.name, line.name):
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
    return out


@contextlib.contextmanager
def keeping_host_events(events: list, device_line=trace_lib.is_tpu_op_line):
    """While open, every trace the harness reads also adds its host events
    to ``events``: the raw trace is removed once read."""
    real = trace_lib.read

    def read(path, span_names, *args, **kwargs):
        events.extend(host_events(path, device_line))
        return real(path, span_names, *args, **kwargs)

    trace_lib.read = read
    try:
        yield events
    finally:
        trace_lib.read = real


def idle_causes(trace, events, top: int = 20):
    """[[event name, seconds]]: the first device's idle time in the window,
    each gap charged to the latest-started of ``events`` open at its
    midpoint, ``(none)`` where none is."""
    lo, hi = trace.window
    busy = trace.busy_intervals(sorted(trace.ops)[0])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    events = sorted((s, e, n) for n, s, e in events)
    starts = [s for s, _, _ in events]
    reach, furthest = [], float("-inf")        # latest end among the first k
    for _, e, _ in events:
        furthest = max(furthest, e)
        reach.append(furthest)
    by = defaultdict(int)
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        label = "(none)"
        j = bisect.bisect_right(starts, mid) - 1
        while j >= 0 and reach[j] >= mid:
            if events[j][1] >= mid:
                label = events[j][2]
                break
            j -= 1
        by[label] += e - s
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return [[n, ns / 1e9] for n, ns in ranked]


def report(rec, events, compiled, top: int = 12):
    """The lines printed before the result line."""
    lines = []
    found = scopes.of(rec)
    for span in scopes.SPANS:
        got = found and found[span]
        if not got:
            lines.append(f"scopes {span}: none")
            continue
        total = sum(got.values())
        lines.append(f"scopes {span}: {total:.6f} s busy")
        for label, s in sorted(got.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {s:12.6f} s {100 * s / total:6.2f} %  {label}")
        texts, kinds = rec.programs
        table = scopes.instruction_scopes(texts[span])
        ops = scopes.span_ops(rec.trace, span)
        for op, s in sorted(ops.items(), key=lambda kv: -kv[1])[:top]:
            lines.append(f"  op {s:10.6f} s  {scopes.label_of_op(op, table, kinds):16s}"
                         f"  {op[:150]}")
    lo, hi = rec.trace.window
    lines.append(f"idle: {(hi - lo) / 1e9 - rec.trace.busy_s():.6f} s")
    for name, s in idle_causes(rec.trace, events):
        lines.append(f"  {s:12.6f} s  {name[:120]}")
    start, end = rec.batches[0]["submit"], rec.batches[-1]["times"][-1]
    inside = [c for c in compiled if c[2] > start and c[1] < end]
    lines.append(f"compiles: {len(compiled)} in the run, {len(inside)} in the window")
    for name, s, e in inside:
        lines.append(f"  {name} at {s - start:.3f} s into the window, {e - s:.3f} s")
    return lines


def main(argv=None):
    from repro.launch.compile_cache import compiles, log_compiles

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if entry is None:
        sys.exit(f"diagnose: no workload {args.workload!r} in BENCHMARK.json")
    harness.chips(entry["chips"])
    log_compiles()
    with keeping_host_events([]) as events:
        rec = harness.run_cell(entry, args.seed, args.seconds, True)
    line = harness.result(bench, args.workload, rec, True)
    print("\n".join(report(rec, events, compiles())))
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
