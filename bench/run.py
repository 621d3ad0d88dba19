"""The benchmark's one entry point: run one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: a cell's entry names
its configuration (``bench/configs/<config>.json``, which names its
family's reference and program mapping, ``bench/family.py``) and its
traffic mix (``bench/traffic/<traffic>.json``, which names the driver
that generates it, ``bench/drivers/<driver>.py``); the cell's own file
(``bench/workloads/<cell>.json``) holds the limits of its correctness
check; each metric is read by ``bench/metrics/<metric>.py``, or a
quantity split by the end-to-end metric it moves (``<quantity>.<kind>``)
by ``bench/metrics/<quantity>.py``.  A later change adds a cell, a
configuration of any family, a traffic mix or a metric by adding files
and entries.

With ``--trace 0`` the run reports the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, ``busy_s``/``window_s`` and the
breakdown, from a profiler trace of the window: the device operations
that took most time, the longest idle gaps by host span, and the device
seconds by named scope in each span (``bench/scopes.py``; null where the
record gives none).  The last line of standard output is one JSON
object; the numbers that decided ``correct`` are the last lines of
standard error and the last key of that object.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero before printing a result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def metric_names(bench: dict, workload: str, trace: bool):
    """The metrics this cell reports: end-to-end without tracing,
    per-layer with it; a metric without ``workloads`` covers every cell."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def reader(name: str) -> Path:
    """``bench/metrics/<name>.py``, or for a quantity split by the metric
    it moves (``<quantity>.<kind>``) the quantity's own reader."""
    path = BENCH / "metrics" / f"{name}.py"
    return path if path.exists() else BENCH / "metrics" / f"{name.split('.')[0]}.py"


def read_metric(name: str, rec):
    path = reader(name)
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def chips(n: int):
    """The TPU devices, or exit non-zero: never fall back to the CPU."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"bench: JAX found no backend: {e}")
    if devices[0].platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform {devices[0].platform!r}); "
                 f"the benchmark runs on the chip only")
    if len(devices) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX found {len(devices)}")
    return devices


def result(bench: dict, workload: str, rec, trace: bool) -> dict:
    from bench import scopes
    from bench.peaks import peaks

    rec.peaks = peaks(rec.device.device_kind)
    metrics = {}
    for m in metric_names(bench, workload, trace):
        value = read_metric(m["name"], rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    import jax

    device = {"platform": rec.device.platform, "kind": rec.device.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": rec.peak_bytes}
    line = {"correct": bool(rec.correct), "attempted": rec.attempted,
            "failed": rec.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = rec.trace.busy_s()
        device["window_s"] = rec.trace.window_s()
        line["breakdown"] = {"device_ops": rec.trace.device_ops(),
                             "idle_gaps": rec.trace.idle_gaps(),
                             "scopes": scopes.of(rec)}
    line["checks"] = rec.checks
    return line


def run_cell(entry: dict, seed: int, seconds: float, trace: bool,
             files: dict | None = None, calibrate: str = ""):
    """Drive the cell of ``BENCHMARK.json`` entry ``entry``; ``files``
    may replace any of its loaded "cell", "config" and "traffic" (tests).
    ``calibrate`` ("program" or "control", bench/calibrate.py) reads the
    numbers a limit is set from: a training cell then skips its window,
    and "control" also reads the control and the planted faults."""
    from repro.launch.compile_cache import enable_compile_cache

    import jax

    f = dict(files or {})
    for key, kind, name in (("cell", "workloads", entry["name"]),
                            ("config", "configs", entry["config"]),
                            ("traffic", "traffic", entry["traffic"])):
        if key not in f:
            f[key] = load(kind, name)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    driver = importlib.import_module(f"bench.drivers.{f['traffic']['driver']}")
    return driver.run(f["cell"], f["config"], f["traffic"], seed, seconds,
                      trace, T0, calibrate=calibrate)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if entry is None:
        sys.exit(f"bench: no workload {args.workload!r} in BENCHMARK.json")
    devices = chips(entry["chips"])
    from bench.peaks import peaks
    peaks(devices[0].device_kind)               # an unknown chip fails here

    rec = run_cell(entry, args.seed, args.seconds, bool(args.trace))
    line = result(bench, args.workload, rec, bool(args.trace))
    if rec.trace is not None:
        print(f"device clock shift (ns): {rec.trace.shift}", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr)
    for name, c in rec.checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
