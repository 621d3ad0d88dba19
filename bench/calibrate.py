"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds 8]
        [--config <config> --traffic <traffic>]

For each seed, in one process: the program's numbers (the timed path
against the plain reference, as a run compares them), the control's (the
reference computed with float8 matrix-product operands, put in the
program's place) and, for training cells, those of the planted fault
"half of the batch left out, the mean taken over the rest" (the reference
on the first half of each batch).  Training needs no window; a serving
cell runs a short one of ``--seconds`` at the cell's own load, long
enough to finish as many requests as a run compares.  One JSON line per
seed on standard output.  The benchmark's own runs never do this.

A cell that is not (or not yet) in ``BENCHMARK.json`` is named by
``--config`` and ``--traffic``; without a file of its own in
``bench/workloads/`` it is read with no limits.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", type=int, default=3,
                    help="read the control (and planted faults) on the "
                         "first this many seeds")
    ap.add_argument("--config", help="for a cell not in BENCHMARK.json")
    ap.add_argument("--traffic", help="for a cell not in BENCHMARK.json")
    args = ap.parse_args(argv)
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = {w["name"]: w for w in bench["workloads"]}.get(args.workload) or {
        "name": args.workload, "config": args.config,
        "traffic": args.traffic, "chips": 1}
    files = ({} if (harness.BENCH / "workloads" / f"{args.workload}.json")
             .exists() else {"cell": {"limits": {}}})
    harness.chips(entry["chips"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        mode = "control" if i < args.control else "program"
        rec = harness.run_cell(entry, seed, args.seconds, False, files=files,
                               calibrate=mode)
        line = {"workload": args.workload, "seed": seed,
                "program": rec.numbers, "peak_bytes": rec.peak_bytes}
        for extra in ("control", "half_batch", "compared", "details"):
            if hasattr(rec, extra):
                line[extra] = getattr(rec, extra)
        print(json.dumps(line, default=float), flush=True)


if __name__ == "__main__":
    main()
