#!/usr/bin/env python3
"""Readings that set a cell's limits, on many seeds in one process.

    python benchmarks/chip/control.py --workload <cell> --seconds <s> \\
        --seeds 11,12,... --control-seeds 11,12,13 [--fault-seeds 11,12]

Every seed is a benchmark run of the cell (``run.run_cell``: weights and
traffic from the seed, the window at the cell's load, the comparison with
the float32 reference), and prints the program's numbers.  For each
control seed it also prints the control, the reference computed in
float8 (e4m3) in the program's place, the precision step below the
configurations' bfloat16; for each fault seed, the faults the cell can
have, planted in the reference in the program's place or in what the
program produced (the mode's ``Job.controls``).

One JSON line per seed on stdout.  Compiled programs are shared between
seeds through the compile cache.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run  # noqa: E402


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args()
    w = run.cell(args.workload)
    if not run.on_chip(w):
        return 2
    controls, faults = seeds(args.control_seeds), seeds(args.fault_seeds)
    for seed in seeds(args.seeds):
        def hook(job, ctx, ref, arch):
            line = {"program": job.details}
            if seed in controls or seed in faults:
                line.update(job.controls(ref, arch, run.LOWP,
                                         faults=seed in faults))
            return line
        t = time.perf_counter()
        r = run.run_cell(w, seed, args.seconds, False, hook=hook, t_start=t)
        print(json.dumps({
            "seed": seed, "correct": r["correct"], **r["extra"],
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "memory_peak_bytes": r["device"]["memory_peak_bytes"],
            "s": round(time.perf_counter() - t, 1)}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
