#!/usr/bin/env python3
"""Find a serving cell's knee: the cell's mix offered at a few fixed rates,
one benchmark run (``run.run_cell``) a rate, in one process.

    python benchmarks/chip/knee_sweep.py --workload <cell> \\
        --rates 0.6,0.9,1.2,1.5 --seconds 51 --seed 7

One JSON line per rate: offered and completed requests, tokens per second,
TTFT and TPOT percentiles, and the queue left at the close.  The knee is
the highest rate whose queue does not grow through the window; a cell's
traffic file then fixes its rate at about four fifths of it.
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


def readings(job, ctx, ref, arch) -> dict:
    import readers
    due = readers.due_in_window(ctx)
    end = ctx["window_s"]
    return {
        "due": len(due),
        "admitted": sum(r.admit is not None for r in due),
        "completed": sum(r.done for r in ctx["records"]),
        "waiting_at_close": sum(r.admit is None for r in due),
        "waiting_late_half": sum(r.admit is None and r.due > end / 2
                                 for r in due),
        "out_tok_s": readers.out_tok_s(ctx),
        "ttft_p50_ms": readers.ttft_ms(ctx, 50),
        "ttft_p90_ms": readers.ttft_ms(ctx, 90),
        "tpot_p90_ms": readers.tpot_ms(ctx, 90),
        "decode_ms_p50": readers.decode_ms(ctx, 50),
        "prefill_share": readers.prefill_share(ctx)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    import traffic
    w = run.cell(args.workload)
    if not run.on_chip(w):
        return 2
    tr = traffic.load(w["traffic"])
    base = tr["arrivals"]
    for rate in (float(r) for r in args.rates.split(",")):
        # as many requests in flight at the start as the rate keeps there
        t = {**tr, "arrivals": {**base, "rate_per_s": rate, "in_flight": min(
            tr["pool"]["slots"], round(base.get("in_flight", 0) * rate
                                       / base["rate_per_s"]))}}
        r = run.run_cell(w, args.seed, args.seconds, False, tr=t,
                         hook=readings, t_start=time.perf_counter())
        print(json.dumps({"rate_per_s": rate, "correct": r["correct"],
                          **r["extra"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
