"""Knee of an open-loop cell: the highest offered rate whose backlog of due
batches does not grow across the window.

    python3 fdnbench/sweep.py --workload <cell> --rates 200 400 800 ... \\
        --seconds <s> --seed <n>

Runs the cell once per rate in one process, each with the mix's rate
replaced, and prints how late the batches went in (first and last
quarter of the window) with the admission tails.  A backlog grows when
the last quarter's mean lateness exceeds one admission window and the
first quarter's.  The cell's rate is then set in its mix file, at 0.8 of
the knee, capped at the fleet's sustainable simulated rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from fdnbench import harness, stats
    if not args.cpu_rehearsal:
        harness.use_compile_cache()
    for rps in args.rates:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               False, cpu=args.cpu_rehearsal,
                               mix_override={"rate": {"rps": rps}})
        w = out.window
        win_ms = 1e3 * float(out.cell.mix["window_s"])
        grows = w["late_last_quarter_ms"] > max(
            win_ms, w["late_first_quarter_ms"])
        print(json.dumps({
            "workload": args.workload, "rps": rps, "grows": grows,
            "device": out.result["device"]["kind"],
            "late_first_quarter_ms": w["late_first_quarter_ms"],
            "late_last_quarter_ms": w["late_last_quarter_ms"],
            "late_max_ms": w["late_max_ms"],
            "admit_p50_ms": 1e3 * stats.row_percentile(
                w["latency_s"], w["rows_per_batch"], 50),
            "admit_p99_ms": 1e3 * stats.row_percentile(
                w["latency_s"], w["rows_per_batch"], 99),
            "rows": w["rows"], "correct": out.result["correct"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
