"""Run one cell of the FDN benchmark once.

    python3 fdnbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Loads, warms up, measures for ``--seconds`` and checks what the window
produced; the last line of standard output is the result as one JSON
object, the numbers compared are the last lines of standard error.  Exits
1 without a result when JAX finds no TPU or fewer chips than the cell
asks for, and 2 when the program under test is not beside the benchmark.
``--cpu-rehearsal`` runs the same path on the CPU for tests; its result
says ``"rehearsal": true`` and names the CPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--trace-out", default=None,
                    help="keep the trace's .xplane.pb in this directory")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("fdnbench: the program under test (src/repro) is missing",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from fdnbench import harness
    if not args.cpu_rehearsal:
        harness.use_compile_cache()
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), cpu=args.cpu_rehearsal,
                               t_start=T_START, trace_out=args.trace_out)
    except harness.NoChip as e:
        print(f"fdnbench: {e}", file=sys.stderr)
        return 1
    harness.print_checks(out.result)
    print(json.dumps(out.result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
