"""Readings the limits of ``correct`` are set from, for one cell.

    python3 fdnbench/control.py --workload <cell> --seeds 1 2 3 ... \\
        --seconds <s> [--out FILE]

Runs the cell once per seed in one process (the chip is held once) and,
at the positions of each run's own window, prints the compared numbers of
the program (sound runs: the lower readings) and of the controls put in
the program's place (``fdnbench/check.py``): ``frozen``, ``stale``,
``no_util``, ``no_degrade``, ``frozen_estimates`` and
``stale_estimates``.  ``bfloat16`` (the reference's cascade in bfloat16)
is printed beside them for a later comparison of costs: the timed kernel
returns only its choices, so it is not a control of this check.
``--fault`` plants one of ``fdnbench/faults.py``'s faults in the program
for every run.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(out) -> dict:
    import ml_dtypes
    from fdnbench import check
    cap, ref = out.capture, out.reference
    program = dict(check.admission_numbers(cap))
    program.update(check.decision_numbers(cap, ref))
    program.update(check.estimator_numbers(cap, out.fleet, out.estimates))
    return {"program": program,
            "frozen": check.held_control(cap, ref, frozen=True),
            "stale": check.held_control(cap, ref, frozen=False),
            "no_util": check.replaced_control(
                ref, check.reference(cap, out.fleet, util_filter=False)),
            "no_degrade": check.replaced_control(
                ref, check.reference(cap, out.fleet, degrade=False)),
            "frozen_estimates": check.held_estimates(
                cap, out.fleet, out.estimates, frozen=True),
            "stale_estimates": check.held_estimates(
                cap, out.fleet, out.estimates, frozen=False),
            "bfloat16": check.replaced_control(
                ref, check.reference(cap, out.fleet, ml_dtypes.bfloat16)),
            "decisions": int(cap.d.n), "rows": out.window["rows"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import contextlib
    from fdnbench import faults, harness
    plant = faults.FAULTS[args.fault] if args.fault else \
        contextlib.nullcontext
    if not args.cpu_rehearsal:
        harness.use_compile_cache()
    rows = []
    for seed in args.seeds:
        with plant():
            out = harness.run_cell(args.workload, seed, args.seconds,
                                   False, cpu=args.cpu_rehearsal)
        r = {"workload": args.workload, "seed": seed, "fault": args.fault,
             "correct": out.result["correct"],
             "device": out.result["device"]["kind"], **readings(out)}
        rows.append(r)
        print(json.dumps(r), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
