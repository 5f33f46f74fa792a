"""Find a fleet's sustainable simulated rate on the CPU.

    JAX_PLATFORMS=cpu python3 fdnbench/rehearse_rate.py --config paper-fdn \\
        --traffic azure-bulk

Replays the traffic mix through the configuration's fleet for
``CRITERION["sim_s"]`` seconds of sim time at a trial rate, in the mix's
own admission windows, as fast as the host allows (``harness.drive``),
and samples the rows queued on every platform once per sim-second.  A
rate is sustainable when, once the fleet has come out of its start-up
(``settle_s``: cold starts, empty estimators), the backlog never holds
more than ``backlog_s`` seconds of arrivals, on every seed of
``seeds``.  Bisection between ``lo_rps`` and ``hi_rps`` finds the highest
such rate.  The rate is a property of the simulation, not of the chip, so
it is found here and recorded in the configuration file under
``sustainable_rps``, keyed by the admission window it was found with (a
fleet fed in 1 s windows takes a whole second's arrivals of a function on
one platform, so it sustains less than one fed in 5 ms windows), with
``CRITERION`` beside it as ``sustainable_criterion``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The criterion every recorded ``sustainable_rps`` was found with.
CRITERION = {"sim_s": 600.0, "settle_s": 120, "backlog_s": 2.0,
             "seeds": [1, 2], "lo_rps": 20.0, "hi_rps": 2000.0, "steps": 7}


def queue_trace(config, mix, rps: float, seed: int):
    """Rows queued on the fleet at the end of every sim-second."""
    from fdnbench import deployment, harness
    from fdnbench.traffic import Traffic
    mix = dict(mix, rate={"rps": rps})
    dep = deployment.build(config, mix.get("control_plane", {}))
    traffic = Traffic(mix, config, dep.specs, seed)
    depth = []
    harness.drive(dep, traffic, traffic.window_s, CRITERION["sim_s"],
                  every_s=1.0,
                  sample=lambda d: depth.append(deployment.queued_rows(d.cp)))
    return depth


def sustainable(depth, rps: float) -> bool:
    return max(depth[CRITERION["settle_s"]:]) <= CRITERION["backlog_s"] * rps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    with open(os.path.join(ROOT, "fdnbench", "configs",
                           args.config + ".json")) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, "fdnbench", "traffic",
                           args.traffic + ".json")) as fh:
        mix = json.load(fh)
    lo, hi = CRITERION["lo_rps"], CRITERION["hi_rps"]
    settle = CRITERION["settle_s"]
    for _ in range(CRITERION["steps"]):
        mid = (lo * hi) ** 0.5
        ok = True
        for seed in CRITERION["seeds"]:
            depth = queue_trace(config, mix, mid, seed)
            held = sustainable(depth, mid)
            print(json.dumps({"rps": mid, "seed": seed, "sustainable": held,
                              "max_backlog_s": max(depth[settle:]) / mid,
                              "depth_every_30s": depth[29::30]}),
                  flush=True)
            ok = ok and held
            if not ok:
                break
        lo, hi = (mid, hi) if ok else (lo, mid)
    print(json.dumps({"config": args.config, "traffic": args.traffic,
                      "window_s": mix["window_s"], "sustainable_rps": lo,
                      "sustainable_criterion": CRITERION}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
