"""Warm-pool controller throughput + prewarm-policy A/B claims
(repro.autoscale).

Two measurements:

  * ``tick throughput`` — controller ticks/second over the five Table-3
    platforms x the Table-2 function mix (25 managed rows) under the
    predictive forecaster, with an arrival burst landing every 8th tick:
    the mixed steady-state the dormant fast-forward + cached-decision
    paths are built for.  The full run pins >= 1e5 ticks/s; CI checks the
    pinned floor in ``benchmarks/perf_floor.json`` via ``--check-floor``.
  * ``policy A/B`` — the registry's prewarm-policy studies, asserting the
    energy-vs-SLO trade-off in BOTH directions (seed-deterministic; the
    same numbers are drift-gated by the golden reports):
      - diurnal deep-trough trace: predictive prewarming beats the fixed
        60 s keep-alive on cold-start rate at equal-or-lower idle Wh;
      - sparse trace: scale-to-zero wins idle Wh but pays for it in p99
        (cold start on nearly every arrival);
      - MMPP burst trace: predictive holds equal-or-lower idle Wh.

``--smoke`` runs fewer ticks and only the sparse A/B (the diurnal pair is
covered by the CI golden gate); ``--json PATH`` writes the measurements;
``--check-floor FLOOR.json`` fails when a pinned metric drops more than
30% below its floor.
"""
from __future__ import annotations

import gc
import json
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.fdn_common import Row, build_fdn, check, use_compile_cache

FULL_TICKS = 200_000
SMOKE_TICKS = 50_000
ARRIVAL_EVERY = 8
FLOOR_GRACE = 0.30
TICKS_PER_S_PIN = 1e5


def _bench_ticks(n_ticks: int, reps: int) -> Tuple[float, int]:
    """(ticks/s best-of-reps, managed rows): drives ``controller.tick``
    directly with a synthetic admission stream (counters written the same
    way the platforms write them), isolating the control loop itself."""
    from repro.autoscale import WarmPoolController, make_policy
    cp, _gw, _fns = build_fdn(analytic=True)
    ctl = WarmPoolController(cp.platforms, cp.perf, cp.clock,
                             make_policy("predictive"), tick_s=1.0).attach()
    clock = cp.clock
    p0 = next(iter(cp.platforms.values()))
    for _ in range(256):                   # settle pools / warm caches
        clock._t += 1.0
        ctl.tick()
    best = float("inf")
    for _ in range(reps):
        # collect previous arms' garbage outside the timed region (GC
        # stays ON inside it; the controller allocates nothing per tick)
        gc.collect()
        t0 = time.perf_counter()
        for i in range(n_ticks):
            clock._t += 1.0
            if i % ARRIVAL_EVERY == 0:
                c = p0.autoscale_counts
                c["nodeinfo"] = c.get("nodeinfo", 0) + 5
            ctl.tick()
        best = min(best, time.perf_counter() - t0)
    return n_ticks / best, ctl._rows


def _run_ab(name: str) -> Dict[str, float]:
    from repro.inspector import registry, run_scenario
    t = run_scenario(registry.get(name)).totals
    return {"cold_start_rate": t["cold_start_rate"],
            "cold_starts": t["cold_starts"], "idle_wh": t["idle_wh"],
            "p99_s": t["p99_s"], "completed": t["completed"]}


def _check_parity(failures: List[str]) -> None:
    """NumPy and jax forecaster backends must make byte-identical prewarm
    decisions on a seeded arrival stream."""
    from repro.autoscale import PredictivePolicy
    rng = np.random.default_rng(7)
    rows, ticks = 12, 400
    streams = rng.poisson(2.0, size=(ticks, rows)) * \
        (rng.random(size=(ticks, rows)) < 0.3)
    exec_s = rng.uniform(0.01, 0.5, rows)
    decisions = {}
    for backend in ("numpy", "jax"):
        pol = PredictivePolicy(backend=backend)
        pol.resize(rows)
        pol.set_exec(exec_s, 1.0)
        out = []
        for k in range(ticks):
            counts = streams[k].astype(float)
            desired, ttl = pol.tick(counts, bool(counts.any()))
            out.append((desired.astype(int).tolist(),
                        np.asarray(ttl).astype(int).tolist()))
        decisions[backend] = out
    check(decisions["numpy"] == decisions["jax"],
          "jax forecaster must make byte-identical prewarm decisions to "
          "the NumPy oracle", failures)


def run_bench(smoke: bool = False,
              results_out: Optional[Dict] = None
              ) -> Tuple[List[Row], List[str]]:
    rows: List[Row] = []
    failures: List[str] = []
    n_ticks = SMOKE_TICKS if smoke else FULL_TICKS
    reps = 2 if smoke else 3

    ticks_per_s, n_rows = _bench_ticks(n_ticks, reps)
    rows.append(Row("autoscale/tick_throughput", 1e6 / ticks_per_s,
                    f"ticks_per_s={ticks_per_s:.0f};rows={n_rows};"
                    f"arrival_every={ARRIVAL_EVERY};best_of={reps}"))
    if not smoke:
        check(ticks_per_s >= TICKS_PER_S_PIN,
              f"controller should sustain >= {TICKS_PER_S_PIN:.0e} "
              f"ticks/s (got {ticks_per_s:.0f})", failures)

    # -------------------------------------------------- policy A/B ----
    ab: Dict[str, Dict[str, float]] = {}
    arms = ["sparse-ttl", "sparse-scale-to-zero"]
    if not smoke:
        arms += ["diurnal-ttl", "diurnal-predictive",
                 "burst-ttl", "burst-predictive"]
    for arm in arms:
        ab[arm] = s = _run_ab(f"autoscale/{arm}")
        rows.append(Row(
            f"autoscale/{arm}", 0.0,
            f"cold_rate={s['cold_start_rate']:.4f};"
            f"idle_wh={s['idle_wh']:.4f};p99_s={s['p99_s']:.3f};"
            f"n={s['completed']}"))

    s2z, ttl = ab["sparse-scale-to-zero"], ab["sparse-ttl"]
    check(s2z["idle_wh"] < ttl["idle_wh"],
          "sparse: scale-to-zero should win idle Wh over the fixed TTL",
          failures)
    check(s2z["p99_s"] > ttl["p99_s"],
          "sparse: scale-to-zero should pay for idle Wh with worse p99",
          failures)
    if not smoke:
        pred, ttl = ab["diurnal-predictive"], ab["diurnal-ttl"]
        check(pred["cold_start_rate"] < ttl["cold_start_rate"],
              "diurnal: predictive prewarming should beat the fixed TTL "
              "on cold-start rate", failures)
        check(pred["idle_wh"] <= ttl["idle_wh"],
              "diurnal: predictive should spend equal-or-lower idle Wh "
              "than the fixed TTL", failures)
        check(ab["burst-predictive"]["idle_wh"] <=
              ab["burst-ttl"]["idle_wh"],
              "burst: predictive should hold equal-or-lower idle Wh",
              failures)
        _check_parity(failures)

    if results_out is not None:
        results_out.update({
            "smoke": smoke, "n_ticks": n_ticks, "rows": n_rows,
            "autoscale_ticks_per_s": round(ticks_per_s, 1),
            "ab": ab,
        })
    return rows, failures


def check_floor(results: Dict, floor_path: str,
                failures: List[str]) -> None:
    with open(floor_path) as f:
        floors = json.load(f)
    floor = floors.get("autoscale_ticks_per_s")
    if floor is None:
        return
    got = results["autoscale_ticks_per_s"]
    limit = floor * (1.0 - FLOOR_GRACE)
    check(got >= limit,
          f"perf floor breach: autoscale_ticks_per_s = {got:.0f} < "
          f"{limit:.0f} (floor {floor:.0f} - {FLOOR_GRACE:.0%})", failures)


def main(argv: List[str]) -> int:
    use_compile_cache()
    smoke = "--smoke" in argv
    floor_path = None
    json_path = "BENCH_autoscale.json"   # always emitted; --json overrides
    if "--json" in argv:
        json_path = argv[argv.index("--json") + 1]
    if "--check-floor" in argv:
        floor_path = argv[argv.index("--check-floor") + 1]
    results: Dict = {}
    rows, failures = run_bench(smoke=smoke, results_out=results)
    if floor_path is not None:
        check_floor(results, floor_path, failures)
    with open(json_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    for r in rows:
        print(r.csv())
    print("failures:", failures or "none")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
