"""Chain-planner throughput + the split-vs-colocate A/B claims.

Arm 1 — planner throughput: the data-gravity planner places whole chains
against the five Table-3 platforms with the production
``SLOCompositePolicy``.  Measured per *stage* (the unit a per-invocation
scheduler would decide): one ``Policy.score`` call per plan covers every
stage, so a plan costs array ops, not S x P platform scans.  Two
sub-arms: a fresh ``PlatformSnapshot`` per plan (the cold path) and a
shared snapshot across a batch of plans (the ``submit_batch``-style fast
path).  Claim: the shared-snapshot planner places >= 10^4 stages/s.

Arm 2 — collaborative execution vs forced co-location: the registered
``chains/split-vs-colocate-ab`` scenarios must show the flip the paper's
§3.1.3/§5.1.4 predict — with a fast interconnect the split arm beats the
co-located arm on end-to-end chain p90 (queue relief outweighs cheap
transfers); with a slow WAN the order reverses (features crossing
platforms dominate).  Reports are seed-deterministic (byte-identical
JSON across runs).
"""
from __future__ import annotations

import json
import sys
import time
from typing import Dict, List, Optional, Tuple

from benchmarks.fdn_common import Row, build_fdn, check, use_compile_cache
from repro.chains import DataGravityPlanner, catalog
from repro.core.scheduler import PlatformSnapshot

FULL_PLANS = 3_000
SMOKE_PLANS = 400


def _build_harness():
    cp, _gw, fns = build_fdn(analytic=True)
    tmpl = catalog.get("etl-pipeline")
    allfns = dict(fns)
    allfns.update(tmpl.functions)
    for spec in tmpl.functions.values():
        for p in cp.platforms.values():
            p.deploy(spec)
    for inp in tmpl.inputs:
        cp.placement.stores["cloud-cluster"].put(inp.key, inp.size_bytes)
    planner = DataGravityPlanner(cp.policy, cp.placement, allfns)
    return cp, planner, tmpl


def _bench_planner(n_plans: int) -> Tuple[float, float, int]:
    """Returns (fresh_stages_per_s, shared_stages_per_s, stages)."""
    cp, planner, tmpl = _build_harness()
    plats = list(cp.platforms.values())
    stages = tmpl.chain.n_stages

    t0 = time.perf_counter()
    for _ in range(n_plans):
        planner.plan(tmpl.chain, plats, mode="auto")
    fresh = n_plans * stages / max(time.perf_counter() - t0, 1e-9)

    snap = PlatformSnapshot(plats)
    t0 = time.perf_counter()
    for _ in range(n_plans):
        planner.plan(tmpl.chain, snap, mode="auto")
    shared = n_plans * stages / max(time.perf_counter() - t0, 1e-9)
    return fresh, shared, n_plans * stages


def _run_ab(smoke: bool):
    from repro.inspector import registry, run_scenario
    from repro.inspector.registry import split_vs_colocate
    if smoke:
        fast = run_scenario(split_vs_colocate(2e9, duration_s=40.0))
        slow = run_scenario(split_vs_colocate(3e6, rps=1.0,
                                              duration_s=40.0,
                                              suffix="-slowwan"))
    else:
        fast = run_scenario(registry.get("chains/split-vs-colocate-ab"))
        slow = run_scenario(
            registry.get("chains/split-vs-colocate-ab-slowwan"))
    return fast, slow


def run_bench(smoke: bool = False,
              results_out: Optional[Dict] = None
              ) -> Tuple[List[Row], List[str]]:
    rows: List[Row] = []
    failures: List[str] = []

    n = SMOKE_PLANS if smoke else FULL_PLANS
    fresh, shared, stages = _bench_planner(n)
    stages_per_plan = stages // n
    rows.append(Row("chain_throughput/plan_fresh_snapshot",
                    1e6 * stages_per_plan / max(fresh, 1e-9),
                    f"stages_per_s={fresh:.0f};plans={n}"))
    rows.append(Row("chain_throughput/plan_shared_snapshot",
                    1e6 * stages_per_plan / max(shared, 1e-9),
                    f"stages_per_s={shared:.0f};plans={n}"))
    target = 2.5e3 if smoke else 1e4
    check(shared >= target,
          f"shared-snapshot planner should place >= {target:.0f} "
          f"stages/s on 5 platforms (got {shared:.0f})", failures)

    fast, slow = _run_ab(smoke)
    f_split = fast.per_chain["ab@split"]["p90_s"]
    f_coloc = fast.per_chain["ab@colocate"]["p90_s"]
    s_split = slow.per_chain["ab@split"]["p90_s"]
    s_coloc = slow.per_chain["ab@colocate"]["p90_s"]
    rows.append(Row("chain_ab/fast_wan", f_split * 1e6,
                    f"split_p90={f_split:.3f};colocate_p90={f_coloc:.3f};"
                    f"completed={fast.per_chain['ab@split']['completed']}"))
    rows.append(Row("chain_ab/slow_wan", s_split * 1e6,
                    f"split_p90={s_split:.3f};colocate_p90={s_coloc:.3f};"
                    f"completed={slow.per_chain['ab@split']['completed']}"))
    check(f_split < f_coloc,
          "fast WAN: collaborative split should beat forced co-location "
          f"on chain p90 (split={f_split:.3f} vs coloc={f_coloc:.3f})",
          failures)
    check(s_split > s_coloc,
          "slow WAN: forced co-location should beat the gravity-blind "
          f"split on chain p90 (split={s_split:.3f} vs "
          f"coloc={s_coloc:.3f})", failures)

    if results_out is not None:
        results_out.update({
            "smoke": smoke, "plans": n, "stages": stages,
            "stages_per_s": {
                "fresh_snapshot": round(fresh, 1),
                "shared_snapshot": round(shared, 1),
            },
            "ab": {
                "fast_wan": {"split_p90_s": f_split,
                             "colocate_p90_s": f_coloc},
                "slow_wan": {"split_p90_s": s_split,
                             "colocate_p90_s": s_coloc},
            },
        })
    return rows, failures


def main(argv: List[str]) -> int:
    use_compile_cache()
    smoke = "--smoke" in argv
    json_path = "BENCH_chain.json"       # always emitted; --json overrides
    if "--json" in argv:
        json_path = argv[argv.index("--json") + 1]
    results: Dict = {}
    rows, failures = run_bench(smoke=smoke, results_out=results)
    with open(json_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    for r in rows:
        print(r.csv())
    print("failures:", failures or "none")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
