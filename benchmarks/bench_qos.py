"""QoS + overload-resilience A/B: DRR vs FIFO under a burst storm, and
graceful load-shedding under sustained overload.

Runs the registered ``qos/*`` scenarios end to end (identical arrival
streams per pair — same seeds, same workload mixes) and checks the
headline claims of the QoS layer:

  * ``qos/burst-storm-drr`` vs ``qos/burst-storm-fifo`` — the same
    MMPP burst storm drained with weighted deficit-round-robin (8:3:1)
    vs a pure FIFO (uniform weights).  DRR must hold the
    latency_critical class's p99 and SLO-violation rate far below the
    FIFO arm's, while still serving the batch class (no starvation);
    the FIFO arm must actually violate under the storm, so the A/B is
    not vacuous.
  * ``qos/overload-shed`` — admission control under a ramp that
    saturates the fleet: batch (and then standard) rows are shed at
    ingress, latency_critical is never shed and keeps a low violation
    rate.
  * ``qos/brownout-energy-cap`` — an energy cap below the fleet's
    loaded power: brownout mode sheds ONLY the batch class while
    latency_critical stays within SLO.

Measurements land in ``BENCH_qos.json`` (``--json PATH`` overrides);
the scenarios are seeded, so the asserted margins are deterministic on
a given NumPy version.
"""
from __future__ import annotations

import json
import sys
import time
from typing import Dict, List, Optional, Tuple

from benchmarks.fdn_common import Row, check, use_compile_cache

CLS = ("latency_critical", "standard", "batch")


def _run(name: str) -> Tuple[Dict, float]:
    from repro.inspector import registry, run_scenario
    t0 = time.perf_counter()
    report = run_scenario(registry.get(name))
    return report.qos, time.perf_counter() - t0


def _rows_for(name: str, qos: Dict, wall: float, rows: List[Row]):
    per_class = qos["per_class"]
    shed = qos["admission"]["shed_by_class"]
    for cls in CLS:
        s = per_class[cls]
        rows.append(Row(f"qos/{name.split('/')[1]}/{cls}",
                        wall / max(s["completed"], 1) * 1e6,
                        f"p99_s={s['p99_s']:.3f};"
                        f"viol={s['slo_violation_rate']:.3f};"
                        f"share={s['served_share']:.3f};"
                        f"shed={shed[cls]}"))


def run_bench(smoke: bool = False,
              results_out: Optional[Dict] = None
              ) -> Tuple[List[Row], List[str]]:
    rows: List[Row] = []
    failures: List[str] = []
    out: Dict[str, Dict] = {}
    for name in ("qos/burst-storm-drr", "qos/burst-storm-fifo",
                 "qos/overload-shed", "qos/brownout-energy-cap"):
        qos, wall = _run(name)
        out[name] = qos
        _rows_for(name, qos, wall, rows)

    drr = out["qos/burst-storm-drr"]["per_class"]["latency_critical"]
    fifo = out["qos/burst-storm-fifo"]["per_class"]["latency_critical"]
    drr_batch = out["qos/burst-storm-drr"]["per_class"]["batch"]
    rows.append(Row("qos/drr_vs_fifo", 0.0,
                    f"lc_p99_drr={drr['p99_s']:.2f};"
                    f"lc_p99_fifo={fifo['p99_s']:.2f};"
                    f"lc_viol_drr={drr['slo_violation_rate']:.3f};"
                    f"lc_viol_fifo={fifo['slo_violation_rate']:.3f};"
                    f"batch_share_drr={drr_batch['served_share']:.3f}"))

    # the A/B is only meaningful if the FIFO arm actually melts down
    check(fifo["slo_violation_rate"] >= 0.3,
          "burst storm should overload the FIFO arm's latency_critical "
          f"class (got viol={fifo['slo_violation_rate']:.3f})", failures)
    check(drr["slo_violation_rate"] <= 0.5 * fifo["slo_violation_rate"],
          "DRR should at least halve the FIFO latency_critical violation "
          f"rate (got {drr['slo_violation_rate']:.3f} vs "
          f"{fifo['slo_violation_rate']:.3f})", failures)
    check(drr["p99_s"] <= 0.6 * fifo["p99_s"],
          "DRR should hold latency_critical p99 well under FIFO's "
          f"(got {drr['p99_s']:.2f}s vs {fifo['p99_s']:.2f}s)", failures)
    check(drr_batch["completed"] > 0
          and drr_batch["served_share"] >= 0.15,
          "DRR must not starve the batch class (got share="
          f"{drr_batch['served_share']:.3f})", failures)

    adm = out["qos/overload-shed"]["admission"]
    lc = out["qos/overload-shed"]["per_class"]["latency_critical"]
    check(adm["shed_by_class"]["latency_critical"] == 0,
          "overload shedding must never drop latency_critical rows "
          f"(got {adm['shed_by_class']['latency_critical']})", failures)
    check(adm["shed_by_class"]["batch"] > 0,
          "sustained overload should shed batch rows at ingress "
          f"(got {adm['shed_by_class']['batch']})", failures)
    check(lc["slo_violation_rate"] <= 0.15,
          "with shedding on, latency_critical should stay within SLO "
          f"(got viol={lc['slo_violation_rate']:.3f})", failures)

    brown = out["qos/brownout-energy-cap"]["admission"]
    check(brown["brownout_events"] > 0
          and brown["brownout_shed"]["batch"] > 0
          and brown["brownout_shed"]["latency_critical"] == 0
          and brown["brownout_shed"]["standard"] == 0,
          "the energy cap should trip brownout mode and shed ONLY the "
          f"batch class (got {brown['brownout_shed']})", failures)

    if results_out is not None:
        results_out.update({
            "smoke": smoke,
            "drr_vs_fifo": {
                "lc_p99_drr_s": round(drr["p99_s"], 3),
                "lc_p99_fifo_s": round(fifo["p99_s"], 3),
                "lc_viol_drr": round(drr["slo_violation_rate"], 4),
                "lc_viol_fifo": round(fifo["slo_violation_rate"], 4),
                "batch_share_drr": round(drr_batch["served_share"], 4),
            },
            "overload_shed": {k: dict(v) if isinstance(v, dict) else v
                              for k, v in adm.items()},
            "brownout": {k: dict(v) if isinstance(v, dict) else v
                         for k, v in brown.items()},
        })
    return rows, failures


def main(argv: List[str]) -> int:
    use_compile_cache()
    smoke = "--smoke" in argv
    json_path = "BENCH_qos.json"
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
