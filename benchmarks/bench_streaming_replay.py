"""Streaming replay at Azure scale: chunked minute columns through the
fused admission step in bounded memory.

Two scales:

  * ``--smoke`` — ``scale/million-burst``: one burst hour, ~10^6
    invocations (the CI peak-RSS gate: a million-arrival burst must NOT
    inflate the resident set, because arrivals never exist as objects);
  * full (default) — the 14-day Azure-trace shape, ~10^8 invocations
    streamed through hour chunks (the array-native-core exit criterion).

Claims checked at both scales:

  * every generated arrival is submitted and decided
    (submitted == admitted + rejected == the trace's total count);
  * the SLO-composite policy admits the whole trace on the five
    Table-3 platforms (analytic predictions: nothing is infeasible);
  * perf-model cells absorbed the folded population (the columnar sink
    actually received the stream);
  * peak RSS stays under ``--rss-limit-mb`` (default 1024) — measured
    with ``resource.getrusage``, so it covers the whole process
    including the trace's count matrix;
  * live telemetry rollups (on by default; ``--no-rollups`` disables)
    fold every admitted row into the multi-resolution tier rings under
    the SAME RSS bound — O(tiers x capacity) rollup state regardless of
    trace length is the engine's headline claim.

``--json PATH`` writes measurements (rows/s, peak RSS, totals) for the
CI artifact."""
from __future__ import annotations

import gc
import json
import resource
import sys
import time
from typing import Dict, List, Optional, Tuple

from benchmarks.fdn_common import Row, build_fdn, check, use_compile_cache
from repro.inspector.streaming import stream_replay
from repro.inspector.traces import synthetic_azure_counts

FN_MIX = ("nodeinfo", "primes-python", "JSON-loads", "image-processing")
FULL_DAYS = 14
FULL_TOTAL = 100_000_000        # ~10^8: the Azure-trace scale
SMOKE_TOTAL = 1_000_000         # scale/million-burst
CHUNK_MINUTES = 60
DEFAULT_RSS_LIMIT_MB = 1024


def _peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _trace(minutes: int, total: int) -> Dict:
    """Synthetic Azure minute counts sized to ~``total`` arrivals."""
    mean_rpm = total / (len(FN_MIX) * minutes)
    return synthetic_azure_counts(FN_MIX, minutes=minutes,
                                  mean_rpm=mean_rpm, seed=7)


def run_bench(smoke: bool = False,
              rss_limit_mb: float = DEFAULT_RSS_LIMIT_MB,
              results_out: Optional[Dict] = None,
              rollups: bool = True
              ) -> Tuple[List[Row], List[str]]:
    rows: List[Row] = []
    failures: List[str] = []
    label = "million-burst" if smoke else "azure-14d"
    minutes = 60 if smoke else FULL_DAYS * 1440
    counts = _trace(minutes, SMOKE_TOTAL if smoke else FULL_TOTAL)
    total = int(sum(int(c.sum()) for c in counts.values()))

    cp, _gw, fns = build_fdn(analytic=True)
    cp.kb.log_decisions = False
    engine = None
    if rollups:
        from repro.obs.telemetry import TelemetryConfig, TelemetryEngine
        # capacity 4096 lets a whole hour chunk (3600 finest buckets)
        # fold as one vectorized span group instead of 8 ring wraps
        engine = cp.attach_telemetry(
            TelemetryEngine(TelemetryConfig(capacity=4096,
                                            auto_flush_samples=None)))
    gc.collect()
    t0 = time.perf_counter()
    stats = stream_replay(cp, fns, counts, chunk_minutes=CHUNK_MINUTES,
                          seed=7)
    dt = time.perf_counter() - t0
    peak_mb = _peak_rss_mb()
    rate = stats.submitted / max(dt, 1e-9)

    extra = ""
    if engine is not None:
        engine.finalize()
        roll = engine.rollup_summary()
        extra = (f";rollup_samples={roll['samples']}"
                 f";rollup_keys={roll['keys']}")
        check(roll["samples"] == stats.admitted,
              "rollups must fold every admitted row "
              f"(got {roll['samples']}/{stats.admitted})", failures)
    rows.append(Row(f"streaming_replay/{label}", dt / max(total, 1) * 1e6,
                    f"rows_per_s={rate:.0f};submitted={stats.submitted};"
                    f"chunks={stats.chunks};"
                    f"peak_chunk_rows={stats.peak_chunk_rows};"
                    f"peak_rss_mb={peak_mb:.0f}" + extra))

    check(stats.submitted == total,
          f"every trace arrival must be submitted "
          f"(got {stats.submitted}/{total})", failures)
    check(stats.admitted + stats.rejected == stats.submitted,
          "every submission must be decided", failures)
    check(stats.rejected == 0,
          "SLO-composite should admit the whole trace on the Table-3 "
          f"platforms (rejected {stats.rejected})", failures)
    folded = sum(int(cp.perf._state.exec_n[cp.perf._frow[name], :].sum())
                 for name in FN_MIX if name in cp.perf._frow)
    check(folded == stats.admitted,
          "perf-model cells must absorb the folded population "
          f"(folded {folded} != admitted {stats.admitted})", failures)
    check(peak_mb <= rss_limit_mb,
          f"peak RSS {peak_mb:.0f} MB exceeds the {rss_limit_mb:.0f} MB "
          "bound — arrivals are leaking into objects", failures)

    if results_out is not None:
        results_out.update({
            "scale": label, "total": total, "seconds": round(dt, 3),
            "rows_per_s": round(rate, 1), "peak_rss_mb": round(peak_mb, 1),
            "rss_limit_mb": rss_limit_mb,
            "chunk_minutes": CHUNK_MINUTES, **stats.to_dict(),
        })
        if engine is not None:
            results_out["rollup"] = engine.rollup_summary()
    return rows, failures


def main(argv: List[str]) -> int:
    use_compile_cache()
    smoke = "--smoke" in argv
    rss_limit = DEFAULT_RSS_LIMIT_MB
    json_path = "BENCH_replay.json"      # always emitted; --json overrides
    if "--rss-limit-mb" in argv:
        rss_limit = float(argv[argv.index("--rss-limit-mb") + 1])
    if "--json" in argv:
        json_path = argv[argv.index("--json") + 1]
    results: Dict = {}
    rows, failures = run_bench(smoke=smoke, rss_limit_mb=rss_limit,
                               results_out=results,
                               rollups="--no-rollups" not in argv)
    with open(json_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    for r in rows:
        print(r.csv())
    print("failures:", failures or "none")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
