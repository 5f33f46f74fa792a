"""Metrics-ingest throughput: columnar completion path vs per-sample adds.

A 10^6-invocation FDNInspector scenario must not pay a per-sample Python
hot path for metrics.  This benchmark ingests the same synthetic
completion set three ways:

  * single-metric arms — ``WindowSeries.add`` per sample vs ONE
    ``ColumnarWindowSeries.add_many`` (the raw series backends);
  * per-completion baseline — the old ``record_completion`` hot path:
    seven ``WindowSeries.add`` calls per completion into the
    (platform, fn, metric)-keyed registry;
  * full bulk path — ``MetricsRegistry.record_completions`` over a
    ``ColumnarResultSink``: the same Table-1 metric set, grouped with
    array masks, one ``add_many`` per (platform, fn, metric).

Claim checked: on identical work (all 7 metrics per completion) the bulk
path sustains >= 5x the per-sample completion throughput, and the
aggregates (count / total / p90) agree across backends.

The ``rollup`` arm re-runs the bulk path with a live telemetry engine
subscribed (repro.obs.telemetry) and splits the cost in two: the *tap*
(what every ingest pays while telemetry is on — buffering the
subscribed series) and the *fold* (downsampling into the tier rings,
deferred off the hot path).  Gates, pinned in ``perf_floor.json`` via
``--check-floor`` like the scheduler bench's ``columnar_traced`` arm:
tap overhead <= 15% of plain bulk ingest, fold throughput above its
pinned samples/s floor.
"""
from __future__ import annotations

import json
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.fdn_common import Row, check, use_compile_cache
from repro.core.loadgen import ColumnarResultSink
from repro.core.monitoring import (ColumnarWindowSeries, MetricsRegistry,
                                   WindowSeries)
from repro.core.types import FunctionSpec
from repro.obs.telemetry import TelemetryConfig, TelemetryEngine

FULL_N = 1_000_000
SMOKE_N = 200_000
WINDOW_S = 10.0
DURATION_S = 600.0
FLOOR_GRACE = 0.30           # fail when > 30% below a pinned rate floor


def _synthetic_completions(n: int):
    rng = np.random.default_rng(7)
    arrival = np.sort(rng.uniform(0.0, DURATION_S, n))
    rt = rng.exponential(0.4, n)
    end = arrival + rt
    fns = [FunctionSpec(name="nodeinfo", flops=1e6, memory_mb=128),
           FunctionSpec(name="JSON-loads", flops=1e7, read_bytes=1e5,
                        memory_mb=256)]
    platforms = ["hpc-node-cluster", "edge-cluster"]
    sink = ColumnarResultSink.from_columns(
        arrival, end, platforms, rng.integers(0, len(platforms), n),
        fns, rng.integers(0, len(fns), n), cold=rng.random(n) < 0.01,
        exec_s=rt * 0.8)
    return sink, end, end - arrival


def run_bench(smoke: bool = False,
              results_out: Optional[Dict] = None
              ) -> Tuple[List[Row], List[str]]:
    n = SMOKE_N if smoke else FULL_N
    rows: List[Row] = []
    failures: List[str] = []
    sink, ts, vs = _synthetic_completions(n)

    ws = WindowSeries(WINDOW_S)
    ts_list, vs_list = ts.tolist(), vs.tolist()
    t0 = time.perf_counter()
    for t, v in zip(ts_list, vs_list):
        ws.add(t, v)
    t_base = time.perf_counter() - t0

    cw = ColumnarWindowSeries(WINDOW_S)
    t0 = time.perf_counter()
    cw.add_many(ts, vs)
    t_col = time.perf_counter() - t0

    # per-completion baseline: the old record_completion hot path —
    # seven per-sample adds into the keyed registry, driven from
    # pre-extracted Python scalars (no Invocation construction billed)
    cols = sink.completion_columns()
    pnames = [name for name, _ in sorted(cols["platform_ids"].items(),
                                         key=lambda kv: kv[1])]
    fnames = [name for name, _ in sorted(cols["fn_ids"].items(),
                                         key=lambda kv: kv[1])]
    prow = [pnames[i] for i in cols["platform"].tolist()]
    frow = [fnames[i] for i in cols["fn"].tolist()]
    mem = {f: float(cols["fn_specs"][f].memory_mb) for f in fnames}
    io = {f: cols["fn_specs"][f].read_bytes + cols["fn_specs"][f].write_bytes
          for f in fnames}
    end_l, rt_l = ts.tolist(), vs.tolist()
    exec_l = cols["exec"].tolist()
    cold_l = cols["cold"].tolist()
    reg_seq = MetricsRegistry(WINDOW_S, columnar=False)
    t0 = time.perf_counter()
    for i in range(n):
        p, f, t = prow[i], frow[i], end_l[i]
        reg_seq.add(p, f, "requests", t, 1.0)
        reg_seq.add(p, f, "response_time", t, rt_l[i])
        reg_seq.add(p, f, "invocations", t, 1.0)
        reg_seq.add(p, f, "exec_time", t, exec_l[i])
        if cold_l[i]:
            reg_seq.add(p, f, "cold_starts", t, 1.0)
        reg_seq.add(p, f, "memory_mb", t, mem[f])
        reg_seq.add(p, f, "disk_io", t, io[f])
    t_seq = time.perf_counter() - t0

    # bulk vs rollup-tapped bulk: best-of-2 with a fresh registry per
    # rep — the tap-overhead gate is a ratio of two fast runs, and one
    # cold first pass (allocator + numpy warmup) can swamp a 15% margin
    # at smoke scale
    def _time_bulk(telemetry: bool):
        best, keep = float("inf"), None
        for _ in range(2):
            r = MetricsRegistry(WINDOW_S)
            eng = None
            if telemetry:
                # capacity 1024 keeps all DURATION_S 1 s buckets live
                # for the correctness checks below (nothing evicted)
                eng = TelemetryEngine(TelemetryConfig(
                    capacity=1024, auto_flush_samples=None))
                r.telemetry = eng
            t0 = time.perf_counter()
            r.record_completions(sink, visible_infra=True)
            dt = time.perf_counter() - t0
            if dt < best:
                best, keep = dt, (r, eng)
        return best, keep

    t_bulk, (reg, _none) = _time_bulk(telemetry=False)
    t_tap, (reg_tel, engine) = _time_bulk(telemetry=True)
    # the fold is off the hot path: tier downsampling, timed separately
    t0 = time.perf_counter()
    folded = engine.flush()
    t_fold = time.perf_counter() - t0

    base_rate = n / max(t_base, 1e-9)
    col_rate = n / max(t_col, 1e-9)
    seq_rate = n / max(t_seq, 1e-9)
    bulk_rate = n / max(t_bulk, 1e-9)
    speedup = bulk_rate / max(seq_rate, 1e-9)

    rows.append(Row("metrics_ingest/per_sample_add", t_base / n * 1e6,
                    f"samples_per_s={base_rate:.0f};n={n}"))
    rows.append(Row("metrics_ingest/columnar_add_many", t_col / n * 1e6,
                    f"samples_per_s={col_rate:.0f};"
                    f"speedup={col_rate / max(base_rate, 1e-9):.1f}x"))
    rows.append(Row("metrics_ingest/record_completion_seq", t_seq / n * 1e6,
                    f"completions_per_s={seq_rate:.0f};metrics=7"))
    rows.append(Row("metrics_ingest/record_completions", t_bulk / n * 1e6,
                    f"completions_per_s={bulk_rate:.0f};metrics=7;"
                    f"speedup={speedup:.1f}x"))
    tap_rate = n / max(t_tap, 1e-9)
    fold_rate = folded / max(t_fold, 1e-9)
    tap_overhead = t_tap / max(t_bulk, 1e-9) - 1.0
    rows.append(Row("metrics_ingest/rollup_tapped", t_tap / n * 1e6,
                    f"completions_per_s={tap_rate:.0f};"
                    f"overhead={tap_overhead * 100:.1f}%"))
    rows.append(Row("metrics_ingest/rollup_fold", t_fold / max(folded, 1)
                    * 1e6, f"samples_per_s={fold_rate:.0f};"
                    f"folded={folded}"))

    # correctness: both backends agree on the aggregates
    check(cw.count() == ws.count() == n, "sample counts must match",
          failures)
    check(abs(cw.total() - ws.total()) < 1e-6 * max(ws.total(), 1.0),
          "window totals must match", failures)
    check(abs(cw.p90() - ws.p90()) < 1e-9, "p90 must match", failures)
    got = sum(int(reg.total(p, f, "requests"))
              for p in sink.platform_counts()
              for f in sink.fn_counts())
    check(got == n, f"record_completions should ingest every completion "
          f"(got {got}/{n})", failures)
    for p in sink.platform_counts():
        for f in sink.fn_counts():
            a = reg.total(p, f, "exec_time")
            b = reg_seq.total(p, f, "exec_time")
            check(abs(a - b) < 1e-6 * max(abs(b), 1.0),
                  f"bulk vs per-sample exec_time mismatch on {p}/{f}",
                  failures)
    target = 5.0
    check(speedup >= target,
          f"record_completions should be >= {target:.0f}x the per-sample "
          f"record_completion baseline (got {speedup:.1f}x)", failures)
    # rollup correctness: every subscribed sample reaches the tier rings
    # (response_time for all completions + cold_starts for the cold ones)
    expect_folded = n + int(cols["cold"].sum())
    check(folded == expect_folded,
          f"rollup should fold every subscribed sample "
          f"(got {folded}/{expect_folded})", failures)
    check(sum(int(engine.series[k].tiers[0].counts.sum())
              for k in engine.keys() if k[2] == "response_time") == n,
          "finest-tier response_time counts must cover every completion",
          failures)

    if results_out is not None:
        results_out.update({
            "n": n, "smoke": smoke,
            "samples_per_s": {
                "per_sample_add": round(base_rate, 1),
                "columnar_add_many": round(col_rate, 1),
            },
            "completions_per_s": {
                "record_completion_seq": round(seq_rate, 1),
                "record_completions": round(bulk_rate, 1),
                "rollup_tapped": round(tap_rate, 1),
            },
            "speedup_bulk_vs_seq": round(speedup, 2),
            "rollup": {
                "tap_overhead_frac": round(tap_overhead, 4),
                "fold_samples_per_s": round(fold_rate, 1),
                "folded_samples": int(folded),
            },
        })
    return rows, failures


def check_floor(results: Dict, floor_path: str,
                failures: List[str]) -> None:
    """Enforce the pinned rollup gates from ``perf_floor.json``: the
    tap-overhead ceiling is absolute, the fold-rate floor gets the same
    30% cold-runner grace as the scheduler floors."""
    with open(floor_path) as f:
        floors = json.load(f).get("metrics_ingest", {})
    if not floors:
        return
    rollup = results.get("rollup", {})
    max_overhead = floors.get("rollup_tap_max_overhead_frac")
    if max_overhead is not None:
        got = rollup.get("tap_overhead_frac", 0.0)
        check(got <= max_overhead,
              f"telemetry tap overhead {got * 100:.1f}% exceeds the "
              f"{max_overhead * 100:.0f}% ceiling", failures)
    fold_floor = floors.get("rollup_fold_samples_per_s")
    if fold_floor is not None:
        limit = fold_floor * (1.0 - FLOOR_GRACE)
        got = rollup.get("fold_samples_per_s", 0.0)
        check(got >= limit,
              f"rollup fold {got:.0f} samples/s below pinned floor "
              f"{fold_floor:.0f} (grace limit {limit:.0f})", failures)


def main(argv: List[str]) -> int:
    use_compile_cache()
    smoke = "--smoke" in argv
    json_path = "BENCH_metrics.json"     # always emitted; --json overrides
    if "--json" in argv:
        json_path = argv[argv.index("--json") + 1]
    results: Dict = {}
    rows, failures = run_bench(smoke=smoke, results_out=results)
    if "--check-floor" in argv:
        check_floor(results, argv[argv.index("--check-floor") + 1],
                    failures)
    with open(json_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    for r in rows:
        print(r.csv())
    print("failures:", failures or "none")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
