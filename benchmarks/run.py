"""FDNInspector benchmark suite — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows for every experiment and a
summary of the paper-claim assertions. Roofline/dry-run results (the
pod-scale analyses) are summarized from results/*.json when present; run
``python -m benchmarks.roofline`` / ``python -m repro.launch.dryrun`` to
regenerate them (they need the 512-device XLA flag set at process start,
so they are separate entry points).
"""
from __future__ import annotations

import importlib
import json
import os
import sys
import time

BENCHES = [
    "fig5_platform_capability",
    "fig6_metric_detail",
    "fig7_function_heterogeneity",
    "fig8_cpu_interference",
    "fig9_memory_interference",
    "fig10_collaboration",
    "fig11_data_locality",
    "table4_energy",
    "policy_sweep",
    "bench_sched_throughput",
    "bench_metrics_ingest",
    "bench_chain_throughput",
    "bench_autoscale",
    "bench_streaming_replay",
    "bench_qos",
]


def scenario_main(args) -> int:
    """``python benchmarks/run.py scenario [name]``: run one registered
    FDNInspector scenario, validate its report schema, print the canonical
    JSON.  No name (or --list) lists the registry."""
    from repro.inspector import ScenarioReport, registry, run_scenario
    if not args or args[0] in ("-l", "--list"):
        for name in registry.names():
            print(name)
        return 0
    name = args[0]
    report = run_scenario(registry.get(name))
    payload = report.to_json()
    ScenarioReport.validate(json.loads(payload))
    print(payload)
    return 0


def scenario_diff_main(args) -> int:
    """``python benchmarks/run.py scenario-diff a.json b.json``: compare
    two canonical ScenarioReport JSONs with per-metric relative
    tolerances; exit 1 on drift (see benchmarks/scenario_diff.py)."""
    from benchmarks.scenario_diff import main as diff_main
    return diff_main(args)


def trace_main(args) -> int:
    """``python benchmarks/run.py trace <scenario> [--out PATH]
    [--sample S]``: run a registered scenario with the flight recorder
    attached, write a Chrome trace-event JSON (load it in Perfetto /
    chrome://tracing) and print the latency_breakdown section."""
    from repro.inspector import registry
    from repro.inspector.scenario import run_scenario_state
    from repro.obs import write_chrome_trace
    usage = "usage: trace <scenario> [--out PATH] [--sample S]"
    out_path, sample = None, 1.0
    names = []
    i = 0
    while i < len(args):
        if args[i] == "--out":
            i += 1
            out_path = args[i]
        elif args[i] == "--sample":
            i += 1
            sample = float(args[i])
        else:
            names.append(args[i])
        i += 1
    if len(names) != 1:
        print(usage)
        return 1
    if names[0] not in registry.names():
        print(f"unknown scenario {names[0]!r}; any registered scenario "
              f"works, and these arms come pre-traced:")
        for name in registry.names():
            if name.startswith("trace/"):
                print(f"  {name}")
        return 1
    sc = registry.get(names[0]).replace(trace=True, trace_sample=sample)
    report, cp, _sink = run_scenario_state(sc)
    if out_path is None:
        out_path = "trace_" + names[0].replace("/", "_") + ".json"
    n_events = write_chrome_trace(cp.recorder, out_path,
                                  alerts=report.alerts)
    print(f"# {n_events} trace events -> {out_path}")
    print(json.dumps(report.latency_breakdown, indent=2, sort_keys=True))
    return 0


def explain_main(args) -> int:
    """``python benchmarks/run.py explain <scenario> [--out PATH]
    [--journal PATH] [--whatif policy=NAME[,key=val...]]``: run a
    registered scenario with the decision journal attached, check the
    same-policy replay oracle, optionally re-score the journal under an
    alternate policy config, and print/write the provenance summary."""
    from repro.inspector import registry
    from repro.inspector.scenario import run_scenario_state
    from repro.obs import (WhatIfConfig, decision_provenance_section,
                           replay, whatif_section)
    usage = ("usage: explain <scenario> [--out PATH] [--journal PATH] "
             "[--whatif policy=NAME[,key=val...]]")
    out_path, journal_path, whatif = None, None, None
    names = []
    i = 0
    while i < len(args):
        if args[i] == "--out":
            i += 1
            out_path = args[i]
        elif args[i] == "--journal":
            i += 1
            journal_path = args[i]
        elif args[i] == "--whatif":
            i += 1
            whatif = WhatIfConfig.parse(args[i])
        else:
            names.append(args[i])
        i += 1
    if len(names) != 1:
        print(usage)
        return 1
    if names[0] not in registry.names():
        print(f"unknown scenario {names[0]!r}; any registered scenario "
              f"works, and these arms come pre-journaled:")
        for name in registry.names():
            if name.startswith("prov/"):
                print(f"  {name}")
        return 1
    sc = registry.get(names[0]).replace(provenance=True)
    report, cp, _sink = run_scenario_state(sc)
    journal = cp.journal
    payload = {"scenario": names[0],
               "decision_provenance": report.decision_provenance}
    if journal.n:
        base = replay(journal)
        oracle_ok = base.matches(journal)
        payload["replay_oracle"] = bool(oracle_ok)
        if not oracle_ok:
            print("# REPLAY ORACLE FAILED: same-policy replay diverged "
                  "from the journaled choices")
        if whatif is not None:
            alt = replay(journal, whatif)
            payload["whatif"] = whatif_section(journal, base, alt)
    else:
        payload["replay_oracle"] = True
    if journal_path is not None:
        journal.save(journal_path)
        print(f"# {journal.n} journal rows -> {journal_path}")
    text = json.dumps(payload, indent=2, sort_keys=True)
    if out_path is not None:
        with open(out_path, "w") as f:
            f.write(text + "\n")
        print(f"# explain report -> {out_path}")
    print(text)
    return 0 if payload["replay_oracle"] else 1


def _summarize_json(path: str, kind: str):
    if not os.path.exists(path):
        print(f"# {kind}: {path} not found — run the generator first")
        return
    with open(path) as f:
        data = json.load(f)
    if kind == "dryrun":
        ok = sum(1 for r in data if r.get("ok"))
        print(f"dryrun/cells_ok,{0.0:.1f},ok={ok}/{len(data)}")
        for r in data:
            print(f"dryrun/{r['arch']}/{r['shape']}/m{r['mesh']},"
                  f"{r['compile_s'] * 1e6:.1f},"
                  f"ok={int(r['ok'])};flops_dev={r['flops_per_dev']:.3e};"
                  f"coll_dev={r['coll_bytes_per_dev']:.3e}")
    else:
        for key, r in data.items():
            if not r.get("ok"):
                continue
            print(f"roofline/{key},{0.0:.1f},"
                  f"comp_s={r['compute_s']:.3e};mem_s={r['memory_s']:.3e};"
                  f"coll_s={r['collective_s']:.3e};dom={r['dominant']};"
                  f"useful={r['useful_ratio']:.3f}")


def main() -> int:
    from benchmarks.fdn_common import use_compile_cache
    use_compile_cache()
    if len(sys.argv) > 1 and sys.argv[1] == "scenario":
        return scenario_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "scenario-diff":
        return scenario_diff_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "trace":
        return trace_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "explain":
        return explain_main(sys.argv[2:])
    t0 = time.time()
    all_failures = []
    print("name,us_per_call,derived")
    for name in BENCHES:
        mod = importlib.import_module(f"benchmarks.{name}")
        t = time.time()
        rows, failures = mod.run_bench()
        for r in rows:
            print(r.csv())
        status = "PASS" if not failures else "FAIL:" + "|".join(failures)
        print(f"{name}/_claims,{(time.time() - t) * 1e6:.1f},{status}")
        all_failures += [f"{name}: {f}" for f in failures]
    _summarize_json("results/dryrun.json", "dryrun")
    _summarize_json("results/roofline.json", "roofline")
    print(f"# total wall: {time.time() - t0:.1f}s")
    if all_failures:
        print("# PAPER-CLAIM FAILURES:")
        for f in all_failures:
            print("#  -", f)
        return 1
    print("# all paper-claim assertions PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
