"""Scheduler-admission throughput: per-invocation vs batched vs the
fused jit admission fast path.

The FDN's control plane routes every invocation through a policy decision
(paper §3.1.3).  This benchmark measures decisions/sec of the admission
paths on the five Table-3 platforms with the production
``SLOCompositePolicy``:

  * ``per_invocation`` — ``FDNControlPlane.submit`` in a loop: one
    platform scan + policy evaluation + queue drain per invocation (the
    paper-scale path: 5 platforms x 50 VUs);
  * ``batched`` — ``FDNControlPlane.submit_batch``, PR-1 default config
    (knowledge-base decision rows retained);
  * ``pr1_hedged`` — the PR-1 batched admission under the paper's
    production fault-tolerance config (hedging armed): full-matrix
    ``Policy.score`` over (N, P), per-invocation KB decision rows, and
    one hedge ``watch`` registration (alternates list + timer event) per
    invocation — a faithful re-implementation of the PR-1 loop on
    today's substrate (the substrate underneath is *faster* than PR-1's,
    so the measured speedup is conservative);
  * ``jit_hedged`` — the fused admission path under the same config:
    one jitted filter-cascade + argmin decision per distinct function
    (``repro.kernels.policy_score``), bulk KB counters, and ONE
    vectorized hedge timer per (fn, platform) admission group;
  * ``columnar`` — ``InvocationBatch`` struct-of-arrays admission:
    arrivals live as NumPy columns end to end, ``submit_batch`` takes
    zero-copy chunk views of one preallocated stream, and ``Invocation``
    objects materialize lazily only when a replica starts a row (the
    streaming-replay configuration: no KB decision rows);
  * ``columnar_traced`` — the columnar arm with the flight recorder
    attached at 1/16 head-based sampling (repro.obs): the tracing-
    overhead gate, pinned <= 15% below the untraced columnar rate;
  * ``columnar_qos`` — the columnar arm with the QoS layer armed
    (three classes + tenants on every row, non-uniform DRR weights, the
    admission gate in the path): the QoS-overhead gate, pinned <= 15%
    below the plain columnar rate;
  * ``columnar_provenance`` — the columnar arm with the decision
    journal attached (repro.obs.provenance): every fused decision
    records its kill bits, score columns, choice and runner-up margin;
    the provenance-overhead gate, pinned <= 15% below the plain
    columnar rate.

No simulated time elapses while submitting, so all arms schedule against
identical platform-state snapshots at t=0 and the measurement isolates
the admission engine.  Claims checked:

  * ``batched`` sustains >= 10x ``per_invocation`` (>= 3x in --smoke);
  * ``jit_hedged`` sustains >= 3x ``pr1_hedged`` at 5 platforms x 10^4
    invocations (the compiled-admission acceptance pin);
  * ``columnar`` sustains >= 2x ``batched`` (the array-native-core
    acceptance pin: the next jump past the PR-4 729k/s floor);
  * jax and NumPy score backends pick identical platforms.

Measurements always land in ``BENCH_sched.json`` (``--json PATH``
overrides the location; CI uploads it); ``--check-floor FLOOR.json`` fails when
any pinned metric drops more than 30% below its floor
(``benchmarks/perf_floor.json`` — re-bless it alongside intentional
hot-path changes).
"""
from __future__ import annotations

import gc
import json
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.fdn_common import Row, build_fdn, check, use_compile_cache
from repro.core import scheduler as sched
from repro.core.faults import HedgePolicy
from repro.core.invocation_batch import InvocationBatch
from repro.core.scheduler import SLOCompositePolicy
from repro.core.types import Invocation

FULL_N = 40_000
SMOKE_N = 4_000
HEDGE_FULL_N = 10_000        # the acceptance pin's 5 platforms x 10^4
BATCH = 2_048
FLOOR_GRACE = 0.30           # fail when > 30% below the pinned floor
FN_MIX = ("nodeinfo", "primes-python", "JSON-loads", "image-processing")


class PR1CompositePolicy(SLOCompositePolicy):
    """SLOCompositePolicy pinned to the PR-1 decision path: no fused
    per-function decisions, so ``choose_batch`` scores the full (N, P)
    matrix and row-argmins it."""

    def fn_decisions(self, fns, snap, n=None):
        return None


def _make_invs(fns, n: int) -> List[Invocation]:
    specs = [fns[name] for name in FN_MIX]
    return [Invocation(specs[i % len(specs)], 0.0) for i in range(n)]


def _make_stream(fns, n: int, qos: bool = False) -> InvocationBatch:
    """The same round-robin mix as ``_make_invs``, born columnar.  With
    ``qos`` every row carries a class (cycling through all three) and a
    tenant, so the QoS arm pays the full column cost."""
    specs = [fns[name] for name in FN_MIX]
    idx = np.arange(n, dtype=np.int32)
    kw = {}
    if qos:
        kw = {"qos": (idx % 3).astype(np.int8),
              "tenant": (idx % 7).astype(np.int32)}
    return InvocationBatch(specs, idx % len(specs), np.zeros(n), **kw)


def _seed_observations(cp, fns, per_pair: int = 12):
    """>= 10 latency observations per (fn, platform): the hedge policy
    only arms timers once the P90 model has real samples."""
    for name in FN_MIX:
        for pname in cp.platforms:
            for _ in range(per_pair):
                inv = Invocation(fns[name], 0.0)
                inv.platform = pname
                inv.exec_time = 0.05
                inv.end_t = 0.05
                cp.perf.observe(inv)


def _run_arm(kind: str, n: int) -> Tuple[float, int, int]:
    """Returns (seconds, accepted, n)."""
    cp, _gw, fns = build_fdn(analytic=True)
    if kind == "pr1_hedged":
        cp.policy = PR1CompositePolicy(cp.perf, cp.placement)
        _seed_observations(cp, fns)
        hedge = HedgePolicy(cp.clock, cp.perf, enabled=True)
    elif kind == "jit_hedged":
        cp.hedge.enabled = True
        cp.kb.log_decisions = False
        sched.set_score_backend("jax")
        _seed_observations(cp, fns)
    elif kind == "columnar":
        cp.kb.log_decisions = False
    elif kind == "columnar_traced":
        from repro.obs import FlightRecorder
        cp.kb.log_decisions = False
        cp.attach_recorder(FlightRecorder(sample=1.0 / 16))
    elif kind == "columnar_qos":
        from repro.core.qos import QosSpec
        cp.kb.log_decisions = False
        # DRR queues + admission gate armed; no limits or thresholds,
        # so every row is still accepted and the arms stay comparable
        cp.attach_qos(QosSpec(weights=(4, 2, 1)))
    elif kind == "columnar_provenance":
        from repro.obs import DecisionJournal
        cp.kb.log_decisions = False
        cp.attach_provenance(DecisionJournal())
    if kind in ("columnar", "columnar_traced", "columnar_qos",
                "columnar_provenance"):
        stream = _make_stream(fns, n, qos=kind == "columnar_qos")
    else:
        invs = _make_invs(fns, n)

    # the previous arm's control plane (queues, timer closures) is garbage
    # by now; collect it OUTSIDE the timed region so each arm pays for its
    # own allocation behavior only (GC stays ON — collector pressure from
    # per-invocation timer closures is a real cost of that design)
    gc.collect()
    t0 = time.perf_counter()
    if kind == "per_invocation":
        accepted = sum(1 for inv in invs if cp.submit(inv))
    elif kind in ("batched", "jit_hedged"):
        accepted = 0
        for lo in range(0, n, BATCH):
            accepted += cp.submit_batch(invs[lo:lo + BATCH])
    elif kind in ("columnar", "columnar_traced", "columnar_qos",
                  "columnar_provenance"):
        accepted = 0
        for lo in range(0, n, BATCH):
            accepted += cp.submit_batch(stream.view(lo,
                                                    min(lo + BATCH, n)))
    elif kind == "pr1_hedged":
        accepted = 0
        admit = {name: sc.admit for name, sc in cp.sidecars.items()}
        for lo in range(0, n, BATCH):
            batch = invs[lo:lo + BATCH]
            accepted += cp.submit_batch(batch)
            # PR-1's hedging block: alternates + watch per invocation
            alive = cp.alive_platforms()
            for inv in batch:
                if inv.platform is None:
                    continue
                target = cp.platforms[inv.platform]
                alternates = [p for p in alive if p is not target]
                hedge.watch(inv, target, alternates,
                            lambda i, p: admit[p.prof.name](i))
    else:
        raise ValueError(kind)
    dt = time.perf_counter() - t0
    sched.set_score_backend("auto")
    return dt, accepted, n


def _check_backend_parity(failures: List[str]):
    """jax and NumPy cascades must pick identical platforms."""
    cp, _gw, fns = build_fdn(analytic=True)
    _seed_observations(cp, fns)
    invs = _make_invs(fns, 512)
    plats = list(cp.platforms.values())
    picks = {}
    for backend in ("numpy", "jax"):
        sched.set_score_backend(backend)
        picks[backend] = [p.prof.name if p else None for p in
                          cp.policy.choose_batch(invs, plats)]
    sched.set_score_backend("auto")
    check(picks["numpy"] == picks["jax"],
          "jax score backend must pick byte-identical platforms to the "
          "NumPy oracle", failures)


def _warmup():
    """Absorb one-time costs (jax import, jit traces) outside timing."""
    sched.set_score_backend("jax")
    cp, _gw, fns = build_fdn(analytic=True)
    cp.submit_batch(_make_invs(fns, 128))
    sched.set_score_backend("auto")


def _planned_stages_per_s(smoke: bool) -> float:
    from benchmarks.bench_chain_throughput import (SMOKE_PLANS,
                                                   _bench_planner)
    _fresh, shared, _stages = _bench_planner(SMOKE_PLANS if smoke
                                             else 1_000)
    return shared


def check_floor(results: Dict, floor_path: str,
                failures: List[str]) -> None:
    with open(floor_path) as f:
        floors = json.load(f)
    for name, floor in floors.get("decisions_per_s", {}).items():
        got = results["decisions_per_s"].get(name)
        limit = floor * (1.0 - FLOOR_GRACE)
        check(got is not None and got >= limit,
              f"perf floor breach: decisions_per_s[{name}] = "
              f"{got if got is None else round(got)} < {limit:.0f} "
              f"(floor {floor:.0f} - {FLOOR_GRACE:.0%})", failures)
    floor = floors.get("planned_stages_per_s")
    if floor is not None:
        got = results["planned_stages_per_s"]
        limit = floor * (1.0 - FLOOR_GRACE)
        check(got >= limit,
              f"perf floor breach: planned_stages_per_s = {got:.0f} < "
              f"{limit:.0f} (floor {floor:.0f} - {FLOOR_GRACE:.0%})",
              failures)


def run_bench(smoke: bool = False,
              results_out: Optional[Dict] = None
              ) -> Tuple[List[Row], List[str]]:
    n = SMOKE_N if smoke else FULL_N
    # the hedged arms always run the acceptance pin's 10^4 invocations:
    # they are cheap, and the per-invocation-timer arm's cost profile
    # (and so the measured speedup) only stabilizes at full batch count
    hedge_n = HEDGE_FULL_N
    rows: List[Row] = []
    failures: List[str] = []
    _warmup()

    rates: Dict[str, float] = {}
    reps = 2 if smoke else 3                   # best-of: tame CI jitter
    for kind, kn in (("per_invocation", n), ("batched", n),
                     ("columnar", n), ("columnar_traced", n),
                     ("columnar_qos", n), ("columnar_provenance", n),
                     ("pr1_hedged", hedge_n), ("jit_hedged", hedge_n)):
        dt = float("inf")
        for _ in range(reps):
            rep_dt, acc, kn = _run_arm(kind, kn)
            dt = min(dt, rep_dt)
            check(acc == kn, f"{kind} should accept every invocation "
                  f"(got {acc}/{kn})", failures)
        rates[kind] = kn / max(dt, 1e-9)
        rows.append(Row(f"sched_throughput/{kind}", dt / kn * 1e6,
                        f"decisions_per_s={rates[kind]:.0f};"
                        f"accepted={acc}/{kn};best_of={reps}"))

    speedup = rates["batched"] / max(rates["per_invocation"], 1e-9)
    hedged_speedup = rates["jit_hedged"] / max(rates["pr1_hedged"], 1e-9)
    columnar_speedup = rates["columnar"] / max(rates["batched"], 1e-9)
    traced_frac = rates["columnar_traced"] / max(rates["columnar"], 1e-9)
    qos_frac = rates["columnar_qos"] / max(rates["columnar"], 1e-9)
    prov_frac = (rates["columnar_provenance"]
                 / max(rates["columnar"], 1e-9))
    rows.append(Row("sched_throughput/speedups", 0.0,
                    f"batched_vs_per_invocation={speedup:.1f}x;"
                    f"jit_hedged_vs_pr1_hedged={hedged_speedup:.1f}x;"
                    f"columnar_vs_batched={columnar_speedup:.1f}x;"
                    f"traced_vs_columnar={traced_frac:.2f}x;"
                    f"qos_vs_columnar={qos_frac:.2f}x;"
                    f"provenance_vs_columnar={prov_frac:.2f}x;"
                    f"batch={BATCH}"))

    target = 3.0 if smoke else 10.0
    check(speedup >= target,
          f"submit_batch should be >= {target:.0f}x per-invocation submit "
          f"(got {speedup:.1f}x)", failures)
    check(hedged_speedup >= 3.0,
          "fused jit admission (grouped hedging) should be >= 3x the "
          f"PR-1 batched path (got {hedged_speedup:.1f}x)", failures)
    check(columnar_speedup >= 2.0,
          "struct-of-arrays admission should be >= 2x the object-list "
          f"batched path (got {columnar_speedup:.1f}x)", failures)
    check(traced_frac >= 0.85,
          "sampled tracing (1/16) should cost <= 15% of the columnar "
          f"admission rate (got {traced_frac:.2f}x)", failures)
    # at smoke scale (~3 ms per timed rep) the ratio is jitter-dominated;
    # the 15% pin is enforced at full scale, where the per-drain DRR cost
    # amortizes (measured ~0.9-1.0x), and absolutely via the pinned
    # columnar_qos decisions/s floor
    qos_target = 0.70 if smoke else 0.85
    check(qos_frac >= qos_target,
          f"QoS classes + DRR + admission gate should cost <= "
          f"{(1.0 - qos_target):.0%} of the columnar admission rate "
          f"(got {qos_frac:.2f}x)", failures)
    # same smoke-jitter caveat as the QoS gate: the 15% provenance pin
    # is enforced at full scale and absolutely via the pinned
    # columnar_provenance decisions/s floor
    prov_target = 0.70 if smoke else 0.85
    check(prov_frac >= prov_target,
          f"decision-journal recording should cost <= "
          f"{(1.0 - prov_target):.0%} of the columnar admission rate "
          f"(got {prov_frac:.2f}x)", failures)
    _check_backend_parity(failures)

    if results_out is not None:
        results_out.update({
            "n": n, "hedge_n": hedge_n, "batch": BATCH, "smoke": smoke,
            "decisions_per_s": {k: round(v, 1) for k, v in rates.items()},
            "speedups": {"batched_vs_per_invocation": round(speedup, 2),
                         "jit_hedged_vs_pr1_hedged":
                         round(hedged_speedup, 2),
                         "columnar_vs_batched":
                         round(columnar_speedup, 2),
                         "traced_vs_columnar": round(traced_frac, 3),
                         "qos_vs_columnar": round(qos_frac, 3),
                         "provenance_vs_columnar": round(prov_frac, 3)},
            "tracing_overhead_pct": round((1.0 - traced_frac) * 100.0, 1),
            "provenance_overhead_pct":
            round((1.0 - prov_frac) * 100.0, 1),
            "planned_stages_per_s":
            round(_planned_stages_per_s(smoke), 1),
        })
    return rows, failures


def main(argv: List[str]) -> int:
    use_compile_cache()
    smoke = "--smoke" in argv
    floor_path = None
    json_path = "BENCH_sched.json"       # always emitted; --json overrides
    if "--json" in argv:
        json_path = argv[argv.index("--json") + 1]
    if "--check-floor" in argv:
        floor_path = argv[argv.index("--check-floor") + 1]
    results: Dict = {}
    rows, failures = run_bench(smoke=smoke, results_out=results)
    if floor_path is not None:
        check_floor(results, floor_path, failures)
    if json_path is not None:
        with open(json_path, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
            f.write("\n")
    for r in rows:
        print(r.csv())
    print("failures:", failures or "none")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
