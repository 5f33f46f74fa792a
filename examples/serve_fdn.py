"""End-to-end FDN serving driver (the paper's kind of deployment).

Builds the heterogeneous Function Delivery Network — five target platforms
from small edge boxes to a full pod — deploys both the paper's benchmark
functions and ML-serving functions for the assigned architectures, then
drives a mixed workload through the Gateway and prints where the FDN
delivered every function, the SLO outcomes, and the per-platform energy.

    PYTHONPATH=src python examples/serve_fdn.py
"""
from repro.core import (FDNControlPlane, Gateway, SLOCompositePolicy)
from repro.core import functions as fn_mod
from repro.core import profiles
from repro.core.loadgen import attach_completion_hooks, run_load
from repro.core.types import DeploymentSpec, SLO
from repro.core.deployment import DeploymentGenerator


def build_serving_fdn():
    """The serving deployment: every TPU platform, the paper functions and
    three model-serving functions, hedging and predictive prewarm on."""
    cp = FDNControlPlane(enable_hedging=True, predictive_prewarm=True)
    for prof in profiles.TPU_PLATFORMS.values():
        cp.create_platform(prof)

    # functions: 2 paper-style CPU functions + 3 model-serving functions
    fns = fn_mod.paper_functions()
    serve_fns = {a: fn_mod.serving_function(a).replace(slo=SLO(5.0))
                 for a in ("qwen3-0.6b", "mixtral-8x7b", "llama3-405b")}
    all_fns = list(fns.values()) + list(serve_fns.values())
    fn_mod.seed_object_stores(cp.placement, location="hpc-pod")

    spec = DeploymentSpec("fdn-serve", all_fns, list(cp.platforms))
    spec = DeploymentGenerator(cp.kb, cp.events).annotate(spec)
    cp.deploy(spec)
    attach_completion_hooks(cp)
    cp.policy = SLOCompositePolicy(cp.perf, cp.placement)
    return cp, Gateway(cp), all_fns


def drive(cp, gw, all_fns, duration_s: float = 240.0):
    """Four closed-loop virtual users per function through the gateway,
    one function after another; returns one LoadResult per function."""
    return [run_load(cp.clock, lambda i: gw.request(i), fn, vus=4,
                     duration_s=duration_s, sleep_s=0.5)
            for fn in all_fns]


def main():
    cp, gw, all_fns = build_serving_fdn()
    print("== driving mixed workload through the FDN gateway ==")
    drive(cp, gw, all_fns)

    print(f"\n{'function':>22s} -> platform decisions")
    by_fn = {}
    for d in cp.kb.decisions:
        by_fn.setdefault(d["fn"], {}).setdefault(d["platform"], 0)
        by_fn[d["fn"]][d["platform"]] += 1
    for fn, plats in by_fn.items():
        top = max(plats, key=plats.get)
        print(f"{fn:>22s} -> {top:14s} ({plats})")

    print(f"\n{'platform':>14s} {'served':>7s} {'P90 s':>8s} {'joules':>10s}")
    for name in cp.platforms:
        print(f"{name:>14s} {cp.metrics.requests_served(name):7d} "
              f"{cp.metrics.p90_response(name):8.3f} "
              f"{cp.energy.joules(name):10.1f}")
    met = sum(1 for i in cp.completed
              if i.response_time is not None
              and i.response_time <= i.fn.slo.p90_response_s)
    print(f"\nSLO-satisfying completions: {met}/{len(cp.completed)} "
          f"hedges={cp.hedge.hedges_sent} "
          f"redelivered={cp.redeliverer.redelivered}")


if __name__ == "__main__":
    main()
