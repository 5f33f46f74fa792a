"""The benchmark's plain references against the program: the NumPy
cascade against the program's cascades (the jitted device kernel and the
host oracle) at small sizes, on dyadic columns where every precision
computes the same numbers, and the recomputed estimators against the
program's performance model fed the same completions."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from fdnbench import deployment  # noqa: E402
from fdnbench.reference import cascade, estimators  # noqa: E402


def dyadic_config(nf, npl, rng):
    plats = [{"name": f"p{j}", "faas": "openwhisk",
              "nodes": int(rng.integers(1, 4)), "replicas_per_node": 4,
              "memory_mb_per_node": 1024 * int(rng.integers(1, 5)),
              "replica_flops": 2.0 ** int(rng.integers(28, 33)),
              "net_bw": 2.0 ** int(rng.integers(24, 30)),
              "loaded_w_per_node": float(rng.integers(1, 64)) / 4}
             for j in range(npl)]
    objs, fns = [], []
    for i in range(nf):
        f = {"name": f"f{i}", "flops": 2.0 ** int(rng.integers(20, 34)),
             "memory_mb": 512 * int(rng.integers(1, 9)),
             "slo_p90_s": 2.0 ** int(rng.integers(-3, 4))}
        if rng.random() < 0.5:
            f["read_bytes"] = 2.0 ** int(rng.integers(10, 20))
            f["data_objects"] = [f"obj{i}"]
            objs.append({"key": f"obj{i}",
                         "bytes": 2.0 ** int(rng.integers(10, 20)),
                         "location": f"p{int(rng.integers(npl))}"})
        fns.append(f)
    return {"name": "dyadic", "platforms": plats, "functions": fns,
            "objects": objs,
            "placement": {"local_bw": 2.0 ** 33, "wan_bw": 2.0 ** 25},
            "policy": {"name": "slo_composite", "cpu_threshold": 0.875,
                       "mem_threshold": 0.9375, "energy_weight": 0.125},
            "perf_model": {"exec_min_obs": 3, "p90_min_obs": 10,
                           "p90_bootstrap": 1.5, "exec_ewma_alpha": 0.2,
                           "p90_quantile": 0.9},
            "control_plane": {"enable_hedging": False,
                              "retain_completions": False,
                              "kb_log_decisions": False},
            "autoscaler": None}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("nf,npl", [(1, 1), (2, 3), (4, 5), (8, 16)])
def test_reference_matches_program_cascades(nf, npl, seed):
    from repro.core import scheduler
    from repro.core.scheduler import PlatformSnapshot, SLOCompositePolicy
    from repro.kernels import policy_score as ps
    rng = np.random.default_rng(1000 * nf + 10 * npl + seed)
    config = dyadic_config(nf, npl, rng)
    backend = scheduler.get_score_backend()
    try:
        dep = deployment.build(config, {})
    finally:
        scheduler.set_score_backend(backend)
    cp, specs = dep.cp, dep.specs
    snap = PlatformSnapshot(list(cp.platforms.values()))
    levels = np.array([0.0, 0.25, 0.5, 0.875, 0.9375, 1.0])
    snap.cpu_util = rng.choice(levels, npl)
    snap.mem_util = rng.choice(levels, npl)
    base = snap.fn_matrix(specs, None, cp.placement)
    analytic = cp.perf.analytic_matrix(specs, snap.profs)
    nodes, loaded_w = snap.power
    unloaded = (snap.cpu_util < 0.875) & (snap.mem_util < 0.9375)
    slo = np.array([s.slo.p90_response_s for s in specs])
    ewma_v = rng.integers(1, 64, (nf, npl)) / 8.0
    ewma_n = rng.integers(0, 6, (nf, npl))
    resp_h2 = rng.integers(1, 128, (nf, npl)) / 8.0
    resp_n = rng.choice([0, 12], (nf, npl))

    rows = np.ones((nf, 1))
    ref = cascade.decide(cascade.Fleet(config), np.arange(nf), ewma_v,
                         ewma_n, resp_h2, resp_n, rows * snap.cpu_util,
                         rows * snap.mem_util, np.ones((nf, npl), bool))

    idx, ok = ps.fused_composite_decide(
        ewma_v, ewma_n, analytic, resp_h2, resp_n, base["data_s"], nodes,
        loaded_w, base["alive"], unloaded, slo, 0.125)
    np.testing.assert_array_equal(np.asarray(ok), ref.ok)
    np.testing.assert_array_equal(np.asarray(idx)[ref.ok], ref.best[ref.ok])

    exec_s = np.where(ewma_n >= 3, ewma_v, analytic)
    feats = {"alive": base["alive"], "exec_s": exec_s,
             "data_s": base["data_s"],
             "p90_s": np.where(resp_n >= 10, resp_h2, exec_s * 1.5),
             "energy_j": exec_s * nodes[None, :] * loaded_w[None, :],
             "cpu_util": snap.cpu_util, "mem_util": snap.mem_util,
             "slo_s": slo}
    cost, kill = SLOCompositePolicy.cascade(
        feats, {"cpu_threshold": 0.875, "mem_threshold": 0.9375,
                "energy_weight": 0.125})
    np.testing.assert_array_equal(cost, ref.cost)
    np.testing.assert_array_equal(kill == 0, ref.feasible)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n,levels", [(7, 0), (200, 0), (200, 6),
                                      (3000, 0), (3000, 20)])
def test_recomputed_estimators_match_the_program(n, levels, seed):
    # levels > 0 draws from a few values, so the P-square markers meet
    # equal observations
    from repro.core.behavioral import EWMA, P2Quantile
    rng = np.random.default_rng(100 * n + 10 * levels + seed)
    x = rng.lognormal(0.0, 1.0, n)
    if levels:
        x = rng.choice(x[:levels], n)
    ewma, p2 = EWMA(0.2), P2Quantile(0.9)
    want_e, want_p = [], []
    for v in x.tolist():
        ewma.add(v)
        p2.add(v)
        want_e.append(ewma.value())
        want_p.append(p2.value() if p2.count >= 5 else np.nan)
    np.testing.assert_array_equal(estimators.ewma_trace(x, 0.2), want_e)
    np.testing.assert_array_equal(estimators.p2_trace(x, 0.9), want_p)


def test_estimates_at_decisions_count_only_earlier_completions():
    # two platforms, one function; decisions after 0, 2 and 5 completions
    fn = np.zeros(5, np.int64)
    plat = np.array([0, 1, 0, 0, 1])
    x = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
    est = estimators.at_decisions(fn, plat, x, 2 * x, 2, [0, 0, 0],
                                  [0, 2, 5], 0.5, 0.9)
    np.testing.assert_array_equal(est.exec_n, [[0, 0], [1, 1], [3, 2]])
    np.testing.assert_array_equal(est.exec_v, [[0, 0], [1, 2], [3, 3.5]])
