"""One definition per policy decision (``repro.kernels.policy_score``).

Each stateless policy's cascade is one array function run under NumPy
(the host oracle, the journal's ``cascade``, ``fn_cost_matrix``) and
under ``jax.jit`` (the device).  Pinned here, for every stateless
policy, ``warm_aware`` included:

  * the jitted decision equals the NumPy decision on dyadic columns
    (exact in float32 and float64): choice, ok and kill bits;
  * the kill bits name the filter that dropped each candidate;
  * ``fn_cost_matrix`` on a live snapshot equals ``cascade`` over
    ``decision_features``, masked by its kill bits;
  * ``predict_columns`` — the estimator gates the device applies —
    equals ``predict_matrix`` and the scalar ``predict_*`` bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import functions, profiles
from repro.core.control_plane import FDNControlPlane
from repro.core import scheduler as sched
from repro.core.scheduler import (POLICIES, DataLocalityPolicy,
                                  EnergyAwarePolicy,
                                  PerformanceRankedPolicy,
                                  SLOCompositePolicy,
                                  UtilizationAwarePolicy, WarmAwarePolicy,
                                  decision_features)
from repro.core.types import SLO, DeploymentSpec, Invocation
from repro.kernels import policy_score as ps

STATELESS = {
    "perf_ranked": lambda cp: PerformanceRankedPolicy(cp.perf),
    "utilization_aware": lambda cp: UtilizationAwarePolicy(
        cp.perf, cpu_threshold=0.7),
    "data_locality": lambda cp: DataLocalityPolicy(cp.perf, cp.placement),
    "warm_aware": lambda cp: WarmAwarePolicy(cp.perf, cp.placement),
    "energy_aware": lambda cp: EnergyAwarePolicy(cp.perf),
    "slo_composite": lambda cp: SLOCompositePolicy(cp.perf, cp.placement),
}


def _dyadic_features(f, p, seed):
    """Every stateless policy's columns on a k/64 grid, with dead and
    loaded platforms, idle warm pools and SLOs on both sides of P90."""
    rng = np.random.default_rng([seed, f, p])

    def grid(shape, span=256):
        return rng.integers(0, span, shape).astype(np.float64) / 64.0

    return {"alive": rng.random((f, p)) < 0.8,
            "exec_s": grid((f, p)), "data_s": grid((f, p)),
            "p90_s": grid((f, p)), "energy_j": grid((f, p)),
            "warm_free": grid((f, p), 4), "cold_start_s": grid(p),
            "cpu_util": grid(p, 96), "mem_util": grid(p, 96),
            "slo_s": grid(f)}


_DYADIC_PARAMS = {"cpu_threshold": 0.75, "mem_threshold": 0.875,
                  "energy_weight": 0.125}


@pytest.mark.parametrize("name", sorted(STATELESS))
def test_jitted_decision_matches_numpy_decision(name):
    cls = POLICIES[name]
    params = {k: _DYADIC_PARAMS[k] for k in cls.CASCADE_PARAMS}

    @jax.jit
    def device_kill(*ops):
        return ps.kill_bits(jnp, *cls.kernel(jnp, *ops)[1])

    for seed, (f, p) in enumerate([(1, 1), (3, 5), (6, 4), (9, 17)]):
        feats = _dyadic_features(f, p, seed)
        cost, kill = cls.cascade(feats, params)
        want_choice, want_ok = ps.masked_argmin(np, cost, kill == 0)
        ops = sched._operands(cls.kernel, feats, params)
        choice, ok = ps.jit_decide(cls.kernel, *ops)
        np.testing.assert_array_equal(np.asarray(ok), want_ok)
        np.testing.assert_array_equal(np.asarray(choice), want_choice)
        np.testing.assert_array_equal(np.asarray(device_kill(*ops)), kill)


def test_kill_bits_name_the_filter_that_dropped_each_candidate():
    """SLO composite over four platforms: dead, over the utilization
    thresholds, over the SLO, feasible — and a row whose SLO no platform
    meets, which degrades to the utilization survivors."""
    feats = {"alive": np.array([[False, True, True, True]] * 2),
             "exec_s": np.ones((2, 4)), "data_s": np.zeros((2, 4)),
             "p90_s": np.array([[1.0, 1.0, 4.0, 1.0]] * 2),
             "energy_j": np.zeros((2, 4)),
             "cpu_util": np.array([0.0, 0.95, 0.0, 0.0]),
             "mem_util": np.zeros(4), "slo_s": np.array([2.0, 0.5])}
    _cost, kill = SLOCompositePolicy.cascade(
        feats, SLOCompositePolicy.CASCADE_PARAMS)
    assert kill.tolist() == [
        [ps.KILL_DEAD, ps.KILL_UTIL, ps.KILL_SLO, 0],
        [ps.KILL_DEAD, ps.KILL_UTIL, 0, 0]]
    assert (ps.KILL_DEAD, ps.KILL_UTIL, ps.KILL_SLO) == (1, 2, 4)


def _build():
    rng = np.random.default_rng(20261019)
    cp = FDNControlPlane()
    for n in profiles.PAPER_PLATFORMS:
        cp.create_platform(profiles.PAPER_PLATFORMS[n])
    fns = [f.replace(real_fn=None)
           for f in functions.paper_functions().values()]
    functions.seed_object_stores(cp.placement, location="cloud-cluster")
    cp.deploy(DeploymentSpec("t", fns, list(cp.platforms)))
    for p in cp.platforms.values():
        p.bg_cpu = float(rng.uniform(0, 1.2))
        p.bg_mem = float(rng.uniform(0, 0.8))
    for fn in fns:
        for pname in cp.platforms:
            for _ in range(int(rng.integers(0, 15))):
                inv = Invocation(fn, 0.0)
                inv.platform = pname
                inv.exec_time = float(rng.uniform(0.01, 8.0))
                inv.end_t = inv.exec_time
                cp.perf.observe(inv)
    fns += [f.replace(slo=SLO(p90_response_s=s))
            for f, s in zip(fns, (0.05, 0.5, 2.0, 10.0))]
    return cp, fns


@pytest.mark.parametrize("name", sorted(STATELESS))
def test_fn_cost_matrix_is_the_cascade_over_decision_features(name):
    cp, fns = _build()
    pol = STATELESS[name](cp)
    snap = sched.PlatformSnapshot(list(cp.platforms.values()))
    feats = decision_features(fns, snap, cp.perf,
                              getattr(pol, "placement", None))
    cost, kill = type(pol).cascade(feats, pol.cascade_params())
    np.testing.assert_array_equal(pol.fn_cost_matrix(fns, snap),
                                  np.where(kill == 0, cost, np.inf))


def test_predict_columns_matches_predict_matrix_and_scalar_predicts():
    cp, fns = _build()
    profs = [p.prof for p in cp.platforms.values()]
    est = cp.perf.estimator_columns(fns, profs)
    nodes = np.array([float(pr.nodes) for pr in profs])
    loaded_w = np.array([pr.loaded_w_per_node for pr in profs])
    exec_s, p90_s, energy_j = ps.predict_columns(
        np, est["ewma_v"], est["ewma_n"], est["analytic_s"],
        est["resp_h2"], est["resp_n"], nodes, loaded_w)
    # both gates are taken on both sides in this state
    assert (est["ewma_n"] >= 3).any() and (est["ewma_n"] < 3).any()
    assert (est["resp_n"] >= 10).any() and (est["resp_n"] < 10).any()
    m = cp.perf.predict_matrix(fns, profs, p90=True, energy=True)
    np.testing.assert_array_equal(m["exec_s"], exec_s)
    np.testing.assert_array_equal(m["p90_s"], p90_s)
    np.testing.assert_array_equal(m["energy_j"], energy_j)
    assert set(cp.perf.predict_matrix(fns, profs)) == {"exec_s"}
    for i, fn in enumerate(fns):
        for j, pr in enumerate(profs):
            assert cp.perf.predict_exec(fn, pr) == exec_s[i, j]
            assert cp.perf.predict_p90_response(fn, pr) == p90_s[i, j]
            assert cp.perf.predict_energy(fn, pr) == energy_j[i, j]
