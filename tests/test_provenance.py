"""Decision provenance (repro.obs.provenance / whatif): the columnar
decision journal, the calibration analyzer and counterfactual replay.

Load-bearing invariants pinned here:

  * same-policy replay oracle — re-scoring the journaled feature columns
    under the journaled policy + params reproduces every original choice
    byte-identically, on all three prov/* acceptance scenarios AND for
    every stateless registry policy driven directly;
  * backend parity — the one SLO-composite definition under ``jax.jit``
    (float32) and under NumPy (``SLOCompositePolicy.cascade``, float64)
    agree on cost / kill bits / choice / ok bit-for-bit on a dyadic input
    grid (values exactly representable in float32, so the f32/f64 width
    difference vanishes);
  * join integrity — every completion stamped with a journal row id ran
    on exactly the platform that journal row chose;
  * persistence — ``save``/``load_journal`` round-trips every column and
    the loaded journal still passes the replay oracle.
"""
import json

import numpy as np
import pytest

from repro.core import (FDNControlPlane, Invocation, functions, profiles)
from repro.core.loadgen import attach_completion_hooks
from repro.core.scheduler import (POLICIES, RoundRobinCollaboration,
                                  SLOCompositePolicy,
                                  WeightedCollaboration)
from repro.core.types import DeploymentSpec
from repro.inspector import registry
from repro.inspector.scenario import ScenarioReport, run_scenario_state
from repro.obs import (DecisionJournal, WhatIfConfig, load_journal, replay,
                       replay_matches, whatif_section)
from repro.obs.provenance import FEATURE_COLS, KILL_PAD

try:                 # hypothesis is an optional test extra; without it
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # the seeded sweep twin below still runs
    HAVE_HYPOTHESIS = False

    def given(*a, **kw):
        return lambda fn: pytest.mark.skip("hypothesis not installed")(fn)

    def settings(*a, **kw):
        return lambda fn: fn

    class st:        # placeholder strategies so decorators still build
        @staticmethod
        def _none(*a, **kw):
            return None
        integers = _none

try:
    import jax
    import jax.numpy as jnp
    from repro.kernels import policy_score as ps
    HAVE_JAX = True
except Exception:
    ps = None
    HAVE_JAX = False


@pytest.fixture(scope="module")
def prov_tiny():
    return run_scenario_state(registry.get("prov/smoke-tiny"))


@pytest.fixture(scope="module")
def prov_etl():
    return run_scenario_state(registry.get("prov/etl-pipeline"))


@pytest.fixture(scope="module")
def prov_drr():
    return run_scenario_state(registry.get("prov/burst-storm-drr"))


# ---------------------------------------------------------------------------
# journal recording + report section
# ---------------------------------------------------------------------------

def test_journal_columns_are_consistent(prov_tiny):
    _report, cp, _sink = prov_tiny
    j = cp.journal
    assert j is not None and j.n > 0
    jc = j.columns()
    n = j.n
    pmax = jc["kill"].shape[1]
    assert all(jc[k].shape == (n, pmax) for k in FEATURE_COLS)
    assert jc["alive"].shape == (n, pmax)
    assert jc["alive"].dtype == bool
    # every pset id resolves, every choice is a valid slot of its set
    width = np.array([len(j.pset_names[int(p)]) for p in jc["pset"]])
    assert (width <= pmax).all()
    assert ((jc["choice"] >= -1) & (jc["choice"] < width)).all()
    assert (jc["count"] > 0).all()
    # pad slots past each row's platform-set width: never alive, kill
    # bits all-set, features NaN
    pad = np.arange(pmax)[None, :] >= width[:, None]
    assert (jc["kill"][pad] == KILL_PAD).all()
    assert not jc["alive"][pad].any()
    assert np.isnan(jc["exec_s"][pad]).all()
    # feasible chosen slots carry kill == 0
    ok = jc["choice"] >= 0
    assert (jc["kill"][np.nonzero(ok)[0], jc["choice"][ok]] == 0).all()


def test_report_section_schema_and_validate(prov_tiny):
    report, cp, _sink = prov_tiny
    dp = report.decision_provenance
    assert dp["policy"] == cp.journal.policy_name
    assert dp["decisions"] == cp.journal.n
    assert dp["invocations"] > 0
    assert dp["matched_completions"] > 0
    assert set(dp["kill_counts"]) == {"dead", "utilization", "slo"}
    # each matched completion lands in exactly one calibration cell
    cells = [c for per_p in dp["calibration"].values()
             for c in per_p.values()]
    assert cells and sum(c["count"] for c in cells) == \
        dp["matched_completions"]
    for c in cells:
        assert c["mean_abs_err_s"] >= 0.0
        assert abs(c["bias_s"]) <= c["mean_abs_err_s"] + 1e-12
    assert 0.0 <= dp["churn"]["overall"] <= 1.0
    # the full report (with the additive section) passes schema check
    ScenarioReport.validate(json.loads(report.to_json()))


def test_decision_ids_join_to_the_chosen_platform(prov_tiny):
    """Every completion stamped with a journal row id ran on exactly the
    platform that row chose — the join the calibration analyzer relies
    on is not merely shape-compatible but semantically exact."""
    _report, cp, sink = prov_tiny
    cols = sink.completion_columns()
    d = np.asarray(cols["decision"])
    sel = d >= 0
    assert sel.any()
    pid_to_name = {v: k for k, v in cols["platform_ids"].items()}
    plat = cols["platform"]
    for i in np.nonzero(sel)[0]:
        assert pid_to_name[int(plat[i])] == cp.journal.platform_of(int(d[i]))


# ---------------------------------------------------------------------------
# same-policy replay oracle (the byte-identity guarantee)
# ---------------------------------------------------------------------------

def test_replay_oracle_smoke_tiny(prov_tiny):
    assert replay_matches(prov_tiny[1].journal)


def test_replay_oracle_etl_pipeline(prov_etl):
    assert replay_matches(prov_etl[1].journal)


def test_replay_oracle_burst_storm_drr(prov_drr):
    assert replay_matches(prov_drr[1].journal)


_STATELESS_BUILDERS = {
    "perf_ranked": lambda cp: POLICIES["perf_ranked"](cp.perf),
    "utilization_aware":
        lambda cp: POLICIES["utilization_aware"](cp.perf),
    "data_locality":
        lambda cp: POLICIES["data_locality"](cp.perf, cp.placement),
    "warm_aware": lambda cp: POLICIES["warm_aware"](cp.perf, cp.placement),
    "energy_aware": lambda cp: POLICIES["energy_aware"](cp.perf),
    "slo_composite":
        lambda cp: POLICIES["slo_composite"](cp.perf, cp.placement),
}


def _drive(cp, fns, rounds=5):
    """Several small mixed-function bursts (below JAX_DECIDE_MIN, so the
    fused decision runs on the numpy host path); platform queues fill
    between rounds, so the journaled features actually vary."""
    picks = [fns["nodeinfo"], fns["image-processing"], fns["JSON-loads"]]
    for r in range(rounds):
        t = float(r)
        cp.submit_batch([Invocation(f, t)
                         for f in picks[:1 + r % 3] for _ in range(4)])


def _build_cp(names=("cloud-cluster", "edge-cluster")):
    cp = FDNControlPlane()
    for n in names:
        cp.create_platform(profiles.PAPER_PLATFORMS[n])
    fns = {k: f.replace(real_fn=None)
           for k, f in functions.paper_functions().items()}
    functions.seed_object_stores(cp.placement, location=names[0])
    cp.deploy(DeploymentSpec("t", list(fns.values()), list(cp.platforms)))
    attach_completion_hooks(cp)
    return cp, fns


def test_stateless_builders_cover_the_registry():
    stateless = {n for n, c in POLICIES.items() if c.cascade is not None}
    assert stateless == set(_STATELESS_BUILDERS)


@pytest.mark.parametrize("policy_name", sorted(_STATELESS_BUILDERS))
def test_replay_oracle_every_stateless_policy(policy_name):
    cp, fns = _build_cp()
    cp.policy = _STATELESS_BUILDERS[policy_name](cp)
    journal = cp.attach_provenance(DecisionJournal())
    _drive(cp, fns)
    assert journal.policy_name == policy_name
    assert journal.n > 0
    assert replay_matches(journal)


@pytest.mark.parametrize("policy", [
    RoundRobinCollaboration(),
    WeightedCollaboration({"cloud-cluster": 2, "edge-cluster": 1}),
], ids=["round_robin", "weighted"])
def test_stateful_policies_never_journal(policy):
    cp, fns = _build_cp()
    cp.policy = policy
    journal = cp.attach_provenance(DecisionJournal())
    _drive(cp, fns, rounds=2)
    assert journal.n == 0            # object fallback: nothing recorded
    with pytest.raises(ValueError, match="stateful"):
        replay(journal)


# ---------------------------------------------------------------------------
# counterfactual what-if
# ---------------------------------------------------------------------------

def test_whatif_section_is_conserved(prov_tiny):
    j = prov_tiny[1].journal
    base = replay(j)
    alt = replay(j, WhatIfConfig("energy_aware"))
    sec = whatif_section(j, base, alt)
    assert sec["policy"] == "energy_aware"
    assert sec["decisions"] == j.n
    assert sec["changed_decisions"] == \
        int((alt.choice != j.columns()["choice"]).sum())
    total = int(j.columns()["count"].sum())
    # invocation mass is conserved: shares + infeasible cover everything
    for key, res in (("platform_share_before", base),
                     ("platform_share_after", alt)):
        routed = sum(sec[key].values())
        unrouted = int(j.columns()["count"][res.choice < 0].sum())
        assert routed + unrouted == total


def test_whatif_parse_rejects_missing_policy():
    with pytest.raises(ValueError):
        WhatIfConfig.parse("slo_scale=2.0")
    cfg = WhatIfConfig.parse("policy=slo_composite,energy_weight=0.5,"
                             "slo_scale=2.0")
    assert cfg.policy == "slo_composite"
    assert cfg.params == {"energy_weight": 0.5}
    assert cfg.slo_scale == 2.0


def test_slo_scale_feasibility_monotone(prov_tiny):
    """Scaling every SLO budget up can only keep or grow the feasible
    set; scaling down can only shrink it (graceful degrade means routed
    counts move monotonically, never erratically)."""
    j = prov_tiny[1].journal
    name = j.policy_name

    def routed(scale):
        r = replay(j, WhatIfConfig(name, slo_scale=scale))
        assert r.ok.sum() == (r.choice >= 0).sum()
        return int((r.choice >= 0).sum())

    base = int((replay(j).choice >= 0).sum())
    assert routed(4.0) >= base
    assert routed(0.25) <= base


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

def test_save_load_roundtrip(tmp_path, prov_tiny):
    j = prov_tiny[1].journal
    path = str(tmp_path / "journal.npz")
    j.save(path)
    j2 = load_journal(path)
    assert j2.n == j.n
    assert j2.policy_name == j.policy_name
    assert j2.params == {k: float(v) for k, v in j.params.items()}
    assert j2.fn_names == j.fn_names
    assert j2.pset_names == j.pset_names
    a, b = j.columns(), j2.columns()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    # the loaded journal still satisfies the oracle (replay resolves the
    # cascade from policy_name — no live bindings required)
    assert replay_matches(j2)


# ---------------------------------------------------------------------------
# one SLO-composite definition: jitted vs NumPy (backend identity)
# ---------------------------------------------------------------------------

# dyadic grid: every value is k/64 (and the energy weight 1/8), so the
# cascade arithmetic is exact in float32 and the jitted kernel must agree
# with the float64 host cascade bit-for-bit — no near-tie caveat.
_PARAMS = {"cpu_threshold": 0.75, "mem_threshold": 0.875,
           "energy_weight": 0.125}


def _dyadic_case(F, P, seed):
    rng = np.random.default_rng(seed)

    def grid(shape, span=256):
        return rng.integers(0, span, shape).astype(np.float64) / 64.0

    feats = {
        "exec_s": grid((F, P)), "data_s": grid((F, P)),
        "p90_s": grid((F, P)), "energy_j": grid((F, P)),
        "alive": rng.random((F, P)) < 0.85,
        "cpu_util": grid(P, 96), "mem_util": grid(P, 96),
        "slo_s": grid(F),
    }
    return feats


def _host_decision(feats):
    cost, kill = SLOCompositePolicy.cascade(feats, _PARAMS)
    choice, ok = ps.masked_argmin(np, cost, kill == 0)
    return choice, ok, kill, cost


def _device_decision(*cols):
    """The same definition under ``jax.jit``: choice, ok, kill, cost."""
    cost, stages = ps.slo_composite(jnp, *cols)
    choice, ok = ps.masked_argmin(jnp, cost, stages[-1])
    return choice, ok, ps.kill_bits(jnp, *stages), cost


def _assert_backend_parity(F, P, seed):
    feats = _dyadic_case(F, P, seed)
    h_choice, h_ok, h_kill, h_cost = _host_decision(feats)
    unloaded = (feats["cpu_util"] < _PARAMS["cpu_threshold"]) & \
        (feats["mem_util"] < _PARAMS["mem_threshold"])
    out = jax.jit(_device_decision)(
        feats["alive"], feats["exec_s"], feats["data_s"], feats["p90_s"],
        feats["energy_j"], unloaded[None, :], feats["slo_s"],
        _PARAMS["energy_weight"])
    choice, ok, kill, cost = (np.asarray(a) for a in out)
    assert cost.dtype == np.float32
    np.testing.assert_array_equal(ok, h_ok)
    np.testing.assert_array_equal(kill, h_kill)
    np.testing.assert_array_equal(cost.astype(np.float64), h_cost)
    np.testing.assert_array_equal(choice[h_ok], h_choice[h_ok])


@pytest.mark.skipif(not HAVE_JAX, reason="jax kernels unavailable")
@given(st.integers(1, 6), st.integers(1, 5), st.integers(0, 2**32 - 1))
@settings(max_examples=60, deadline=None)
def test_composite_explain_matches_host_cascade(F, P, seed):
    _assert_backend_parity(F, P, seed)


@pytest.mark.skipif(not HAVE_JAX, reason="jax kernels unavailable")
def test_composite_explain_parity_seeded_sweep():
    """Always-on twin of the hypothesis property (hypothesis is an
    optional extra): 200 seeded shapes including the degenerate 1x1."""
    rng = np.random.default_rng(7)
    _assert_backend_parity(1, 1, 0)
    for _ in range(200):
        _assert_backend_parity(int(rng.integers(1, 7)),
                               int(rng.integers(1, 6)),
                               int(rng.integers(0, 2**32)))
