"""JIT-compiled admission fast path: jitted-vs-NumPy score-backend
parity (every Policy subclass, byte-identical choices on seeded
scenarios), grouped hedge timers vs per-invocation watchers, batched local-trigger delegation, and the
columnar drain's exact equivalence to sequential invokes."""
import numpy as np
import pytest

from repro.core import functions, profiles
from repro.core.control_plane import FDNControlPlane
from repro.core.faults import HedgePolicy
from repro.core.loadgen import attach_completion_hooks
from repro.core import scheduler as sched
from repro.core.scheduler import (DataLocalityPolicy, EnergyAwarePolicy,
                                  PerformanceRankedPolicy,
                                  RoundRobinCollaboration,
                                  SLOCompositePolicy,
                                  UtilizationAwarePolicy,
                                  WeightedCollaboration)
from repro.core.types import SLO, DeploymentSpec, Invocation


@pytest.fixture(autouse=True)
def _restore_backend():
    yield
    sched.set_score_backend("auto")


def build(names=None, **kw):
    cp = FDNControlPlane(**kw)
    for n in (names or list(profiles.PAPER_PLATFORMS)):
        cp.create_platform(profiles.PAPER_PLATFORMS[n])
    fns = {k: f.replace(real_fn=None)
           for k, f in functions.paper_functions().items()}
    functions.seed_object_stores(cp.placement, location="cloud-cluster")
    cp.deploy(DeploymentSpec("t", list(fns.values()), list(cp.platforms)))
    attach_completion_hooks(cp)
    return cp, fns


def _randomized_state(cp, fns, rng):
    for p in cp.platforms.values():
        p.bg_cpu = float(rng.uniform(0, 1.2))
        p.bg_mem = float(rng.uniform(0, 0.8))
    for fn in fns.values():
        for pname in cp.platforms:
            for _ in range(int(rng.integers(0, 15))):
                inv = Invocation(fn, 0.0)
                inv.platform = pname
                inv.exec_time = float(rng.uniform(0.01, 8.0))
                inv.end_t = inv.exec_time
                cp.perf.observe(inv)


def _mixed_invs(fns, rng, n):
    specs = list(fns.values())
    specs = [s if rng.random() < 0.5 else
             s.replace(slo=SLO(p90_response_s=float(rng.uniform(0.05, 10))))
             for s in specs]
    return [Invocation(specs[int(rng.integers(0, len(specs)))], 0.0)
            for _ in range(n)]


POLICY_FACTORIES = {
    "perf_ranked": lambda cp: PerformanceRankedPolicy(cp.perf),
    "utilization": lambda cp: UtilizationAwarePolicy(cp.perf,
                                                     cpu_threshold=0.7),
    "round_robin": lambda cp: RoundRobinCollaboration(),
    "weighted": lambda cp: WeightedCollaboration(
        {"hpc-node-cluster": 5, "cloud-cluster": 1, "edge-cluster": 2}),
    "data_locality": lambda cp: DataLocalityPolicy(cp.perf, cp.placement),
    "energy": lambda cp: EnergyAwarePolicy(cp.perf),
    "slo_composite": lambda cp: SLOCompositePolicy(cp.perf, cp.placement),
}


# ---------------------------------------------------------------------------
# jitted-vs-NumPy backend parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pname", sorted(POLICY_FACTORIES))
def test_jax_backend_matches_numpy_choices(pname):
    """Every Policy subclass must pick byte-identical platforms under the
    numpy and jax score backends, across randomized seeded platform
    states, invocation mixes, and platform subsets."""
    rng = np.random.default_rng(20260730)
    all_names = list(profiles.PAPER_PLATFORMS)
    for trial in range(4):
        k = int(rng.integers(2, len(all_names) + 1))
        names = list(rng.choice(all_names, size=k, replace=False))
        cp, fns = build(names=names)
        _randomized_state(cp, fns, rng)
        specs = _mixed_invs(fns, rng, 96)
        plats = list(cp.platforms.values())

        picks = {}
        for backend in ("numpy", "jax"):
            sched.set_score_backend(backend)
            pol = POLICY_FACTORIES[pname](cp)   # fresh rotation state
            invs = [Invocation(i.fn, 0.0) for i in specs]
            picks[backend] = [p.prof.name if p else None
                              for p in pol.choose_batch(invs, plats)]
        assert picks["numpy"] == picks["jax"], \
            f"{pname} trial {trial}: backend decisions diverge"


def test_jax_backend_matches_numpy_on_registry_scenarios():
    """End-to-end: running a registry scenario with the score backend
    forced to jax produces the same canonical report as numpy (admission
    decisions — and so every downstream metric — are identical)."""
    from repro.inspector import registry, run_scenario
    for name in ("smoke/tiny", "burst/mmpp-storm"):
        reports = {}
        for backend in ("numpy", "jax"):
            sched.set_score_backend(backend)
            reports[backend] = run_scenario(registry.get(name)).to_json()
        assert reports["numpy"] == reports["jax"], \
            f"{name}: scenario report drifts across score backends"


def test_fn_decisions_match_full_score_matrix():
    """The fused per-function decision must equal row-argmin over the
    full (N, P) score matrix for stateless policies."""
    rng = np.random.default_rng(3)
    cp, fns = build()
    _randomized_state(cp, fns, rng)
    invs = _mixed_invs(fns, rng, 40)
    snap = sched.PlatformSnapshot(list(cp.platforms.values()))
    pol = SLOCompositePolicy(cp.perf, cp.placement)
    groups = sched.group_by_fn(invs)
    idx, ok = pol.fn_decisions([g[0] for g in groups], snap)
    costs = pol.score(invs, snap)
    finite = np.isfinite(costs)
    row_idx = np.argmin(np.where(finite, costs, np.inf), axis=1)
    for g, (_fn, idxs) in enumerate(groups):
        for i in idxs:
            assert finite[i].any() == ok[g]
            if ok[g]:
                assert row_idx[i] == idx[g]


def test_backend_behavior_without_jax(monkeypatch):
    """"auto" decides batches below JAX_DECIDE_MIN on NumPy without
    touching the jitted cascades, while an EXPLICIT "jax" request always
    calls them and lets their failure surface — a broken jax install
    must never be silently measured (or CI-gated) as the numpy path."""
    class BrokenCascades:
        def __getattr__(self, name):
            raise RuntimeError(f"jax cascade {name} unavailable")

    monkeypatch.setattr(sched, "ps", BrokenCascades())
    cp, fns = build(names=["hpc-node-cluster", "cloud-cluster"])
    plats = list(cp.platforms.values())
    invs = [Invocation(fns["nodeinfo"], 0.0)
            for _ in range(sched.JAX_DECIDE_MIN - 1)]
    sched.set_score_backend("auto")
    assert cp.policy.choose_batch(invs, plats)[0] is not None
    sched.set_score_backend("jax")
    with pytest.raises(RuntimeError, match="jax"):
        cp.policy.choose_batch(invs, plats)


# ---------------------------------------------------------------------------
# grouped hedge timers
# ---------------------------------------------------------------------------

def _seed_resp_obs(cp, fns, names, value=0.05, count=12):
    for fname in names:
        for pname in cp.platforms:
            for _ in range(count):
                inv = Invocation(fns[fname], 0.0)
                inv.platform = pname
                inv.exec_time = value
                inv.end_t = value
                cp.perf.observe(inv)


def test_group_hedge_timer_equivalent_to_per_invocation_watchers():
    """ONE timer per (fn, platform) admission group must fire equivalently
    to per-invocation watchers: same hedges for the same stragglers, same
    total completions — with an order-of-batch fewer clock events."""
    n = 60
    results = {}
    for mode in ("grouped", "per_inv"):
        cp, fns = build(names=["hpc-node-cluster", "old-hpc-node-cluster"])
        _seed_resp_obs(cp, fns, ("nodeinfo", "primes-python"))
        # make every platform slow so originals straggle past the budget
        for p in cp.platforms.values():
            p.bg_cpu = 1.0
        cp.kb.log_decisions = False
        specs = [fns["nodeinfo"], fns["primes-python"]]
        invs = [Invocation(specs[i % 2], 0.0) for i in range(n)]
        if mode == "grouped":
            cp.hedge.enabled = True
            cp.submit_batch(invs)
            timers = cp.clock.pending
        else:
            hedge = cp.hedge
            hedge.enabled = False          # plain admission...
            cp.submit_batch(invs)
            hedge.enabled = True           # ...then PR-1 per-inv watchers
            alive = cp.alive_platforms()
            for inv in invs:
                target = cp.platforms[inv.platform]
                alternates = [p for p in alive if p is not target]
                hedge.watch(inv, target, alternates,
                            lambda i, p: cp.sidecars[p.prof.name].admit(i))
            timers = cp.clock.pending
        cp.run_until(300.0)
        done = sum(1 for i in invs if i.status == "done")
        results[mode] = {"hedges_sent": cp.hedge.hedges_sent,
                         "hedged_from": None, "done": done,
                         "timers": timers}
    assert results["grouped"]["hedges_sent"] == \
        results["per_inv"]["hedges_sent"] > 0
    assert results["grouped"]["done"] == results["per_inv"]["done"] == n
    # the grouped path arms one timer per (fn, platform) group, not per inv
    assert results["grouped"]["timers"] < results["per_inv"]["timers"] - n // 2


def test_group_hedge_skips_completed_invocations():
    cp, fns = build(names=["hpc-node-cluster", "old-hpc-node-cluster"],
                    enable_hedging=True)
    # generous learned P90 -> hedge budget far beyond actual latency
    _seed_resp_obs(cp, fns, ("nodeinfo",), value=5.0)
    invs = [Invocation(fns["nodeinfo"], 0.0) for _ in range(10)]
    cp.submit_batch(invs)
    cp.run_until(120.0)            # fast platform: all done before budget
    assert all(i.status == "done" for i in invs)
    assert cp.hedge.hedges_sent == 0


# ---------------------------------------------------------------------------
# batched local-trigger delegation
# ---------------------------------------------------------------------------

def test_handle_local_triggers_matches_scalar_path():
    for pressured in (False, True):
        cp_a, fns_a = build(names=["edge-cluster", "cloud-cluster"])
        cp_b, fns_b = build(names=["edge-cluster", "cloud-cluster"])
        if pressured:
            cp_a.platforms["edge-cluster"].bg_cpu = 1.0
            cp_b.platforms["edge-cluster"].bg_cpu = 1.0
        # teach an SLO risk for one function only
        for cp, fns in ((cp_a, fns_a), (cp_b, fns_b)):
            for _ in range(12):
                inv = Invocation(fns["primes-python"], 0.0)
                inv.platform = "edge-cluster"
                inv.exec_time = 30.0
                inv.end_t = 30.0
                cp.perf.observe(inv)
        mix = ["nodeinfo", "primes-python"] * 8
        invs_a = [Invocation(fns_a[m], 0.0) for m in mix]
        invs_b = [Invocation(fns_b[m], 0.0) for m in mix]
        sc_a = cp_a.sidecars["edge-cluster"]
        sc_b = cp_b.sidecars["edge-cluster"]
        del_a, del_b = [], []
        for inv in invs_a:
            sc_a.handle_local_trigger(inv, delegate=del_a.append)
        sc_b.handle_local_triggers(invs_b, delegate_batch=del_b.extend)
        assert (sc_a.local, sc_a.delegated) == (sc_b.local, sc_b.delegated)
        assert [i.fn.name for i in del_a] == [i.fn.name for i in del_b]
        assert len(cp_a.platforms["edge-cluster"].queue) == \
            len(cp_b.platforms["edge-cluster"].queue)


# ---------------------------------------------------------------------------
# columnar drain: exact equivalence with sequential invokes
# ---------------------------------------------------------------------------

def test_vectorized_drain_bitwise_matches_sequential_invokes():
    """The batched drain's vectorized start math must reproduce the
    sequential per-invocation drain bit for bit: same start/queue/exec
    times, same cold-start flags, same completion times — including
    interference crossovers mid-burst."""
    cp_a, fns_a = build(names=["old-hpc-node-cluster"])
    cp_b, fns_b = build(names=["old-hpc-node-cluster"])
    pa = cp_a.platforms["old-hpc-node-cluster"]
    pb = cp_b.platforms["old-hpc-node-cluster"]
    pa.bg_cpu = pb.bg_cpu = 0.5          # busy crossover mid-burst
    mix = ["nodeinfo", "JSON-loads", "primes-python"] * 10
    invs_a = [Invocation(fns_a[m], 0.0) for m in mix]
    invs_b = [Invocation(fns_b[m], 0.0) for m in mix]
    for inv in invs_a:
        pa.invoke(inv)
    pb.invoke_batch(invs_b)
    for a, b in zip(invs_a, invs_b):
        assert a.status == b.status
        assert a.cold_start == b.cold_start
        if a.status == "running":
            assert a.start_t == b.start_t
            assert a.queue_time == b.queue_time
            assert a.exec_time == b.exec_time
            assert a.data_time == b.data_time
    cp_a.run_until(600.0)
    cp_b.run_until(600.0)
    ends_a = sorted(i.end_t for i in invs_a if i.end_t is not None)
    ends_b = sorted(i.end_t for i in invs_b if i.end_t is not None)
    assert ends_a == ends_b
    assert pa.mem_used_mb() == pb.mem_used_mb()


def test_mem_accounting_running_total_matches_scan():
    """The O(1) replica-memory counter must track the old full scan
    through deploy / prewarm / idler / destroy / recover."""
    cp, fns = build(names=["cloud-cluster"])
    p = cp.platforms["cloud-cluster"]

    def scan():
        return sum(len(rs) * p.deployed[f].memory_mb
                   for f, rs in p.replicas.items() if f in p.deployed)

    assert p._mem_replicas_mb == scan()
    p.prewarm("nodeinfo", 3)
    assert p._mem_replicas_mb == scan()
    for _ in range(10):
        p.invoke(Invocation(fns["JSON-loads"], 0.0))
    assert p._mem_replicas_mb == scan()
    cp.run_until(2000.0)                 # idler retires idle replicas
    assert p._mem_replicas_mb == scan()
    p.destroy("nodeinfo")
    assert p._mem_replicas_mb == scan()
    p.recover()
    assert p._mem_replicas_mb == scan() == 0


# ---------------------------------------------------------------------------
# chains: hedged duplicates complete stages
# ---------------------------------------------------------------------------

def test_hedged_duplicate_completes_chain_stage():
    from repro.chains.planner import ChainPlan
    from repro.chains.spec import EXTERNAL, Chain, DataEdge, Stage

    cp = FDNControlPlane(enable_hedging=True)
    # planned platform: old-hpc (slow, and soon throttled); the hedge
    # alternate is the fast hpc cluster
    for n in ("old-hpc-node-cluster", "hpc-node-cluster"):
        cp.create_platform(profiles.PAPER_PLATFORMS[n])
    fns = {k: f.replace(real_fn=None)
           for k, f in functions.paper_functions().items()}
    slow_fn = fns["primes-python"].replace(name="crunch", flops=20e9)
    fns["crunch"] = slow_fn
    functions.seed_object_stores(cp.placement,
                                 location="old-hpc-node-cluster")
    cp.deploy(DeploymentSpec("t", list(fns.values()), list(cp.platforms)))
    attach_completion_hooks(cp)
    _seed_resp_obs(cp, fns, ("crunch",))
    # planned platform straggles: background load doubles its latency
    cp.platforms["old-hpc-node-cluster"].bg_cpu = 1.0

    chain = Chain("one", (Stage("s0", "crunch"),),
                  (DataEdge(EXTERNAL, "s0", "in/obj", 1e6),))
    cp.placement.stores["old-hpc-node-cluster"].put("in/obj", 1e6)
    plan = ChainPlan(chain="one", mode="pin", requested_mode="pin",
                     assignment={"s0": "old-hpc-node-cluster"},
                     est_makespan_s=0.0, est_compute_s=0.0,
                     est_transfer_s=0.0, est_bytes_moved=0.0)
    ex = cp.chain_executor(fns)
    inst = ex.launch(chain, plan)
    cp.run_until(600.0)
    assert inst.status == "done"
    assert cp.hedge.hedges_sent >= 1
    assert cp.hedge.hedges_won >= 1
    # the duplicate won on the fast alternate well before the straggling
    # original (>= 2 * 20e9/4.2e9 s ~ 9.5 s) would have finished
    straggler_exec = 2 * (slow_fn.flops /
                          profiles.PAPER_PLATFORMS["old-hpc-node-cluster"]
                          .replica_flops)
    assert inst.latency < 0.7 * straggler_exec
