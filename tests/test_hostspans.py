"""Wall-clock host spans (repro.obs.hostspans): under ``jax.profiler.trace``
a columnar admission plus a ``run_until`` with the tracer attached yields
every span once per call, nested as the module says, with its stats; a
run with the tracer attached routes and completes exactly like one
without."""
import dataclasses
import glob

import jax
import numpy as np
import pytest

from repro.core import functions, profiles
from repro.core import scheduler as sched
from repro.core.control_plane import FDNControlPlane
from repro.core.invocation_batch import InvocationBatch
from repro.core.loadgen import ColumnarResultSink, attach_completion_hooks
from repro.core.types import DeploymentSpec
from repro.kernels import policy_score as ps
from repro.obs import hostspans as hs


@pytest.fixture(autouse=True)
def _jax_backend():
    sched.set_score_backend("jax")
    yield
    sched.set_score_backend("auto")


def _build():
    cp = FDNControlPlane(retain_completions=False)
    cp.kb.log_decisions = False         # the columnar admission path
    for prof in profiles.PAPER_PLATFORMS.values():
        cp.create_platform(prof)
    fns = [f.replace(real_fn=None)
           for f in functions.paper_functions().values()]
    functions.seed_object_stores(cp.placement, location="cloud-cluster")
    cp.deploy(DeploymentSpec("t", fns, list(cp.platforms)))
    attach_completion_hooks(cp)
    sink = ColumnarResultSink().install(cp)
    return cp, fns, sink


def _batch(fns, rng, n, t):
    return InvocationBatch(fns, rng.integers(0, len(fns), n),
                           np.full(n, t))


def _drive(cp, fns, seed, windows=6, rows=40):
    """Admit ``windows`` columnar batches one sim second apart, running
    the event loop between them; returns the batches."""
    rng = np.random.default_rng(seed)
    batches = []
    for k in range(windows):
        cp.clock.run_until(float(k))
        b = _batch(fns, rng, rows, float(k))
        cp.submit_batch(b)
        batches.append(b)
    cp.clock.run_until(float(windows) + 30.0)
    return batches


def _spans(path):
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("fdn/"))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One admission and one event-loop advance, traced with the tracer
    attached; the spans with their innermost enclosing fdn/ span."""
    sched.set_score_backend("jax")
    cp, fns, _sink = _build()
    _drive(cp, fns, seed=1, windows=3)          # warm: compiles, replicas
    cp.clock.run_until(40.0)
    rng = np.random.default_rng(7)
    batch = _batch(fns, rng, 50, cp.clock.now())
    log = str(tmp_path_factory.mktemp("hostspans"))
    with jax.profiler.trace(log):
        cp.attach_tracer()
        cp.submit_batch(batch)
        cp.clock.run_until(cp.clock.now() + 60.0)
        cp.attach_tracer(None)
    sched.set_score_backend("auto")
    (path,) = glob.glob(f"{log}/**/*.xplane.pb", recursive=True)
    spans = _spans(path)
    parent, stack = [], []
    for name, a, b, _st in spans:
        while stack and stack[-1][2] <= a:
            stack.pop()
        parent.append(stack[-1][0] if stack else None)
        stack.append((name, a, b))
    return spans, parent, batch, cp


def test_every_span_is_recorded_with_its_stats(traced):
    spans, _parent, _batch_, _cp = traced
    assert {s[0] for s in spans} == set(hs.STATS)
    for name, _a, _b, stats in spans:
        assert set(stats) == set(hs.STATS[name]), name


def test_spans_nest_as_documented(traced):
    spans, parent, _b, _cp = traced
    allowed = {
        hs.ADMIT: {None}, hs.SNAPSHOT: {hs.ADMIT}, hs.DECIDE: {hs.ADMIT},
        hs.GATHER: {hs.DECIDE}, hs.DISPATCH: {hs.DECIDE},
        hs.SYNC: {hs.DECIDE}, hs.ENQUEUE: {hs.ADMIT},
        hs.DRAIN: {hs.ENQUEUE, hs.ADVANCE},
        hs.LAUNCH: {hs.DRAIN, hs.COMPLETE},
        hs.COMPLETE: {hs.ADVANCE}, hs.ADVANCE: {None}}
    for (name, *_), par in zip(spans, parent):
        assert par in allowed[name], (name, par)
    # the drain a completion triggers follows its completion span
    assert any(p == hs.ADVANCE for (n, *_), p in zip(spans, parent)
               if n == hs.DRAIN)


def test_once_per_call_and_counters(traced):
    spans, parent, batch, cp = traced
    count = {n: sum(1 for s in spans if s[0] == n) for n in hs.STATS}
    for name in (hs.ADMIT, hs.SNAPSHOT, hs.DECIDE, hs.GATHER, hs.DISPATCH,
                 hs.SYNC, hs.ENQUEUE, hs.ADVANCE):
        assert count[name] == 1, name
    (admit,) = [s for s in spans if s[0] == hs.ADMIT]
    assert admit[3] == {"rows": 50,
                        "fns": len(np.unique(batch.fn_idx))}
    (disp,) = [s for s in spans if s[0] == hs.DISPATCH]
    f, p = len(np.unique(batch.fn_idx)), len(cp.platforms)
    assert disp[3] == {"f": f, "p": p, "bytes": 4 * ps.packed_words(f, p)}
    (enq,) = [s for s in spans if s[0] == hs.ENQUEUE]
    assert cp.rejected_count == 0 and enq[3]["rows"] == 50
    # every row completes in the advance, in one fdn/complete per clock
    # event: a group folded whole (rows == grouped > 1) or one row
    # (rows 1, grouped 0), followed by its own drain; every row the run
    # started was launched
    (adv,) = [s for s in spans if s[0] == hs.ADVANCE]
    completes = [s[3] for s in spans if s[0] == hs.COMPLETE]
    assert sum(c["rows"] for c in completes) == 50
    assert adv[3]["events"] >= count[hs.COMPLETE]
    grouped = [c for c in completes if c["grouped"]]
    single = [c for c in completes if not c["grouped"]]
    assert grouped and single
    assert all(c["rows"] == c["grouped"] > 1 for c in grouped)
    assert all(c["rows"] == 1 for c in single)
    drains = [s for s, p in zip(spans, parent) if s[0] == hs.DRAIN]
    assert sum(1 for s, p in zip(spans, parent)
               if s[0] == hs.DRAIN and p == hs.ADVANCE) >= len(single)
    started = sum(s[3]["started"] for s in drains)
    launched = sum(s[3]["rows"] for s in spans if s[0] == hs.LAUNCH)
    assert started == launched == 50
    # columnar path: every row the drain started was materialized there
    assert sum(s[3]["materialized"] for s in drains) == started
    assert count[hs.LAUNCH] == sum(1 for s in drains if s[3]["started"])


def test_tracer_does_not_perturb_routing_or_sink():
    out = []
    for attach in (False, True):
        cp, fns, sink = _build()
        if attach:
            cp.attach_tracer()
        batches = _drive(cp, fns, seed=3)
        cols = sink.completion_columns()
        out.append(([b.state.copy() for b in batches],
                    {k: np.asarray(cols[k]).copy() for k in
                     ("fn", "platform", "arrival", "end")},
                    cp.completed_count))
    (s0, c0, n0), (s1, c1, n1) = out
    assert n0 == n1 > 0
    for a, b in zip(s0, s1):
        np.testing.assert_array_equal(a, b)
    for k in c0:
        np.testing.assert_array_equal(c0[k], c1[k])


def test_attach_reaches_platforms_added_later():
    cp, _fns, _sink = _build()
    cp.attach_tracer()
    late = cp.create_platform(dataclasses.replace(
        profiles.PAPER_PLATFORMS["edge-cluster"], name="late-edge"))
    assert late.tracer is cp.tracer is cp.policy.tracer is cp.clock.tracer
    cp.attach_tracer(None)
    assert all(p.tracer is None for p in cp.platforms.values())
    assert cp.policy.tracer is None and cp.clock.tracer is None
