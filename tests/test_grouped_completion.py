"""Grouped completion: every row a burst ends at one instant on one
platform is one clock event, folded columnar where nothing watches between
two of its rows' completions, with the drains those completions trigger
made row by row and launched as one burst.  The fold must leave exactly
the state the per-row path leaves: metrics series, energy, the perf
model's arrays, the sink's columns, every invocation's fields.  A plain
per-row ``on_complete`` callback (one with no batch form) sends every
group down the per-row path, which makes the reference twin."""
import dataclasses

import numpy as np
import pytest

from repro.core import functions, profiles
from repro.core.behavioral import FunctionPerformanceModel
from repro.core.control_plane import FDNControlPlane
from repro.core.invocation_batch import InvocationBatch
from repro.core.loadgen import ColumnarResultSink, attach_completion_hooks
from repro.core.monitoring import MetricsRegistry
from repro.core.qos import QosSpec
from repro.core.types import DeploymentSpec, FunctionSpec, Invocation


class _Spans:
    """A tracer that keeps each span's name and stats (no clock)."""

    def __init__(self):
        self.spans = []

    def __call__(self, name, **stats):
        rec = dict(stats)
        self.spans.append((name, rec))
        return _Span(rec)

    def total(self, name, stat):
        return sum(s.get(stat, 0) for n, s in self.spans if n == name)


class _Span:
    def __init__(self, rec):
        self.rec = rec

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        self.rec.update(stats)


def _bits(a) -> bytes:
    return np.ascontiguousarray(a).tobytes()


def _drive(per_row: bool, seed: int):
    """A run on the paper fleet whose bursts share completion instants:
    scheduled columnar batches (empty queues, cold starts in groups), a
    backlogged FIFO platform, a DRR platform backlogged and then not,
    replicas retired while their rows run, rows with a per-row hook, and
    a platform with free cold starts where cold and warm rows of two
    functions of equal cost end together."""
    base = Invocation(FunctionSpec("probe"), 0.0).id
    cp = FDNControlPlane()
    cp.kb.log_decisions = False
    for prof in profiles.PAPER_PLATFORMS.values():
        cp.create_platform(prof)
    zero = cp.create_platform(dataclasses.replace(
        profiles.PAPER_PLATFORMS["hpc-node-cluster"], name="zero-cold",
        cold_start_s=0.0))
    fns = [f.replace(real_fn=None)
           for f in functions.paper_functions().values()]
    functions.seed_object_stores(cp.placement, location="cloud-cluster")
    cp.deploy(DeploymentSpec("t", fns, list(cp.platforms)))
    twin = fns[0].replace(name=fns[0].name + "-twin")
    zero.deploy(twin)
    attach_completion_hooks(cp)
    sink = ColumnarResultSink(capacity=8).install(cp)
    cp.platforms["cloud-cluster"].set_qos(QosSpec(weights=(4, 2, 1)))
    spans = _Spans()
    cp.attach_tracer(spans)
    folds = []                 # (rows, rows queued) of each group fold
    for p in cp.platforms.values():
        if per_row:
            p.on_complete.append(lambda inv: None)
        else:
            def fold(rows, many, p=p, fold=p._fold_group):
                folds.append((len(rows), p.queued_rows))
                fold(rows, many)
            p._fold_group = fold
    rng = np.random.default_rng(seed)
    invs, batches, hooked = [], [], []
    hpc, edge = cp.platforms["hpc-node-cluster"], cp.platforms["edge-cluster"]
    drr = cp.platforms["cloud-cluster"]
    for k in range(8):
        t = 2.0 * k
        cp.clock.run_until(t)
        n = int(rng.integers(20, 60))
        b = InvocationBatch(fns, rng.integers(0, len(fns), n), np.full(n, t),
                            qos=rng.integers(0, 3, n).astype(np.int8))
        cp.submit_batch(b)
        batches.append(b)
        # a backlog on a 12-replica FIFO platform and on the DRR one
        objs = [Invocation(fns[int(i)], t) for i in rng.integers(0, 2, 30)]
        invs += objs
        edge.invoke_batch(objs)
        m = 30
        cb = InvocationBatch(fns, rng.integers(0, 2, m), np.full(m, t),
                             qos=rng.integers(0, 3, m).astype(np.int8))
        drr.invoke_columns(cb, np.arange(m))
        batches.append(cb)
        # rows with a per-row hook
        objs = [Invocation(fns[0], t) for _ in range(4)]
        for inv in objs:
            inv._on_done = lambda inv=inv: hooked.append(inv.id - base)
        invs += objs
        hpc.invoke_batch(objs)
        if k == 3:
            # rows in flight on replicas that are retired before they end
            objs = [Invocation(fns[2], t) for _ in range(12)]
            invs += objs
            hpc.invoke_batch(objs)
            hpc.destroy(fns[2].name)
            hpc.deploy(fns[2])
        objs = [Invocation((fns[0], twin)[i % 2], t) for i in range(6 + k)]
        invs += objs
        zero.invoke_batch(objs)
    for k in range(4):
        # the DRR platform's backlog has cleared: its bursts fold
        t = 60.0 + 10.0 * k
        cp.clock.run_until(t)
        cb = InvocationBatch(fns, np.zeros(6, np.int32), np.full(6, t),
                             qos=rng.integers(0, 3, 6).astype(np.int8))
        drr.invoke_columns(cb, np.arange(6))
        batches.append(cb)
    cp.clock.run_until(120.0)
    for b in batches:
        invs += list(b._objs.values())
    return cp, sink, invs, hooked, spans, base, folds


def _state(cp, sink, invs, hooked, base):
    m = cp.metrics
    metrics = [(key, _bits(s._t[:s._n]), _bits(s._v[:s._n]))
               for key, s in m._m.items()]
    e = cp.energy
    energy = {name: {k: float(v).hex() for k, v in getattr(e, name).items()}
              for name in ("_joules", "_busy_joules", "_keepalive_joules",
                           "_last_t", "_last_util", "_last_idle")}
    st = cp.perf._state
    perf = ([_bits(a) for a in (st.exec_v, st.exec_n, st.cold_v, st.cold_n)]
            + [_bits(a) for q in (st.exec_q, st.resp_q) for a in q]
            + [dict(cp.perf._frow), dict(cp.perf._pcol), cp.perf.version])
    cols = sink.completion_columns()
    cols["inv_id"] = cols["inv_id"] - base
    cols["fn_specs"] = list(cols["fn_specs"])
    sinkc = {k: (_bits(v) if isinstance(v, np.ndarray) else v)
             for k, v in cols.items()}
    rows = sorted(
        (inv.id - base, inv.fn.name, inv.platform, inv.arrival_t,
         inv.scheduled_t, inv.start_t, inv.end_t, inv.status,
         inv.cold_start, inv.exec_time, inv.data_time, inv.queue_time,
         inv.attempts, int(inv.qos), inv.tenant, inv.decision)
        for inv in invs)
    plats = {name: (p._busy, p._idle_total, p.idle_gen, p.queued_rows,
                    p._mem_replicas_mb, sorted(i - base for i in p.inflight))
             for name, p in cp.platforms.items()}
    return {"metrics": metrics, "energy": energy, "perf": perf,
            "sink": sinkc, "invocations": rows, "hooked": hooked,
            "platforms": plats, "clock": cp.clock.now(),
            "accesses": (list(cp.placement.access_model.reads.items()),
                         list(cp.placement.access_model.writes.items())),
            "counts": (cp.completed_count,
                       [i.id - base for i in cp.completed],
                       cp.hedge.hedges_won)}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_grouped_fold_matches_per_row_path(seed):
    out = {}
    for per_row in (False, True):
        cp, sink, invs, hooked, spans, base, folds = _drive(per_row, seed)
        out[per_row] = (_state(cp, sink, invs, hooked, base), spans, sink,
                        cp, folds)
    (grouped, g_spans, g_sink, g_cp, folds), (ref, r_spans, *_r) = \
        out[False], out[True]
    for key in ref:
        assert grouped[key] == ref[key], key
    # the run completed rows on both paths, cold starts and the DRR
    # platform among them, and the grouped twin folded most rows
    n = g_sink.completed
    assert n == g_spans.total("fdn/complete", "rows") == \
        r_spans.total("fdn/complete", "rows")
    assert r_spans.total("fdn/complete", "grouped") == 0
    folded = g_spans.total("fdn/complete", "grouped")
    assert 0 < folded < n
    # groups folded over an empty queue and over a backlog, whose drains
    # the fold makes row by row
    assert folded == sum(k for k, _q in folds)
    assert any(q == 0 for _k, q in folds) and any(q > 0 for _k, q in folds)
    # the same rows launched, in fewer bursts where folds drained
    launched = g_spans.total("fdn/launch", "rows")
    assert launched == r_spans.total("fdn/launch", "rows") > 0
    assert sum(1 for s in g_spans.spans if s[0] == "fdn/launch") < \
        sum(1 for s in r_spans.spans if s[0] == "fdn/launch")
    assert g_sink.completion_columns()["cold"].any()
    assert g_cp.platforms["cloud-cluster"]._cqueues is not None
    assert grouped["hooked"] and len(grouped["hooked"]) == 32


# ------------------------------------------------------- batch folds ---

def _observations(rng, n, nf, npl, cold=0.2):
    """``n`` completed invocations over ``nf`` functions and ``npl``
    platforms, a share ``cold`` of them cold."""
    specs = [FunctionSpec(f"f{i}") for i in range(nf)]
    out = []
    for _ in range(n):
        inv = Invocation(specs[int(rng.integers(nf))],
                         float(rng.uniform(0, 100)))
        inv.platform = f"p{int(rng.integers(npl))}"
        inv.exec_time = float(rng.lognormal(0.0, 1.0))
        inv.queue_time = float(rng.exponential(0.5))
        inv.cold_start = bool(rng.random() < cold)
        inv.end_t = inv.arrival_t + inv.queue_time + inv.exec_time
        inv.qos = int(rng.integers(0, 3))
        inv.tenant = int(rng.integers(0, 4))
        inv.status = "done"
        out.append(inv)
    return out


def _chunks(rng, invs):
    i = 0
    while i < len(invs):
        k = int(rng.integers(1, 24))
        yield invs[i:i + k]
        i += k


def _interleaved(rng, invs):
    """The rows reordered round-robin over their (function, platform)
    cells, so every group alternates between cells."""
    cells = {}
    for inv in invs:
        cells.setdefault((inv.fn.name, inv.platform), []).append(inv)
    rows = []
    while cells:
        for key in list(cells):
            rows.append(cells[key].pop())
            if not cells[key]:
                del cells[key]
    return rows


def _shaped(shape, rng, invs):
    """The rows in order and the groups ``observe_many`` folds them in:

    * ``random``: groups of 1 to 23 rows;
    * ``interleaved``: rows alternating between cells, in groups of 8 to
      39, so each group runs several cells at once;
    * ``bootstrap``: rows alternating between cells, in groups of 3, 4
      and then 7 rows per cell, so every cell's fourth to seventh rows
      (the bootstrap's end and the first marker updates) share a group;
    * ``cold_mix``: half the rows cold, some with no platform (folded
      into the ``?`` column, with no cold-start sample) and some with no
      end (no response time), in groups of 1 to 23 rows.
    """
    if shape == "cold_mix":
        for inv in invs[::7]:
            inv.platform = None
        for inv in invs[3::11]:
            inv.end_t = None
    if shape in ("interleaved", "bootstrap"):
        invs = _interleaved(rng, invs)
    if shape in ("random", "cold_mix"):
        return invs, list(_chunks(rng, invs))
    ncells = len({(inv.fn.name, inv.platform) for inv in invs})
    if shape == "interleaved":
        sizes = iter(lambda: int(rng.integers(8, 40)), None)
    else:
        sizes = iter([3 * ncells, 4 * ncells] + [7 * ncells] * len(invs))
    groups, i = [], 0
    while i < len(invs):
        k = next(sizes)
        groups.append(invs[i:i + k])
        i += k
    return invs, groups


@pytest.mark.parametrize("seed,nf,npl,shape", [
    pytest.param(0, 3, 2, "random", id="0-3-2"),
    pytest.param(1, 40, 3, "random", id="1-40-3"),
    pytest.param(2, 5, 11, "random", id="2-5-11"),
    pytest.param(3, 1, 1, "random", id="3-1-1"),
    pytest.param(4, 4, 3, "interleaved", id="interleaved-4-4-3"),
    pytest.param(5, 3, 2, "bootstrap", id="bootstrap-5-3-2"),
    pytest.param(6, 2, 5, "bootstrap", id="bootstrap-6-2-5"),
    pytest.param(7, 4, 4, "cold_mix", id="cold_mix-7-4-4")])
def test_observe_many_matches_observe(seed, nf, npl, shape):
    """Sequences that cross the five-sample P² bootstrap in several cells,
    with cold starts, and (40 functions, 11 platforms) grow the arrays;
    groups that interleave cells, that cross a cell's bootstrap inside
    the group, and that mix cold and warm rows across platforms."""
    rng = np.random.default_rng(seed)
    invs, groups = _shaped(shape, rng, _observations(
        rng, 600, nf, npl, cold=0.5 if shape == "cold_mix" else 0.2))
    one, many = FunctionPerformanceModel(), FunctionPerformanceModel()
    for inv in invs:
        one.observe(inv)
    cells = [many.observe_many(group) for group in groups]
    a, b = one._state, many._state
    assert a.exec_n.shape == b.exec_n.shape
    for x, y in zip((a.exec_v, a.exec_n, a.cold_v, a.cold_n, *a.exec_q,
                     *a.resp_q),
                    (b.exec_v, b.exec_n, b.cold_v, b.cold_n, *b.exec_q,
                     *b.resp_q)):
        assert _bits(x) == _bits(y)
    assert one._frow == many._frow and one._pcol == many._pcol
    assert one.version == many.version == len(invs)
    # each group loaded each of its cells once
    assert cells == [len({(inv.fn.name, inv.platform or "?")
                          for inv in group}) for group in groups]
    if shape != "random":
        assert max(c for c in cells) > 1
    if shape == "bootstrap":
        # every cell's run in the second group crosses its bootstrap
        seen = {}
        for inv in groups[0]:
            key = (inv.fn.name, inv.platform)
            seen[key] = seen.get(key, 0) + 1
        after = dict(seen)
        for inv in groups[1]:
            key = (inv.fn.name, inv.platform)
            after[key] = after.get(key, 0) + 1
        assert all(seen[k] < 5 < after[k] for k in after)
    if shape == "cold_mix":
        assert int(a.cold_n.sum()) == sum(
            1 for inv in invs if inv.cold_start and inv.platform)
        assert 0 < int(a.resp_q.count.sum()) < int(a.exec_q.count.sum())


def test_observe_many_calls_an_overridden_observe():
    seen = []

    class Counting(FunctionPerformanceModel):
        def observe(self, inv):
            seen.append(inv.id)
            super().observe(inv)

    invs = _observations(np.random.default_rng(5), 10, 2, 2)
    Counting().observe_many(invs)
    assert seen == [inv.id for inv in invs]


@pytest.mark.parametrize("seed,cap", [(0, 1), (1, 4), (2, 1024)])
def test_record_many_matches_record_completion(seed, cap):
    rng = np.random.default_rng(seed)
    invs = _observations(rng, 300, 6, 4)
    invs[3].end_t = None                   # recorded as NaN
    one, many = ColumnarResultSink(cap), ColumnarResultSink(cap)
    for inv in invs:
        one.record_completion(inv)
    for chunk in _chunks(rng, invs):
        many.record_many(chunk)
    many.record_many([])
    a, b = one.completion_columns(), many.completion_columns()
    assert a.keys() == b.keys()
    for k in a:
        if isinstance(a[k], np.ndarray):
            assert a[k].dtype == b[k].dtype and _bits(a[k]) == _bits(b[k]), k
        else:
            assert a[k] == b[k], k
    assert one._arrival.size == many._arrival.size      # same doublings


@pytest.mark.parametrize("defer,infra", [(False, True), (False, False),
                                         (True, True)])
def test_record_completion_many_matches_record_completion(defer, infra):
    """Groups that end at one instant, cold rows among warm ones of the
    same function, with a per-row extra sample: the same series, keys
    created in the same order."""
    rng = np.random.default_rng(11)
    invs = _observations(rng, 400, 5, 1)
    one, many = MetricsRegistry(), MetricsRegistry()
    one.defer_completions = many.defer_completions = defer
    for chunk in _chunks(rng, invs):
        t = chunk[0].end_t
        reps = [float(rng.integers(1, 9)) for _ in chunk]
        for i, inv in enumerate(chunk):
            inv.end_t = t
            one.record_completion(inv, visible_infra=infra)
            one.add(inv.platform, inv.fn.name, "replicas", t, reps[i])
        many.record_completion_many(chunk, visible_infra=infra,
                                    extra=(("replicas", reps),))
    many.record_completion_many([])
    assert list(one._m) == list(many._m)
    for key in one._m:
        a, b = one._m[key], many._m[key]
        assert (_bits(a._t[:a._n]), _bits(a._v[:a._n])) == \
            (_bits(b._t[:b._n]), _bits(b._v[:b._n])), key
