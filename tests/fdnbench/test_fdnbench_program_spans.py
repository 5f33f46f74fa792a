"""The reduction of the program's own ``fdn/`` host spans
(``fdnbench/programspans.py``), on hand-built event lists and on a trace
recorded on one TPU v5e chip (``recorded/``)."""
import gzip
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from fdnbench import layers, programspans, tracereduce  # noqa: E402


def _batch(t):
    """One admission batch at t (ns) and the event loop after it, as the
    program's spans: admit 200 (snapshot 20; decide 100 = gather 20,
    dispatch 20, sync 40; enqueue 30 holding a drain of 20 holding a
    launch of 5), then advance 100 (complete 10, drain 10 holding a
    launch of 2).  The device runs 10 ns inside the sync."""
    spans = [
        ("fdn/admit", t, 200.0, {"rows": 10, "fns": 2}),
        ("fdn/snapshot", t + 10, 20.0, {}),
        ("fdn/decide", t + 40, 100.0, {}),
        ("fdn/decide/gather", t + 45, 20.0, {}),
        ("fdn/decide/dispatch", t + 70, 20.0, {"f": 2, "p": 5}),
        ("fdn/decide/sync", t + 95, 40.0, {}),
        ("fdn/enqueue", t + 150, 30.0, {"rows": 10}),
        ("fdn/drain", t + 155, 20.0, {"started": 4, "materialized": 4}),
        ("fdn/launch", t + 160, 5.0, {"rows": 4}),
        ("fdn/advance", t + 200, 100.0, {"events": 3}),
        ("fdn/complete", t + 210, 10.0, {}),
        ("fdn/drain", t + 220, 10.0, {"started": 1, "materialized": 0}),
        ("fdn/launch", t + 222, 2.0, {"rows": 1}),
    ]
    ops = [("%fusion.1 = f32[4]{0} fusion(f32[4,5]{1,0} %p)", t + 100,
            10.0)]
    return spans, ops


def _events(n=4):
    ev = {"spans": [(layers.WINDOW, 0.0, 400.0 * n)], "ops": [],
          "modules": []}
    spans = []
    for k in range(n):
        s, o = _batch(400.0 * k)
        spans += s
        ev["ops"] += o
    return ev, spans


def test_program_times_counts_and_metrics():
    ev, spans = _events(4)
    s = programspans.summarize(ev, spans, n_batches=4)
    ms = s["program_ms"]
    assert ms["fdn/admit"]["total"] == pytest.approx(200e-6)
    assert ms["fdn/admit"]["self"] == pytest.approx(50e-6)  # -20-100-30
    assert ms["fdn/decide"]["self"] == pytest.approx(20e-6)  # -20-20-40
    # a drain at admission and one in the event loop, each without its
    # launch
    assert ms["fdn/drain"]["total"] == pytest.approx(30e-6)
    assert ms["fdn/drain"]["self"] == pytest.approx(23e-6)
    assert ms["fdn/advance"]["self"] == pytest.approx(80e-6)
    counts = s["program_counts"]
    assert counts["fdn/drain"] == {"spans": 2.0, "started": 5.0,
                                   "materialized": 4.0}
    assert counts["fdn/admit"] == {"spans": 1.0, "rows": 10.0, "fns": 2.0}
    assert s["metrics"] == pytest.approx({
        "decide_gather_ms": 20e-6, "decide_dispatch_ms": 20e-6,
        "decide_sync_ms": 40e-6, "complete_ms": 10e-6, "drain_ms": 23e-6,
        "launch_ms": 7e-6, "events_per_batch": 3.0,
        "materialized_per_batch": 4.0})


def test_idle_by_innermost_program_span_and_longest_spans():
    ev, spans = _events(4)
    s = programspans.summarize(ev, spans, n_batches=4)
    gaps = dict(s["idle_gaps_program"])
    # per batch: sync 40 - 10 on the device, 100 outside any span
    assert gaps["fdn/decide/sync"] == pytest.approx(4 * 30e-9)
    assert gaps[programspans.OUTSIDE] == pytest.approx(4 * 100e-9)
    assert gaps["fdn/launch"] == pytest.approx(4 * 7e-9)
    assert sum(gaps.values()) == pytest.approx(4 * 390e-9)
    longest = s["longest_spans"]
    assert [x[0] for x in longest] == ["fdn/admit"] * 4 + ["fdn/decide"]
    assert longest[0][1] == pytest.approx(200e-9)
    assert sorted(x[2] for x in longest[:4]) == pytest.approx(
        [0.0, 400e-9, 800e-9, 1200e-9])


def test_spans_outside_the_window_and_absent_spans():
    ev, spans = _events(2)
    early = [(n, a - 1e6, d, st) for n, a, d, st in spans]
    s = programspans.summarize(ev, spans + early, n_batches=2)
    assert s["program_counts"]["fdn/admit"]["spans"] == 1.0
    dropped = [x for x in spans if x[0] != "fdn/decide/gather"]
    s = programspans.summarize(ev, dropped, n_batches=2)
    assert s["metrics"]["decide_gather_ms"] is None
    assert s["metrics"]["decide_sync_ms"] == pytest.approx(40e-6)


# one second of paper-fdn.poisson-gateway on one TPU v5 lite chip, run by
# program_trace.py: the harness's spans and the program's, one trace
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded",
                        "paper-fdn.poisson-gateway.program.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("recorded") / "trace.xplane.pb"
    with gzip.open(RECORDED) as src:
        path.write_bytes(src.read())
    return (tracereduce.load_xplane(str(path)),
            programspans.load(str(path)))


def _named(spans, name):
    return sorted((s for s in spans if s[0] == name), key=lambda s: s[1])


def test_recorded_kernel_runs_between_its_dispatch_and_its_sync(recorded):
    # the program's spans and the device's module executions share the
    # profiler's clock
    ev, spans = recorded
    disp = _named(spans, "fdn/decide/dispatch")
    sync = _named(spans, "fdn/decide/sync")
    mods = sorted((m for m in ev["modules"]
                   if layers.KERNEL_NAME in m[0]), key=lambda m: m[1])
    assert len(mods) == len(disp) == len(sync) > 100
    for d, s, m in zip(disp, sync, mods):
        assert d[1] <= m[1], "module starts before its dispatch opens"
        assert m[1] + m[2] <= s[1] + s[2], "module ends after its sync"
        assert d[1] + d[2] <= s[1]


def test_recorded_program_decisions_nest_in_the_harness_spans(recorded):
    ev, spans = recorded
    outer = _named(ev["spans"], layers.DECIDE)
    inner = _named(spans, "fdn/decide")
    assert len(outer) == len(inner) > 100
    for o, i in zip(outer, inner):
        assert o[1] <= i[1] and i[1] + i[2] <= o[1] + o[2]


def test_recorded_program_readings_split_the_decision(recorded):
    ev, spans = recorded
    s = programspans.summarize(ev, spans, n_batches=200)
    assert s["metrics"] == pytest.approx({
        "decide_gather_ms": 0.12061855, "decide_dispatch_ms": 1.45021665,
        "decide_sync_ms": 1.04354925, "complete_ms": 0.10855101,
        "drain_ms": 0.07071874, "launch_ms": 0.03120899,
        "events_per_batch": 2.005, "materialized_per_batch": 1.6})
    # gather, dispatch and sync account for the harness's decision span
    harness = tracereduce.summarize(ev, 200)
    decide = harness["layer_ms"]["decide"]
    split = sum(s["metrics"][k] for k in (
        "decide_gather_ms", "decide_dispatch_ms", "decide_sync_ms"))
    assert 0.9 * decide <= split <= decide
    # the program's idle attribution covers the device's idle time
    gaps = dict(s["idle_gaps_program"])
    assert sum(gaps.values()) == pytest.approx(
        harness["window_s"] - harness["busy_s"])
    assert gaps["fdn/decide/dispatch"] > gaps["fdn/decide/gather"]
