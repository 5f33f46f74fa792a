"""The one P² pass (``behavioral._p2_update_many``): a run of observations
folded with the markers held in locals must leave the markers bit for bit
where the textbook update, applied one observation at a time, leaves
them; and where the benchmark's independent recomputation
(``fdnbench/reference/estimators.py``) puts the P90.  Every caller goes
through it: the columnar cells (``_q_add`` / ``_q_add_many``, across the
five-row bootstrap), ``P2Quantile`` and telemetry's sketches, whose
vectorized lane step must agree with it too."""
import os
import sys

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.behavioral import (  # noqa: E402
    P2Quantile, QuantileState, _p2_update, _p2_update_many, _q_add,
    _q_add_many)
from repro.obs.telemetry import _q_add_block  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from fdnbench.reference import estimators  # noqa: E402

Q = 0.9


def _textbook(h, n, ns, q, x):
    """The list-based P² update as the program had it before the one
    pass, kept verbatim as the oracle."""
    if x < h[0]:
        h[0] = x
        k = 0
    elif x >= h[4]:
        h[4] = x
        k = 3
    else:
        k = next(i for i in range(4) if h[i] <= x < h[i + 1])
    for i in range(k + 1, 5):
        n[i] += 1
    for i, d in enumerate((0, q / 2, q, (1 + q) / 2, 1)):
        ns[i] += d
    for i in (1, 2, 3):
        d = ns[i] - n[i]
        if (d >= 1 and n[i + 1] - n[i] > 1) or \
           (d <= -1 and n[i - 1] - n[i] < -1):
            d = 1 if d > 0 else -1
            # parabolic
            hp = h[i] + d / (n[i + 1] - n[i - 1]) * (
                (n[i] - n[i - 1] + d) * (h[i + 1] - h[i]) /
                (n[i + 1] - n[i]) +
                (n[i + 1] - n[i] - d) * (h[i] - h[i - 1]) /
                (n[i] - n[i - 1]))
            if not h[i - 1] < hp < h[i + 1]:
                hp = h[i] + d * (h[i + d] - h[i]) / (n[i + d] - n[i])
            h[i] = hp
            n[i] += d


def _markers(xs):
    """Markers of the first five observations, as the bootstrap sorts
    them."""
    return (sorted(xs[:5]), [0, 1, 2, 3, 4],
            [0.0, 2 * Q, 4 * Q, 2 + 2 * Q, 4.0])


def _bits(h, n, ns):
    return (np.asarray(h, np.float64).tobytes(),
            np.asarray(n, np.int64).tobytes(),
            np.asarray(ns, np.float64).tobytes())


def _stream(kind, rng, size=3000):
    if kind == "ties":
        # a few levels: observations equal to every marker height
        return rng.choice([0.25, 0.5, 1.0, 2.0, 4.0], size).tolist()
    if kind == "extremes":
        # new minima and maxima, and repeats of the current extremes
        out, lo, hi = [], 0.0, 1.0
        for i in range(size):
            r = rng.random()
            if r < 0.2:
                lo -= float(rng.exponential())
                out.append(lo)
            elif r < 0.4:
                hi += float(rng.exponential())
                out.append(hi)
            elif r < 0.5:
                out.append(hi if i % 2 else lo)
            else:
                out.append(float(rng.uniform(lo, hi)))
        return out
    if kind == "constant_runs":
        out = []
        while len(out) < size:
            out += [float(rng.choice([0.1, 0.3, 7.0]))] * \
                int(rng.integers(50, 400))
        return out[:size]
    if kind == "heavy_tail":
        return (rng.pareto(0.8, size) * rng.lognormal(0.0, 2.0, size)
                ).tolist()
    if kind == "signed_zeros":
        x = rng.normal(0.0, 1.0, size)
        x[rng.random(size) < 0.2] = 0.0
        x[rng.random(size) < 0.1] = -0.0
        return x.tolist()
    return rng.lognormal(0.0, 1.0, size).tolist()


def _runs(chunking, rng, m):
    """Run lengths that cover ``m`` observations."""
    if chunking == "whole":
        return [m]
    out = []
    while sum(out) < m:
        out.append({"one": 1, "two": 2}.get(chunking)
                   or int(rng.integers(3, 65)))
    out[-1] -= sum(out) - m
    return out


STREAMS = ("ties", "extremes", "constant_runs", "heavy_tail",
           "signed_zeros", "lognormal")


@pytest.mark.parametrize("chunking", ["one", "two", "many", "whole"])
@pytest.mark.parametrize("kind", STREAMS)
def test_pass_matches_textbook_update(kind, chunking):
    rng = np.random.default_rng(STREAMS.index(kind))
    xs = _stream(kind, rng)
    want = _markers(xs)
    for x in xs[5:]:
        _textbook(*want[:3], Q, x)
    got = _markers(xs)
    i = 5
    for k in _runs(chunking, rng, len(xs) - 5):
        _p2_update_many(*got, Q, xs[i:i + k])
        i += k
    assert _bits(*got) == _bits(*want)


@pytest.mark.parametrize("chunking", ["one", "many"])
@pytest.mark.parametrize("kind", STREAMS)
def test_pass_matches_reference_recomputation(kind, chunking):
    """The P90 after each run equals the reference's trace at that row."""
    rng = np.random.default_rng(10 + STREAMS.index(kind))
    xs = _stream(kind, rng)
    trace = estimators.p2_trace(np.array(xs), Q)
    h, n, ns = _markers(xs)
    i, got, want = 5, [], []
    for k in _runs(chunking, rng, len(xs) - 5):
        _p2_update_many(h, n, ns, Q, xs[i:i + k])
        i += k
        got.append(h[2])
        want.append(trace[i - 1])
    np.testing.assert_array_equal(np.array(got), np.array(want))


@pytest.mark.parametrize("k", [1, 2, 7, 500])
def test_run_of_k_equals_k_one_row_calls(k):
    rng = np.random.default_rng(k)
    xs = rng.lognormal(0.0, 1.5, k + 5).tolist()
    one, run = _markers(xs), _markers(xs)
    for x in xs[5:]:
        _p2_update(*one, Q, x)
    _p2_update_many(*run, Q, xs[5:])
    assert _bits(*one) == _bits(*run)


def _cell_bits(qs, fi, pi):
    return tuple(np.ascontiguousarray(a[fi, pi]).tobytes() for a in qs)


@pytest.mark.parametrize("group", [1, 2, 6, 40])
@pytest.mark.parametrize("before", [0, 3, 4])
def test_group_crossing_the_bootstrap(before, group):
    """``before`` rows one by one, then a run of ``group`` rows that may
    cross the five-row bootstrap, then the rest in runs: the columnar
    cell equals ``_q_add`` row by row and ``P2Quantile``'s markers."""
    rng = np.random.default_rng(100 * before + group)
    xs = rng.lognormal(0.0, 1.0, 300).tolist()
    one, many = QuantileState.alloc(2, 3), QuantileState.alloc(2, 3)
    p2 = P2Quantile(Q)
    for x in xs:
        _q_add(one, 1, 2, x, Q)
        p2.add(x)
    for x in xs[:before]:
        _q_add_many(many, 1, 2, [x], Q)
    i = before
    for k in [group] + _runs("many", rng, len(xs) - before - group):
        _q_add_many(many, 1, 2, xs[i:i + k], Q)
        i += k
    assert _cell_bits(one, 1, 2) == _cell_bits(many, 1, 2)
    assert int(many.count[1, 2]) == p2.count == len(xs)
    assert _bits(many.heights[1, 2], many.pos[1, 2], many.want[1, 2]) == \
        _bits(p2.heights, p2.n, p2.ns)
    # the other cells are untouched
    assert int(many.count.sum()) == len(xs)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_telemetry_lane_step_matches_the_pass(seed):
    """Telemetry's vectorized lanes (one sample column at a time across
    cells) equal the one pass run per cell, from any prior count."""
    rng = np.random.default_rng(seed)
    lanes, width = 8, 60
    block, ref = QuantileState.alloc(lanes, 1), QuantileState.alloc(lanes, 1)
    for s in range(lanes):
        prior = rng.lognormal(0.0, 1.0, int(rng.integers(0, 9))).tolist()
        _q_add_many(block, s, 0, prior, Q)
        _q_add_many(ref, s, 0, prior, Q)
    X = rng.lognormal(0.0, 1.0, (lanes, width))
    X[:, ::7] = 1.0                        # ties across lanes
    L = rng.integers(0, width + 1, lanes)
    _q_add_block(block, np.arange(lanes), X, L, Q)
    for s in range(lanes):
        _q_add_many(ref, s, 0, X[s, :L[s]].tolist(), Q)
    for a, b in zip(block, ref):
        assert a.tobytes() == b.tobytes()


_VALUES = st.one_of(
    st.floats(-1e6, 1e6, allow_nan=False, allow_infinity=False),
    st.sampled_from([0.0, 1.0, 2.5, 1e-300, 5e5]))


@settings(max_examples=150, deadline=None)
@given(xs=st.lists(_VALUES, min_size=5, max_size=300),
       cuts=st.lists(st.integers(1, 40), min_size=1, max_size=30))
def test_pass_matches_textbook_on_any_stream(xs, cuts):
    want = _markers(xs)
    for x in xs[5:]:
        _textbook(*want[:3], Q, x)
    got = _markers(xs)
    i, j = 5, 0
    while i < len(xs):
        k = cuts[j % len(cuts)]
        _p2_update_many(*got, Q, xs[i:i + k])
        i += k
        j += 1
    assert _bits(*got) == _bits(*want)


@settings(max_examples=100, deadline=None)
@given(xs=st.lists(_VALUES, min_size=1, max_size=120),
       cuts=st.lists(st.integers(1, 12), min_size=1, max_size=20))
def test_cell_runs_match_scalar_estimator_on_any_stream(xs, cuts):
    qs = QuantileState.alloc(1, 1)
    p2 = P2Quantile(Q)
    for x in xs:
        p2.add(x)
    i, j = 0, 0
    while i < len(xs):
        k = cuts[j % len(cuts)]
        _q_add_many(qs, 0, 0, xs[i:i + k], Q)
        i += k
        j += 1
    assert int(qs.count[0, 0]) == p2.count
    if p2.heights is not None:
        assert _bits(qs.heights[0, 0], qs.pos[0, 0], qs.want[0, 0]) == \
            _bits(p2.heights, p2.n, p2.ns)
    else:
        assert qs.buf[0, 0, :len(xs)].tolist() == xs
