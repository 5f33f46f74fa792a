"""Plain recomputation of the observed state a decision reads.

The FDN's performance model (Jindal et al., arXiv:2102.02330, §3.6 and
§5.1.1) keeps, for each (function, platform) pair, an estimate of the
execution time and of the P90 response time, and updates both from every
completed invocation.  The configuration states how
(``perf_model``): the execution time is an exponentially weighted moving
average that weighs a new observation ``exec_ewma_alpha`` (the first
observation sets it), and the P90 is the P-square estimate of quantile
``p90_quantile`` (Jain and Chlamtac, "The P2 algorithm for dynamic
calculation of quantiles and histograms without storing observations",
CACM 28(10), 1985, Box 1).

Both are recomputed here from the completion record alone (function,
platform, execution seconds, arrival and end of each completion, in the
order they completed), so a decision's estimator columns can be checked
against what the completions before it imply.  This module imports
nothing of the program.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def ewma_trace(x: np.ndarray, alpha: float) -> np.ndarray:
    """The moving average after each observation of ``x``."""
    out = np.empty(x.size)
    v = 0.0
    for i, xi in enumerate(x.tolist()):
        v = xi if i == 0 else alpha * xi + (1 - alpha) * v
        out[i] = v
    return out


def p2_trace(x: np.ndarray, p: float) -> np.ndarray:
    """The P-square estimate of quantile ``p`` after each observation of
    ``x``; NaN until five observations have placed the markers.

    Markers are numbered 0-4; ``n`` are their positions and ``want`` the
    desired ones, counted from 0 (the paper counts from 1: the same
    differences)."""
    out = np.full(x.size, np.nan)
    xs = x.tolist()
    if len(xs) < 5:
        return out
    q = sorted(xs[:5])
    n = [0, 1, 2, 3, 4]
    want = [0, 2 * p, 4 * p, 2 + 2 * p, 4]
    step = (0, p / 2, p, (1 + p) / 2, 1)
    out[4] = q[2]
    for j in range(5, len(xs)):
        v = xs[j]
        # B1: the cell k with q[k] <= v < q[k+1]; extremes move out
        if v < q[0]:
            q[0] = v
            k = 0
        elif v > q[4]:
            q[4] = v
            k = 3
        else:
            k = 0
            while k < 3 and v >= q[k + 1]:
                k += 1
        # B2: positions of the markers above the cell, desired positions
        for i in range(k + 1, 5):
            n[i] += 1
        for i in range(5):
            want[i] += step[i]
        # B3: move the middle markers toward their desired positions
        for i in (1, 2, 3):
            d = want[i] - n[i]
            if (d >= 1 and n[i + 1] - n[i] > 1) or \
                    (d <= -1 and n[i - 1] - n[i] < -1):
                d = 1 if d > 0 else -1
                qp = q[i] + d / (n[i + 1] - n[i - 1]) * (
                    (n[i] - n[i - 1] + d) * (q[i + 1] - q[i]) /
                    (n[i + 1] - n[i]) +
                    (n[i + 1] - n[i] - d) * (q[i] - q[i - 1]) /
                    (n[i] - n[i - 1]))
                if not q[i - 1] < qp < q[i + 1]:
                    qp = q[i] + d * (q[i + d] - q[i]) / (n[i + d] - n[i])
                q[i] = qp
                n[i] += d
        out[j] = q[2]
    return out


@dataclass
class Estimates:
    exec_v: np.ndarray    # (R, P) execution-time EWMA
    exec_n: np.ndarray    # (R, P) observations it folded
    p90_v: np.ndarray     # (R, P) P90 response time
    p90_n: np.ndarray     # (R, P) observations it folded


def at_decisions(fn, plat, exec_s, resp_s, n_platforms: int, row_fn,
                 done, alpha: float, p: float) -> Estimates:
    """The estimates decision row ``r`` (function ``row_fn[r]``) should
    have read, having seen the first ``done[r]`` completions.  ``fn`` and
    ``plat`` index the configuration's functions and platforms (-1: not
    one of them, skipped)."""
    row_fn = np.asarray(row_fn, np.int64)
    done = np.asarray(done, np.int64)
    r, npl = row_fn.size, n_platforms
    est = Estimates(np.zeros((r, npl)), np.zeros((r, npl), np.int64),
                    np.zeros((r, npl)), np.zeros((r, npl), np.int64))
    fn, plat = np.asarray(fn, np.int64), np.asarray(plat, np.int64)
    key = np.where((fn >= 0) & (plat >= 0), fn * npl + plat, -1)
    for c in np.unique(key[key >= 0]):
        f, j = divmod(int(c), npl)
        rows = np.flatnonzero(row_fn == f)
        if rows.size == 0:
            continue
        at = np.flatnonzero(key == c)
        m = np.searchsorted(at, done[rows], side="left")
        seen = m > 0
        ev = ewma_trace(exec_s[at], alpha)
        pv = p2_trace(resp_s[at], p)
        est.exec_n[rows, j] = m
        est.p90_n[rows, j] = m
        est.exec_v[rows[seen], j] = ev[m[seen] - 1]
        est.p90_v[rows[seen], j] = pv[m[seen] - 1]
    return est
