"""The comparison that decides ``correct``.

It covers the layers the cells exercise, on what the timed path itself
produced in the window (``layers.Capture``):

* decision: every fused decision the window made is recomputed by the
  plain reference (``fdnbench/reference/cascade.py``) from the observed
  state it was made on.  ``infeasible_choices`` counts choices the
  reference's cascade filtered out (or a wrong any-feasible flag);
  ``decision_regret`` is the widest relative gap by which a chosen
  platform's reference cost lies above the reference's least cost.
* estimators: the columns each decision read (execution-time EWMA, P90
  response and their observation counts) are recomputed by the plain
  reference (``fdnbench/reference/estimators.py``) from the completions
  the run recorded before that decision.  ``estimator_count_mismatches``
  counts (decision, platform) cells where a count gate (enough
  observations to use the estimate) differs, or a used estimate folded
  another number of observations; ``estimator_gap`` is the widest
  relative gap between a used estimate and its recomputation.
* admission: ``unrouted_rows`` counts offered rows not decided exactly
  once through the kernel, or not enqueued exactly once on admission
  (none when rejected); ``misrouted_rows`` counts rows enqueued on a
  platform other than the one decided for their function.

The controls put a broken decision in the program's place at the same
positions: ``frozen`` decides each function as the window's first
decision for it did (decisions cached for the window), ``stale`` as the
previous decision for it did (one batch stale), ``no_util`` drops the
utilization filter (the snapshot's per-platform utilization never read),
``no_degrade`` drops the cascade's two fall-backs (the per-row
reductions a kernel would be tempted to skip); ``frozen`` and ``stale``
estimators put in each decision's place the columns its function's
first, or previous, decision of the window read.  A cell compares the
numbers its limits file (``fdnbench/checks/<cell>.json``) lists.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from fdnbench.reference import cascade, estimators


def reference(cap, fleet: cascade.Fleet, dtype=np.float64,
              util_filter: bool = True,
              degrade: bool = True) -> cascade.Decision:
    """The reference's decision at every position the window decided."""
    d, n = cap.d, cap.d.n
    cpu, mem = d.cpu[:n], d.mem[:n]
    if not util_filter:
        cpu, mem = np.zeros_like(cpu), np.zeros_like(mem)
    return cascade.decide(fleet, d.fn[:n], d.ewma_v[:n], d.ewma_n[:n],
                          d.resp_h2[:n], d.resp_n[:n], cpu, mem,
                          d.present[:n], dtype, degrade)


def judge(ref: cascade.Decision, choice, ok) -> Dict[str, float]:
    """The decision numbers of ``choice``/``ok`` (one per position)."""
    choice = np.asarray(choice, np.int64)
    ok = np.asarray(ok, bool)
    rows = np.arange(choice.size)
    safe = np.clip(choice, 0, ref.cost.shape[1] - 1)
    wrong = (ok != ref.ok) | (ok & ~ref.feasible[rows, safe])
    good = ok & ~wrong
    best = ref.cost[rows, ref.best]
    gap = (ref.cost[rows, safe] - best) / np.abs(best)
    return {"infeasible_choices": int(wrong.sum()),
            "decision_regret": float(gap[good].max()) if good.any()
            else 0.0}


def decision_numbers(cap, ref) -> Dict[str, float]:
    n = cap.d.n
    return judge(ref, cap.d.idx[:n], cap.d.ok[:n])


def held_control(cap, ref, frozen: bool) -> Dict[str, float]:
    """Each function decided as its previous decision of the window was
    (``frozen``: as its first one was); a function's first decision is
    its own."""
    src = _held(cap.d.fn[:cap.d.n], frozen)
    return judge(ref, ref.best[src], ref.ok[src])


def _held(fn, frozen: bool) -> np.ndarray:
    """For each position, the position of its function's previous decision
    (``frozen``: first decision); a function's first decision is its own."""
    fn = np.asarray(fn, np.int64)
    pos = np.arange(fn.size)
    src = pos.copy()
    for f in np.unique(fn):
        at = pos[fn == f]
        src[at] = at[0] if frozen else np.concatenate([at[:1], at[:-1]])
    return src


def replaced_control(ref, other) -> Dict[str, float]:
    """Another decision put in the program's place at the same positions."""
    return judge(ref, other.best, other.ok)


def estimates(cap, fleet: cascade.Fleet,
              sink_cols: Dict) -> estimators.Estimates:
    """The reference's estimator columns at every position the window
    decided, from the run's completion record."""
    f_of = np.full(len(sink_cols["fn_ids"]), -1, np.int64)
    for name, i in sink_cols["fn_ids"].items():
        f_of[i] = fleet.f_index.get(name, -1)
    p_of = np.full(len(sink_cols["platform_ids"]), -1, np.int64)
    for name, j in sink_cols["platform_ids"].items():
        p_of[j] = fleet.p_index.get(name, -1)
    n = cap.d.n
    return estimators.at_decisions(
        f_of[sink_cols["fn"]], p_of[sink_cols["platform"]],
        sink_cols["exec"], sink_cols["end"] - sink_cols["arrival"],
        len(fleet.p_index), cap.d.fn[:n], cap.d.done[:n],
        fleet.ewma_alpha, fleet.p90_quantile)


def judge_estimates(fleet: cascade.Fleet, est: estimators.Estimates,
                    present, ewma_v, ewma_n, resp_h2,
                    resp_n) -> Dict[str, float]:
    """The estimator numbers of the columns a decision read (one row per
    position) against the reference's ``est``."""
    present = np.asarray(present, bool)
    bad = np.zeros(present.shape, bool)
    gap = 0.0
    for v, nobs, rv, rn, least in (
            (ewma_v, ewma_n, est.exec_v, est.exec_n, fleet.exec_min_obs),
            (resp_h2, resp_n, est.p90_v, est.p90_n, fleet.p90_min_obs)):
        used, ref_used = nobs >= least, rn >= least
        bad |= present & ((used != ref_used) | (ref_used & (nobs != rn)))
        both = present & used & ref_used
        if both.any():
            gap = max(gap, float((np.abs(v[both] - rv[both]) /
                                  np.abs(rv[both])).max()))
    return {"estimator_count_mismatches": int(bad.sum()),
            "estimator_gap": gap}


def estimator_numbers(cap, fleet, est) -> Dict[str, float]:
    d, n = cap.d, cap.d.n
    return judge_estimates(fleet, est, d.present[:n], d.ewma_v[:n],
                           d.ewma_n[:n], d.resp_h2[:n], d.resp_n[:n])


def held_estimates(cap, fleet, est, frozen: bool) -> Dict[str, float]:
    """Each decision given the estimator columns its function's previous
    decision of the window read (``frozen``: its first one)."""
    d, n = cap.d, cap.d.n
    src = _held(d.fn[:n], frozen)
    return judge_estimates(fleet, est, d.present[:n], d.ewma_v[src],
                           d.ewma_n[src], d.resp_h2[src], d.resp_n[src])


def admission_numbers(cap) -> Dict[str, int]:
    b, r, d = cap.b, cap.r, cap.d
    nb, nr, nd = b.n, r.n, d.n
    nf = len(cap.fn_names)
    row_batch = np.repeat(np.arange(nb), b.rows[:nb])
    key = d.batch[:nd] * nf + d.fn[:nd]
    times = np.bincount(key, minlength=nb * nf)
    target = np.full(nb * nf, -1, np.int64)       # -1: rejected
    target[key] = np.where(d.ok[:nd], d.idx[:nd], -1)
    row_key = row_batch * nf + r.fn[:nr]
    decided_once = times[row_key] == 1
    via_kernel = (b.calls[:nb] == 1) & (b.kcalls[:nb] == 1) & \
        ~b.stateful[:nb]
    tgt = target[row_key]
    enq = r.enq[:nr]
    bad = ~decided_once | ~via_kernel[row_batch] | \
        ((tgt >= 0) & (enq != 1)) | ((tgt < 0) & (enq != 0))
    misrouted = (enq >= 1) & (r.plat[:nr] != tgt)
    return {"unrouted_rows": int(bad.sum()) + cap.foreign_rows,
            "misrouted_rows": int(misrouted.sum())}


def rejected_rows(cap) -> int:
    b, r, d = cap.b, cap.r, cap.d
    nf = len(cap.fn_names)
    rej = np.zeros(b.n * nf, bool)
    key = d.batch[:d.n] * nf + d.fn[:d.n]
    rej[key] = ~d.ok[:d.n]
    row_batch = np.repeat(np.arange(b.n), b.rows[:b.n])
    return int(rej[row_batch * nf + r.fn[:r.n]].sum())
