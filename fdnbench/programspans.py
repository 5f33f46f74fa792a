"""Reduce the program's own host spans in one profiler trace.

``FDNControlPlane.attach_tracer()`` puts ``fdn/...`` spans
(``repro.obs.hostspans``) on the control plane's host work, with counters
as the spans' stats.  ``tracereduce`` reads the harness's ``fdnbench/...``
spans only; this reads the ``fdn/`` ones (``load``) and reduces those
inside the traced window (``summarize``):

* ``program_ms``: per span name, its ``total`` and ``self`` (without its
  ``fdn/`` children) time in ms per admission window;
* ``program_counts``: per span name, its spans (``spans``) and each stat
  summed, per admission window;
* ``idle_gaps_program``: the device's idle seconds by the innermost
  program span open then (``OUTSIDE`` where none is);
* ``longest_spans``: the 5 longest single spans: name, seconds, and
  seconds from the window's start;
* ``metrics``: the per-layer readings named in ``METRICS``.

All times are in nanoseconds on the profiler's clock until reported.
"""
from __future__ import annotations

import heapq
from collections import defaultdict
from typing import Dict, List, Tuple

from fdnbench import layers, tracereduce

PREFIX = "fdn/"
OUTSIDE = "outside"          # window time in no program span
Span = Tuple[str, float, float, Dict[str, int]]   # name, start, dur, stats

# per-layer reading: ("program_ms", span, "total" | "self") or
# ("program_counts", span, stat), per admission window
METRICS = {
    "decide_gather_ms": ("program_ms", "fdn/decide/gather", "total"),
    "decide_dispatch_ms": ("program_ms", "fdn/decide/dispatch", "total"),
    "decide_sync_ms": ("program_ms", "fdn/decide/sync", "total"),
    "complete_ms": ("program_ms", "fdn/complete", "self"),
    "drain_ms": ("program_ms", "fdn/drain", "self"),
    "launch_ms": ("program_ms", "fdn/launch", "total"),
    "events_per_batch": ("program_counts", "fdn/advance", "events"),
    "materialized_per_batch": ("program_counts", "fdn/drain",
                               "materialized"),
}


def load(path: str) -> List[Span]:
    """Every ``fdn/`` span on the host planes, with its stats."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            continue
        for line in plane.lines:
            out.extend((e.name, float(e.start_ns), float(e.duration_ns),
                        dict(e.stats)) for e in line.events
                       if e.name.startswith(PREFIX))
    return out


def summarize(ev: Dict[str, List], spans: List[Span],
              n_batches: int) -> Dict:
    """``ev`` is ``tracereduce.load_xplane``'s (the window and the device
    ops), ``spans`` is ``load``'s, ``n_batches`` the window's admission
    windows."""
    wins = [e for e in ev["spans"] if e[0] == layers.WINDOW]
    if len(wins) != 1:
        raise tracereduce.TraceError(f"expected one {layers.WINDOW} span, "
                                     f"found {len(wins)}")
    lo, hi = wins[0][1], wins[0][1] + wins[0][2]
    inside = [s for s in spans if lo <= s[1] < hi]
    segs, self_ns, total_ns = tracereduce._segments(
        [(n, a, d) for n, a, d, _st in inside] + [wins[0]], lo, hi)
    per_batch = max(n_batches, 1)
    ms: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"total": 0.0, "self": 0.0})
    for key, t in total_ns.items():
        if key[0] != layers.WINDOW:
            ms[key[0]]["total"] += t / 1e6 / per_batch
            ms[key[0]]["self"] += self_ns[key] / 1e6 / per_batch
    counts: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    for name, _a, _d, stats in inside:
        c = counts[name]
        c["spans"] += 1.0 / per_batch
        for k, v in stats.items():
            c[k] += v / per_batch
    ops = [(max(a, lo), min(a + d, hi)) for _n, a, d in ev["ops"]
           if a + d > lo and a < hi]
    gaps = tracereduce._complement(tracereduce.union_ns(ops), lo, hi)
    idle = {OUTSIDE if k == tracereduce.HARNESS else k: v for k, v in
            tracereduce._overlap_by_label(gaps, segs).items()}
    longest = heapq.nlargest(5, inside, key=lambda s: s[2])
    out = {
        "program_ms": {k: dict(v) for k, v in ms.items()},
        "program_counts": {k: dict(v) for k, v in counts.items()},
        "idle_gaps_program": tracereduce._top(idle, k=len(idle)),
        "longest_spans": [[n, d / 1e9, (a - lo) / 1e9]
                          for n, a, d, _st in longest],
    }
    out["metrics"] = {name: read(out, *how) for name, how in
                      METRICS.items()}
    return out


def read(summary: Dict, key: str, span: str, field: str):
    """One reading of ``METRICS``; None where the span or stat is absent,
    never 0."""
    return summary[key].get(span, {}).get(field)
