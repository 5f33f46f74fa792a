"""Reduce one JAX profiler trace to per-layer metrics and a breakdown.

Stage 1 (``load_xplane``) reads the ``.xplane.pb`` file into plain event
lists: device operations and XLA module executions from the TPU planes,
and the harness's ``fdnbench/...`` host spans.  Stage 2 (``summarize``)
works on those lists alone, so it is checked on a recorded trace.

All times are in nanoseconds on the profiler's clock, which places device
events and host spans on one time line.
"""
from __future__ import annotations

import bisect
import heapq
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

from fdnbench import layers

Event = Tuple[str, float, float]          # name, start_ns, duration_ns
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HARNESS = "harness"                       # window time outside any layer


class TraceError(RuntimeError):
    pass


def load_xplane(path: str) -> Dict[str, List[Event]]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict[str, List[Event]] = {"ops": [], "modules": [], "spans": []}
    for plane in pd.planes:
        device = plane.name.startswith("/device:") and \
            "CPU" not in plane.name
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                out[key].extend((e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events)
            elif not device:
                out["spans"].extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.name.startswith("fdnbench/"))
    return out


def union_ns(intervals: Sequence[Tuple[float, float]]) -> List[
        Tuple[float, float]]:
    """Merged, sorted intervals covering the union of ``intervals``."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _complement(busy, lo, hi):
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def _segments(spans: List[Event], lo: float, hi: float):
    """Label every instant of ``[lo, hi)`` with the innermost span open
    then (``HARNESS`` where only the window is), and sum each span's self
    time by (name, parent name).  Spans of one thread nest properly, so
    one stack walk does both."""
    order = sorted(spans, key=lambda e: (e[1], -e[2]))
    segs: List[Tuple[float, float, str]] = []
    self_ns: Dict[Tuple[str, str], float] = defaultdict(float)
    total_ns: Dict[Tuple[str, str], float] = defaultdict(float)
    stack: List[list] = []          # [name, end, child_ns, parent, dur]
    cursor = lo

    def label(name):
        return HARNESS if name == layers.WINDOW else name

    def emit(t, name):
        nonlocal cursor
        a, b = max(cursor, lo), min(t, hi)
        if b > a:
            segs.append((a, b, label(name)))
        cursor = max(cursor, t)

    def close_until(t):
        while stack and stack[-1][1] <= t:
            name, end, child, parent, dur = stack.pop()
            emit(end, name)
            self_ns[(name, parent)] += dur - child
            total_ns[(name, parent)] += dur

    for name, start, dur in order:
        close_until(start)
        emit(start, stack[-1][0] if stack else layers.WINDOW)
        parent = stack[-1][0] if stack else ""
        if stack:
            stack[-1][2] += dur
        stack.append([name, start + dur, 0.0, parent, dur])
    close_until(float("inf"))
    emit(hi, layers.WINDOW)
    return segs, self_ns, total_ns


def _overlap_by_label(gaps, segs) -> Dict[str, float]:
    """Sum of the gaps' overlap with each labelled segment."""
    out: Dict[str, float] = defaultdict(float)
    i = 0
    for a, b, name in segs:
        while i < len(gaps) and gaps[i][1] <= a:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < b:
            out[name] += min(b, gaps[j][1]) - max(a, gaps[j][0])
            j += 1
    return out


def _op_namer(modules: List[Event]):
    """Name a device op ``<module>/<instruction>``: its trace name is its
    HLO text (``%fusion.2 = ... fusion(...)``), its module the XLA module
    execution that encloses it."""
    mods = sorted(modules, key=lambda e: e[1])
    starts = [m[1] for m in mods]

    def name(text: str, t: float) -> str:
        short = text.split(" = ", 1)[0].lstrip("%")
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < mods[i][1] + mods[i][2]:
            return mods[i][0].split("(", 1)[0] + "/" + short
        return short
    return name


def _top(d: Dict[str, float], k: int = 10) -> List[list]:
    return [[n, v / 1e9] for n, v in heapq.nlargest(
        k, d.items(), key=lambda kv: kv[1])]


def summarize(ev: Dict[str, List[Event]], n_batches: int) -> Dict:
    """Per-layer times (ms per batch), device busy and idle, the decision
    kernel's executions, and the breakdown of one traced window."""
    wins = [e for e in ev["spans"] if e[0] == layers.WINDOW]
    if len(wins) != 1:
        raise TraceError(f"expected one {layers.WINDOW} span, found "
                         f"{len(wins)}")
    lo, hi = wins[0][1], wins[0][1] + wins[0][2]
    ops = [(n, max(s, lo), min(s + d, hi)) for n, s, d in ev["ops"]
           if s + d > lo and s < hi]
    if not ops:
        raise TraceError("no device operation ran in the traced window")
    busy = union_ns([(a, b) for _n, a, b in ops])
    busy_ns = sum(b - a for a, b in busy)
    op_ns: Dict[str, float] = defaultdict(float)
    op_name = _op_namer(ev["modules"])
    for n, a, b in ops:
        op_ns[op_name(n, a)] += b - a
    segs, self_ns, total_ns = _segments(
        [e for e in ev["spans"] if e[0] != layers.WINDOW or e is wins[0]],
        lo, hi)
    idle = _overlap_by_label(_complement(busy, lo, hi), segs)
    per_batch = 1e6 * max(n_batches, 1)

    def ms(name, parent=None, own=False):
        keys = [k for k in total_ns if k[0] == name and
                (parent is None or k[1] == parent)]
        if not keys:
            return None
        src = self_ns if own else total_ns
        return sum(src[k] for k in keys) / per_batch

    kernel = [(s, d) for n, s, d in ev["modules"]
              if layers.KERNEL_NAME in n and lo <= s < hi]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "device_idle_pct": 100.0 * (1.0 - busy_ns / (hi - lo)),
        "layer_ms": {
            "admit_self": ms(layers.ADMIT, own=True),
            "snapshot": ms(layers.SNAPSHOT, layers.ADMIT),
            "decide": ms(layers.DECIDE),
            "enqueue": ms(layers.ENQUEUE, layers.ADMIT),
            "advance": ms(layers.ADVANCE),
        },
        "kernel_calls": len(kernel),
        "kernel_s": sum(d for _s, d in kernel) / 1e9,
        "breakdown": {"device_ops": _top(op_ns), "idle_gaps": _top(idle)},
    }
