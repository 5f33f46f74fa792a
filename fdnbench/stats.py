"""Rate and tail arithmetic of the end-to-end metrics.

Every invocation of a batch shares its batch's admission latency, so a
tail over all invocations is a percentile of the batch latencies weighted
by their row counts: never a percentile of per-batch medians.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


def row_percentile(latency: Sequence[float], rows: Sequence[int],
                   q: float) -> float:
    """``q``-th percentile (0-100, numpy's linear interpolation) of the
    latency of every row, where batch ``i`` holds ``rows[i]`` rows that
    all waited ``latency[i]``."""
    lat = np.asarray(latency, float)
    n = np.asarray(rows, np.int64)
    keep = n > 0
    if not keep.any():
        raise ValueError("no rows: the tail is undefined")
    return float(np.percentile(np.repeat(lat[keep], n[keep]), q))


def rate(count: int, seconds: float) -> float:
    """Work per second over the whole window."""
    if seconds <= 0:
        raise ValueError("empty window")
    return count / seconds

