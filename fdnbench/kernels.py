"""Operations and bytes the decision kernel must move, from its shapes,
and its share of the chip's roofline.

``fused_composite_decide`` takes, for F functions over P platforms, six
(F, P) estimator and data columns (four float32, two int32 counts), the
(F, P) liveness mask (bool), two (P,) power columns (float32), the (P,)
utilization mask (bool), the (F,) SLOs (float32) and the energy weight,
and returns the (F,) choice (int32) and any-feasible flag (bool).  Each
(F, P) element takes a fixed number of elementwise operations: two
estimate gates, the P90 bootstrap, the energy model, two filter masks,
two graceful-degrade selects, the cost, the mask and the argmin compare.
"""
from __future__ import annotations

import json
import os
from typing import Dict

OPS_PER_ELEMENT = 22


def decide_bytes(f: int, p: int) -> int:
    fp = f * p
    return (4 * 4 * fp + 2 * 4 * fp + fp      # inputs, (F, P)
            + 2 * 4 * p + p                     # power columns, mask
            + 4 * f + 4                         # SLOs, energy weight
            + 4 * f + f)                        # outputs


def decide_ops(f: int, p: int) -> int:
    return OPS_PER_ELEMENT * f * p


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; an unknown device is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "fdnbench/peaks.json")
    return table[device_kind]


def roofline_pct(shapes, kernel_s: float, kernel_calls: int,
                 peak: Dict[str, float]):
    """Share (%) of the least time the chip could take for the captured
    calls (``shapes``: their (F, P)), over the device time the trace
    shows for them; None where the trace shows no execution."""
    if kernel_calls == 0 or kernel_s <= 0 or not shapes:
        return None
    byts = sum(decide_bytes(f, p) for f, p in shapes)
    ops = sum(decide_ops(f, p) for f, p in shapes)
    least = max(byts / peak["hbm_bytes_per_s"], ops / peak["flops_per_s"])
    # per call, so a call the trace cut at the window's edge cannot skew it
    return 100.0 * (least / len(shapes)) / (kernel_s / kernel_calls)
