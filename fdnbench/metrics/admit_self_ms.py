"""Admission self time, ms per batch: the ``Gateway.request_batch`` span
minus its snapshot, decision and enqueue child spans (bookkeeping,
grouping, the object-path fallback)."""


def read(summary):
    return summary["layer_ms"]["admit_self"]
