"""Event loop and sink, ms per batch: ``SimClock.run_until`` spans between
batches (completions, the drains they trigger, the sink, autoscaler ticks)."""


def read(summary):
    return summary["layer_ms"]["advance"]
