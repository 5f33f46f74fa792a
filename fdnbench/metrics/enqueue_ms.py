"""Enqueue, drain and launch at admission, ms per batch: sidecar
``admit_columns`` / ``admit_many`` / ``admit`` spans inside admission."""


def read(summary):
    return summary["layer_ms"]["enqueue"]
