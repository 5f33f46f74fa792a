"""Share (%) of the traced window in which no operation ran on the
device: 1 - union of device operation intervals / window."""


def read(summary):
    return summary["device_idle_pct"]
