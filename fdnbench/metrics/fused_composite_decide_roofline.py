"""The decision kernel's share (%) of its roofline: least time for the
bytes and operations its captured calls need (fdnbench/kernels.py) over
the device time of its executions in the trace."""


def read(summary):
    return summary["kernel_roofline_pct"]
