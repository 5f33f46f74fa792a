"""Decision, ms per batch: ``Policy.fn_decisions`` spans (feature gather,
host-to-device transfer, the kernel, the host sync)."""


def read(summary):
    return summary["layer_ms"]["decide"]
