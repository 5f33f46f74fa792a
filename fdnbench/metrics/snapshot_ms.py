"""Snapshot build, ms per batch: ``control_plane.as_snapshot`` spans inside
admission (``PlatformSnapshot.__init__``)."""


def read(summary):
    return summary["layer_ms"]["snapshot"]
