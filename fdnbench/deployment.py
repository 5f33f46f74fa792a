"""Build the system under test from a configuration file.

A configuration (``fdnbench/configs/<name>.json``) holds a deployment as
data: platform profiles, function specs, stored objects, the policy and
control-plane flags.  Nothing here reads the program's presets
(``profiles.PAPER_PLATFORMS``, ``functions.paper_functions``), so a later
change to them cannot move the yardstick.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List


@dataclass
class Deployment:
    """The live system one run drives."""

    cp: object            # FDNControlPlane
    gateway: object       # Gateway
    specs: List[object]   # FunctionSpec, in the configuration's order
    sink: object          # ColumnarResultSink


def build(config: Dict, overrides: Dict) -> Deployment:
    """Build the control plane, platforms, functions and gateway that
    ``config`` states; ``overrides`` are control-plane flags a traffic mix
    sets (hedging requested by its clients)."""
    from repro.core import scheduler
    from repro.core.control_plane import FDNControlPlane
    from repro.core.gateway import Gateway
    from repro.core.loadgen import (ColumnarResultSink,
                                    attach_completion_hooks)
    from repro.core.scheduler import SLOCompositePolicy
    from repro.core.types import (SLO, DeploymentSpec, FunctionSpec,
                                  PlatformProfile)

    flags = dict(config["control_plane"])
    flags.update(overrides)
    cp = FDNControlPlane(enable_hedging=bool(flags["enable_hedging"]),
                         retain_completions=bool(
                             flags["retain_completions"]))
    cp.kb.log_decisions = bool(flags["kb_log_decisions"])
    cp.placement.local_bw = float(config["placement"]["local_bw"])
    cp.placement.wan_bw = float(config["placement"]["wan_bw"])
    pol = dict(config["policy"])
    if pol.pop("name") != "slo_composite":
        raise ValueError("fdnbench drives the SLO-composite policy only")
    cp.policy = SLOCompositePolicy(cp.perf, cp.placement, **pol)
    for fields in config["platforms"]:
        cp.create_platform(PlatformProfile(**fields))
    specs = []
    for f in config["functions"]:
        kw = {k: v for k, v in f.items() if k != "slo_p90_s"}
        kw["data_objects"] = tuple(kw.get("data_objects", ()))
        specs.append(FunctionSpec(slo=SLO(float(f["slo_p90_s"])), **kw))
    for obj in config.get("objects", ()):
        if obj["location"] not in cp.placement.stores:
            cp.placement.add_store(obj["location"])
        cp.placement.stores[obj["location"]].put(obj["key"],
                                                 float(obj["bytes"]))
    cp.deploy(DeploymentSpec(config["name"], specs, list(cp.platforms)))
    auto = config.get("autoscaler")
    if auto is not None:
        cp.attach_autoscaler(policy=auto["policy"],
                             tick_s=float(auto["tick_s"]),
                             backend=auto["backend"])
    attach_completion_hooks(cp)
    sink = ColumnarResultSink().install(cp)
    scheduler.set_score_backend("jax")
    return Deployment(cp, Gateway(cp), specs, sink)


def queued_rows(cp) -> int:
    """Rows waiting in every platform queue (the backlog a fleet that
    cannot keep up grows)."""
    return int(sum(p.queued_rows for p in cp.platforms.values()))
