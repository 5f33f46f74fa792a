"""Plain NumPy SLO-composite decision, written from the paper's §5.

Jindal et al., "Function Delivery Network", arXiv:2102.02330, §5: the
control plane routes each invocation to a target platform by a cascade of
filters and a cost.  For one function and the platforms that hold it:

1. Utilization (§5.1.2): keep platforms whose CPU and memory utilization
   are under their thresholds; if none is, keep every live platform.
2. SLO feasibility (§5.1.1): of those, keep platforms whose predicted
   P90 response time meets the function's SLO; if none does, keep step
   1's platforms.
3. Cost (§5.1.4, §5.2): predicted execution seconds plus data-access
   seconds, plus a weight times the predicted energy of one invocation
   (execution seconds times the platform's nodes times its loaded watts
   per node).  The least cost wins; a tie goes to the first platform.

Predictions come from the observed state: the execution-time EWMA once a
(function, platform) pair has ``exec_min_obs`` observations, otherwise the
analytic estimate (FLOPs over a replica's FLOP/s plus bytes over the
platform's bandwidth); the P90 response once ``p90_min_obs`` responses
were seen, otherwise ``p90_bootstrap`` times the execution estimate.

Everything static (function demands, platform profiles, object sizes,
thresholds) is read from the configuration file; only the observed state
at the decision instant (estimator columns, utilization) comes from the
run.  This module imports nothing of the program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import numpy as np


@dataclass
class Decision:
    cost: np.ndarray       # (R, P) cost of every pairing
    feasible: np.ndarray   # (R, P) survivors of the cascade
    ok: np.ndarray         # (R,) some platform survives
    best: np.ndarray       # (R,) least-cost survivor (first on ties)


class Fleet:
    """The static columns of one configuration, by name."""

    def __init__(self, config: Dict):
        plats = config["platforms"]
        self.p_index = {p["name"]: j for j, p in enumerate(plats)}
        self.f_index = {f["name"]: i for i, f in enumerate(
            config["functions"])}
        fns = config["functions"]
        self.flops = np.array([float(f["flops"]) for f in fns])
        self.bytes = np.array([float(f.get("read_bytes", 0.0)) +
                               float(f.get("write_bytes", 0.0))
                               for f in fns])
        self.mem_mb = np.array([float(f["memory_mb"]) for f in fns])
        self.slo = np.array([float(f["slo_p90_s"]) for f in fns])
        self.replica_flops = np.array([float(p["replica_flops"])
                                       for p in plats])
        self.net_bw = np.array([float(p["net_bw"]) for p in plats])
        self.nodes = np.array([float(p["nodes"]) for p in plats])
        self.loaded_w = np.array([float(p["loaded_w_per_node"])
                                  for p in plats])
        self.total_mem = np.array([float(p["nodes"]) *
                                   float(p["memory_mb_per_node"])
                                   for p in plats])
        # data-access seconds of one invocation on each platform: the
        # object is read at local bandwidth where it is stored, over the
        # WAN elsewhere
        place = config["placement"]
        objs = {o["key"]: o for o in config.get("objects", ())}
        self.data_s = np.zeros((len(fns), len(plats)))
        for i, f in enumerate(fns):
            for key in f.get("data_objects", ()):
                o = objs.get(key)
                if o is None:
                    continue
                size = max(float(o["bytes"]), 1.0)
                for j, p in enumerate(plats):
                    bw = place["local_bw"] if p["name"] == o["location"] \
                        else place["wan_bw"]
                    self.data_s[i, j] += size / float(bw)
        pol = config["policy"]
        self.cpu_thr = float(pol["cpu_threshold"])
        self.mem_thr = float(pol["mem_threshold"])
        self.energy_weight = float(pol["energy_weight"])
        pm = config["perf_model"]
        self.exec_min_obs = int(pm["exec_min_obs"])
        self.p90_min_obs = int(pm["p90_min_obs"])
        self.p90_bootstrap = float(pm["p90_bootstrap"])
        self.ewma_alpha = float(pm["exec_ewma_alpha"])
        self.p90_quantile = float(pm["p90_quantile"])


def decide(fleet: Fleet, fn, ewma_v, ewma_n, resp_h2, resp_n, cpu_util,
           mem_util, present, dtype=np.float64,
           degrade: bool = True) -> Decision:
    """The cascade for R decisions: row r decides function ``fn[r]`` (its
    index in the configuration) over the configuration's platforms, from
    the observed (R, P) columns; ``present[r]`` marks the platforms the
    decision could see (live, in its snapshot).  Computed in ``dtype``;
    ``degrade=False`` leaves out the two fall-backs to the previous
    step's platforms (a control, not the paper's cascade)."""
    fn = np.asarray(fn, np.int64)

    def c(x):
        return np.asarray(x).astype(dtype)

    analytic = c(fleet.flops[fn][:, None] /
                 np.maximum(fleet.replica_flops, 1.0)[None, :] +
                 fleet.bytes[fn][:, None] /
                 np.maximum(fleet.net_bw, 1.0)[None, :])
    exec_s = np.where(np.asarray(ewma_n) >= fleet.exec_min_obs,
                      c(ewma_v), analytic)
    p90 = np.where(np.asarray(resp_n) >= fleet.p90_min_obs, c(resp_h2),
                   exec_s * c(fleet.p90_bootstrap))
    energy = exec_s * c(fleet.nodes)[None, :] * c(fleet.loaded_w)[None, :]
    alive = np.asarray(present, bool) & \
        (fleet.total_mem[None, :] >= fleet.mem_mb[fn][:, None])
    unloaded = (np.asarray(cpu_util) < fleet.cpu_thr) & \
        (np.asarray(mem_util) < fleet.mem_thr)
    ok = alive & unloaded
    if degrade:
        ok = np.where(ok.any(axis=1, keepdims=True), ok, alive)
    feasible = ok & (p90 <= c(fleet.slo[fn])[:, None])
    if degrade:
        feasible = np.where(feasible.any(axis=1, keepdims=True), feasible,
                            ok)
    cost = (exec_s + c(fleet.data_s[fn])) + c(fleet.energy_weight) * energy
    masked = np.where(feasible, cost, np.inf)
    return Decision(cost.astype(np.float64), feasible,
                    feasible.any(axis=1), np.argmin(masked, axis=1))
