"""Shared FDNInspector harness for the paper-figure benchmarks.

Builds a control plane with the five Table-3 platforms, deploys the Table-2
functions, seeds the object stores (MinIO analogues: one local, one in
us-east), and provides the measurement/report helpers every fig*.py uses.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax

from repro.core import (FDNControlPlane, Gateway, Invocation,
                        WeightedCollaboration, RoundRobinCollaboration)
from repro.core import profiles as prof_mod
from repro.core import functions as fn_mod
from repro.core.loadgen import (LoadResult, attach_completion_hooks,
                                run_load, run_open_loop)
from repro.core.types import DeploymentSpec

IMAGE_KEY = "images/sample.jpg"
JSON_KEY = "json/coords.json"
REMOTE_STORE = "gcp-us-east"

# A persistent-cache entry is only found by a later run that looks in the
# same directory, so the fallback path is fixed: one built from a temp
# name, pid or time would never hit.
COMPILE_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX; otherwise the
    cache goes to the repo's git-ignored ``.jax_cache/``.  Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def build_fdn(policy=None, platforms: Optional[List[str]] = None,
              data_location: str = "cloud-cluster",
              analytic: bool = False) -> Tuple[
                  FDNControlPlane, Gateway, Dict]:
    """``analytic=True`` strips the real JAX callables so execution cost
    comes from the analytic model only — scheduler-focused benchmarks must
    not fold one-off JIT compilation into their measurement."""
    cp = FDNControlPlane(policy=policy)
    names = platforms or list(prof_mod.PAPER_PLATFORMS)
    for name in names:
        cp.create_platform(prof_mod.PAPER_PLATFORMS[name])
    fns = fn_mod.paper_functions(IMAGE_KEY, JSON_KEY)
    if analytic:
        fns = {k: f.replace(real_fn=None) for k, f in fns.items()}
    fn_mod.seed_object_stores(cp.placement, IMAGE_KEY, JSON_KEY,
                              location=data_location)
    # remote MinIO instance on GCP us-east (Fig. 11)
    cp.placement.add_store(REMOTE_STORE)
    fn_mod.seed_object_stores(cp.placement, IMAGE_KEY, JSON_KEY,
                              location=REMOTE_STORE)
    # WAN bandwidth Germany <-> us-east (the paper's cross-region latency)
    for name in names:
        cp.placement.set_bandwidth(name, REMOTE_STORE, 2e6)
    spec = DeploymentSpec("fdninspector", list(fns.values()), names)
    cp.deploy(spec)
    attach_completion_hooks(cp)
    gw = Gateway(cp)
    return cp, gw, fns


def run_on_platform(cp: FDNControlPlane, gw: Gateway, fn, platform: str,
                    vus: int, duration_s: float = 120.0,
                    sleep_s: float = 0.05, seed: int = 42) -> LoadResult:
    """Exclusive execution on one platform (paper's per-platform tests)."""
    return run_load(cp.clock,
                    lambda inv: cp.submit(inv, platform_override=platform),
                    fn, vus, duration_s, sleep_s, seed=seed)


@dataclass
class Row:
    name: str
    us_per_call: float
    derived: str

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.1f},{self.derived}"


def result_row(name: str, res: LoadResult, duration: float,
               extra: str = "") -> Row:
    comp = res.completed
    mean_rt = (sum(i.response_time for i in comp) / len(comp)
               if comp else float("nan"))
    derived = (f"p90_s={res.p90_response():.3f};"
               f"rps={res.requests_per_s(duration):.1f};n={len(comp)}")
    if extra:
        derived += ";" + extra
    return Row(name, mean_rt * 1e6, derived)


def scenario_row(name: str, stats: Dict, extra: str = "") -> Row:
    """CSV row from one ScenarioReport per-platform/per-function entry."""
    derived = (f"p90_s={stats['p90_s']:.3f};"
               f"rps={stats['rps']:.1f};n={stats['completed']}")
    if extra:
        derived += ";" + extra
    return Row(name, stats["mean_s"] * 1e6, derived)


class CheckFailure(AssertionError):
    pass


def check(cond: bool, msg: str, failures: List[str]):
    if not cond:
        failures.append(msg)
    return cond
