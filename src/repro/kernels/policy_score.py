"""The Scheduler's admission decisions (paper §3.1.3), each written once
and run on the host under NumPy and on the device under ``jax.jit``.

Each stateless policy is one pure array function, generic over an array
namespace ``xp`` (``numpy`` or ``jax.numpy``), of the columns it reads:

    policy(xp, alive, ...) -> (cost, (alive, ok, feasible))

``cost`` is the (F, P) cost matrix — F functions being decided, P
candidate platforms — and the three (F, P) bool masks are its filter
stages: alive, after the utilization filter, after SLO feasibility (a
policy without a stage repeats the mask before it).  Both filters
degrade gracefully: a row the filter would empty keeps the mask before
it.  Per-platform columns come in row-shaped ((1, P) or (F, P)), the
SLOs as (F,); the utilization thresholds are compared on the host, in
float64, and come in as the bool ``unloaded``.

``masked_argmin`` turns cost and final mask into the decision

    (choice: (F,) int32 platform index, ok: (F,) bool any-feasible)

with ties broken to the lowest platform index; ``kill_bits`` turns the
stages into the decision journal's per-candidate bitmask.
``repro.core.scheduler`` derives the host oracle, the journal's cascade
and the what-if replay (NumPy, float64) and ``jit_decide`` (the device)
from these definitions.  Caveat: without jax x64 the device computes in
float32 while the oracle is float64 — costs within float32 eps of each
other could in principle flip an argmin.  Parity is pinned empirically
on every registry scenario; if a live workload ever manufactures such a
near-tie, prefer the numpy backend.

The SLO composite, the decision the control plane serves, runs on the
device from the raw estimator state in one program
(``fused_composite_decide``): ``predict_columns``, ``slo_composite`` and
``masked_argmin``, its twelve operands packed into one transfer in and
its result into one transfer out.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_INT = jnp.int32

# Filter-kill bitmask bits recorded by the decision journal
# (repro.obs.provenance)
KILL_DEAD = 1    # platform failed / no replicas (alive mask)
KILL_UTIL = 2    # alive but dropped by the utilization filter
KILL_SLO = 4     # survived utilization but dropped by SLO feasibility


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------

def _degrade(xp, ok, fallback):
    """Per-row graceful degrade: rows where the filter left no candidate
    fall back to the unfiltered mask."""
    return xp.where(ok.any(axis=1, keepdims=True), ok, fallback)


def masked_cost(xp, cost, mask):
    """``where(mask, cost, inf)`` with NaN read as inf, and the mask of
    its finite entries."""
    masked = xp.where(mask, cost, xp.inf)
    finite = xp.isfinite(masked)
    return xp.where(finite, masked, xp.inf), finite


def masked_argmin(xp, cost, mask):
    """Row-wise argmin of the masked cost, first lowest on ties; ok marks
    rows with at least one finite candidate."""
    masked, finite = masked_cost(xp, cost, mask)
    return xp.argmin(masked, axis=1).astype(xp.int32), finite.any(axis=1)


def kill_bits(xp, alive, ok, feasible):
    """(F, P) uint8 filter-kill bitmask of the three filter stages;
    0 == feasible after graceful degrade."""
    return (xp.where(~alive, KILL_DEAD, 0)
            | xp.where(alive & ~ok, KILL_UTIL, 0)
            | xp.where(ok & ~feasible, KILL_SLO, 0)).astype(xp.uint8)


def predict_columns(xp, ewma_v, ewma_n, analytic_s, resp_h2, resp_n,
                    nodes, loaded_w):
    """The (F, P) prediction columns from the raw estimator state
    (``FunctionPerformanceModel.estimator_columns``): exec seconds (the
    EWMA from its third observation, else the analytic estimate), P90
    response seconds (the P² marker from the tenth response, else 1.5x
    exec) and joules (the whole platform's loaded power for the run).
    ``nodes`` and ``loaded_w`` are per platform.  A ``None`` ``resp_h2``
    or ``nodes`` skips that column, returned as None."""
    exec_s = xp.where(ewma_n >= 3, ewma_v, analytic_s)
    p90_s = None if resp_h2 is None else \
        xp.where(resp_n >= 10, resp_h2, exec_s * 1.5)
    energy_j = None if nodes is None else (exec_s * nodes) * loaded_w
    return exec_s, p90_s, energy_j


# ---------------------------------------------------------------------------
# One function per stateless policy
# ---------------------------------------------------------------------------

def perf_ranked(xp, alive, exec_s):
    """§5.1.1: fastest alive platform per function."""
    return exec_s, (alive, alive, alive)


def utilization(xp, alive, exec_s, unloaded):
    """§5.1.2: fastest among un-pressured platforms (degrade to alive)."""
    ok = _degrade(xp, alive & unloaded, alive)
    return exec_s, (alive, ok, ok)


def locality(xp, alive, exec_s, data_s):
    """§5.1.4: execution + data-access seconds."""
    return exec_s + data_s, (alive, alive, alive)


def warm(xp, alive, exec_s, data_s, warm_free, cold_start_s):
    """Warm-pool-aware routing (repro.autoscale): execution + data-access
    seconds, plus the platform's cold-start penalty where the function has
    no idle warm replica standing by."""
    cold = xp.where(warm_free > 0.0, 0.0, cold_start_s)
    return exec_s + data_s + cold, (alive, alive, alive)


def energy(xp, alive, p90_s, energy_j, slo_s):
    """§5.2: cheapest energy among SLO-feasible (degrade to alive)."""
    feasible = _degrade(xp, alive & (p90_s <= slo_s[:, None]), alive)
    return energy_j, (alive, alive, feasible)


def slo_composite(xp, alive, exec_s, data_s, p90_s, energy_j, unloaded,
                  slo_s, energy_weight):
    """The FDN's hierarchical decision: utilization filter (§5.1.2) ->
    SLO feasibility (§5.1.1) -> locality-adjusted latency (§5.1.4) plus
    an energy tie-break (§5.2)."""
    ok = _degrade(xp, alive & unloaded, alive)
    feasible = _degrade(xp, ok & (p90_s <= slo_s[:, None]), ok)
    cost = (exec_s + data_s) + energy_weight * energy_j
    return cost, (alive, ok, feasible)


@functools.partial(jax.jit, static_argnums=0)
def jit_decide(policy, *cols):
    """``policy``'s decision as one device program: (choice, ok) as
    device arrays."""
    cost, (*_, feasible) = policy(jnp, *cols)
    return masked_argmin(jnp, cost, feasible)


# ---------------------------------------------------------------------------
# The served decision: estimator state -> SLO composite, packed
# ---------------------------------------------------------------------------

def _fused_composite(ewma_v, ewma_n, analytic_s, resp_h2, resp_n, data_s,
                     nodes, loaded_w, alive, unloaded, slo_s, energy_weight):
    """The whole admission step from raw estimator state: the prediction
    columns, then the SLO composite and its argmin on them."""
    exec_s, p90_s, energy_j = predict_columns(
        jnp, ewma_v, ewma_n, analytic_s, resp_h2, resp_n, nodes, loaded_w)
    cost, (*_, feasible) = slo_composite(
        jnp, alive, exec_s, data_s, p90_s, energy_j, unloaded[None, :],
        slo_s, energy_weight)
    return masked_argmin(jnp, cost, feasible)


# the (F, P) blocks of the packed buffer that hold int32 bit patterns:
# ewma_n, resp_n and alive
_INT_BLOCKS = (1, 4, 6)


def packed_words(f: int, p: int) -> int:
    """Length of ``fused_composite_decide``'s packed operand buffer in
    32-bit words: seven (F, P) blocks in parameter order (ewma_v, ewma_n,
    analytic_s, resp_h2, resp_n, data_s, alive), then nodes, loaded_w and
    unloaded (P each), the (F,) SLOs and the energy weight."""
    return 7 * f * p + 3 * p + f + 1


def _unpack(buf, f: int, p: int):
    """The twelve operands of ``_fused_composite``, in its order, sliced
    out of the packed float32 buffer (layout: ``packed_words``); counts
    and masks are read back from their int32 bit patterns."""
    fp = f * p
    words = jax.lax.bitcast_convert_type(buf, _INT)
    cols = [(words if k in _INT_BLOCKS else buf)[k * fp:(k + 1) * fp]
            .reshape(f, p) for k in range(7)]
    *cols, alive = cols
    at = 7 * fp
    return (*cols, buf[at:at + p], buf[at + p:at + 2 * p], alive != 0,
            words[at + 2 * p:at + 3 * p] != 0,
            buf[at + 3 * p:at + 3 * p + f], buf[at + 3 * p + f])


@functools.partial(jax.jit, static_argnames=("f", "p"))
def _fused_composite_decide_packed(buf, *, f: int, p: int):
    """``_fused_composite`` on the packed operand buffer.  Returns one
    (2, F) int32 array: choice, then ok."""
    choice, ok = _fused_composite(*_unpack(buf, f, p))
    return jnp.stack([choice, ok.astype(_INT)])


def fused_composite_decide(ewma_v, ewma_n, analytic_s, resp_h2, resp_n,
                           data_s, nodes, loaded_w, alive, unloaded,
                           slo_s, energy_weight
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """The whole admission step in ONE device program, with one
    host-to-device and one device-to-host transfer: the twelve host
    operands (``FunctionPerformanceModel.estimator_columns``, the
    snapshot's data, liveness, power and utilization columns, the SLOs
    and the energy weight) are packed into one float32 buffer, the
    prediction columns, filter cascade and argmin run on the device
    (``_fused_composite``), and the (2, F) result comes back in one copy.

    Floats are cast to float32 as JAX's own argument canonicalisation
    casts them; counts and masks go in as int32 bit patterns, exact.
    Returns host arrays: choice (F,) int32 and ok (F,) bool."""
    f, p = np.shape(analytic_s)
    fp = f * p
    buf = np.empty(packed_words(f, p), np.float32)
    words = buf.view(np.int32)
    for k, col in enumerate((ewma_v, ewma_n, analytic_s, resp_h2, resp_n,
                             data_s, alive)):
        (words if k in _INT_BLOCKS else buf)[k * fp:(k + 1) * fp] = \
            np.ravel(col)
    at = 7 * fp
    buf[at:at + p] = nodes
    buf[at + p:at + 2 * p] = loaded_w
    words[at + 2 * p:at + 3 * p] = unloaded
    buf[at + 3 * p:at + 3 * p + f] = slo_s
    buf[at + 3 * p + f] = energy_weight
    out = np.asarray(_fused_composite_decide_packed(buf, f=f, p=p))
    return out[0], out[1] != 0
