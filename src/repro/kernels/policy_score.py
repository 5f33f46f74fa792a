"""Jitted admission decisions: the Scheduler's policy filter cascades,
cost matrices and argmin compiled with ``jax.jit`` over the columnar
``PlatformSnapshot`` (paper §3.1.3).

Each function takes per-distinct-function matrices of shape (F, P) —
F functions being decided, P candidate platforms — plus per-platform or
per-function vectors, and returns the fused decision

    (choice: (F,) int32 platform index, ok: (F,) bool any-feasible)

with ties broken to the lowest platform index, exactly like the NumPy
``Policy.score`` + row-argmin path in ``repro.core.scheduler`` (which
stays as the small-batch path and the parity oracle — tests assert
byte-identical platform choices under both backends).  Caveat: without
jax x64, the cascades compute in float32 while the oracle is float64 —
costs within float32 eps of each other could in principle flip an
argmin.  Parity is
pinned empirically on every registry scenario; if a live workload ever
manufactures such a near-tie, prefer the numpy backend.

The graceful-degrade cascades mirror the host policies:
  * utilization filter: drop loaded platforms unless that empties a row;
  * SLO feasibility: drop SLO-violating platforms unless that empties a
    row (per function).

``composite_decide`` additionally has a Pallas kernel variant fusing the
whole filter cascade + argmin in one VMEM-resident pass
(``composite_decide_pallas``); on TPU it runs compiled, elsewhere in
interpret mode.  Shapes are padded to (8, 128) tiles.  It is opt-in via
``set_use_pallas`` (the jnp path is faster at the tiny F x P of the FDN's
platform sets; the kernel exists for pod-scale platform registries).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INT = jnp.int32

# Filter-kill bitmask bits for the explain bundle.  Values mirror
# ``repro.core.scheduler.KILL_*`` (kernels must stay importable without
# the core package, so the literals are repeated here).
KILL_DEAD = 1    # platform failed / no replicas (alive mask)
KILL_UTIL = 2    # alive but dropped by the utilization filter
KILL_SLO = 4     # survived utilization but dropped by SLO feasibility

_use_pallas = False


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def set_use_pallas(enabled: bool) -> None:
    """Route ``composite_decide`` through the fused Pallas kernel."""
    global _use_pallas
    _use_pallas = bool(enabled)


def use_pallas() -> bool:
    return _use_pallas


# ---------------------------------------------------------------------------
# Shared argmin
# ---------------------------------------------------------------------------

def _masked_argmin(cost: jax.Array, mask: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """Row-wise argmin of ``where(mask, cost, inf)``; ok marks rows with
    at least one finite candidate.  First-lowest tie-break matches
    ``np.argmin`` over the host cost matrices."""
    masked = jnp.where(mask, cost, jnp.inf)
    finite = jnp.isfinite(masked)
    masked = jnp.where(finite, masked, jnp.inf)   # NaN -> inf, like host
    return (jnp.argmin(masked, axis=1).astype(_INT), finite.any(axis=1))


def _degrade(ok: jax.Array, fallback: jax.Array) -> jax.Array:
    """Per-row graceful degrade: rows where the filter left no candidate
    fall back to the unfiltered mask."""
    return jnp.where(ok.any(axis=1, keepdims=True), ok, fallback)


# ---------------------------------------------------------------------------
# Per-policy decisions (jit; shapes (F, P) compile once per shape)
# ---------------------------------------------------------------------------

@jax.jit
def perf_ranked_decide(exec_s, alive):
    """§5.1.1: fastest alive platform per function."""
    return _masked_argmin(exec_s, alive)


@jax.jit
def utilization_decide(exec_s, alive, unloaded):
    """§5.1.2: fastest among un-pressured platforms (degrade to alive)."""
    ok = _degrade(alive & unloaded[None, :], alive)
    return _masked_argmin(exec_s, ok)


@jax.jit
def locality_decide(exec_s, data_s, alive):
    """§5.1.4: execution + data-access seconds."""
    return _masked_argmin(exec_s + data_s, alive)


@jax.jit
def warm_decide(exec_s, data_s, warm_free, cold_start_s, alive):
    """Warm-pool-aware routing (repro.autoscale): execution + data-access
    seconds, plus the platform's cold-start penalty where the function has
    no idle warm replica standing by."""
    cold = jnp.where(warm_free > 0.0, 0.0, cold_start_s[None, :])
    return _masked_argmin(exec_s + data_s + cold, alive)


@jax.jit
def energy_decide(energy_j, p90_s, slo_s, alive):
    """§5.2: cheapest energy among SLO-feasible (degrade to alive)."""
    feasible = _degrade(alive & (p90_s <= slo_s[:, None]), alive)
    return _masked_argmin(energy_j, feasible)


@jax.jit
def composite_decide(exec_s, data_s, p90_s, energy_j, alive, unloaded,
                     slo_s, energy_weight):
    """The full SLOCompositePolicy cascade: utilization mask -> SLO
    feasibility -> locality-adjusted latency + energy tie-break."""
    ok = _degrade(alive & unloaded[None, :], alive)
    feasible = _degrade(ok & (p90_s <= slo_s[:, None]), ok)
    cost = (exec_s + data_s) + energy_weight * energy_j
    return _masked_argmin(cost, feasible)


# ---------------------------------------------------------------------------
# Explain bundle: decision + provenance in one fused pass
# ---------------------------------------------------------------------------

def _masked_argmin_explain(cost, mask):
    """``_masked_argmin`` plus the provenance extras: the runner-up
    (best feasible candidate excluding the winner, -1 when fewer than two
    are feasible) and the runner-up margin (inf in that case)."""
    masked = jnp.where(mask, cost, jnp.inf)
    finite = jnp.isfinite(masked)
    masked = jnp.where(finite, masked, jnp.inf)
    choice = jnp.argmin(masked, axis=1).astype(_INT)
    ok = finite.any(axis=1)
    ncols = masked.shape[1]
    col = jax.lax.broadcasted_iota(_INT, masked.shape, 1)
    rest = jnp.where(col == choice[:, None], jnp.inf, masked)
    runner = jnp.argmin(rest, axis=1).astype(_INT)
    best2 = rest.min(axis=1)
    chosen = jnp.take_along_axis(masked, choice[:, None], axis=1)[:, 0]
    margin = jnp.where(jnp.isfinite(best2), best2 - chosen, jnp.inf)
    runner = jnp.where(jnp.isfinite(best2), runner, -1)
    return choice, ok, runner, margin


@jax.jit
def composite_explain(exec_s, data_s, p90_s, energy_j, alive, unloaded,
                      slo_s, energy_weight):
    """``composite_decide`` returning the full explain bundle:

        (choice, ok, kill, runner, margin, cost)

    ``kill`` is a uint8 (F, P) filter-kill bitmask (KILL_DEAD / KILL_UTIL
    / KILL_SLO; 0 == feasible after graceful degrade), ``cost`` the
    unmasked score columns, ``runner``/``margin`` the runner-up platform
    and its cost gap.  Same cascade arithmetic as ``composite_decide`` —
    the host ``SLOCompositePolicy.cascade`` is the f64 parity oracle."""
    ok = _degrade(alive & unloaded[None, :], alive)
    feasible = _degrade(ok & (p90_s <= slo_s[:, None]), ok)
    cost = (exec_s + data_s) + energy_weight * energy_j
    kill = (jnp.where(~alive, KILL_DEAD, 0)
            | jnp.where(alive & ~ok, KILL_UTIL, 0)
            | jnp.where(ok & ~feasible, KILL_SLO, 0)).astype(jnp.uint8)
    choice, any_ok, runner, margin = _masked_argmin_explain(cost, feasible)
    return choice, any_ok, kill, runner, margin, cost


def _fused_composite(ewma_v, ewma_n, analytic_s, resp_h2, resp_n, data_s,
                     nodes, loaded_w, alive, unloaded, slo_s, energy_weight):
    """The whole admission step from raw estimator state: snapshot
    prediction columns (exec EWMA-vs-analytic gate, P90 marker-vs-bootstrap
    gate, energy from the platform power model), then the SLOComposite
    filter cascade + argmin on them.

    Arithmetic mirrors ``predict_matrix`` + ``composite_decide`` op for
    op (same operand association), so the only divergence from the NumPy
    oracle is the float32 compute width — covered by the same
    empirically-pinned near-tie caveat as the other cascades."""
    exec_s = jnp.where(ewma_n >= 3, ewma_v, analytic_s)
    p90_s = jnp.where(resp_n >= 10, resp_h2, exec_s * 1.5)
    energy_j = (exec_s * nodes[None, :]) * loaded_w[None, :]
    ok = _degrade(alive & unloaded[None, :], alive)
    feasible = _degrade(ok & (p90_s <= slo_s[:, None]), ok)
    cost = (exec_s + data_s) + energy_weight * energy_j
    return _masked_argmin(cost, feasible)


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


# ---------------------------------------------------------------------------
# Pallas variant: fused filter cascade + argmin in one kernel
# ---------------------------------------------------------------------------

def _composite_kernel(exec_ref, data_ref, p90_ref, wenergy_ref, alive_ref,
                      unloaded_ref, slo_ref, idx_ref, ok_ref):
    alive = alive_ref[...] > 0
    ok = alive & (unloaded_ref[...] > 0)
    ok = jnp.where(ok.any(axis=1, keepdims=True), ok, alive)
    feasible = ok & (p90_ref[...] <= slo_ref[...])
    feasible = jnp.where(feasible.any(axis=1, keepdims=True), feasible, ok)
    cost = (exec_ref[...] + data_ref[...]) + wenergy_ref[...]
    masked = jnp.where(feasible, cost, jnp.inf)
    row_min = masked.min(axis=1, keepdims=True)
    ncols = masked.shape[1]
    col = jax.lax.broadcasted_iota(_INT, masked.shape, 1)
    first = jnp.where(masked == row_min, col, ncols).min(
        axis=1, keepdims=True)
    idx_ref[...] = jnp.broadcast_to(first, idx_ref.shape)
    ok_ref[...] = jnp.broadcast_to(
        jnp.isfinite(row_min).astype(_INT), ok_ref.shape)


def _pad2(x, rows: int, cols: int, fill):
    f, p = x.shape
    return jnp.pad(x, ((0, rows - f), (0, cols - p)), constant_values=fill)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _composite_pallas(exec_s, data_s, p90_s, wenergy, alive, unloaded,
                      slo_s, *, interpret: bool):
    f, p = exec_s.shape
    fp = max(-(-f // 8) * 8, 8)           # sublane multiple
    pp = max(-(-p // 128) * 128, 128)     # lane multiple
    f32 = jnp.float32
    args = (_pad2(exec_s.astype(f32), fp, pp, 0.0),
            _pad2(data_s.astype(f32), fp, pp, 0.0),
            _pad2(p90_s.astype(f32), fp, pp, jnp.inf),
            _pad2(wenergy.astype(f32), fp, pp, 0.0),
            _pad2(alive.astype(_INT), fp, pp, 0),
            _pad2(jnp.broadcast_to(unloaded[None, :], (f, p)).astype(_INT),
                  fp, pp, 0),
            _pad2(jnp.broadcast_to(slo_s[:, None], (f, p)).astype(f32),
                  fp, pp, 0.0))
    idx, ok = pl.pallas_call(
        _composite_kernel,
        out_shape=(jax.ShapeDtypeStruct((fp, 128), _INT),
                   jax.ShapeDtypeStruct((fp, 128), _INT)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY
                               if interpret else pltpu.VMEM)] * 7,
        out_specs=(pl.BlockSpec(memory_space=pl.ANY
                                if interpret else pltpu.VMEM),) * 2,
        interpret=interpret,
    )(*args)
    return idx[:f, 0], ok[:f, 0] > 0


def composite_decide_pallas(exec_s, data_s, p90_s, energy_j, alive,
                            unloaded, slo_s, energy_weight,
                            interpret=None):
    """Pallas-fused SLOComposite decision; same contract (and the same
    first-lowest tie-break) as ``composite_decide``."""
    if interpret is None:
        interpret = not on_tpu()
    wenergy = jnp.asarray(energy_weight, jnp.float32) * \
        jnp.asarray(energy_j, jnp.float32)
    return _composite_pallas(jnp.asarray(exec_s), jnp.asarray(data_s),
                             jnp.asarray(p90_s), wenergy,
                             jnp.asarray(alive), jnp.asarray(unloaded),
                             jnp.asarray(slo_s), interpret=bool(interpret))


# ---------------------------------------------------------------------------
# Fully-fused Pallas variant: estimator gates + prediction columns +
# filter cascade + argmin in one VMEM-resident kernel
# ---------------------------------------------------------------------------

def _fused_composite_kernel(ewma_v_ref, ewma_n_ref, analytic_ref,
                            resp_h2_ref, resp_n_ref, data_ref, nodes_ref,
                            loadedw_ref, weight_ref, alive_ref,
                            unloaded_ref, slo_ref, idx_ref, ok_ref):
    exec_s = jnp.where(ewma_n_ref[...] >= 3, ewma_v_ref[...],
                       analytic_ref[...])
    p90 = jnp.where(resp_n_ref[...] >= 10, resp_h2_ref[...],
                    exec_s * 1.5)
    energy = (exec_s * nodes_ref[...]) * loadedw_ref[...]
    # Graceful degrade as boolean algebra: Mosaic cannot lower a select
    # between two bool vectors (an i8 -> i1 truncation), so
    # ``where(ok.any(1), ok, alive)`` is written ``ok | (alive & ~ok.any(1))``.
    alive = alive_ref[...] > 0
    ok = alive & (unloaded_ref[...] > 0)
    ok = ok | (alive & ~ok.any(axis=1, keepdims=True))
    feasible = ok & (p90 <= slo_ref[...])
    feasible = feasible | (ok & ~feasible.any(axis=1, keepdims=True))
    cost = (exec_s + data_ref[...]) + weight_ref[...] * energy
    masked = jnp.where(feasible, cost, jnp.inf)
    row_min = masked.min(axis=1, keepdims=True)
    ncols = masked.shape[1]
    col = jax.lax.broadcasted_iota(_INT, masked.shape, 1)
    first = jnp.where(masked == row_min, col, ncols).min(
        axis=1, keepdims=True)
    idx_ref[...] = jnp.broadcast_to(first, idx_ref.shape)
    ok_ref[...] = jnp.broadcast_to(
        jnp.isfinite(row_min).astype(_INT), ok_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_composite_pallas(ewma_v, ewma_n, analytic_s, resp_h2, resp_n,
                            data_s, nodes, loaded_w, weight, alive,
                            unloaded, slo_s, *, interpret: bool):
    f, p = analytic_s.shape
    fp = max(-(-f // 8) * 8, 8)           # sublane multiple
    pp = max(-(-p // 128) * 128, 128)     # lane multiple
    f32 = jnp.float32

    def row(v, fill):                      # (P,) vector -> padded (F, P)
        return _pad2(jnp.broadcast_to(v[None, :], (f, p)).astype(f32),
                     fp, pp, fill)

    args = (_pad2(ewma_v.astype(f32), fp, pp, 0.0),
            _pad2(ewma_n.astype(_INT), fp, pp, 0),
            _pad2(analytic_s.astype(f32), fp, pp, 0.0),
            _pad2(resp_h2.astype(f32), fp, pp, 0.0),
            _pad2(resp_n.astype(_INT), fp, pp, 0),
            _pad2(data_s.astype(f32), fp, pp, 0.0),
            row(nodes, 0.0), row(loaded_w, 0.0),
            _pad2(jnp.full((f, p), weight, f32), fp, pp, 0.0),
            _pad2(alive.astype(_INT), fp, pp, 0),
            _pad2(jnp.broadcast_to(unloaded[None, :], (f, p)).astype(_INT),
                  fp, pp, 0),
            _pad2(jnp.broadcast_to(slo_s[:, None], (f, p)).astype(f32),
                  fp, pp, -jnp.inf))
    idx, ok = pl.pallas_call(
        _fused_composite_kernel,
        out_shape=(jax.ShapeDtypeStruct((fp, 128), _INT),
                   jax.ShapeDtypeStruct((fp, 128), _INT)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY
                               if interpret else pltpu.VMEM)] * 12,
        out_specs=(pl.BlockSpec(memory_space=pl.ANY
                                if interpret else pltpu.VMEM),) * 2,
        interpret=interpret,
    )(*args)
    return idx[:f, 0], ok[:f, 0] > 0


def fused_composite_decide_pallas(ewma_v, ewma_n, analytic_s, resp_h2,
                                  resp_n, data_s, nodes, loaded_w, alive,
                                  unloaded, slo_s, energy_weight,
                                  interpret=None):
    """Pallas twin of ``fused_composite_decide``: raw estimator state in,
    (choice, ok) out, one kernel.  Padding columns carry slo = -inf so a
    padded platform can never look SLO-feasible."""
    if interpret is None:
        interpret = not on_tpu()
    return _fused_composite_pallas(
        jnp.asarray(ewma_v), jnp.asarray(ewma_n), jnp.asarray(analytic_s),
        jnp.asarray(resp_h2), jnp.asarray(resp_n), jnp.asarray(data_s),
        jnp.asarray(nodes), jnp.asarray(loaded_w),
        jnp.float32(energy_weight), jnp.asarray(alive),
        jnp.asarray(unloaded), jnp.asarray(slo_s),
        interpret=bool(interpret))
