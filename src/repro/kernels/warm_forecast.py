"""Jitted warm-pool forecasting: the predictive prewarmer's fused
Holt-linear + gap-histogram tick compiled with ``jax.jit`` over the
columnar per-(function, platform) state (repro.autoscale.forecast).

One call advances every managed row: Holt level/trend smoothing of the
tick's arrival counts, inter-arrival-gap histogram scatter (one-hot — the
row count is tiny relative to a device pass), Little's-law desired-pool
sizing, and the gap-quantile keep-alive TTL.  The NumPy reference in
``repro.autoscale.forecast`` stays the small-state path and the parity
oracle: tests pin byte-identical prewarm decisions (desired pools and TTL ticks)
from both backends on seeded arrival streams.  Caveat mirrors
``policy_score``: without jax x64 this computes in float32 while the
oracle is float64 — a demand landing exactly on an integer in one
precision could in principle flip a ceil; parity is pinned empirically,
and the NumPy backend is preferred at the FDN's actual row counts anyway.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_INT = jnp.int32


@jax.jit
def predictive_tick(counts, level, trend, idle_ticks, hist, coeff,
                    alpha, beta, min_demand, max_pool, quantile,
                    default_ttl, min_ttl, max_ttl, min_gap_obs,
                    hold_thr):
    """Fused forecaster tick; returns the advanced state plus decisions:
    (level, trend, idle_ticks, hist, desired, ttl_ticks)."""
    pred = level + trend
    err = counts - pred
    new_level = pred + alpha * err
    new_trend = trend + (alpha * beta) * err

    active = counts > 0.0
    gap_closed = active & (idle_ticks > 0.0)
    bucket = jnp.clip(
        jnp.floor(jnp.log2(jnp.maximum(idle_ticks, 1.0))).astype(_INT),
        0, hist.shape[1] - 1)
    onehot = (jax.lax.broadcasted_iota(_INT, hist.shape, 1)
              == bucket[:, None]) & gap_closed[:, None]
    new_hist = hist + onehot.astype(hist.dtype)
    new_idle = jnp.where(active, 0.0, idle_ticks + 1.0)

    rate = jnp.maximum(new_level + new_trend, 0.0)
    hold = (rate >= hold_thr).astype(counts.dtype)   # warm floor of one
    desired = jnp.clip(jnp.maximum(jnp.ceil(rate * coeff - min_demand),
                                   hold), 0.0, max_pool)

    total = new_hist.sum(axis=1)
    cum = jnp.cumsum(new_hist, axis=1)
    b = jnp.argmax(cum >= (quantile * total)[:, None], axis=1)
    ttl = jnp.exp2(b + 1.0)
    ttl = jnp.where(total >= min_gap_obs, ttl, default_ttl)
    ttl = jnp.clip(ttl, min_ttl, max_ttl)
    return new_level, new_trend, new_idle, new_hist, desired, ttl
