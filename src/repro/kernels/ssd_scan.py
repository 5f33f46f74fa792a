"""Mamba-2 SSD chunked forward as a Pallas TPU kernel.

The grid walks (batch*head-block, n_chunks); the chunk axis is the LAST grid
dimension, so TPU grid iteration order lets the inter-chunk SSM state live
in f32 VMEM scratch and carry across chunk programs — the sequential state
pass becomes free (no HBM round-trip per chunk). Intra-chunk work is two
dense matmuls (C·B^T decay-weighted, and the state in/out projections) that
map onto the MXU — this is the "state-space duality" insight restated for
TPU: quadratic-in-chunk attention-like compute + linear state recurrence.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, fin_ref, state_ref,
            *, chunk: int, nheads: int):
    """One (bh, ci) program.

    x_ref: (chunk, P); dt_ref: (chunk, 1); a_ref: (1, 1); b_ref/c_ref:
    (chunk, N); y_ref: (chunk, P); fin_ref: (P, N) final state output;
    state_ref: (P, N) f32 scratch carrying the running state.
    """
    ci = pl.program_id(1)
    n_chunks = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)                  # (Q,P)
    dt = dt_ref[...].astype(jnp.float32)                # (Q,1)
    a = a_ref[0, 0].astype(jnp.float32)                 # scalar (<0)
    bm = b_ref[...].astype(jnp.float32)                 # (Q,N)
    cm = c_ref[...].astype(jnp.float32)                 # (Q,N)

    da = dt * a                                         # (Q,1)
    cum = jnp.cumsum(da, axis=0)                        # (Q,1)
    total = cum[-1, 0]

    # ---- intra-chunk (quadratic, MXU) ----
    li = cum                                            # (Q,1)
    lj = cum.T                                          # (1,Q)
    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(iq >= jq, jnp.exp(li - lj), 0.0)      # (Q,Q)
    cb = cm @ bm.T                                      # (Q,Q)
    w = cb * L * dt.T                                   # weight over j
    y = w @ x                                           # (Q,P)

    # ---- contribution of the incoming state ----
    state = state_ref[...]                              # (P,N)
    y += (cm @ state.T) * jnp.exp(cum)                  # (Q,N)@(N,P)->(Q,P)

    # ---- state update for the next chunk ----
    decay_to_end = jnp.exp(total - cum)                 # (Q,1)
    xdt = x * (dt * decay_to_end)                       # (Q,P)
    new_state = state * jnp.exp(total) + xdt.T @ bm     # (P,N)
    state_ref[...] = new_state

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _fin():
        fin_ref[...] = new_state.astype(fin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 64,
             interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,G,N).

    Returns (y: (B,S,H,P), final_state: (B,H,P,N)). G must divide H.
    """
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    hpg = h // g
    assert s % chunk == 0
    nc = s // chunk

    # lay out as (B*H, S, ...) with heads sharing their group's B/C
    xr = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtr = dt.transpose(0, 2, 1).reshape(b * h, s, 1)
    ar = jnp.repeat(A.reshape(1, h), b, axis=0).reshape(b * h, 1, 1)
    Br = jnp.repeat(Bm.transpose(0, 2, 1, 3), hpg, axis=1).reshape(
        b * h, s, n)
    Cr = jnp.repeat(Cm.transpose(0, 2, 1, 3), hpg, axis=1).reshape(
        b * h, s, n)

    kernel = functools.partial(_kernel, chunk=chunk, nheads=h)
    y, fin = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, p), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((None, chunk, 1), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((None, 1, 1), lambda i, ci: (i, 0, 0)),
            pl.BlockSpec((None, chunk, n), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((None, chunk, n), lambda i, ci: (i, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, p), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((None, p, n), lambda i, ci: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xr, dtr, ar, Br, Cr)

    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    fin = fin.reshape(b, h, p, n)
    return y, fin
