"""RG-LRU linear recurrence h_t = a_t * h_{t-1} + b_t as a Pallas TPU
kernel.

Chunked formulation: the grid walks (batch, width-block, chunk); inside a
chunk the recurrence is rewritten in log-space prefix form
    h_t = exp(cumlog_a_t) * (h_0 + sum_{j<=t} b_j / exp(cumlog_a_j))
(a_t in (0,1] so log is safe), which is two cumulative ops + elementwise
math on the VPU — no sequential loop over time steps. The carry h across
chunks lives in f32 VMEM scratch, persisting across grid iterations along
the (last) chunk axis exactly like the SSD kernel's state.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-20


def _kernel(a_ref, b_ref, h_ref, carry_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    a = a_ref[...].astype(jnp.float32)              # (Q, W)
    b = b_ref[...].astype(jnp.float32)
    h0 = carry_ref[...]                             # (1, W)

    log_a = jnp.log(jnp.maximum(a, _EPS))
    cum = jnp.cumsum(log_a, axis=0)                 # (Q, W)
    # h_t = exp(cum_t) * (h0 + sum_{j<=t} b_j * exp(-cum_j))
    scaled_b = b * jnp.exp(-cum)
    prefix = jnp.cumsum(scaled_b, axis=0)
    h = jnp.exp(cum) * (h0 + prefix)
    h_ref[...] = h.astype(h_ref.dtype)
    carry_ref[...] = h[-1:, :]


@functools.partial(jax.jit, static_argnames=("chunk", "width_block",
                                             "interpret"))
def rglru_scan(a: jax.Array, b: jax.Array, *, chunk: int = 64,
               width_block: int = 128,
               interpret: bool) -> jax.Array:
    """a, b: (B, S, W) -> h: (B, S, W) with h_t = a_t*h_{t-1} + b_t."""
    bs, s, w = a.shape
    chunk = min(chunk, s)
    width_block = min(width_block, w)
    assert s % chunk == 0 and w % width_block == 0
    nc, nw = s // chunk, w // width_block

    kernel = functools.partial(_kernel, chunk=chunk)
    h = pl.pallas_call(
        kernel,
        grid=(bs, nw, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, width_block),
                         lambda bi, wi, ci: (bi, ci, wi)),
            pl.BlockSpec((None, chunk, width_block),
                         lambda bi, wi, ci: (bi, ci, wi)),
        ],
        out_specs=pl.BlockSpec((None, chunk, width_block),
                               lambda bi, wi, ci: (bi, ci, wi)),
        out_shape=jax.ShapeDtypeStruct((bs, s, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, width_block), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return h
