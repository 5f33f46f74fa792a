"""Flash attention (prefill/train) as a Pallas TPU kernel.

TPU adaptation of the classic algorithm: the grid walks (batch, kv_head,
q_block); each program holds one q block in VMEM, streams k/v blocks of the
same kv head through VMEM with `pl.ds`, and keeps the online-softmax
accumulators (m, l, acc) in f32 VMEM scratch. Block sizes default to
MXU-aligned (128) multiples; causal + sliding-window masks are applied from
block-relative iotas so no (S, T) mask is ever materialized.

GQA layout note: q arrives as (B, KH, G*Bq?, ...) — we fold the group dim
into the q rows (rows = G * q_block) so the MXU sees a tall skinny matmul,
which is the TPU-native way to exploit grouped queries sharing one kv head.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, kv_block: int, q_block: int,
            causal: bool, window: Optional[int], scale: float,
            seq_q: int, seq_kv: int, groups: int):
    """One (b, kh, qi) program. Shapes inside:
    q_ref: (q_block*G, D); k_ref/v_ref: (T, D); o_ref: (q_block*G, D)."""
    qi = pl.program_id(2)
    d = q_ref.shape[-1]
    rows = q_ref.shape[0]                       # q_block * groups
    q = q_ref[...].astype(jnp.float32) * scale

    m = jnp.full((rows, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((rows, 1), jnp.float32)
    acc = jnp.zeros((rows, d), jnp.float32)

    q_pos = qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) // groups      # row -> q position

    n_kv = seq_kv // kv_block

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(ki * kv_block, kv_block), :].astype(jnp.float32)
        v = v_ref[pl.ds(ki * kv_block, kv_block), :].astype(jnp.float32)
        s = q @ k.T                             # (rows, kv_block)
        k_pos = ki * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_block), 1)
        ok = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window is not None:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + p @ v
        return m_new, l, acc

    if causal:
        # only kv blocks that intersect the causal triangle for this q block
        hi = jnp.minimum(((qi + 1) * q_block + kv_block - 1) // kv_block,
                         n_kv)
    else:
        hi = n_kv
    lo = 0
    if window is not None:
        lo = jnp.maximum((qi * q_block - window) // kv_block, 0)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m, l, acc))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "kv_block", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_block: int = 128, kv_block: int = 128,
                    interpret: bool) -> jax.Array:
    """q: (B,Sq,H,D); k,v: (B,T,KH,D) -> (B,Sq,H,D)."""
    b, sq, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    q_block = min(q_block, sq)
    kv_block = min(kv_block, t)
    assert sq % q_block == 0 and t % kv_block == 0, (sq, q_block, t, kv_block)
    nq = sq // q_block

    # (B,Sq,H,D) -> (B,KH, Sq*G, D) rows grouped as (q position, group)
    qr = q.reshape(b, sq, kh, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, kh, sq * g, d)
    kr = k.transpose(0, 2, 1, 3)                 # (B,KH,T,D)
    vr = v.transpose(0, 2, 1, 3)

    rows = q_block * g
    kernel = functools.partial(
        _kernel, kv_block=kv_block, q_block=q_block, causal=causal,
        window=window, scale=d ** -0.5, seq_q=sq, seq_kv=t, groups=g)

    out = pl.pallas_call(
        kernel,
        grid=(b, kh, nq),
        in_specs=[
            pl.BlockSpec((None, None, rows, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, t, d),
                         lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, t, d),
                         lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, rows, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, sq * g, d), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)

    return out.reshape(b, kh, sq, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, sq, h, d)
