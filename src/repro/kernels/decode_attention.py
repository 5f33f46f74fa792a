"""Flash-decode (split-K) attention for single-token decode over long KV
caches, as a Pallas TPU kernel.

The cache sequence axis is cut into `splits` segments; the grid walks
(batch, kv_head, split) and each program reduces its segment with online
softmax, emitting partial (max, sumexp, weighted-acc) triples. The cheap
cross-split combine runs in the jit'd wrapper (ops-level), mirroring how the
sequence-sharded decode path combines partial softmax across the "model"
mesh axis — the kernel is the single-chip version of that same pattern.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, len_ref, m_ref, l_ref, acc_ref, *,
            split_len: int, kv_block: int, scale: float):
    """One (b, kh, split). q_ref: (G,D); k/v_ref: (split_len, D);
    len_ref: (1,1) valid length for this row; outputs per split."""
    si = pl.program_id(2)
    g, d = q_ref.shape
    q = q_ref[...].astype(jnp.float32) * scale
    valid_len = len_ref[0, 0]                      # global valid prefix

    m = jnp.full((g, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((g, 1), jnp.float32)
    acc = jnp.zeros((g, d), jnp.float32)

    base = si * split_len
    n_blocks = split_len // kv_block

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(ki * kv_block, kv_block), :].astype(jnp.float32)
        v = v_ref[pl.ds(ki * kv_block, kv_block), :].astype(jnp.float32)
        s = q @ k.T                                # (G, kv_block)
        k_pos = base + ki * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_block), 1)
        s = jnp.where(k_pos < valid_len, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + p @ v
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m, l, acc))
    m_ref[...] = m
    l_ref[...] = l
    acc_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("splits", "kv_block",
                                             "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, splits: int = 4,
                     kv_block: int = 128,
                     interpret: bool) -> jax.Array:
    """q: (B,H,D); k,v: (B,T,KH,D); lengths: (B,). Returns (B,H,D)."""
    b, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    while t % (splits * kv_block) and splits > 1:
        splits -= 1
    kv_block = min(kv_block, t // splits)
    assert t % splits == 0 and (t // splits) % kv_block == 0
    split_len = t // splits

    qr = q.reshape(b, kh, g, d)
    kr = k.transpose(0, 2, 1, 3)                  # (B,KH,T,D)
    vr = v.transpose(0, 2, 1, 3)
    lens = lengths.astype(jnp.int32).reshape(b, 1, 1)

    kernel = functools.partial(_kernel, split_len=split_len,
                               kv_block=kv_block, scale=d ** -0.5)
    m, l, acc = pl.pallas_call(
        kernel,
        grid=(b, kh, splits),
        in_specs=[
            pl.BlockSpec((None, None, g, d),
                         lambda bi, hi, si: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, split_len, d),
                         lambda bi, hi, si: (bi, hi, si, 0)),
            pl.BlockSpec((None, None, split_len, d),
                         lambda bi, hi, si: (bi, hi, si, 0)),
            pl.BlockSpec((None, 1, 1), lambda bi, hi, si: (bi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, None, g, 1),
                         lambda bi, hi, si: (bi, hi, si, 0, 0)),
            pl.BlockSpec((None, None, None, g, 1),
                         lambda bi, hi, si: (bi, hi, si, 0, 0)),
            pl.BlockSpec((None, None, None, g, d),
                         lambda bi, hi, si: (bi, hi, si, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, splits, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kh, splits, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kh, splits, g, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, lens)

    # cross-split combine (tiny): renormalize partials by the global max
    m_g = jnp.max(m, axis=2, keepdims=True)               # (B,KH,1,G,1)
    w = jnp.exp(m - m_g)
    l_g = jnp.sum(l * w, axis=2)                          # (B,KH,G,1)
    acc_g = jnp.sum(acc * w, axis=2)                      # (B,KH,G,D)
    out = acc_g / jnp.maximum(l_g, 1e-30)
    return out.reshape(b, h, d).astype(q.dtype)
