"""Mixture-of-Experts block (mixtral-8x7b top-2, dbrx top-4).

TPU-native capacity-based dispatch: tokens are grouped (one group per batch
row), routed with top-k, and dispatched to experts through one-hot einsums —
the all-to-all pattern XLA SPMD lowers for expert parallelism. Experts shard
over the "model" axis when the expert count divides it (dbrx: 16/16); when it
does not (mixtral: 8), the sharding rules fall back to tensor-parallel
experts (per-expert d_ff over "model") automatically.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import Spec
from repro.sharding import constrain


def moe_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": Spec((d, e), ("embed", "experts")),
        "wi": Spec((e, d, f), ("experts", "embed", "expert_mlp")),
        "wg": Spec((e, d, f), ("experts", "embed", "expert_mlp")),
        "wo": Spec((e, f, d), ("experts", "expert_mlp", "embed")),
    }


def _capacity(cfg: ModelConfig, group_tokens: int) -> int:
    cap = int(group_tokens * cfg.top_k * cfg.capacity_factor
              // cfg.n_experts)
    return max(cap, cfg.top_k)


def moe_block(cfg: ModelConfig, p: Dict, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_load_balance_loss). Groups = batch rows."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(cfg, s)

    gate_logits = (x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(gate_logits, axis=-1)             # (B,S,E)
    top_p, top_i = jax.lax.top_k(probs, k)                   # (B,S,k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)   # renormalize

    # Load-balancing auxiliary loss (Switch/Mixtral style).
    me = jnp.mean(probs, axis=(0, 1))                               # (E,)
    ce = jnp.mean(jax.nn.one_hot(top_i[..., 0], e, dtype=jnp.float32),
                  axis=(0, 1))
    aux = cfg.router_aux_coef * e * jnp.sum(me * ce)

    if cfg.moe_impl == "sorted":
        y = _sorted_dispatch(cfg, p, x, top_p, top_i, cap)
        return y, aux
    if cfg.moe_impl == "sorted_shmap":
        return _sorted_shard_map(cfg, p, x)

    # Position of each (token, choice) inside its expert's buffer.
    onehot = jax.nn.one_hot(top_i, e, dtype=jnp.int32)        # (B,S,k,E)
    flat = onehot.reshape(b, s * k, e)
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat           # (B,S*k,E)
    pos_in_expert = pos_in_expert.reshape(b, s, k, e)
    within_cap = pos_in_expert < cap

    # dispatch: (B,S,E,C) one-hot; combine carries the gate weight.
    slot_oh = jax.nn.one_hot(pos_in_expert, cap, dtype=x.dtype)   # (B,S,k,E,C)
    sel = (onehot.astype(x.dtype) * within_cap.astype(x.dtype))[..., None]
    dispatch = jnp.sum(slot_oh * sel, axis=2)                     # (B,S,E,C)
    combine = jnp.sum(slot_oh * sel * top_p[..., None, None].astype(x.dtype),
                      axis=2)                                     # (B,S,E,C)

    xe = jnp.einsum("bsd,bsec->ebcd", x, dispatch)                # (E,B,C,D)
    xe = constrain(xe, "experts", "batch", None, "embed")
    h = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", xe, p["wi"]))
    h = h * jnp.einsum("ebcd,edf->ebcf", xe, p["wg"])
    h = constrain(h, "experts", "batch", None, "expert_mlp")
    ye = jnp.einsum("ebcf,efd->ebcd", h, p["wo"])                 # (E,B,C,D)
    y = jnp.einsum("ebcd,bsec->bsd", ye, combine)
    return y, aux


# ---------------------------------------------------------------------------
# §Perf: sort-based dispatch — O(T·D) data movement instead of O(T·E·C·D)
# one-hot matmuls. Same group-local capacity/drop semantics as the einsum
# path (stable sort preserves token order within an expert).
# ---------------------------------------------------------------------------


def _group_sorted(cfg: ModelConfig, wi, wg, wo, xg, pg, ig, cap: int,
                  psum_axis=None):
    """One group's sorted dispatch. xg: (S,D); pg/ig: (S,k) -> (S,D).

    When the per-expert ffn dim is model-sharded (wi: (E,D,F_loc)), the
    caller passes psum_axis and the partial wo contraction is psum'ed.
    """
    s, d = xg.shape
    e, k = cfg.n_experts, cfg.top_k
    n = s * k
    gate = pg.reshape(n)
    expert = ig.reshape(n)
    tok = jnp.repeat(jnp.arange(s), k)
    order = jnp.argsort(expert, stable=True)          # (n,)
    se, st, sg = expert[order], tok[order], gate[order]
    seg_start = jnp.searchsorted(se, jnp.arange(e), side="left")
    pos = jnp.arange(n) - seg_start[se]
    slot = jnp.where(pos < cap, se * cap + pos, e * cap)   # drop -> tail
    buf = jnp.zeros((e * cap + 1, d), xg.dtype)
    buf = buf.at[slot].set(xg[st])
    xe = buf[:e * cap].reshape(e, cap, d)             # (E,C,D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wi))
    h = h * jnp.einsum("ecd,edf->ecf", xe, wg)
    ye = jnp.einsum("ecf,efd->ecd", h, wo).reshape(e * cap, d)
    ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)])
    out_choice = ye[slot] * sg[:, None].astype(ye.dtype)
    y = jnp.zeros((s, d), xg.dtype).at[st].add(out_choice)
    if psum_axis is not None:
        y = jax.lax.psum(y, psum_axis)
    return y


def _sorted_shard_map(cfg: ModelConfig, p: Dict, x: jax.Array):
    """§Perf: sorted dispatch under shard_map — every scatter/gather runs
    shard-LOCAL on the data-parallel shard, so GSPMD can never decide to
    replicate the dispatch buffers (the failure mode of the plain vmap
    version: an all-gathered f32[B, E*C, D] buffer on every device).

    Requires the mixtral-style layout (experts replicated, per-expert ffn
    dim sharded over "model"); falls back to the vmap path without a mesh
    or when the batch does not divide the dp axes.
    """
    from jax.sharding import PartitionSpec as P
    from repro import sharding as shd

    mesh = shd.current_mesh()
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(cfg, s)
    dp = shd.dp_axes(mesh) if mesh is not None else ()
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    experts_sharded = (mesh is not None and e % mesh.shape.get("model", 1)
                       == 0 and mesh.shape.get("model", 1) > 1)
    if mesh is None or b % max(dp_size, 1) != 0 or experts_sharded:
        # no mesh / ragged batch / EP layout: plain paths handle it
        gate_logits = (x.astype(jnp.float32)
                       @ p["router"].astype(jnp.float32))
        probs = jax.nn.softmax(gate_logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=(0, 1))
        ce = jnp.mean(jax.nn.one_hot(top_i[..., 0], e, dtype=jnp.float32),
                      axis=(0, 1))
        aux = cfg.router_aux_coef * e * jnp.sum(me * ce)
        return _sorted_dispatch(cfg, p, x, top_p, top_i, cap), aux

    def local(xl, router, wi, wg, wo):
        gate_logits = xl.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(gate_logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = (top_p / jnp.sum(top_p, axis=-1, keepdims=True)
                 ).astype(xl.dtype)
        me = jnp.mean(probs, axis=(0, 1))
        ce = jnp.mean(jax.nn.one_hot(top_i[..., 0], e, dtype=jnp.float32),
                      axis=(0, 1))
        aux_l = cfg.router_aux_coef * e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux_l, dp) if dp else aux_l
        y = jax.vmap(lambda xg, pg, ig: _group_sorted(
            cfg, wi, wg, wo, xg, pg, ig, cap))(xl, top_p, top_i)
        if "model" in mesh.axis_names:
            y = jax.lax.psum(y, "model")
        return y, aux

    wspec = P(None, None, "model")
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None), wspec, wspec,
                  P(None, "model", None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    return out


def _sorted_dispatch(cfg: ModelConfig, p: Dict, x: jax.Array,
                     top_p: jax.Array, top_i: jax.Array,
                     cap: int) -> jax.Array:
    return jax.vmap(lambda xg, pg, ig: _group_sorted(
        cfg, p["wi"], p["wg"], p["wo"], xg, pg, ig, cap))(
            x, top_p.astype(x.dtype), top_i)
