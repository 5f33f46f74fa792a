"""Decoder-only transformer families: dense (qwen3/yi/llama3), MoE
(mixtral/dbrx) and VLM (phi-3-vision backbone; stub image frontend).

Layers are stacked and scanned (``lax.scan``) so HLO size and compile time
are O(1) in depth. Decode uses either a full-length KV cache (dense archs)
or a rolling window buffer (SWA archs) — both position-mask based.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MOE, VLM
from repro.models import layers as nn
from repro.models import moe as moe_mod
from repro.models.params import Spec, stack
from repro.sharding import constrain

# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------


def attn_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    out: Dict[str, Any] = {
        "wq": Spec((d, cfg.q_dim), ("embed", "heads")),
        "wk": Spec((d, cfg.kv_dim), ("embed", "kv")),
        "wv": Spec((d, cfg.kv_dim), ("embed", "kv")),
        "wo": Spec((cfg.q_dim, d), ("heads", "embed")),
    }
    if cfg.qk_norm:
        out["q_norm"] = Spec((cfg.head_dim,), (None,), "zeros")
        out["k_norm"] = Spec((cfg.head_dim,), (None,), "zeros")
    return out


def mlp_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "wi": Spec((d, f), ("embed", "mlp")),
        "wg": Spec((d, f), ("embed", "mlp")),
        "wo": Spec((f, d), ("mlp", "embed")),
    }


def layer_specs(cfg: ModelConfig) -> Dict[str, Any]:
    out = {
        "ln1": Spec((cfg.d_model,), ("embed",), "zeros"),
        "ln2": Spec((cfg.d_model,), ("embed",), "zeros"),
        "attn": attn_specs(cfg),
    }
    if cfg.family == MOE:
        out["moe"] = moe_mod.moe_specs(cfg)
    else:
        out["mlp"] = mlp_specs(cfg)
    return out


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    out = {
        "embed": Spec((cfg.vocab_size, d), ("vocab", "embed"), "normal", 0.7),
        "layers": stack(cfg.num_layers, layer_specs(cfg)),
        "final_norm": Spec((d,), ("embed",), "zeros"),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = Spec((d, cfg.vocab_size), ("embed", "vocab"))
    return out


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _project_qkv(cfg: ModelConfig, p: Dict, h: jax.Array, positions):
    b, s, _ = h.shape
    q = (h @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (h @ p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = nn.qk_norm(q, p["q_norm"])
        k = nn.qk_norm(k, p["k_norm"])
    q = nn.apply_rope(q, positions, cfg.rope_theta)
    k = nn.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_block(cfg: ModelConfig, p: Dict, x: jax.Array,
               positions: jax.Array) -> Tuple[jax.Array, Tuple]:
    """Self-attention over the in-context sequence (train / prefill)."""
    h = nn.rmsnorm(x, p["ln1"])
    q, k, v = _project_qkv(cfg, p["attn"], h, positions)
    q = constrain(q, "batch", None, "heads", None)
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        blk = min(128, q.shape[1])
        ctx = kops.flash_attention(q, k, v, causal=cfg.causal,
                                   window=cfg.sliding_window,
                                   q_block=blk, kv_block=blk)
    else:
        ctx = nn.chunked_attention(q, k, v, causal=cfg.causal,
                                   window=cfg.sliding_window,
                                   q_chunk=cfg.attn_q_chunk,
                                   unroll=cfg.unroll_scans)
    b, s, _, _ = ctx.shape
    out = ctx.reshape(b, s, cfg.q_dim) @ p["attn"]["wo"]
    return x + out, (k, v)


def ffn_block(cfg: ModelConfig, p: Dict, x: jax.Array) -> Tuple[jax.Array,
                                                                jax.Array]:
    h = nn.rmsnorm(x, p["ln2"])
    if cfg.family == MOE:
        out, aux = moe_mod.moe_block(cfg, p["moe"], h)
    else:
        out = nn.gated_mlp(h, **p["mlp"])
        aux = jnp.zeros((), jnp.float32)
    return x + out, aux


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def embed_inputs(cfg: ModelConfig, params: Dict, batch: Dict) -> jax.Array:
    tok = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.family == VLM:
        img = batch["image_embeds"].astype(tok.dtype)       # (B, Nimg, D)
        tok = jnp.concatenate([img, tok], axis=1)
    return constrain(tok, "batch", None, "embed")


def forward_hidden(cfg: ModelConfig, params: Dict, embeds: jax.Array, *,
                   collect_kv: bool = False, remat: bool = False):
    """Run the layer stack. Returns (hidden, kv_stack|None, aux_loss)."""
    b, s, _ = embeds.shape
    positions = jnp.arange(s)

    def body(x, p):
        x, kv = attn_block(cfg, p, x, positions)
        x, aux = ffn_block(cfg, p, x)
        seq_ax = "seq_sp" if cfg.seq_parallel else None
        x = constrain(x, "batch", seq_ax, "embed")
        return x, ((kv if collect_kv else None), aux)

    fn = _remat(cfg, body) if remat else body
    if cfg.scan_layers:
        x, (kvs, auxs) = jax.lax.scan(fn, embeds, params["layers"],
                                      unroll=cfg.unroll_scans)
        aux = jnp.sum(auxs)
    else:
        x, kvs_l, aux = embeds, [], jnp.zeros((), jnp.float32)
        leaves = jax.tree_util.tree_map(lambda a: list(a), params["layers"])
        for i in range(cfg.num_layers):
            p_i = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            x, (kv, a) = fn(x, p_i)
            kvs_l.append(kv)
            aux = aux + a
        kvs = (jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *kvs_l)
               if collect_kv else None)
    x = nn.rmsnorm(x, params["final_norm"])
    return x, kvs, aux


def logits_fn(cfg: ModelConfig, params: Dict, h: jax.Array) -> jax.Array:
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    out = h @ head
    return constrain(out, "batch", None, "vocab")


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------


def cache_capacity(cfg: ModelConfig, context_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(cfg.sliding_window, context_len + 128)
    return context_len + 128


def cache_specs(cfg: ModelConfig, batch_size: int,
                context_len: int) -> Dict[str, Any]:
    """Declarative cache layout (Spec tree) — reused by input_specs().

    ``pos`` is PER ROW (B,), which is what allows the serving engine to run
    continuous batching (each slot at its own decode position).
    """
    cap = cache_capacity(cfg, context_len)
    seq_ax = "kv_seq" if cfg.decode_seq_shard else None
    kv = Spec((cfg.num_layers, batch_size, cap, cfg.n_kv_heads, cfg.head_dim),
              ("layers", "batch", seq_ax, None, None), "zeros")
    return {
        "k": kv,
        "v": kv,
        "k_pos": Spec((batch_size, cap), ("batch", None), "zeros"),
        "pos": Spec((batch_size,), ("batch",), "zeros"),
    }


def init_cache(cfg: ModelConfig, batch_size: int, context_len: int) -> Dict:
    from repro.models import params as pm
    tree = cache_specs(cfg, batch_size, context_len)
    cache = pm.tree_map(lambda s: jnp.zeros(s.shape, jnp.bfloat16), tree)
    cache["k_pos"] = jnp.full(tree["k_pos"].shape, -1, jnp.int32)
    cache["pos"] = jnp.zeros(tree["pos"].shape, jnp.int32)
    return cache


def pack_cache(stack: jax.Array, lens: jax.Array, cap: int) -> jax.Array:
    """Per-row gather of the last min(len_i, cap) entries of a (B,S,...) kv
    stack into a (B,cap,...) cache, right-padded prompts supported."""
    b, s = stack.shape[0], stack.shape[1]
    start = jnp.maximum(lens - cap, 0)                     # (B,)
    idx = start[:, None] + jnp.arange(cap)[None, :]        # (B,cap)
    idx = jnp.minimum(idx, s - 1)
    return jnp.take_along_axis(
        stack, idx.reshape(b, cap, *([1] * (stack.ndim - 2))), axis=1)


def prefill(cfg: ModelConfig, params: Dict, batch: Dict,
            context_len: Optional[int] = None):
    """Process the prompt; return (last-token logits, populated cache).

    ``batch["prompt_lens"]`` (B,) optionally marks right-padded prompts;
    defaults to the full sequence length for every row.
    """
    embeds = embed_inputs(cfg, params, batch)
    b, s, _ = embeds.shape
    context_len = context_len if context_len is not None else s
    raw_lens = batch.get("prompt_lens")
    lens = (jnp.full((b,), s, jnp.int32) if raw_lens is None
            else raw_lens.astype(jnp.int32))
    h, kvs, _ = forward_hidden(cfg, params, embeds, collect_kv=True)
    cache = init_cache(cfg, b, context_len)
    cap = cache["k"].shape[2]
    k_stack, v_stack = kvs                      # (L,B,S,KH,Dh)
    if raw_lens is None:
        # uniform prompt lengths (the pod-scale path): static slices only —
        # per-row gathers on a kv_seq-sharded cache force the SPMD
        # partitioner into full rematerialization.
        logits = logits_fn(cfg, params, h[:, -1:, :])
        keep = min(s, cap)
        cache["k"] = cache["k"].at[:, :, :keep].set(k_stack[:, :, s - keep:])
        cache["v"] = cache["v"].at[:, :, :keep].set(v_stack[:, :, s - keep:])
        pos = jnp.arange(s - keep, s, dtype=jnp.int32)
        cache["k_pos"] = cache["k_pos"].at[:, :keep].set(pos[None, :])
    else:
        # ragged prompts (serving engine): per-row gather
        last = jnp.take_along_axis(h, (lens - 1)[:, None, None], axis=1)
        logits = logits_fn(cfg, params, last)
        vm = jax.vmap(pack_cache, in_axes=(0, None, None))  # over layers
        cache["k"] = vm(k_stack, lens, cap)
        cache["v"] = vm(v_stack, lens, cap)
        start = jnp.maximum(lens - cap, 0)
        k_pos = start[:, None] + jnp.arange(cap)[None, :]
        cache["k_pos"] = jnp.where(k_pos < lens[:, None], k_pos,
                                   -1).astype(jnp.int32)
    cache["pos"] = lens
    return logits, cache


# ---------------------------------------------------------------------------
# §Perf: shard_mapped split-K flash decode.
#
# The GSPMD path updates the sequence-sharded cache with a masked select
# (a full read+write of the cache every step) and lets the partitioner pick
# the attention schedule. Under shard_map each "model" shard owns one cache
# slice: the token write is a LOCAL per-row scatter (no SPMD involvement),
# attention reduces its slice with online-softmax partials, and a tiny
# pmax/psum combine (the Pallas decode_attention kernel's split-K pattern
# lifted to the mesh) produces the context.
# ---------------------------------------------------------------------------


def _flash_decode_shmap(q, kc, vc, k_new, v_new, slot, pos, mesh):
    """q: (B,1,H,Dh); kc/vc: (B,T,KH,Dh) seq-sharded over "model";
    k_new/v_new: (B,1,KH,Dh); slot/pos: (B,). Returns (ctx, kc, vc).

    Only used for full (non-rolling) caches, where slot index == position.
    """
    from jax.sharding import PartitionSpec as P
    from repro import sharding as shd

    dp = shd.dp_axes(mesh)
    b, _, h, dh = q.shape
    kh = kc.shape[2]
    g = h // kh
    scale = dh ** -0.5

    def local(q, kc, vc, k_new, v_new, slot, pos):
        b_loc, t_loc = kc.shape[0], kc.shape[1]
        off = jax.lax.axis_index("model") * t_loc
        rows = jnp.arange(b_loc)
        slot_loc = slot - off
        own = (slot_loc >= 0) & (slot_loc < t_loc)
        idx = jnp.clip(slot_loc, 0, t_loc - 1)
        upd_k = jnp.where(own[:, None, None], k_new[:, 0], kc[rows, idx])
        upd_v = jnp.where(own[:, None, None], v_new[:, 0], vc[rows, idx])
        kc = kc.at[rows, idx].set(upd_k)
        vc = vc.at[rows, idx].set(upd_v)
        j = off + jnp.arange(t_loc)[None, :]                  # (1,T_loc)
        valid = j <= pos[:, None]                             # (B,T_loc)
        qr = q.reshape(b_loc, kh, g, dh)
        s = jnp.einsum("bkgd,btkd->bkgt", qr, kc,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)                # (B,KH,G,1)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum("bkgt,btkd->bkgd", p.astype(vc.dtype), vc)
        m_g = jax.lax.pmax(m, "model")
        w = jnp.exp(m - m_g)                                  # (B,KH,G,1)
        l_g = jax.lax.psum(l * w, "model")
        acc_g = jax.lax.psum(acc.astype(jnp.float32) * w, "model")
        out = acc_g / jnp.maximum(l_g, 1e-30)
        return out.reshape(b_loc, 1, h, dh).astype(q.dtype), kc, vc

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, "model", None, None),
                  P(dp, "model", None, None), P(dp, None, None, None),
                  P(dp, None, None, None), P(dp), P(dp)),
        out_specs=(P(dp, None, None, None), P(dp, "model", None, None),
                   P(dp, "model", None, None)),
        check_vma=False,
    )(q, kc, vc, k_new, v_new, slot, pos)


def decode_step(cfg: ModelConfig, params: Dict, cache: Dict, batch: Dict):
    """One token for every row. batch: {"token": (B,1)}. Rows may sit at
    different positions (continuous batching)."""
    tok = batch["token"]
    x = jnp.take(params["embed"], tok, axis=0)          # (B,1,D)
    b = x.shape[0]
    pos = cache["pos"]                                   # (B,)
    positions = pos[:, None]
    cap = cache["k"].shape[2]
    slot = (pos % cap).astype(jnp.int32)                 # (B,)
    window = cfg.sliding_window
    k_pos = jnp.where(jnp.arange(cache["k_pos"].shape[1])[None, :]
                  == slot[:, None], pos[:, None], cache["k_pos"])

    from repro.sharding import current_mesh
    mesh = current_mesh()
    use_shmap = (cfg.decode_impl == "shmap_flash" and mesh is not None
                 and "model" in mesh.axis_names and window is None
                 and cfg.decode_seq_shard
                 and cap % mesh.shape["model"] == 0)

    def body(x, args):
        p, kc, vc = args
        h = nn.rmsnorm(x, p["ln1"])
        q, k, v = _project_qkv(cfg, p["attn"], h, positions)
        if use_shmap:
            ctx, kc, vc = _flash_decode_shmap(q, kc, vc, k, v, slot, pos,
                                              mesh)
        else:
            kc = nn.masked_cache_update(kc, k, slot)
            vc = nn.masked_cache_update(vc, v, slot)
            ctx = nn.attend(q, kc, vc, positions, k_pos,
                            causal=True, window=window)
        x = x + ctx.reshape(b, 1, cfg.q_dim) @ p["attn"]["wo"]
        x, _ = ffn_block(cfg, p, x)
        return x, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(body, x,
                                     (params["layers"], cache["k"],
                                      cache["v"]),
                                     unroll=cfg.unroll_scans)
    x = nn.rmsnorm(x, params["final_norm"])
    logits = logits_fn(cfg, params, x)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = k_new, v_new
    new_cache["k_pos"] = k_pos
    new_cache["pos"] = pos + 1
    return logits, new_cache
