"""Logical-axis sharding rules -> NamedSharding / PartitionSpec.

Every parameter and activation in the model zoo is annotated with *logical*
axis names ("vocab", "mlp", "heads", ...). This module maps logical names to
mesh axes with divisibility-checked fallback (replicate when a dim does not
divide), so the same model code lowers on a 1-device CPU mesh, the 16x16
single-pod mesh, and the 2x16x16 multi-pod mesh.

DP  = "batch"   -> ("pod", "data") when the mesh has a pod axis, else ("data",)
TP  = width-ish -> "model" (heads / flattened q-kv dims / mlp / vocab / lru /
                   ssm inner dim)
EP  = "experts" -> "model" when the expert count divides it (dbrx), else the
                   per-expert ffn dim takes "model" (mixtral)
SP  = "kv_seq"  -> "model" for long decode caches (flash-decode style split-K)
ZeRO-1: optimizer states additionally shard a replicated dim over "data"
        (see train/optimizer.py).
"""
from __future__ import annotations

import contextvars
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Ordered candidates per logical axis name. "batch" is special-cased.
RULES = {
    "batch":     ("__dp__",),
    "vocab":     ("model",),
    "mlp":       ("model",),
    "heads":     ("model",),     # flattened n_heads*head_dim output dim
    "kv":        ("model",),     # flattened n_kv_heads*head_dim output dim
    "experts":   ("model",),
    "expert_mlp": ("model",),    # per-expert ffn dim (used when EP impossible)
    "lru":       ("model",),     # RG-LRU width
    "ssm_inner": ("model",),     # mamba d_inner / heads*headdim
    "ssm_state": (),
    "kv_seq":    ("model",),     # sequence-sharded decode caches
    "embed":     (),
    "seq":       (),
    "seq_sp":    ("model",),   # Megatron-style sequence parallelism
    "layers":    (),
    "frames":    (),
    None:        (),
}


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _mesh_size(mesh: Mesh, axes) -> int:
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= mesh.shape[a]
    return n


def spec_for(mesh: Mesh, dims: Sequence[Optional[int]],
             axes: Sequence[Optional[str]]) -> P:
    """Build a PartitionSpec for `dims` annotated with logical `axes`.

    A mesh axis is assigned at most once per tensor; a logical axis falls back
    to replication when its dim does not divide the mesh axis size.
    `dims[i]` may be None to skip the divisibility check (e.g. activations
    whose dim is unknown here).
    """
    assert len(dims) == len(axes), (dims, axes)
    used = set()
    out = []
    for dim, name in zip(dims, axes):
        assigned = None
        for cand in RULES.get(name, ()):
            mesh_ax = dp_axes(mesh) if cand == "__dp__" else cand
            if not mesh_ax:
                continue
            flat = mesh_ax if isinstance(mesh_ax, tuple) else (mesh_ax,)
            if any(a not in mesh.axis_names or a in used for a in flat):
                continue
            if dim is not None and dim % _mesh_size(mesh, flat) != 0:
                continue
            assigned = mesh_ax
            used.update(flat)
            break
        out.append(assigned)
    # PartitionSpec drops trailing Nones automatically
    return P(*out)


def named_sharding(mesh: Mesh, dims, axes) -> NamedSharding:
    return NamedSharding(mesh, spec_for(mesh, dims, axes))


# --------------------------------------------------------------------------
# Activation-constraint context. Model code calls constrain(x, ...axes) and
# the launcher installs the mesh; on a bare CPU test no mesh is installed and
# constrain() is the identity.
# --------------------------------------------------------------------------
_MESH: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar(
    "repro_mesh", default=None)


class use_mesh:
    """Context manager installing the mesh used by constrain()."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh
        self._token = None

    def __enter__(self):
        self._token = _MESH.set(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        _MESH.reset(self._token)
        return False


def current_mesh() -> Optional[Mesh]:
    return _MESH.get()


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axis names (no-op without mesh)."""
    mesh = _MESH.get()
    if mesh is None:
        return x
    spec = spec_for(mesh, x.shape, axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
