"""Dry-run library: lower + compile every (arch x shape x mesh) cell and
extract memory / cost / collective statistics from the compiled artifact.

Import this ONLY after the XLA device-count flag is set (dryrun.py and the
roofline harness do that in their first two lines). Importing this module
itself does not touch jax device state.
"""
from __future__ import annotations

import dataclasses
import json
import re
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding as shd
from repro.configs.base import ModelConfig, InputShape, HYBRID, ENCDEC
from repro.models import model_api as api
from repro.models import params as pm
from repro.train import optimizer as opt
from repro.train import train_step as ts


# ---------------------------------------------------------------------------
# Depth control (used by the roofline 2-point scan-body calibration)
# ---------------------------------------------------------------------------


def with_depth(cfg: ModelConfig, d: int) -> ModelConfig:
    if cfg.family == HYBRID:
        pat = len(cfg.block_pattern)
        tail = cfg.num_layers % pat
        return cfg.replace(num_layers=pat * d + tail)
    if cfg.family == ENCDEC:
        return cfg.replace(num_layers=d, n_enc_layers=d)
    return cfg.replace(num_layers=d)


def full_depth_units(cfg: ModelConfig) -> int:
    if cfg.family == HYBRID:
        return cfg.num_layers // len(cfg.block_pattern)
    return cfg.num_layers


# ---------------------------------------------------------------------------
# Collective-bytes parsing from HLO text
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Any]:
    """Sum operand bytes for every collective op, by kind.

    Works on post-SPMD-partitioning HLO, so shapes are per-device; counts
    are per-device bytes moved per executable invocation (scan bodies appear
    once — the roofline harness undoes that with a depth fit).
    """
    by_kind = {k: 0 for k in _COLL_KINDS}
    counts = {k: 0 for k in _COLL_KINDS}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+ = .+? ([a-z\-]+)(?:-start)?\(", ls)
        if not m:
            continue
        kind = m.group(1)
        if kind.endswith("-start"):
            kind = kind[:-6]
        if kind not in by_kind or "-done" in ls.split("=")[1][:40]:
            continue
        # operand shapes: inside the call parens
        paren = ls.find("(")
        args = ls[paren + 1:ls.rfind(")")]
        by_kind[kind] += _shape_bytes(args)
        counts[kind] += 1
    return {"bytes_by_kind": by_kind, "counts": counts,
            "total_bytes": sum(by_kind.values())}


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    kind: str
    ok: bool
    error: str = ""
    lower_s: float = 0.0
    compile_s: float = 0.0
    flops_per_dev: float = 0.0
    bytes_per_dev: float = 0.0
    coll_bytes_per_dev: float = 0.0
    coll_detail: Optional[Dict] = None
    mem: Optional[Dict] = None
    n_devices: int = 0
    microbatches: int = 1

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)


def _mesh_name(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


def build_cell(cfg: ModelConfig, shape: InputShape, mesh,
               microbatches: Optional[int] = None):
    """Returns (fn, args, in_shardings, out_shardings, donate, n_micro)."""
    n_chips = mesh.devices.size
    oc = opt.OptConfig()
    mspecs = api.model_specs(cfg)
    params_abs = api.abstract_params(cfg)
    params_sh = api.param_shardings(cfg, mesh)

    if shape.kind == "train":
        n_micro = (microbatches if microbatches is not None
                   else ts.default_microbatches(cfg, shape, n_chips))
        step = ts.make_train_step(cfg, oc, n_micro)
        ostate_abs = jax.eval_shape(lambda: opt.init_state(oc, mspecs))
        ostate_sh = opt.state_shardings(oc, mspecs, mesh)
        batch_abs = api.input_specs(cfg, shape)
        batch_sh = api.batch_shardings(cfg, mesh, shape)
        scalar = shd.named_sharding(mesh, (), ())
        out_sh = (params_sh, ostate_sh,
                  {"loss": scalar, "lr": scalar, "grad_norm": scalar})
        return (step, (params_abs, ostate_abs, batch_abs),
                (params_sh, ostate_sh, batch_sh), out_sh, (0, 1), n_micro)

    if shape.kind == "prefill":
        step = ts.make_prefill_step(cfg, shape.seq_len)
        batch_abs = api.input_specs(cfg, shape)
        batch_sh = api.batch_shardings(cfg, mesh, shape)
        cache_sh = api.cache_shardings(cfg, mesh, shape.global_batch,
                                       shape.seq_len)
        logit_sh = shd.named_sharding(
            mesh, (shape.global_batch, 1, cfg.vocab_size),
            ("batch", None, "vocab"))
        return (step, (params_abs, batch_abs), (params_sh, batch_sh),
                (logit_sh, cache_sh), (), 1)

    # decode
    step = ts.make_serve_step(cfg)
    cache_abs = api.abstract_cache(cfg, shape.global_batch, shape.seq_len)
    cache_sh = api.cache_shardings(cfg, mesh, shape.global_batch,
                                   shape.seq_len)
    batch_abs = api.input_specs(cfg, shape)
    batch_sh = api.batch_shardings(cfg, mesh, shape)
    logit_sh = shd.named_sharding(
        mesh, (shape.global_batch, 1, cfg.vocab_size),
        ("batch", None, "vocab"))
    return (step, (params_abs, cache_abs, batch_abs),
            (params_sh, cache_sh, batch_sh), (logit_sh, cache_sh), (1,), 1)


def lower_cell(cfg: ModelConfig, shape: InputShape, mesh,
               microbatches: Optional[int] = None,
               keep_artifacts: bool = False) -> CellResult:
    res = CellResult(arch=cfg.name, shape=shape.name, mesh=_mesh_name(mesh),
                     kind=shape.kind, ok=False,
                     n_devices=int(mesh.devices.size))
    try:
        fn, args, in_sh, out_sh, donate, n_micro = build_cell(
            cfg, shape, mesh, microbatches)
        res.microbatches = n_micro
        t0 = time.time()
        with shd.use_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
        res.lower_s = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        res.compile_s = time.time() - t0
        ca = compiled.cost_analysis() or {}
        res.flops_per_dev = float(ca.get("flops", 0.0))
        res.bytes_per_dev = float(ca.get("bytes accessed", 0.0))
        try:
            ma = compiled.memory_analysis()
            res.mem = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "code_bytes": int(ma.generated_code_size_in_bytes),
            }
        except Exception:                      # pragma: no cover
            res.mem = None
        txt = compiled.as_text()
        cs = collective_stats(txt)
        res.coll_bytes_per_dev = float(cs["total_bytes"])
        res.coll_detail = cs
        res.ok = True
        if keep_artifacts:
            res.__dict__["_compiled"] = compiled
            res.__dict__["_hlo"] = txt
    except Exception as e:                     # noqa: BLE001
        res.error = f"{type(e).__name__}: {e}"[:2000]
    return res
