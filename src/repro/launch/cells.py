"""Dry-run cells: (architecture × input shape × mesh) lowering + roofline.

No function here allocates device memory for model state: parameters,
optimizer moments and KV caches enter as ShapeDtypeStructs, shardings come
from ``repro.sharding.rules``, and ``jax.jit(...).lower(...).compile()``
produces the artifact that memory/cost/collective analysis reads.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, shape_applicable
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.linkage import L2_BYP, LinkageConfig
from repro.core.step import (TrainState, build_sharded_train_step,
                             init_train_state, make_decode_fn)
from repro.launch import hlo_analysis
from repro.models import ModelOptions, cache_spec, init_params, prefill
from repro.optim import AdamWConfig
from repro.sharding.rules import ArchSharding, named

# TPU v5e hardware constants (assignment)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

BIG_PARAM_THRESHOLD = 5e10   # params above this use bf16 params+moments


def default_options(cfg: ArchConfig, shape: ShapeConfig,
                    mesh: Optional[Mesh] = None, **overrides) -> ModelOptions:
    """The paper-faithful L2/BYP baseline options for at-scale lowering."""
    big_vocab = cfg.vocab_size >= 65536
    act_axes = None
    if mesh is not None:
        sh = ArchSharding(cfg, mesh)
        bspec = sh.batch_spec(shape.global_batch)
        if bspec != P(None):
            act_axes = bspec[0] if isinstance(bspec[0], tuple) else (bspec[0],)
    base = dict(
        attn_impl="chunked",
        scan_impl="chunked",
        q_chunk=512,
        kv_chunk=1024,
        scan_chunk=128,
        dtype=jnp.bfloat16,
        param_dtype=(jnp.bfloat16 if cfg.param_count() > BIG_PARAM_THRESHOLD
                     else jnp.float32),
        remat=shape.kind == "train",
        scan_blocks=True,
        logit_chunk=1024 if (big_vocab and shape.kind == "train") else 0,
        # §Perf-adopted defaults: static causal schedule for inference
        # lowerings (−2x attention work; HLO-size cost acceptable), smaller
        # MoE routing groups (−20% dispatch-einsum compute on kimi-k2)
        causal_skip=shape.kind != "train",
        moe_group=2048,
        act_batch_axes=act_axes,
    )
    base.update(overrides)
    return ModelOptions(**base)


def optimizer_config(cfg: ArchConfig, opts: ModelOptions) -> AdamWConfig:
    return AdamWConfig(moment_dtype=opts.param_dtype)


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def input_specs(arch: str, shape_name: str) -> Dict[str, Any]:
    """Abstract batch for one cell (assignment contract)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    return _input_specs(cfg, shape)


def _input_specs(cfg: ArchConfig, shape: ShapeConfig,
                 opts: Optional[ModelOptions] = None) -> Dict[str, Any]:
    s = jax.ShapeDtypeStruct
    B, S = shape.global_batch, shape.seq_len
    dt = (opts.dtype if opts else jnp.bfloat16)
    out: Dict[str, Any] = {}
    if shape.kind in ("train", "prefill"):
        if cfg.embeds_in:
            out["inputs"] = s((B, S, cfg.d_model), dt)
        else:
            out["inputs"] = s((B, S), jnp.int32)
        if shape.kind == "train":
            out["labels"] = s((B, S), jnp.int32)
        if cfg.xattn_ctx_len:
            out["xctx"] = s((B, cfg.xattn_ctx_len, cfg.xattn_ctx_dim), dt)
    else:  # decode: one new token against a cache of seq_len
        if cfg.embeds_in:
            out["tokens"] = s((B, cfg.d_model), dt)
        else:
            out["tokens"] = s((B,), jnp.int32)
        out["cache"] = cache_spec(cfg, B, S, dt)
    return out


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, mesh: Mesh,
               opts_overrides: Optional[Dict] = None,
               linkage: Optional[LinkageConfig] = None):
    """Build and lower the step program for one cell.

    Returns (lowered, meta) — call ``.compile()`` on the lowered object.
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        raise ValueError(f"{arch} × {shape_name} skipped (full-attention arch "
                         "cannot serve 500k context; see DESIGN.md)")
    overrides = dict(opts_overrides or {})
    # non-ModelOptions knobs
    serve_replicate = overrides.pop("serve_replicate_params", None)
    ep_resident = overrides.pop("ep_resident", False)
    opts = default_options(cfg, shape, mesh, **overrides)
    linkage = linkage or LinkageConfig(level=L2_BYP)
    sh = ArchSharding(cfg, mesh)
    specs = _input_specs(cfg, shape, opts)
    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "tp_report": sh.tp_report(),
            "param_dtype": np.dtype(opts.param_dtype).name}

    if shape.kind == "train":
        ocfg = optimizer_config(cfg, opts)
        state_sds = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(0), cfg, ocfg,
                                     opts.param_dtype))
        fn, state_specs, bspecs = build_sharded_train_step(
            cfg, opts, ocfg, linkage, mesh, state_sds, shape.global_batch,
            ep_resident=ep_resident)
        with mesh:
            lowered = fn.lower(state_sds, specs)
        return lowered, meta

    params_sds = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, opts.param_dtype))
    # Serving: keep weights device-resident (TP-only sharding) when the
    # per-TP-shard footprint fits; FSDP re-gathering weights on every decode
    # step was the dominant collective in the baseline (§Perf).
    param_bytes = cfg.param_count() * np.dtype(opts.param_dtype).itemsize
    replicate = serve_replicate
    if replicate is None:
        replicate = sh.serving_replication_fits(param_bytes)
    meta["serve_replicated_params"] = bool(replicate)
    pspecs = sh.param_specs(params_sds, replicate_fsdp=bool(replicate))

    if shape.kind == "prefill":
        bspec = sh.batch_spec(shape.global_batch)
        in_sh = [named(mesh, pspecs)]
        args = [params_sds]
        tok_spec = P(*bspec, None, None) if cfg.embeds_in else P(*bspec, None)
        in_sh.append(NamedSharding(mesh, tok_spec))
        args.append(specs["inputs"])
        if cfg.xattn_ctx_len:
            in_sh.append(NamedSharding(mesh, P(*bspec, None, None)))
            args.append(specs["xctx"])

            def fn(params, tokens, xctx):
                return prefill(params, tokens, cfg, opts, shape.seq_len,
                               xctx=xctx)
        else:
            def fn(params, tokens):
                return prefill(params, tokens, cfg, opts, shape.seq_len)
        with mesh:
            lowered = jax.jit(fn, in_shardings=tuple(in_sh)).lower(*args)
        return lowered, meta

    # decode
    cspec = sh.cache_specs(specs["cache"], shape.global_batch)
    bspec = sh.batch_spec(shape.global_batch)
    tok_spec = P(*bspec, None) if cfg.embeds_in else P(*bspec)
    decode_fn = make_decode_fn(cfg, opts, linkage)
    with mesh:
        lowered = jax.jit(
            decode_fn,
            in_shardings=(named(mesh, pspecs), named(mesh, cspec),
                          NamedSharding(mesh, tok_spec)),
            donate_argnums=(1,),
        ).lower(params_sds, specs["cache"], specs["tokens"])
    return lowered, meta


# ---------------------------------------------------------------------------
# Roofline record from a compiled cell
# ---------------------------------------------------------------------------

def _attention_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """Causal-aware analytic attention FLOPs (QKᵀ + PV), full precision of
    the 6ND convention's blind spot: at 32k+ context the S² term dominates
    2ND and must be part of MODEL_FLOPS or the useful-flops ratio lies."""
    n_attn_layers = sum(1 for s in cfg.block_pattern
                        if s.mixer in ("attn", "swa", "xattn")) \
        * cfg.num_blocks
    if n_attn_layers == 0 or cfg.n_heads == 0:
        return 0.0
    B, S = shape.global_batch, shape.seq_len
    dh, hq = cfg.head_dim, cfg.n_heads
    if shape.kind in ("train", "prefill"):
        # causal: S^2/2 scored pairs; 2 matmuls; 2 flops/MAC
        per_layer = 4.0 * B * (S * S / 2.0) * dh * hq
        if shape.kind == "train":
            per_layer *= 3.0            # fwd + bwd(2x)
    else:  # decode: one query against S cached keys
        per_layer = 4.0 * B * S * dh * hq
    return per_layer * n_attn_layers


def model_flops_per_device(cfg: ArchConfig, shape: ShapeConfig,
                           n_devices: int) -> float:
    """Analytic MODEL_FLOPS: 6·N_active·D (train) / 2·N_active·D (inference)
    + causal attention FLOPs."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        base = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        base = 2.0 * n_active * tokens
    else:
        base = 2.0 * n_active * shape.global_batch
    return (base + _attention_flops(cfg, shape)) / n_devices


def analyze_cell(arch: str, shape_name: str, mesh: Mesh,
                 opts_overrides: Optional[Dict] = None,
                 linkage: Optional[LinkageConfig] = None) -> Dict[str, Any]:
    """lower + compile + roofline terms for one cell."""
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, mesh, opts_overrides, linkage)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_dev = mesh.devices.size

    rec: Dict[str, Any] = dict(meta)
    rec["lower_s"] = round(t1 - t0, 2)
    rec["compile_s"] = round(t2 - t1, 2)

    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "total_bytes_per_device": int(ma.argument_size_in_bytes
                                          + ma.output_size_in_bytes
                                          + ma.temp_size_in_bytes
                                          - ma.alias_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}

    ca = compiled.cost_analysis() or {}
    rec["xla_flops_per_device"] = float(ca.get("flops", 0.0))
    rec["xla_bytes_per_device"] = float(ca.get("bytes accessed", 0.0))

    stats = hlo_analysis.analyze(compiled.as_text())
    rec["flops_per_device"] = stats.flops
    rec["hbm_bytes_per_device"] = stats.hbm_bytes
    rec["coll_wire_bytes_per_device"] = stats.coll_wire_bytes
    rec["coll_by_type"] = stats.coll_by_type
    rec["while_loops"] = stats.while_loops[:8]

    # roofline terms (seconds)
    compute_s = stats.flops / PEAK_FLOPS
    memory_s = stats.hbm_bytes / HBM_BW
    coll_s = stats.coll_wire_bytes / ICI_BW
    rec["roofline"] = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": coll_s,
        "dominant": max(
            (("compute", compute_s), ("memory", memory_s),
             ("collective", coll_s)), key=lambda kv: kv[1])[0],
    }
    mf = model_flops_per_device(cfg, shape, n_dev)
    rec["model_flops_per_device"] = mf
    rec["useful_flops_ratio"] = mf / stats.flops if stats.flops else 0.0
    bound_s = max(compute_s, memory_s, coll_s)
    rec["roofline"]["step_time_lower_bound_s"] = bound_s
    rec["roofline"]["roofline_fraction"] = (
        (mf / PEAK_FLOPS) / bound_s if bound_s > 0 else 0.0)
    return rec
