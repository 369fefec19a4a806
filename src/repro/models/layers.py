"""Pure-JAX layer library: init + apply for every mixer / MLP kind.

Every layer is a pair of functions:
  init_<layer>(key, cfg) -> params (nested dict of jnp arrays)
  <layer>(params, x, ...) -> y

Implementations come in up to three flavours, selected by ``ModelOptions``:
  "ref"     — straightforward jnp (the oracle; fine for smoke shapes)
  "chunked" — blockwise/online formulations that never materialize O(S^2) or
              O(S·d_state) intermediates in HBM (the shardable default at scale)
  "pallas"  — hand-written TPU kernels from ``repro.kernels`` (the UKL
              "shortcut" level; falls back to "chunked" off-TPU)
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import (ATTN, DENSE, MAMBA, MOE, RWKV, RWKVMIX, SWA,
                                XATTN, ArchConfig, LayerSpec)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ModelOptions:
    """Execution options — orthogonal to the architecture (UKL linkage picks)."""
    attn_impl: str = "ref"          # ref | chunked | pallas
    scan_impl: str = "ref"          # ref | chunked | pallas   (mamba/rwkv)
    q_chunk: int = 512              # blockwise attention q tile
    kv_chunk: int = 1024            # blockwise attention kv tile
    scan_chunk: int = 128           # ssm chunk length
    dtype: Any = jnp.bfloat16       # activation dtype
    param_dtype: Any = jnp.float32  # parameter dtype
    remat: bool = False             # activation checkpointing per block
    scan_blocks: bool = True        # lax.scan over repeated blocks
    logit_chunk: int = 0            # 0 = whole-seq logits; else chunked xent
    fused_norm: bool = False        # use pallas fused rmsnorm (shortcut)
    mesh: Any = None                # serving mesh: pallas kernels run per
                                    # shard (XLA cannot partition them)
    moe_group: int = 4096           # MoE routing-group size (tokens)
    # activation sharding constraint axes (None = let GSPMD propagate).
    # e.g. ("data",) or ("pod","data"): batch dim of every residual-stream
    # tensor is pinned to these mesh axes — without this GSPMD may leave the
    # batch replicated and shard d_model instead (observed; see EXPERIMENTS).
    act_batch_axes: Any = None
    act_seq_axis: Any = None        # sequence-parallel axis for long-context
    # ---- hillclimb knobs (§Perf) ----
    causal_skip: bool = False       # inference-only: dynamic kv-loop bounds
                                    # skip fully-masked chunks (not reverse-
                                    # differentiable: fori_loop w/ traced bound)
    norm_bf16_grad: bool = False    # RMSNorm cotangents in activation dtype:
                                    # halves the Megatron-g all-reduce bytes
    decode_tiled: bool = False      # tile decode attention over the cache.
                                    # REFUTED for sharded serving (§Perf): the
                                    # static chunking conflicts with the
                                    # T-sharded cache and forces re-gathers;
                                    # only useful single-device.


def constrain_acts(x: jax.Array, opts: "ModelOptions") -> jax.Array:
    """Pin (B, S, D) activations to opts.act_batch_axes / act_seq_axis."""
    if opts.act_batch_axes is None and opts.act_seq_axis is None:
        return x
    from jax.sharding import PartitionSpec as P
    spec = [None] * x.ndim
    if opts.act_batch_axes is not None:
        spec[0] = tuple(opts.act_batch_axes)
    if opts.act_seq_axis is not None and x.ndim >= 3:
        spec[1] = opts.act_seq_axis
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def _dense_init(key, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return jax.random.normal(key, shape, dtype) * scale


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(cfg: ArchConfig) -> Params:
    return {"scale": jnp.ones((cfg.d_model,), jnp.float32)}


def _rmsnorm_raw(scale, x, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps) * scale
    return y.astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_bf16_grad(scale, x, eps):
    return _rmsnorm_raw(scale, x, eps)


def _rmsnorm_bf16_fwd(scale, x, eps):
    out, vjp = jax.vjp(lambda s, xx: _rmsnorm_raw(s, xx, eps), scale, x)
    # zero-size dtype witness: residuals must be JAX types, not dtypes
    return out, (vjp, jnp.zeros((0,), x.dtype))


def _rmsnorm_bf16_bwd(eps, res, g):
    """Cotangents cast to the activation dtype before they leave the op:
    this is what turns the (B,S,D) fp32 Megatron-g all-reduces observed in
    the baseline HLO into bf16 ones (2x collective bytes on the TP axis)."""
    vjp, witness = res
    ds, dx = vjp(g)
    return ds, dx.astype(witness.dtype)


_rmsnorm_bf16_grad.defvjp(_rmsnorm_bf16_fwd, _rmsnorm_bf16_bwd)


def rmsnorm(params: Params, x: jax.Array, eps: float, opts: ModelOptions) -> jax.Array:
    with jax.named_scope("norm"):
        if opts.fused_norm:
            from repro.kernels import ops as kops
            return kops.rmsnorm(x, params["scale"], eps=eps, mesh=opts.mesh)
        if opts.norm_bf16_grad:
            return _rmsnorm_bf16_grad(params["scale"], x, eps)
        return _rmsnorm_raw(params["scale"], x, eps)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, dh); positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if 2 * half < dh:  # odd head dims pass the tail through (e.g. d_head=112 -> 56+56)
        rot = jnp.concatenate([rot, x[..., 2 * half:]], axis=-1)
    return rot.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, causal / sliding-window / cross)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig, spec: LayerSpec) -> Params:
    ks = jax.random.split(key, 8)
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": _dense_init(ks[0], (d, hq * dh)),
        "wk": _dense_init(ks[1], (d, hkv * dh)),
        "wv": _dense_init(ks[2], (d, hkv * dh)),
        "wo": _dense_init(ks[3], (hq * dh, d), scale=1.0 / math.sqrt(hq * dh)),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((hq * dh,), jnp.float32)
        p["bk"] = jnp.zeros((hkv * dh,), jnp.float32)
        p["bv"] = jnp.zeros((hkv * dh,), jnp.float32)
    if spec.mixer == XATTN:
        dc = cfg.xattn_ctx_dim
        p["xq"] = _dense_init(ks[4], (d, hq * dh))
        p["xk"] = _dense_init(ks[5], (dc, hkv * dh))
        p["xv"] = _dense_init(ks[6], (dc, hkv * dh))
        p["xo"] = _dense_init(ks[7], (hq * dh, d), scale=1.0 / math.sqrt(hq * dh))
        p["xgate"] = jnp.zeros((1,), jnp.float32)  # gated cross-attn (starts closed)
    return p


def _qkv(params, x, cfg: ArchConfig):
    B, S, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if cfg.attn_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    return (q.reshape(B, S, hq, dh), k.reshape(B, S, hkv, dh),
            v.reshape(B, S, hkv, dh))


def _sdpa_ref(q, k, v, *, causal: bool, window: int, q_pos, k_pos):
    """Reference attention; materializes scores. q:(B,Sq,HQ,dh) k/v:(B,Sk,HKV,dh)."""
    B, Sq, HQ, dh = q.shape
    HKV = k.shape[2]
    G = HQ // HKV
    qg = q.reshape(B, Sq, HKV, G, dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(dh)
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, HQ, dh)


def _sdpa_chunked(q, k, v, *, causal: bool, window: int, q_pos, k_pos,
                  q_chunk: int, kv_chunk: int, causal_skip: bool = False):
    """Blockwise flash-style attention in jnp: online softmax over kv chunks,
    scanned over q chunks. Never materializes (Sq, Sk).

    causal_skip=True: static causal schedule — the q loop unrolls and each
    q chunk scans over exactly its (window-clipped) causal kv prefix. Halves
    attention FLOPs/bytes vs the rectangular scan-with-masking, stays
    differentiable (static scan lengths), and keeps HLO trip counts
    analyzable. Costs HLO size O(nq) per layer, so it is an opt-in
    (§Perf hillclimb knob)."""
    B, Sq, HQ, dh = q.shape
    Sk, HKV = k.shape[1], k.shape[2]
    G = HQ // HKV
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Sk)
    # pad to multiples
    nq, nk = -(-Sq // qc), -(-Sk // kc)
    pq, pk = nq * qc - Sq, nk * kc - Sk
    q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    q_pos = jnp.pad(q_pos, (0, pq), constant_values=-(10 ** 9))
    k_pos = jnp.pad(k_pos, (0, pk), constant_values=2 ** 30)
    scale = 1.0 / math.sqrt(dh)

    qs = q.reshape(B, nq, qc, HKV, G, dh).transpose(1, 0, 2, 3, 4, 5)
    qps = q_pos.reshape(nq, qc)
    ks = k.reshape(B, nk, kc, HKV, dh).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kc, HKV, dh).transpose(1, 0, 2, 3, 4)
    kps = k_pos.reshape(nk, kc)

    def kv_step(acc, ki, vi, kp, qi, qp):
        m, l, o = acc
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, ki).astype(jnp.float32) * scale
        msk = jnp.ones((qc, kc), bool)
        if causal:
            msk &= qp[:, None] >= kp[None, :]
        if window > 0:
            msk &= qp[:, None] - kp[None, :] < window
        s = jnp.where(msk[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(msk[None, None, None], p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        corr = jnp.where(jnp.isfinite(m), corr, 0.0)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(qi.dtype), vi).astype(jnp.float32)
        return m_new, l_new, o_new

    def acc0():
        return (jnp.full((B, HKV, G, qc), -jnp.inf, jnp.float32),
                jnp.zeros((B, HKV, G, qc), jnp.float32),
                jnp.zeros((B, HKV, G, qc, dh), jnp.float32))

    if causal_skip and causal:
        outs_list = []
        for qidx in range(nq):
            hi = min(((qidx + 1) * qc + kc - 1) // kc, nk)
            lo = max((qidx * qc - window) // kc, 0) if window > 0 else 0
            hi = max(hi, lo + 1)

            @partial(jax.checkpoint, prevent_cse=False)
            def kv_block(acc, kb, qidx=qidx):
                ki, vi, kp = kb
                return kv_step(acc, ki, vi, kp, qs[qidx], qps[qidx]), None

            (m, l, o), _ = lax.scan(kv_block, acc0(),
                                    (ks[lo:hi], vs[lo:hi], kps[lo:hi]))
            o = o / jnp.maximum(l, 1e-30)[..., None]
            outs_list.append(o.astype(q.dtype))
        outs = jnp.stack(outs_list)
    else:
        def q_block(carry, qb):
            qi, qp = qb

            @partial(jax.checkpoint, prevent_cse=False)
            def kv_block(acc, kb):
                ki, vi, kp = kb
                return kv_step(acc, ki, vi, kp, qi, qp), None

            (m, l, o), _ = lax.scan(kv_block, acc0(), (ks, vs, kps))
            o = o / jnp.maximum(l, 1e-30)[..., None]
            return carry, o.astype(qi.dtype)

        # flash-style backward: recompute blocks instead of saving the
        # per-chunk probability tensors the inner scan would otherwise
        # stack to O(S^2)
        q_block = jax.checkpoint(q_block, prevent_cse=False)
        _, outs = lax.scan(q_block, None, (qs, qps))
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * qc, HQ, dh)
    return out[:, :Sq]


def attention(params: Params, x: jax.Array, cfg: ArchConfig, spec: LayerSpec,
              opts: ModelOptions, positions: jax.Array,
              xctx: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence (train / prefill) attention."""
    B, S, _ = x.shape
    q, k, v = _qkv(params, x, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    window = cfg.sliding_window if spec.mixer == SWA else 0
    kwargs = dict(causal=True, window=window, q_pos=positions, k_pos=positions)
    if opts.attn_impl == "ref":
        out = _sdpa_ref(q, k, v, **kwargs)
    elif opts.attn_impl == "chunked":
        out = _sdpa_chunked(q, k, v, q_chunk=opts.q_chunk, kv_chunk=opts.kv_chunk,
                            causal_skip=opts.causal_skip, **kwargs)
    elif opts.attn_impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True, window=window,
                                   mesh=opts.mesh)
    else:
        raise ValueError(opts.attn_impl)
    y = out.reshape(B, S, -1) @ params["wo"].astype(x.dtype)

    if spec.mixer == XATTN:
        assert xctx is not None, "cross-attention layer needs ctx embeddings"
        hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        xq = (x @ params["xq"].astype(x.dtype)).reshape(B, S, hq, dh)
        xk = (xctx @ params["xk"].astype(x.dtype)).reshape(B, -1, hkv, dh)
        xv = (xctx @ params["xv"].astype(x.dtype)).reshape(B, -1, hkv, dh)
        n_ctx = xk.shape[1]
        xout = _sdpa_ref(xq, xk, xv, causal=False, window=0,
                         q_pos=jnp.zeros((S,), jnp.int32),
                         k_pos=jnp.zeros((n_ctx,), jnp.int32)) \
            if opts.attn_impl == "ref" else \
            _sdpa_chunked(xq, xk, xv, causal=False, window=0,
                          q_pos=jnp.zeros((S,), jnp.int32),
                          k_pos=jnp.zeros((n_ctx,), jnp.int32),
                          q_chunk=opts.q_chunk, kv_chunk=opts.kv_chunk)
        gate = jnp.tanh(params["xgate"]).astype(x.dtype)
        y = y + gate * (xout.reshape(B, S, -1) @ params["xo"].astype(x.dtype))
    return y


def attention_decode(params: Params, x: jax.Array, cache: Params,
                     cfg: ArchConfig, spec: LayerSpec, opts: ModelOptions,
                     xctx: Optional[jax.Array] = None) -> Tuple[jax.Array, Params]:
    """One-token decode against a (possibly circular / sliding-window) KV cache.

    cache: {"k": (B,T,HKV,dh), "v": (B,T,HKV,dh), "slot_pos": (T,), "pos": ()}.
    For SWA layers T == min(window, max_len): a circular buffer.
    """
    B = x.shape[0]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _qkv(params, x, cfg)  # S == 1
    pos = cache["pos"]
    posv = jnp.full((1,), pos, jnp.int32)
    q = rope(q, posv, cfg.rope_theta)
    k = rope(k, posv, cfg.rope_theta)
    T = cache["k"].shape[1]
    slot = pos % T
    with jax.named_scope("kv_write"):
        ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
        slot_pos = lax.dynamic_update_slice(
            cache["slot_pos"], jnp.full((1,), pos, jnp.int32), (slot,))
    window = cfg.sliding_window if spec.mixer == SWA else 0

    if opts.attn_impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.decode_attention(q, ck, cv, slot_pos, pos, window=window)
    elif opts.attn_impl == "chunked" and opts.decode_tiled:
        # tiled decode (flash-decode in jnp): never materializes the full
        # (B, HQ, T) fp32 score row. Only for unsharded serving — under a
        # T-sharded cache the chunk reshape forces re-gathers (§Perf).
        kpos_eff = jnp.where(slot_pos >= 0, slot_pos, 2 ** 30)
        out = _sdpa_chunked(q, ck, cv, causal=True, window=window,
                            q_pos=posv, k_pos=kpos_eff,
                            q_chunk=1, kv_chunk=opts.kv_chunk)
    else:
        qg = q.reshape(B, 1, hkv, hq // hkv, dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck).astype(jnp.float32)
        s = s / math.sqrt(dh)
        valid = (slot_pos <= pos) & (slot_pos >= 0)
        if window > 0:
            valid &= pos - slot_pos < window
        s = jnp.where(valid[None, None, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p, cv).reshape(B, 1, hq, dh)

    y = out.reshape(B, 1, -1) @ params["wo"].astype(x.dtype)
    if spec.mixer == XATTN:
        xout = _xattn_cached(params, x, cache, cfg)
        gate = jnp.tanh(params["xgate"]).astype(x.dtype)
        y = y + gate * xout
    new_cache = dict(cache, k=ck, v=cv, slot_pos=slot_pos, pos=pos + 1)
    return y, new_cache


def attention_decode_slots(params: Params, x: jax.Array, cache: Params,
                           cfg: ArchConfig, spec: LayerSpec, opts: ModelOptions
                           ) -> Tuple[jax.Array, Params]:
    """One-token decode where each batch row is an independent serving *slot*.

    Unlike ``attention_decode`` (whole batch at one shared position), every
    slot carries its own position and occupancy:

      cache: {"k": (B,T,HKV,dh), "v": (B,T,HKV,dh),
              "slot_pos": (B,T), "pos": (B,)}.

    Rope angles, circular-buffer write indices and validity masks are all
    per-slot, so sequences admitted at different times decode together in one
    program — the continuous-batching primitive.
    """
    B = x.shape[0]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _qkv(params, x, cfg)  # S == 1
    pos = cache["pos"]                                  # (B,)
    q = rope(q, pos[:, None], cfg.rope_theta)
    k = rope(k, pos[:, None], cfg.rope_theta)
    T = cache["k"].shape[1]
    slot = pos % T                                      # (B,) write index
    row_upd = lambda c, u, s: lax.dynamic_update_slice(
        c, u, (s,) + (0,) * (c.ndim - 1))
    with jax.named_scope("kv_write"):
        ck = jax.vmap(row_upd)(cache["k"], k.astype(cache["k"].dtype), slot)
        cv = jax.vmap(row_upd)(cache["v"], v.astype(cache["v"].dtype), slot)
        slot_pos = jax.vmap(row_upd)(cache["slot_pos"], pos[:, None], slot)
    window = cfg.sliding_window if spec.mixer == SWA else 0

    if opts.attn_impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.slot_decode_attention(q, ck, cv, slot_pos, pos,
                                         window=window, mesh=opts.mesh)
    else:
        valid = (slot_pos <= pos[:, None]) & (slot_pos >= 0)   # (B,T)
        if window > 0:
            valid &= pos[:, None] - slot_pos < window
        qg = q.reshape(B, 1, hkv, hq // hkv, dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck).astype(jnp.float32)
        s = s / math.sqrt(dh)
        s = jnp.where(valid[:, None, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p, cv).reshape(B, 1, hq, dh)

    y = out.reshape(B, 1, -1) @ params["wo"].astype(x.dtype)
    if spec.mixer == XATTN:
        xout = _xattn_cached(params, x, cache, cfg)
        gate = jnp.tanh(params["xgate"]).astype(x.dtype)
        y = y + gate * xout
    new_cache = dict(cache, k=ck, v=cv, slot_pos=slot_pos, pos=pos + 1)
    return y, new_cache


def attention_decode_paged(params: Params, x: jax.Array, cache: Params,
                           tables: jax.Array, cfg: ArchConfig,
                           opts: ModelOptions, max_len: int
                           ) -> Tuple[jax.Array, Params]:
    """One-token decode against a *paged* KV cache (serving, `--kv paged`).

    Instead of one dense (T,) row per slot, each slot owns a chain of
    fixed-size physical blocks in a shared pool (virtual memory for the KV
    cache — see ``repro.serve.paging``):

      cache:  {"kp": (P+1, bs, HKV, dh), "vp": (P+1, bs, HKV, dh),
               "pos": (B,)}
      tables: (B, nb) int32 — per-slot logical-block -> physical-block map.

    Row P of the pool is the reserved trash block: table entries of empty /
    finished slots point at it, so their garbage writes never touch a live
    sequence. Logical position p of slot b lives at physical
    (tables[b, p // bs], p % bs). The gather path below reassembles each
    slot's logical view and applies exactly the slotted einsum/softmax with
    the same (B, max_len) shapes — invalid positions are -inf-masked, so the
    physical relayout is invisible to the math (the engine's token-identity
    invariant). The pallas path reads blocks from the pool in place via a
    scalar-prefetched block table (``repro.kernels.paged_decode``).
    """
    B = x.shape[0]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _qkv(params, x, cfg)  # S == 1
    pos = cache["pos"]                                  # (B,)
    q = rope(q, pos[:, None], cfg.rope_theta)
    k = rope(k, pos[:, None], cfg.rope_theta)
    bs = cache["kp"].shape[1]
    nb = tables.shape[1]
    # write the fresh K/V at (tables[b, pos//bs], pos % bs). Live slots have
    # the covering block demand-allocated (and CoW-forked if shared) by the
    # engine before the program; dead slots resolve to the trash block.
    logical_blk = jnp.clip(pos // bs, 0, nb - 1)
    blk = jnp.take_along_axis(tables, logical_blk[:, None], axis=1)[:, 0]
    off = pos % bs
    ks = vs = None
    with jax.named_scope("kv_write"):
        if "ks" in cache:
            # quantized pool: block-level requantize-on-write (see kv_quant)
            from repro.kernels import kv_quant
            kp, ks = kv_quant.quant_insert(cache["kp"], cache["ks"], blk,
                                           off, k[:, 0])
            vp, vs = kv_quant.quant_insert(cache["vp"], cache["vs"], blk,
                                           off, v[:, 0])
        else:
            kp = cache["kp"].at[blk, off].set(
                k[:, 0].astype(cache["kp"].dtype))
            vp = cache["vp"].at[blk, off].set(
                v[:, 0].astype(cache["vp"].dtype))

    if opts.attn_impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.paged_decode_attention(q, kp, vp, tables, pos, ks, vs,
                                          mesh=opts.mesh)
    else:
        # gather the logical view: (B, nb, bs, ...) -> (B, max_len, ...).
        # Same shapes, values and masks as the slotted dense row, so the
        # einsum/softmax below is bit-identical to attention_decode_slots.
        if ks is not None:
            from repro.kernels import kv_quant
            kd = kv_quant.dequantize_pool(kp, ks).astype(x.dtype)
            vd = kv_quant.dequantize_pool(vp, vs).astype(x.dtype)
        else:
            kd, vd = kp, vp
        kg = kd[tables].reshape(B, nb * bs, hkv, dh)[:, :max_len]
        vg = vd[tables].reshape(B, nb * bs, hkv, dh)[:, :max_len]
        valid = jnp.arange(max_len, dtype=jnp.int32)[None] <= pos[:, None]
        qg = q.reshape(B, 1, hkv, hq // hkv, dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kg).astype(jnp.float32)
        s = s / math.sqrt(dh)
        s = jnp.where(valid[:, None, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vg).reshape(B, 1, hq, dh)

    y = out.reshape(B, 1, -1) @ params["wo"].astype(x.dtype)
    new_cache = dict(cache, kp=kp, vp=vp, pos=pos + 1)
    if ks is not None:
        new_cache["ks"], new_cache["vs"] = ks, vs
    return y, new_cache


def attention_serve_chunk(params: Params, x: jax.Array, cache: Params,
                          cfg: ArchConfig, opts: ModelOptions,
                          start: jax.Array, clen: jax.Array
                          ) -> Tuple[jax.Array, Params]:
    """Variable-length *chunk* attention against the slot cache — the unified
    serve step's prefill half (chunked prefill; see ``repro.core.step``).

    Every batch row processes up to W tokens starting at its own position:

      x:     (B, W, D) chunk hidden states (right-padded per row)
      cache: slot layout {"k"/"v": (B,T,HKV,dh), "slot_pos": (B,T),
             "pos": (B,)}
      start: (B,) first position each row's chunk occupies
      clen:  (B,) real tokens in the row's chunk (0 = row has no chunk)

    The chunk K/V is written at positions ``start + j`` for ``j < clen``
    (padding positions write their *old* value back, so a row near max_len
    never clobbers resident state), then all W queries attend over the
    updated row with the per-query causal mask ``slot_pos <= q_pos`` — each
    real row computes exactly what a full prefill computes for it, which is
    what keeps chunked streams identical to two-phase streams. Garbage the
    fused decode microsteps may have marked valid at positions >= start+clen
    (mid-prefill rows riding an NSS program) is masked out by the same
    causal comparison until the covering chunk overwrites it. ``pos`` is
    host-authoritative in chunked serving: it is set to ``start + clen``
    regardless of its stale device value.
    """
    B, W, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _qkv(params, x, cfg)                      # (B, W, H, dh)
    q_pos = start[:, None] + jnp.arange(W, dtype=jnp.int32)[None]   # (B, W)
    q = rope(q, q_pos, cfg.rope_theta)
    k = rope(k, q_pos, cfg.rope_theta)
    T = cache["k"].shape[1]
    idx = q_pos % T                                     # (B, W) write slots
    real = jnp.arange(W, dtype=jnp.int32)[None] < clen[:, None]     # (B, W)

    def row_write(c, u, ix, m):
        old = c[ix]
        return c.at[ix].set(jnp.where(m.reshape((-1,) + (1,) * (u.ndim - 1)),
                                      u, old))

    with jax.named_scope("kv_write"):
        ck = jax.vmap(row_write)(cache["k"], k.astype(cache["k"].dtype), idx,
                                 real)
        cv = jax.vmap(row_write)(cache["v"], v.astype(cache["v"].dtype), idx,
                                 real)
        slot_pos = jax.vmap(row_write)(cache["slot_pos"], q_pos, idx, real)

    # dense masked attention: (B, W) queries over the (B, T) row. The same
    # einsum/softmax structure as the slotted decode ref path, so a width-1
    # chunk reduces to exactly the decode computation.
    valid = (slot_pos[:, None, :] >= 0) & \
        (slot_pos[:, None, :] <= q_pos[:, :, None])     # (B, W, T)
    qg = q.reshape(B, W, hkv, hq // hkv, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck).astype(jnp.float32)
    s = s / math.sqrt(dh)
    s = jnp.where(valid[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, cv).reshape(B, W, hq * dh)

    y = out @ params["wo"].astype(x.dtype)
    new_cache = dict(cache, k=ck, v=cv, slot_pos=slot_pos, pos=start + clen)
    return y, new_cache


def attention_serve_chunk_paged(params: Params, x: jax.Array, cache: Params,
                                tables: jax.Array, cfg: ArchConfig,
                                opts: ModelOptions, start: jax.Array,
                                clen: jax.Array, max_len: int
                                ) -> Tuple[jax.Array, Params]:
    """``attention_serve_chunk`` re-addressed through a paged block pool.

      cache:  {"kp"/"vp": (P+1, bs, HKV, dh), "pos": (B,)}
      tables: (B, nb) logical->physical block map

    Chunk K/V scatters to ``(tables[b, p // bs], p % bs)`` for real positions
    and to the trash row for padding (the engine CoW-forked / demand-
    allocated every block in the write span, so real destinations are
    exclusively owned). The gather path masks by logical position
    ``t <= q_pos`` — garbage beyond a row's resident end always sits at
    positions above every real query, so it is invisible by the same causal
    comparison. The pallas path is the scalar-prefetched block-table flash
    kernel ``repro.kernels.paged_prefill`` (the roadmap's paged prefill
    kernel), told ``clen`` so that each row walks its blocks only up to
    that of its last real query.
    """
    B, W, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _qkv(params, x, cfg)
    q_pos = start[:, None] + jnp.arange(W, dtype=jnp.int32)[None]   # (B, W)
    q = rope(q, q_pos, cfg.rope_theta)
    k = rope(k, q_pos, cfg.rope_theta)
    bs = cache["kp"].shape[1]
    nb = tables.shape[1]
    trash = cache["kp"].shape[0] - 1
    real = jnp.arange(W, dtype=jnp.int32)[None] < clen[:, None]     # (B, W)
    logical_blk = jnp.clip(q_pos // bs, 0, nb - 1)
    blk = jnp.take_along_axis(tables, logical_blk, axis=1)          # (B, W)
    blk = jnp.where(real, blk, trash)
    off = q_pos % bs
    ks = vs = None
    with jax.named_scope("kv_write"):
        if "ks" in cache:
            # quantized pool: padding rows target the trash block, so their
            # garbage writes requantize only the trash row (never validly
            # read)
            from repro.kernels import kv_quant
            kp, ks = kv_quant.quant_insert(cache["kp"], cache["ks"], blk,
                                           off, k)
            vp, vs = kv_quant.quant_insert(cache["vp"], cache["vs"], blk,
                                           off, v)
        else:
            kp = cache["kp"].at[blk, off].set(k.astype(cache["kp"].dtype))
            vp = cache["vp"].at[blk, off].set(v.astype(cache["vp"].dtype))

    if opts.attn_impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.paged_prefill_attention(q, kp, vp, tables, start, ks, vs,
                                           qlen=clen, mesh=opts.mesh)
    else:
        # gather fallback: assemble each row's logical view and mask by
        # position — same shapes and reductions as the dense chunk path
        if ks is not None:
            from repro.kernels import kv_quant
            kd = kv_quant.dequantize_pool(kp, ks).astype(x.dtype)
            vd = kv_quant.dequantize_pool(vp, vs).astype(x.dtype)
        else:
            kd, vd = kp, vp
        kg = kd[tables].reshape(B, nb * bs, hkv, dh)[:, :max_len]
        vg = vd[tables].reshape(B, nb * bs, hkv, dh)[:, :max_len]
        valid = jnp.arange(max_len, dtype=jnp.int32)[None, None, :] \
            <= q_pos[:, :, None]                        # (B, W, max_len)
        qg = q.reshape(B, W, hkv, hq // hkv, dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kg).astype(jnp.float32)
        s = s / math.sqrt(dh)
        s = jnp.where(valid[:, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vg).reshape(B, W, hq, dh)

    y = out.reshape(B, W, -1) @ params["wo"].astype(x.dtype)
    new_cache = dict(cache, kp=kp, vp=vp, pos=start + clen)
    if ks is not None:
        new_cache["ks"], new_cache["vs"] = ks, vs
    return y, new_cache


def _xattn_cached(params, x, cache, cfg):
    B = x.shape[0]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    xq = (x @ params["xq"].astype(x.dtype)).reshape(B, 1, hq, dh)
    xk, xv = cache["xk"], cache["xv"]
    qg = xq.reshape(B, 1, hkv, hq // hkv, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, xk).astype(jnp.float32) / math.sqrt(dh)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, xv).reshape(B, 1, -1)
    return out @ params["xo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense SwiGLU MLP & RWKV channel-mix
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    return {
        "wi": _dense_init(k1, (d, f)),
        "wg": _dense_init(k2, (d, f)),
        "wo": _dense_init(k3, (f, d), scale=1.0 / math.sqrt(f)),
    }


def mlp(params: Params, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ params["wg"].astype(x.dtype)) * (x @ params["wi"].astype(x.dtype))
    return h @ params["wo"].astype(x.dtype)


def init_rwkv_mix(key, cfg: ArchConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    return {
        "wk": _dense_init(k1, (d, f)),
        "wv": _dense_init(k2, (f, d), scale=1.0 / math.sqrt(f)),
        "wr": _dense_init(k3, (d, d)),
        "mix_k": jnp.full((d,), 0.5, jnp.float32),
        "mix_r": jnp.full((d,), 0.5, jnp.float32),
    }


def rwkv_mix(params: Params, x: jax.Array, shifted: jax.Array) -> jax.Array:
    """RWKV channel mix. ``shifted`` is x shifted right one token."""
    mk = params["mix_k"].astype(x.dtype)
    mr = params["mix_r"].astype(x.dtype)
    xk = x * mk + shifted * (1 - mk)
    xr = x * mr + shifted * (1 - mr)
    k = jnp.square(jax.nn.relu(xk @ params["wk"].astype(x.dtype)))
    return jax.nn.sigmoid(xr @ params["wr"].astype(x.dtype)) * (k @ params["wv"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Mixture of Experts (top-k, capacity-based dispatch, EP-shardable)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ArchConfig) -> Params:
    assert cfg.moe is not None
    k0, k1, k2, k3 = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    return {
        "router": _dense_init(k0, (d, e), scale=0.02),
        "wi": _dense_init(k1, (e, d, f)),
        "wg": _dense_init(k2, (e, d, f)),
        "wo": _dense_init(k3, (e, f, d), scale=1.0 / math.sqrt(f)),
    }


def moe(params: Params, x: jax.Array, cfg: ArchConfig, opts: ModelOptions
        ) -> Tuple[jax.Array, jax.Array]:
    """Grouped capacity-based top-k MoE (GShard formulation). Returns
    (output, router aux loss).

    Tokens are split into groups of ≤ ``opts.moe_group`` tokens; routing and
    capacity are per-group (C = ceil(Sg·K·cf/E)), so the dispatch/combine
    one-hots are (G, Sg, E, C) — bounded per device when G is sharded over
    the data axes and E over the model axis (expert parallelism). A flat
    (N, E, C) dispatch would be O(N²·K·cf/E) and is infeasible at the 1M-token
    step sizes this framework targets.
    """
    mcfg = cfg.moe
    B, S, D = x.shape
    N = B * S
    E, K = mcfg.num_experts, mcfg.top_k
    gs = min(opts.moe_group, S)
    while S % gs != 0:                 # largest divisor of S not above cap
        gs -= 1
    G = N // gs
    xt = x.reshape(G, gs, D)

    if opts.scan_impl == "pallas":
        from repro.kernels import ops as kops
        gates_f, idx = kops.moe_route(xt.reshape(N, D),
                                      params["router"].astype(x.dtype), K)
        gates = gates_f.reshape(G, gs, K)
        idx = idx.reshape(G, gs, K)
        logits = (xt @ params["router"].astype(x.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)               # (G,gs,E)
    else:
        logits = (xt @ params["router"].astype(x.dtype)).astype(jnp.float32)
        # softmax in fp32 for stability, but the (G,S,E) tensor downstream
        # (top-k, dispatch one-hots, aux loss) lives in the activation dtype:
        # the fp32 copy was the single largest gathered tensor in the kimi-k2
        # baseline HLO (§Perf)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        gates, idx = lax.top_k(probs, K)                      # (G,gs,K)
    gates = (gates.astype(jnp.float32)
             / jnp.maximum(gates.astype(jnp.float32).sum(-1, keepdims=True),
                           1e-9))

    C = max(int(gs * K * mcfg.capacity_factor / E), 1)
    C = min(C, gs)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)          # (G,gs,K,E)
    flat = onehot.reshape(G, gs * K, E)
    pos_in_e = jnp.cumsum(flat, axis=1) * flat - 1            # (G,gs*K,E)
    pos = pos_in_e.max(axis=-1).reshape(G, gs, K)
    keep = (pos >= 0) & (pos < C)
    gates = gates * keep

    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                            dtype=x.dtype)[..., :C]           # (G,gs,K,C)
    disp = jnp.einsum("gske,gskc->gsec", onehot.astype(x.dtype), pos_oh)
    comb = jnp.einsum("gske,gskc,gsk->gsec", onehot.astype(jnp.float32),
                      pos_oh.astype(jnp.float32), gates).astype(x.dtype)

    xe = jnp.einsum("gsd,gsec->gecd", xt, disp)               # (G,E,C,D)
    h = jnp.einsum("gecd,edf->gecf", xe, params["wg"].astype(x.dtype))
    h = jax.nn.silu(h) * jnp.einsum("gecd,edf->gecf", xe,
                                    params["wi"].astype(x.dtype))
    ye = jnp.einsum("gecf,efd->gecd", h, params["wo"].astype(x.dtype))
    y = jnp.einsum("gecd,gsec->gsd", ye, comb)

    # load-balancing aux loss (Switch-style), averaged over groups
    me = probs.astype(jnp.float32).mean(axis=1)               # (G,E)
    frac = onehot.sum(axis=2).astype(jnp.float32).mean(axis=1)  # (G,E)
    aux = (me * frac).sum(-1).mean() * E * mcfg.router_aux_coef
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# Mamba-1 selective SSM
# ---------------------------------------------------------------------------

def init_mamba(key, cfg: ArchConfig) -> Params:
    mc = cfg.mamba
    d = cfg.d_model
    di = mc.expand * d
    dt_rank = max(d // 16, 1)
    ks = jax.random.split(key, 6)
    A = jnp.tile(jnp.arange(1, mc.d_state + 1, dtype=jnp.float32)[None], (di, 1))
    return {
        "in_proj": _dense_init(ks[0], (d, 2 * di)),
        "conv_w": _dense_init(ks[1], (mc.d_conv, di), scale=0.2),
        "x_proj": _dense_init(ks[2], (di, dt_rank + 2 * mc.d_state)),
        "dt_proj": _dense_init(ks[3], (dt_rank, di), scale=dt_rank ** -0.5),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.clip(jax.random.uniform(ks[4], (di,)) * 0.1, 1e-3, None))),
        "A_log": jnp.log(A),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _dense_init(ks[5], (di, d), scale=1.0 / math.sqrt(di)),
    }


def _mamba_gates(params, x, cfg: ArchConfig, conv_state=None):
    """Shared pre-scan computation. Returns raw gates — the discretized
    (B,S,di,ds) tensors are formed *inside* the scan implementations so the
    chunked / pallas paths never materialize them in HBM."""
    mc = cfg.mamba
    d = cfg.d_model
    di = mc.expand * d
    dt_rank = max(d // 16, 1)
    B_, S, _ = x.shape
    xz = x @ params["in_proj"].astype(x.dtype)
    xin, z = jnp.split(xz, 2, axis=-1)                        # (B,S,di)
    # causal depthwise conv
    w = params["conv_w"].astype(x.dtype)                      # (d_conv, di)
    if conv_state is None:
        pad = jnp.zeros((B_, mc.d_conv - 1, di), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)
    xp = jnp.concatenate([pad, xin], axis=1)
    new_conv_state = xp[:, -(mc.d_conv - 1):] if mc.d_conv > 1 else pad
    xc = sum(xp[:, i:i + S] * w[i] for i in range(mc.d_conv))
    xc = jax.nn.silu(xc)
    proj = xc @ params["x_proj"].astype(x.dtype)
    dt_lr, Bv, Cv = jnp.split(proj, [dt_rank, dt_rank + mc.d_state], axis=-1)
    dt = jax.nn.softplus(dt_lr @ params["dt_proj"].astype(x.dtype)
                         + params["dt_bias"].astype(x.dtype))     # (B,S,di)
    A = -jnp.exp(params["A_log"]).astype(jnp.float32)             # (di,ds)
    return dt, A, Bv, Cv, xc, z, new_conv_state


def _mamba_comb(l, r):
    al, bl = l
    ar, br = r
    return al * ar, bl * ar + br


def _mamba_discretize(x, dt, A, Bv):
    a = jnp.exp(dt.astype(jnp.float32)[..., None] * A)            # (...,di,ds)
    bx = (dt * x).astype(jnp.float32)[..., None] * \
        Bv.astype(jnp.float32)[..., None, :]
    return a, bx


def mamba_scan_ref(x, dt, A, Bv, Cv):
    """Oracle: associative scan over the full sequence (materializes
    (B,S,di,ds) in fp32 — smoke shapes only)."""
    a, bx = _mamba_discretize(x, dt, A, Bv)
    _, h = lax.associative_scan(_mamba_comb, (a, bx), axis=1)
    y = jnp.einsum("bsdn,bsn->bsd", h, Cv.astype(jnp.float32))
    return y, h[:, -1]


def mamba_scan_chunked(x, dt, A, Bv, Cv, chunk: int):
    """lax.scan over chunks; gates discretized per-chunk so the live state
    tensor is bounded to (B, chunk, di, ds)."""
    B, S, di = x.shape
    ds = A.shape[1]
    c = min(chunk, S)
    n = -(-S // c)
    pad = n * c - S
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    dtp = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    Bp = jnp.pad(Bv, ((0, 0), (0, pad), (0, 0)))
    Cp = jnp.pad(Cv, ((0, 0), (0, pad), (0, 0)))
    resh = lambda t: t.reshape(B, n, c, -1).transpose(1, 0, 2, 3)
    xs_all = (resh(xp), resh(dtp), resh(Bp), resh(Cp))

    @partial(jax.checkpoint, prevent_cse=False)
    def step(h0, xs):
        xi, dti, Bi, Ci = xs
        ai, bi = _mamba_discretize(xi, dti, A, Bi)
        aa, hh = lax.associative_scan(_mamba_comb, (ai, bi), axis=1)
        hh = hh + aa * h0[:, None]
        y = jnp.einsum("bsdn,bsn->bsd", hh, Ci.astype(jnp.float32))
        return hh[:, -1], y

    h0 = jnp.zeros((B, di, ds), jnp.float32)
    h_last, ys = lax.scan(step, h0, xs_all)
    y = ys.transpose(1, 0, 2, 3).reshape(B, n * c, di)[:, :S]
    return y, h_last


def _mamba_y(x, dt, A, Bv, Cv, opts: ModelOptions):
    if opts.scan_impl == "ref":
        return mamba_scan_ref(x, dt, A, Bv, Cv)
    if opts.scan_impl == "chunked":
        return mamba_scan_chunked(x, dt, A, Bv, Cv, opts.scan_chunk)
    if opts.scan_impl == "pallas":
        from repro.kernels import ops as kops
        y = kops.mamba_scan_fused(x, dt, A, Bv, Cv, chunk=opts.scan_chunk)
        # pallas path recomputes last state only when a cache is needed
        return y, None
    raise ValueError(opts.scan_impl)


def mamba(params: Params, x: jax.Array, cfg: ArchConfig, opts: ModelOptions
          ) -> jax.Array:
    dt, A, Bv, Cv, xc, z, _ = _mamba_gates(params, x, cfg)
    y, _ = _mamba_y(xc, dt, A, Bv, Cv, opts)
    y = (y + xc.astype(jnp.float32) * params["D"]).astype(x.dtype)
    y = y * jax.nn.silu(z)
    return y @ params["out_proj"].astype(x.dtype)


def mamba_decode(params: Params, x: jax.Array, cache: Params, cfg: ArchConfig
                 ) -> Tuple[jax.Array, Params]:
    """Single-token recurrence. cache: {"conv": (B,d_conv-1,di), "ssm": (B,di,ds)}."""
    dt, A, Bv, Cv, xc, z, new_conv = _mamba_gates(params, x, cfg,
                                                  conv_state=cache["conv"])
    a, bx = _mamba_discretize(xc, dt, A, Bv)
    h = cache["ssm"] * a[:, 0] + bx[:, 0]                     # (B,di,ds)
    y = jnp.einsum("bdn,bn->bd", h, Cv[:, 0].astype(jnp.float32))[:, None]
    y = (y + xc.astype(jnp.float32) * params["D"]).astype(x.dtype)
    y = y * jax.nn.silu(z)
    out = y @ params["out_proj"].astype(x.dtype)
    return out, dict(cache, conv=new_conv.astype(cache["conv"].dtype), ssm=h)


# ---------------------------------------------------------------------------
# RWKV-6 time mix
# ---------------------------------------------------------------------------

def init_rwkv(key, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    hd = cfg.rwkv_head_dim
    nh = d // hd
    ks = jax.random.split(key, 7)
    return {
        "wr": _dense_init(ks[0], (d, d)),
        "wk": _dense_init(ks[1], (d, d)),
        "wv": _dense_init(ks[2], (d, d)),
        "wg": _dense_init(ks[3], (d, d)),
        "ww": _dense_init(ks[4], (d, d), scale=0.01),
        "wo": _dense_init(ks[5], (d, d), scale=1.0 / math.sqrt(d)),
        "w_bias": jnp.zeros((d,), jnp.float32) - 6.0,  # base decay ~ exp(-exp(-6))
        "u": _dense_init(ks[6], (nh, hd), scale=0.5),  # per-head bonus
        "mix_r": jnp.full((d,), 0.5, jnp.float32),
        "mix_k": jnp.full((d,), 0.5, jnp.float32),
        "mix_v": jnp.full((d,), 0.5, jnp.float32),
        "mix_w": jnp.full((d,), 0.5, jnp.float32),
        "mix_g": jnp.full((d,), 0.5, jnp.float32),
        "ln_scale": jnp.ones((d,), jnp.float32),
    }


def _rwkv_gates(params, x, shifted, cfg: ArchConfig):
    d = cfg.d_model
    hd = cfg.rwkv_head_dim
    nh = d // hd
    B, S, _ = x.shape

    def mix(name):
        m = params["mix_" + name].astype(x.dtype)
        return x * m + shifted * (1 - m)

    r = (mix("r") @ params["wr"].astype(x.dtype)).reshape(B, S, nh, hd)
    k = (mix("k") @ params["wk"].astype(x.dtype)).reshape(B, S, nh, hd)
    v = (mix("v") @ params["wv"].astype(x.dtype)).reshape(B, S, nh, hd)
    g = jax.nn.silu(mix("g") @ params["wg"].astype(x.dtype))
    wlog = mix("w") @ params["ww"].astype(x.dtype) + params["w_bias"].astype(x.dtype)
    # data-dependent per-channel decay in (0,1): w = exp(-exp(wlog))
    w = jnp.exp(-jnp.exp(wlog.astype(jnp.float32))).reshape(B, S, nh, hd)
    return r, k, v, g, w


def rwkv_scan_ref(r, k, v, w, u):
    """Oracle recurrence, scanned per-step. fp32 state (B,nh,hd,hd).
    y_t = r_t · (S_{t-1} + u ⊙ k_t v_t^T);  S_t = diag(w_t) S_{t-1} + k_t v_t^T.
    """
    B, S, nh, hd = r.shape
    rf, kf, vf, wf = (t.astype(jnp.float32).transpose(1, 0, 2, 3)
                      for t in (r, k, v, w))

    def step(Sst, xs):
        rt, kt, vt, wt = xs
        kv = kt[..., :, None] * vt[..., None, :]              # (B,nh,hd,hd)
        y = jnp.einsum("bhk,bhkv->bhv", rt, Sst + u[None, :, :, None] * kv)
        Sst = wt[..., :, None] * Sst + kv
        return Sst, y

    S0 = jnp.zeros((B, nh, hd, hd), jnp.float32)
    S_last, ys = lax.scan(step, S0, (rf, kf, vf, wf))
    return ys.transpose(1, 0, 2, 3).reshape(B, S, nh * hd), S_last


def rwkv_scan_chunked(r, k, v, w, u, chunk: int):
    """Chunked RWKV6: lax.scan over chunks carrying the (B,nh,hd,hd) state;
    exact associative scan over full states within a chunk. All decay products
    stay in (0,1], so this is overflow-safe (unlike the factorized matmul form,
    where exp(-cumsum log w) is unbounded). The materialized intermediate is
    (B, c, nh, hd, hd), so the chunk is capped small."""
    B, S, nh, hd = r.shape
    c = min(min(chunk, 16), S)
    n = -(-S // c)
    pad = n * c - S
    rf, kf, vf = (jnp.pad(t.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
                  for t in (r, k, v))
    wf = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)),
                 constant_values=1.0)
    shp = (B, n, c, nh, hd)
    rc, kc, vc, wc = (t.reshape(shp).transpose(1, 0, 2, 3, 4)
                      for t in (rf, kf, vf, wf))               # (n,B,c,nh,hd)

    @partial(jax.checkpoint, prevent_cse=False)
    def step(h0, xs):
        ri, ki, vi, wi = xs                                    # (B,c,nh,hd)
        kv = ki[..., :, None] * vi[..., None, :]               # (B,c,nh,hd,hd)
        a = wi[..., :, None]                                   # decay on k-dim

        def comb(l, rgt):
            al, bl = l
            ar, br = rgt
            return al * ar, bl * ar + br

        aa, hh = lax.associative_scan(comb, (jnp.broadcast_to(a, kv.shape), kv),
                                      axis=1)
        hh = hh + aa * h0[:, None]                             # S_t incl. carry
        s_prev = jnp.concatenate([h0[:, None], hh[:, :-1]], axis=1)
        y = jnp.einsum("bchk,bchkv->bchv", ri, s_prev)
        bonus = jnp.einsum("bchk,hk,bchk->bch", ri, u, ki)
        y = y + bonus[..., None] * vi
        return hh[:, -1], y

    S0 = jnp.zeros((B, nh, hd, hd), jnp.float32)
    S_last, ys = lax.scan(step, S0, (rc, kc, vc, wc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, n * c, nh * hd)[:, :S]
    return y, S_last


def _rwkv_out(params, y, g, x, cfg: ArchConfig):
    B, S, _ = x.shape
    d = cfg.d_model
    hd = cfg.rwkv_head_dim
    nh = d // hd
    # per-head groupnorm, as in RWKV6
    yh = y.reshape(B, S, nh, hd)
    mu = yh.mean(-1, keepdims=True)
    var = yh.var(-1, keepdims=True)
    yh = (yh - mu) * lax.rsqrt(var + 1e-5)
    y = yh.reshape(B, S, d) * params["ln_scale"]
    y = (y.astype(x.dtype) * g)
    return y @ params["wo"].astype(x.dtype)


def rwkv(params: Params, x: jax.Array, shifted: jax.Array, cfg: ArchConfig,
         opts: ModelOptions) -> jax.Array:
    r, k, v, g, w = _rwkv_gates(params, x, shifted, cfg)
    u = params["u"].astype(jnp.float32)
    if opts.scan_impl == "ref":
        y, _ = rwkv_scan_ref(r, k, v, w, u)
    elif opts.scan_impl == "chunked":
        y, _ = rwkv_scan_chunked(r, k, v, w, u, opts.scan_chunk)
    elif opts.scan_impl == "pallas":
        from repro.kernels import ops as kops
        y = kops.rwkv_scan(r.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), w.astype(jnp.float32), u)
    else:
        raise ValueError(opts.scan_impl)
    return _rwkv_out(params, y, g, x, cfg)


def rwkv_decode(params: Params, x: jax.Array, cache: Params, cfg: ArchConfig
                ) -> Tuple[jax.Array, Params]:
    """cache: {"state": (B,nh,hd,hd) fp32, "shift": (B,1,D)}."""
    shifted = cache["shift"].astype(x.dtype)
    r, k, v, g, w = _rwkv_gates(params, x, shifted, cfg)
    u = params["u"].astype(jnp.float32)
    rf, kf, vf, wf = (t.astype(jnp.float32)[:, 0] for t in (r, k, v, w))
    Sst = cache["state"]
    kv = kf[..., :, None] * vf[..., None, :]
    y = jnp.einsum("bhk,bhkv->bhv", rf, Sst + u[None, :, :, None] * kv)
    Sst = wf[..., :, None] * Sst + kv
    y = y.reshape(x.shape[0], 1, -1)
    out = _rwkv_out(params, y, g, x, cfg)
    return out, dict(cache, state=Sst, shift=x)
