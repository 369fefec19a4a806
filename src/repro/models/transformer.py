"""Model assembly: init / train forward / prefill / decode for every arch.

All ten assigned architectures lower through this module. The repeated
``block_pattern`` is scanned with ``lax.scan`` (stacked params, one traced
block body) so 88-layer models compile as fast as 2-layer ones; heterogeneous
patterns (Jamba's 7:1 Mamba:attention, the VLM's cross-attention interleave)
unroll *within* one block only.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import (ATTN, DENSE, MAMBA, MOE, RWKV, RWKVMIX, SWA,
                                XATTN, ArchConfig, LayerSpec)
from repro.models import layers as L
from repro.models.layers import ModelOptions, Params


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ArchConfig, spec: LayerSpec) -> Params:
    k_mix, k_mlp = jax.random.split(key)
    p: Params = {"norm1": L.init_rmsnorm(cfg), "norm2": L.init_rmsnorm(cfg)}
    if spec.mixer in (ATTN, SWA, XATTN):
        p["mixer"] = L.init_attention(k_mix, cfg, spec)
    elif spec.mixer == MAMBA:
        p["mixer"] = L.init_mamba(k_mix, cfg)
    elif spec.mixer == RWKV:
        p["mixer"] = L.init_rwkv(k_mix, cfg)
    else:
        raise ValueError(spec.mixer)
    if spec.mlp == DENSE:
        p["mlp"] = L.init_mlp(k_mlp, cfg)
    elif spec.mlp == MOE:
        p["mlp"] = L.init_moe(k_mlp, cfg)
    elif spec.mlp == RWKVMIX:
        p["mlp"] = L.init_rwkv_mix(k_mlp, cfg)
    else:
        raise ValueError(spec.mlp)
    return p


def init_params(key, cfg: ArchConfig, param_dtype=jnp.float32) -> Params:
    """Stacked-per-pattern-position parameters; leading dim = num_blocks."""
    keys = jax.random.split(key, 3 + len(cfg.block_pattern))
    params: Params = {}
    if not cfg.embeds_in:
        params["embed"] = jax.random.normal(
            keys[0], (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02
    blocks = []
    for i, spec in enumerate(cfg.block_pattern):
        bkeys = jax.random.split(keys[1 + i], cfg.num_blocks)
        stacked = jax.vmap(lambda k: init_layer(k, cfg, spec))(bkeys)
        blocks.append(stacked)
    params["blocks"] = tuple(blocks)
    params["final_norm"] = L.init_rmsnorm(cfg)
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            keys[-1], (cfg.d_model, cfg.vocab_size), jnp.float32) * 0.02
    cast = lambda x: x.astype(param_dtype) if x.dtype == jnp.float32 else x
    return jax.tree.map(cast, params)


# ---------------------------------------------------------------------------
# Token shift helper for RWKV (train path needs x shifted right by one)
# ---------------------------------------------------------------------------

def _shift_right(x: jax.Array) -> jax.Array:
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


# ---------------------------------------------------------------------------
# Single layer apply (full-sequence)
# ---------------------------------------------------------------------------

def apply_layer(p: Params, x: jax.Array, cfg: ArchConfig, spec: LayerSpec,
                opts: ModelOptions, positions: jax.Array,
                xctx: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Pre-norm residual layer. Returns (x, moe_aux). Each half carries its
    scope (``attn`` for the mixer, ``mlp``), residual add included; the
    norms carry ``norm``."""
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps, opts)
    with jax.named_scope("attn"):
        if spec.mixer in (ATTN, SWA, XATTN):
            mix = L.attention(p["mixer"], h, cfg, spec, opts, positions, xctx)
        elif spec.mixer == MAMBA:
            mix = L.mamba(p["mixer"], h, cfg, opts)
        elif spec.mixer == RWKV:
            mix = L.rwkv(p["mixer"], h, _shift_right(h), cfg, opts)
        else:
            raise ValueError(spec.mixer)
        x = x + mix
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps, opts)
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("mlp"):
        if spec.mlp == DENSE:
            out = L.mlp(p["mlp"], h)
        elif spec.mlp == MOE:
            out, aux = L.moe(p["mlp"], h, cfg, opts)
        elif spec.mlp == RWKVMIX:
            out = L.rwkv_mix(p["mlp"], h, _shift_right(h))
        else:
            raise ValueError(spec.mlp)
        return x + out, aux


# ---------------------------------------------------------------------------
# Backbone (full-sequence): shared by train & prefill
# ---------------------------------------------------------------------------

def backbone(params: Params, h: jax.Array, cfg: ArchConfig, opts: ModelOptions,
             positions: jax.Array, xctx: Optional[jax.Array]
             ) -> Tuple[jax.Array, jax.Array]:
    """h: (B,S,D) embedded input -> (final hidden, total moe aux)."""

    h = L.constrain_acts(h, opts)

    def block_fn(carry, block_params):
        x, aux = carry
        for spec, bp in zip(cfg.block_pattern, block_params):
            x, a = apply_layer(bp, x, cfg, spec, opts, positions, xctx)
            aux = aux + a
        x = L.constrain_acts(x, opts)
        return (x, aux), None

    if opts.remat:
        block_fn = jax.checkpoint(block_fn, prevent_cse=False)

    aux0 = jnp.zeros((), jnp.float32)
    with jax.named_scope("layer_scan"):
        if opts.scan_blocks:
            (h, aux), _ = lax.scan(block_fn, (h, aux0), params["blocks"])
        else:
            carry = (h, aux0)
            for i in range(cfg.num_blocks):
                blk = jax.tree.map(lambda a: a[i], params["blocks"])
                carry, _ = block_fn(carry, blk)
            h, aux = carry
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps, opts)
    return h, aux


def embed(params: Params, tokens_or_embeds: jax.Array, cfg: ArchConfig,
          opts: ModelOptions) -> jax.Array:
    if cfg.embeds_in:
        return tokens_or_embeds.astype(opts.dtype)
    e = jnp.take(params["embed"], tokens_or_embeds, axis=0)
    return e.astype(opts.dtype)


def unembed_logits(params: Params, h: jax.Array, cfg: ArchConfig) -> jax.Array:
    w = params["lm_head"] if not cfg.tie_embeddings else params["embed"].T
    return (h @ w.astype(h.dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Loss (with optional chunked cross-entropy that never materializes B,S,V)
# ---------------------------------------------------------------------------

def _xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - gold


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg: ArchConfig,
            opts: ModelOptions) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    inputs = batch["inputs"]          # (B,S) int32 or (B,S,D) embeds
    labels = batch["labels"]          # (B,S) int32
    xctx = batch.get("xctx")
    h = embed(params, inputs, cfg, opts)
    B, S = labels.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    h, aux = backbone(params, h, cfg, opts, positions, xctx)

    if opts.logit_chunk and S > opts.logit_chunk:
        c = opts.logit_chunk
        n = S // c
        assert S % c == 0, "logit_chunk must divide seq len"
        hs = h.reshape(B, n, c, -1).transpose(1, 0, 2, 3)
        ls = labels.reshape(B, n, c).transpose(1, 0, 2)

        def chunk(tot, xs):
            hi, li = xs
            logits = unembed_logits(params, hi, cfg)
            return tot + _xent(logits, li).sum(), None

        total, _ = lax.scan(chunk, jnp.zeros((), jnp.float32), (hs, ls))
        ce = total / (B * S)
    else:
        logits = unembed_logits(params, h, cfg)
        ce = _xent(logits, labels).mean()
    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "moe_aux": aux}


# ---------------------------------------------------------------------------
# KV / state cache
# ---------------------------------------------------------------------------

def _layer_cache_shape(cfg: ArchConfig, spec: LayerSpec, batch: int,
                       max_len: int, dtype) -> Dict[str, Any]:
    """Shape-dtype tree for one layer's decode cache (no allocation here)."""
    s = jax.ShapeDtypeStruct
    if spec.mixer in (ATTN, SWA, XATTN):
        T = min(cfg.sliding_window, max_len) if spec.mixer == SWA else max_len
        c = {
            "k": s((batch, T, cfg.n_kv_heads, cfg.head_dim), dtype),
            "v": s((batch, T, cfg.n_kv_heads, cfg.head_dim), dtype),
            "slot_pos": s((T,), jnp.int32),
            "pos": s((), jnp.int32),
        }
        if spec.mixer == XATTN:
            c["xk"] = s((batch, cfg.xattn_ctx_len, cfg.n_kv_heads, cfg.head_dim), dtype)
            c["xv"] = s((batch, cfg.xattn_ctx_len, cfg.n_kv_heads, cfg.head_dim), dtype)
        return c
    if spec.mixer == MAMBA:
        di = cfg.mamba.expand * cfg.d_model
        return {
            "conv": s((batch, cfg.mamba.d_conv - 1, di), dtype),
            "ssm": s((batch, di, cfg.mamba.d_state), jnp.float32),
            "pos": s((), jnp.int32),
        }
    if spec.mixer == RWKV:
        nh = cfg.d_model // cfg.rwkv_head_dim
        return {
            "state": s((batch, nh, cfg.rwkv_head_dim, cfg.rwkv_head_dim), jnp.float32),
            "shift": s((batch, 1, cfg.d_model), dtype),
            "shift_mlp": s((batch, 1, cfg.d_model), dtype),
            "pos": s((), jnp.int32),
        }
    raise ValueError(spec.mixer)


def cache_spec(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Stacked (num_blocks-leading) ShapeDtypeStruct cache tree."""
    out = []
    for spec in cfg.block_pattern:
        one = _layer_cache_shape(cfg, spec, batch, max_len, dtype)
        stacked = jax.tree.map(
            lambda sd: jax.ShapeDtypeStruct((cfg.num_blocks,) + sd.shape, sd.dtype),
            one)
        out.append(stacked)
    return tuple(out)


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    spec = cache_spec(cfg, batch, max_len, dtype)

    def mk(sd):
        if sd.dtype == jnp.int32 and sd.shape[-1:] != ():  # slot_pos arrays
            return jnp.full(sd.shape, -1, jnp.int32)
        return jnp.zeros(sd.shape, sd.dtype)

    return jax.tree.map(mk, spec)


# ---------------------------------------------------------------------------
# Decode step (one token) — scan over blocks threading the cache
# ---------------------------------------------------------------------------

def _block_loop(params: Params, cache, h, cfg: ArchConfig, opts: ModelOptions,
                block_fn):
    """Scan (or unroll) ``block_fn`` over the stacked blocks, threading the
    cache: ``(h, new_cache)``. Under the ``layer_scan`` scope, so the scan's
    own slicing and restacking of each layer's cache carry that scope and
    no scope of the layer body."""
    with jax.named_scope("layer_scan"):
        if opts.scan_blocks:
            return lax.scan(block_fn, h, (params["blocks"], cache))
        outs = []
        for i in range(cfg.num_blocks):
            blk = jax.tree.map(lambda a: a[i], params["blocks"])
            cb = jax.tree.map(lambda a: a[i], cache)
            h, nc = block_fn(h, (blk, cb))
            outs.append(nc)
        return h, jax.tree.map(lambda *xs: jnp.stack(xs), *outs)


def apply_layer_decode(p: Params, x: jax.Array, cache_l: Params,
                       cfg: ArchConfig, spec: LayerSpec, opts: ModelOptions,
                       slots: bool = False, paged_tables=None,
                       paged_max_len: int = 0) -> Tuple[jax.Array, Params]:
    """One decode layer, scoped as ``apply_layer``."""
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps, opts)
    with jax.named_scope("attn"):
        if spec.mixer in (ATTN, SWA, XATTN):
            if paged_tables is not None:
                mix, cache_l = L.attention_decode_paged(
                    p["mixer"], h, cache_l, paged_tables, cfg, opts,
                    paged_max_len)
            else:
                attn_fn = (L.attention_decode_slots if slots
                           else L.attention_decode)
                mix, cache_l = attn_fn(p["mixer"], h, cache_l, cfg, spec,
                                       opts)
        elif spec.mixer == MAMBA:
            mix, cache_l = L.mamba_decode(p["mixer"], h, cache_l, cfg)
            cache_l = dict(cache_l, pos=cache_l["pos"] + 1)
        elif spec.mixer == RWKV:
            mix, cache_l = L.rwkv_decode(p["mixer"], h, cache_l, cfg)
            cache_l = dict(cache_l, pos=cache_l["pos"] + 1)
        else:
            raise ValueError(spec.mixer)
        x = x + mix
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps, opts)
    with jax.named_scope("mlp"):
        if spec.mlp == DENSE:
            out = L.mlp(p["mlp"], h)
        elif spec.mlp == MOE:
            out, _ = L.moe(p["mlp"], h, cfg, opts)
        elif spec.mlp == RWKVMIX:
            out = L.rwkv_mix(p["mlp"], h,
                             cache_l["shift_mlp"].astype(h.dtype))
            cache_l = dict(cache_l, shift_mlp=h)
        else:
            raise ValueError(spec.mlp)
        return x + out, cache_l


def decode_step(params: Params, cache, tokens: jax.Array, cfg: ArchConfig,
                opts: ModelOptions, slots: bool = False
                ) -> Tuple[jax.Array, Any]:
    """tokens: (B,) int32 (or (B,D) embeds) -> (logits (B,V), new cache).

    With ``slots=True`` the cache is in slot layout (``slot_pos``: (B,T),
    ``pos``: (B,) per layer) and every batch row decodes at its own position —
    the serving-engine decode. See ``repro.serve.cache`` for the layout.
    """
    if cfg.embeds_in:
        h = tokens[:, None, :].astype(opts.dtype)
    else:
        h = jnp.take(params["embed"], tokens[:, None], axis=0).astype(opts.dtype)

    def block_fn(x, xs):
        block_params, cache_b = xs
        new_c = []
        for spec, bp, cl in zip(cfg.block_pattern, block_params, cache_b):
            x, cl = apply_layer_decode(bp, x, cl, cfg, spec, opts, slots=slots)
            new_c.append(cl)
        return x, tuple(new_c)

    h, new_cache = _block_loop(params, cache, h, cfg, opts, block_fn)
    with jax.named_scope("head"):
        h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps, opts)
        logits = unembed_logits(params, h, cfg)[:, 0]
    return logits, new_cache


def decode_step_slots(params: Params, cache, tokens: jax.Array,
                      cfg: ArchConfig, opts: ModelOptions
                      ) -> Tuple[jax.Array, Any]:
    """Slot-layout decode: each batch row at its own position (serving)."""
    return decode_step(params, cache, tokens, cfg, opts, slots=True)


def _check_pageable(cfg: ArchConfig, what: str) -> None:
    """Paged KV (and shared-prefix prefill) covers attention KV only; the
    recurrent mixers carry dense per-slot state with no block structure, and
    the RWKV channel-mix shift depends on the final (padded) position."""
    for spec in cfg.block_pattern:
        if spec.mixer != ATTN or spec.mlp == RWKVMIX:
            raise ValueError(
                f"{what} supports plain-attention architectures only "
                f"(got mixer={spec.mixer!r}, mlp={spec.mlp!r}); run this "
                "arch with the slotted KV backend")


def decode_step_paged(params: Params, cache, tokens: jax.Array,
                      tables: jax.Array, cfg: ArchConfig, opts: ModelOptions,
                      max_len: int) -> Tuple[jax.Array, Any]:
    """Paged-KV decode: tokens (B,) int32, tables (B, nb) block map.

    cache per layer group: {"kp": (L, P+1, bs, HKV, dh), "vp": ..., "pos":
    (L, B)} — the physical block pool plus per-slot positions. The block
    table is shared by all layers (one virtual address space per slot, L
    physical pools), so it is threaded beside the cache, not inside it.
    """
    _check_pageable(cfg, "decode_step_paged")
    h = jnp.take(params["embed"], tokens[:, None], axis=0).astype(opts.dtype)

    def block_fn(x, xs):
        block_params, cache_b = xs
        new_c = []
        for spec, bp, cl in zip(cfg.block_pattern, block_params, cache_b):
            x, cl = apply_layer_decode(bp, x, cl, cfg, spec, opts,
                                       paged_tables=tables,
                                       paged_max_len=max_len)
            new_c.append(cl)
        return x, tuple(new_c)

    h, new_cache = _block_loop(params, cache, h, cfg, opts, block_fn)
    with jax.named_scope("head"):
        h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps, opts)
        logits = unembed_logits(params, h, cfg)[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Serve chunk step: the unified serve program's prefill half
# ---------------------------------------------------------------------------

def _serve_chunk_block(params: Params, cache, h, cfg: ArchConfig,
                       opts: ModelOptions, layer_fn):
    """Shared block loop for the serve chunk passes: scan (or unroll) the
    stacked blocks threading the cache (the caller finishes with final norm
    + logits)."""
    def block_fn(x, xs):
        block_params, cache_b = xs
        new_c = []
        for spec, bp, cl in zip(cfg.block_pattern, block_params, cache_b):
            x, cl = layer_fn(spec, bp, x, cl)
            new_c.append(cl)
        return x, tuple(new_c)

    return _block_loop(params, cache, h, cfg, opts, block_fn)


def _chunk_mlp(p: Params, x, mix, cfg: ArchConfig, spec: LayerSpec,
               opts: ModelOptions):
    """The rest of a chunk layer after its attention: the residual add
    (under ``attn``, as in ``apply_layer``), then the MLP half."""
    with jax.named_scope("attn"):
        x = x + mix
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps, opts)
    with jax.named_scope("mlp"):
        if spec.mlp == MOE:
            out, _ = L.moe(p["mlp"], h, cfg, opts)
        else:
            out = L.mlp(p["mlp"], h)
        return x + out


def _chunk_logits(params: Params, h, clen, cfg: ArchConfig,
                  opts: ModelOptions):
    """Logits at each row's last real chunk position (clamped for rows with
    no chunk — their output is discarded by the caller's emit mask)."""
    with jax.named_scope("head"):
        h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps, opts)
        last = jnp.take_along_axis(
            h, jnp.clip(clen - 1, 0, h.shape[1] - 1)[:, None, None], axis=1)
        return unembed_logits(params, last, cfg)[:, 0]


def serve_chunk_step(params: Params, cache, tokens: jax.Array,
                     start: jax.Array, clen: jax.Array, reset: jax.Array,
                     cfg: ArchConfig, opts: ModelOptions
                     ) -> Tuple[jax.Array, Any]:
    """Chunked-prefill pass over the slot cache: every row absorbs its own
    variable-length prompt chunk in one program (see ``build_serve_step``).

    tokens: (B, W) right-padded chunk ids; start/clen: (B,) per-row write
    position and true length; reset: (B,) bool — rows admitted this step
    get their stale ``slot_pos`` marks invalidated before the write.
    Returns (per-row logits at position ``start + clen - 1``, new cache).
    """
    _check_pageable(cfg, "serve_chunk_step")
    cache = tuple(dict(g, slot_pos=jnp.where(reset[None, :, None], -1,
                                             g["slot_pos"]))
                  for g in cache)
    h = jnp.take(params["embed"], tokens, axis=0).astype(opts.dtype)

    def layer_fn(spec, bp, x, cl):
        hh = L.rmsnorm(bp["norm1"], x, cfg.norm_eps, opts)
        with jax.named_scope("attn"):
            mix, cl = L.attention_serve_chunk(bp["mixer"], hh, cl, cfg,
                                              opts, start, clen)
        x = _chunk_mlp(bp, x, mix, cfg, spec, opts)
        return x, cl

    h, new_cache = _serve_chunk_block(params, cache, h, cfg, opts, layer_fn)
    return _chunk_logits(params, h, clen, cfg, opts), new_cache


def serve_chunk_step_paged(params: Params, cache, tokens: jax.Array,
                           tables: jax.Array, start: jax.Array,
                           clen: jax.Array, cfg: ArchConfig,
                           opts: ModelOptions, max_len: int
                           ) -> Tuple[jax.Array, Any]:
    """``serve_chunk_step`` against the paged block pools (tables: (B, nb)).
    No reset mask: paged validity is positional, and released slots point
    at the trash block."""
    _check_pageable(cfg, "serve_chunk_step_paged")
    h = jnp.take(params["embed"], tokens, axis=0).astype(opts.dtype)

    def layer_fn(spec, bp, x, cl):
        hh = L.rmsnorm(bp["norm1"], x, cfg.norm_eps, opts)
        with jax.named_scope("attn"):
            mix, cl = L.attention_serve_chunk_paged(
                bp["mixer"], hh, cl, tables, cfg, opts, start, clen,
                max_len)
        x = _chunk_mlp(bp, x, mix, cfg, spec, opts)
        return x, cl

    h, new_cache = _serve_chunk_block(params, cache, h, cfg, opts, layer_fn)
    return _chunk_logits(params, h, clen, cfg, opts), new_cache


# ---------------------------------------------------------------------------
# Serve verify step: the chunk pass shape, logits at EVERY fed position
# ---------------------------------------------------------------------------

def _verify_logits(params: Params, h, cfg: ArchConfig, opts: ModelOptions):
    """Logits at all W fed positions: (B, W, V). The verify pass needs the
    model's next-token distribution after each drafted prefix, not just the
    chunk's last position."""
    with jax.named_scope("head"):
        h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps, opts)
        return unembed_logits(params, h, cfg)


def serve_verify_step(params: Params, cache, tokens: jax.Array,
                      start: jax.Array, clen: jax.Array, cfg: ArchConfig,
                      opts: ModelOptions) -> Tuple[jax.Array, Any]:
    """Speculative verify over the slot cache: the ``serve_chunk_step``
    write-then-attend pass, but returning logits at every fed position
    (B, W, V) so the caller can resolve the longest accepted draft prefix.

    No reset mask: verify rows are mid-generation (their cache rows are
    live), and padding rows keep clen 0, writing nothing.
    """
    _check_pageable(cfg, "serve_verify_step")
    h = jnp.take(params["embed"], tokens, axis=0).astype(opts.dtype)

    def layer_fn(spec, bp, x, cl):
        hh = L.rmsnorm(bp["norm1"], x, cfg.norm_eps, opts)
        with jax.named_scope("attn"):
            mix, cl = L.attention_serve_chunk(bp["mixer"], hh, cl, cfg,
                                              opts, start, clen)
        x = _chunk_mlp(bp, x, mix, cfg, spec, opts)
        return x, cl

    h, new_cache = _serve_chunk_block(params, cache, h, cfg, opts, layer_fn)
    return _verify_logits(params, h, cfg, opts), new_cache


def serve_verify_step_paged(params: Params, cache, tokens: jax.Array,
                            tables: jax.Array, start: jax.Array,
                            clen: jax.Array, cfg: ArchConfig,
                            opts: ModelOptions, max_len: int
                            ) -> Tuple[jax.Array, Any]:
    """``serve_verify_step`` against the paged block pools (tables: (B, nb)):
    the ``serve_chunk_step_paged`` pass with all-position logits (B, W, V)."""
    _check_pageable(cfg, "serve_verify_step_paged")
    h = jnp.take(params["embed"], tokens, axis=0).astype(opts.dtype)

    def layer_fn(spec, bp, x, cl):
        hh = L.rmsnorm(bp["norm1"], x, cfg.norm_eps, opts)
        with jax.named_scope("attn"):
            mix, cl = L.attention_serve_chunk_paged(
                bp["mixer"], hh, cl, tables, cfg, opts, start, clen,
                max_len)
        x = _chunk_mlp(bp, x, mix, cfg, spec, opts)
        return x, cl

    h, new_cache = _serve_chunk_block(params, cache, h, cfg, opts, layer_fn)
    return _verify_logits(params, h, cfg, opts), new_cache


# ---------------------------------------------------------------------------
# Prefill: full forward that also fills the cache
# ---------------------------------------------------------------------------

def prefill(params: Params, tokens: jax.Array, cfg: ArchConfig,
            opts: ModelOptions, max_len: int,
            xctx: Optional[jax.Array] = None,
            true_len: Optional[jax.Array] = None) -> Tuple[jax.Array, Any]:
    """Run the full sequence, return (last-position logits, filled cache).

    The cache is produced by re-running each layer's mixer state computation;
    attention layers write their K/V directly (cheap — already computed).

    ``true_len`` (traced scalar) enables *bucketed* prefill: ``tokens`` is a
    right-padded bucket and only the first ``true_len`` positions are real.
    Causality makes the padding invisible to the real positions, so the
    returned logits are taken at ``true_len - 1`` and the cache is fixed up
    (``pos = true_len``, padded ``slot_pos`` entries invalidated) to be
    indistinguishable from an unpadded prefill. Full-window attention only:
    recurrent state (Mamba/RWKV) would be left at the padded end, and an SWA
    circular buffer shorter than the bucket would rotate *real* positions
    out in favor of padding.
    """
    B, S = tokens.shape[:2]
    if true_len is not None:
        for spec in cfg.block_pattern:
            if spec.mixer not in (ATTN, XATTN) or spec.mlp == RWKVMIX:
                raise ValueError(
                    "bucketed prefill (true_len) needs full-window attention "
                    f"layers; got mixer={spec.mixer!r}, mlp={spec.mlp!r}")
    h = embed(params, tokens, cfg, opts)
    positions = jnp.arange(S, dtype=jnp.int32)
    cache = init_cache(cfg, B, max_len, opts.dtype)

    def block_fn(x, xs):
        block_params, cache_b = xs
        new_c = []
        for spec, bp, cl in zip(cfg.block_pattern, block_params, cache_b):
            x, cl = _prefill_layer(bp, x, cl, cfg, spec, opts, positions, xctx)
            new_c.append(cl)
        return x, tuple(new_c)

    h, new_cache = _block_loop(params, cache, h, cfg, opts, block_fn)
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps, opts)
    if true_len is None:
        logits = unembed_logits(params, h[:, -1:], cfg)[:, 0]
    else:
        last = lax.dynamic_slice_in_dim(h, true_len - 1, 1, axis=1)
        logits = unembed_logits(params, last, cfg)[:, 0]
        fixed = []
        for g in new_cache:
            g = dict(g, pos=jnp.full_like(g["pos"], true_len))
            if "slot_pos" in g:
                sp = g["slot_pos"]
                g["slot_pos"] = jnp.where((sp >= 0) & (sp < true_len), sp, -1)
            fixed.append(g)
        new_cache = tuple(fixed)
    return logits, new_cache


def prefill_suffix(params: Params, tokens: jax.Array, prefix_kv: Tuple,
                   prefix_len: jax.Array, cfg: ArchConfig, opts: ModelOptions,
                   true_len: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Tuple]:
    """Prefill only the *suffix* of a prompt whose first ``prefix_len``
    positions' attention K/V are already resident (shared-prefix admission:
    the paged engine found the prefix in its radix index, so an identical
    system prompt is prefilled once and only the per-request tail is run).

    tokens:    (B, S) suffix token ids at positions ``prefix_len + i``
               (right-padded to a bucket when ``true_len`` is given).
    prefix_kv: tuple per layer group of {"k","v"}: (L, B, Tpre, HKV, dh)
               gathered from the block pool; entries at ``arange(Tpre) >=
               prefix_len`` are garbage and are masked out here.

    Returns (logits at suffix position ``(true_len or S) - 1``, per-group
    {"k","v"} suffix K/V (L, B, S, HKV, dh) for the caller to scatter into
    its physical blocks). Suffix rows attend to [masked prefix ++ causal
    suffix] via explicit q/k positions, so each real row computes exactly
    what a full prefill computes for it.
    """
    _check_pageable(cfg, "prefill_suffix")
    B, S = tokens.shape
    Tpre = prefix_kv[0]["k"].shape[2]
    h = embed(params, tokens, cfg, opts)
    q_pos = prefix_len + jnp.arange(S, dtype=jnp.int32)
    # the suffix K/V is written *into* the prefix buffer at prefix_len (the
    # caller guarantees prefix_len + S <= Tpre), so valid entries sit at
    # index == position exactly as in a full prefill and the causal mask
    # alone separates real from garbage — same indices, same reductions,
    # bit-identical rows.
    k_pos = jnp.arange(Tpre, dtype=jnp.int32)

    def block_fn(x, xs):
        block_params, pre_b = xs
        new_kv = []
        for spec, bp, pkv in zip(cfg.block_pattern, block_params, pre_b):
            x, kv = _prefill_suffix_layer(bp, x, pkv, cfg, spec, opts,
                                          q_pos, k_pos)
            new_kv.append(kv)
        return x, tuple(new_kv)

    h, suffix_kv = _block_loop(params, prefix_kv, h, cfg, opts, block_fn)
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps, opts)
    if true_len is None:
        logits = unembed_logits(params, h[:, -1:], cfg)[:, 0]
    else:
        last = lax.dynamic_slice_in_dim(h, true_len - 1, 1, axis=1)
        logits = unembed_logits(params, last, cfg)[:, 0]
    return logits, suffix_kv


def _prefill_suffix_layer(p, x, pkv, cfg, spec, opts, q_pos, k_pos):
    """One plain-attention layer of the suffix prefill. Returns (x, {k, v})."""
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps, opts)
    with jax.named_scope("attn"):
        B, S, _ = x.shape
        q, k, v = L._qkv(p["mixer"], h, cfg)
        q = L.rope(q, q_pos, cfg.rope_theta)
        k = L.rope(k, q_pos, cfg.rope_theta)
        start = (0, q_pos[0], 0, 0)
        with jax.named_scope("kv_write"):
            k_full = lax.dynamic_update_slice(pkv["k"].astype(k.dtype), k,
                                              start)
            v_full = lax.dynamic_update_slice(pkv["v"].astype(v.dtype), v,
                                              start)
        kwargs = dict(causal=True, window=0, q_pos=q_pos, k_pos=k_pos)
        if opts.attn_impl == "ref":
            out = L._sdpa_ref(q, k_full, v_full, **kwargs)
        else:
            # rectangular q/kv: the blockwise form handles it; the Pallas prefill
            # kernel assumes square q/kv, so "pallas" also lowers through here
            out = L._sdpa_chunked(q, k_full, v_full, q_chunk=opts.q_chunk,
                                  kv_chunk=opts.kv_chunk, **kwargs)
        x = x + out.reshape(B, S, -1) @ p["mixer"]["wo"].astype(x.dtype)
    h2 = L.rmsnorm(p["norm2"], x, cfg.norm_eps, opts)
    with jax.named_scope("mlp"):
        if spec.mlp == MOE:
            out2, _ = L.moe(p["mlp"], h2, cfg, opts)
        else:
            out2 = L.mlp(p["mlp"], h2)
        return x + out2, {"k": k, "v": v}


def _prefill_layer(p, x, cache_l, cfg, spec, opts, positions, xctx):
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps, opts)
    S = x.shape[1]
    with jax.named_scope("attn"):
        if spec.mixer in (ATTN, SWA, XATTN):
            mix = L.attention(p["mixer"], h, cfg, spec, opts, positions, xctx)
            # write K/V into cache (recompute projections; XLA CSEs with the above)
            q, k, v = L._qkv(p["mixer"], h, cfg)
            k = L.rope(k, positions, cfg.rope_theta)
            T = cache_l["k"].shape[1]
            if S >= T:
                # decode assumes a circular layout (position p lives at slot p % T)
                roll = S % T
                cache_l = dict(cache_l,
                               k=jnp.roll(k[:, S - T:], roll, axis=1).astype(cache_l["k"].dtype),
                               v=jnp.roll(v[:, S - T:], roll, axis=1).astype(cache_l["v"].dtype),
                               slot_pos=jnp.roll(positions[S - T:], roll),
                               pos=jnp.asarray(S, jnp.int32))
            else:
                ck = lax.dynamic_update_slice(cache_l["k"], k.astype(cache_l["k"].dtype),
                                              (0, 0, 0, 0))
                cv = lax.dynamic_update_slice(cache_l["v"], v.astype(cache_l["v"].dtype),
                                              (0, 0, 0, 0))
                sp = lax.dynamic_update_slice(cache_l["slot_pos"], positions, (0,))
                cache_l = dict(cache_l, k=ck, v=cv, slot_pos=sp,
                               pos=jnp.asarray(S, jnp.int32))
            if spec.mixer == XATTN:
                hkv, dh = cfg.n_kv_heads, cfg.head_dim
                xk = (xctx @ p["mixer"]["xk"].astype(x.dtype)).reshape(x.shape[0], -1, hkv, dh)
                xv = (xctx @ p["mixer"]["xv"].astype(x.dtype)).reshape(x.shape[0], -1, hkv, dh)
                cache_l = dict(cache_l, xk=xk.astype(cache_l["xk"].dtype),
                               xv=xv.astype(cache_l["xv"].dtype))
        elif spec.mixer == MAMBA:
            dt, A, Bv, Cv, xc, z, conv_state = L._mamba_gates(p["mixer"], h, cfg)
            if opts.scan_impl == "ref":
                y, h_last = L.mamba_scan_ref(xc, dt, A, Bv, Cv)
            else:
                y, h_last = L.mamba_scan_chunked(xc, dt, A, Bv, Cv, opts.scan_chunk)
            y = (y + xc.astype(jnp.float32) * p["mixer"]["D"]).astype(x.dtype)
            y = y * jax.nn.silu(z)
            mix = y @ p["mixer"]["out_proj"].astype(x.dtype)
            cache_l = dict(cache_l, conv=conv_state.astype(cache_l["conv"].dtype),
                           ssm=h_last, pos=jnp.asarray(S, jnp.int32))
        elif spec.mixer == RWKV:
            shifted = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :-1]
            r, k, v, g, w = L._rwkv_gates(p["mixer"], h, shifted, cfg)
            u = p["mixer"]["u"].astype(jnp.float32)
            if opts.scan_impl == "ref":
                y, s_last = L.rwkv_scan_ref(r, k, v, w, u)
            else:
                y, s_last = L.rwkv_scan_chunked(r, k, v, w, u, opts.scan_chunk)
            mix = L._rwkv_out(p["mixer"], y, g, h, cfg)
            cache_l = dict(cache_l, state=s_last, shift=h[:, -1:],
                           pos=jnp.asarray(S, jnp.int32))
        else:
            raise ValueError(spec.mixer)
        x = x + mix
    h2 = L.rmsnorm(p["norm2"], x, cfg.norm_eps, opts)
    with jax.named_scope("mlp"):
        if spec.mlp == DENSE:
            out = L.mlp(p["mlp"], h2)
        elif spec.mlp == MOE:
            out, _ = L.moe(p["mlp"], h2, cfg, opts)
        elif spec.mlp == RWKVMIX:
            shifted2 = jnp.pad(h2, ((0, 0), (1, 0), (0, 0)))[:, :-1]
            out = L.rwkv_mix(p["mlp"], h2, shifted2)
            cache_l = dict(cache_l, shift_mlp=h2[:, -1:])
        else:
            raise ValueError(spec.mlp)
        return x + out, cache_l
