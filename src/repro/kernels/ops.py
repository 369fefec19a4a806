"""Public jit'd wrappers for the Pallas kernels — the UKL "shortcut" entry
points.

Backend dispatch mirrors the paper's spectrum discipline: on TPU the compiled
Mosaic kernel runs; off-TPU (this CPU container, and any host-platform
dry-run) the same kernel body runs under ``interpret=True`` so tests exercise
the real kernel logic, while *lowering* paths that need clean HLO (the
dry-run) use the chunked-jnp formulations in ``repro.models.layers`` instead.

XLA cannot partition a Mosaic custom call, so in a program compiled over a
multi-device mesh every kernel runs per shard under ``shard_map``: the
serving wrappers take the engine's ``mesh`` and split attention over whole
GQA groups on the ``"model"`` axis, exactly as the KV pools are sharded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import (decode_attention as _dec, flash_attention as _fa,
                           mamba_ssm as _mamba, moe_route as _route,
                           paged_decode as _paged,
                           paged_prefill as _paged_pf, rmsnorm as _rms,
                           rwkv6 as _rwkv, slot_decode as _slot)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _head_axis(mesh, n_kv_heads: int):
    """``"model"`` when the KV heads divide over it (each shard then holds
    whole GQA groups, as ``ArchSharding`` shards the KV cache), else None:
    the kernel runs replicated."""
    m = dict(mesh.shape).get("model", 1) if mesh is not None else 1
    return "model" if m > 1 and n_kv_heads % m == 0 else None


def _per_shard(kernel, mesh, in_specs, out_specs):
    """``kernel`` as is on one device; under ``shard_map`` on a mesh."""
    if mesh is None or mesh.size == 1:
        return kernel
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512, mesh=None):
    def kernel(q, k, v):
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k,
                                   interpret=_interpret())
    h = P(None, None, _head_axis(mesh, k.shape[2]), None)
    return _per_shard(kernel, mesh, (h, h, h), h)(q, k, v)


def decode_attention(q, ck, cv, slot_pos, pos, *, window: int = 0,
                     block_t: int = 512):
    """q: (B,1,HQ,dh) fresh query; ck/cv: cache; slot_pos: (T,) positions."""
    valid = (slot_pos <= pos) & (slot_pos >= 0)
    if window > 0:
        valid &= pos - slot_pos < window
    out = _dec.decode_attention(q[:, 0], ck, cv, valid, block_t=block_t,
                                interpret=_interpret())
    return out[:, None]


def slot_decode_attention(q, ck, cv, slot_pos, pos, *, window: int = 0,
                          block_t: int = 512, mesh=None):
    """Slot-aware decode: every batch row is at its own position.

    q: (B,1,HQ,dh) fresh query; ck/cv: (B,T,HKV,dh) slotted cache;
    slot_pos: (B,T) per-slot cache-entry positions; pos: (B,) per-slot
    sequence positions. The per-slot validity mask is precomputed here (like
    the uniform wrapper) so the kernel stays branch-free.
    """
    valid = (slot_pos <= pos[:, None]) & (slot_pos >= 0)
    if window > 0:
        valid &= pos[:, None] - slot_pos < window

    def kernel(q, ck, cv, valid):
        return _slot.slot_decode_attention(q, ck, cv, valid, block_t=block_t,
                                           interpret=_interpret())
    h = _head_axis(mesh, ck.shape[2])
    kv = P(None, None, h, None)
    out = _per_shard(kernel, mesh, (P(None, h, None), kv, kv, P()),
                     P(None, h, None))(q[:, 0], ck, cv, valid)
    return out[:, None]


def _paged_call(kernel, mesh, q, kp, vp, tables, rows, ks, vs):
    """Run a paged kernel ``kernel(q, kp, vp, tables, *rows, *scales)`` (q
    heads on axis -2) on one device or per shard of ``mesh``: the block
    table and the per-row operands ``rows`` (positions, query counts) are
    replicated, the pools and their scale tables split by KV head."""
    scales = (ks, vs) if ks is not None else ()
    h = _head_axis(mesh, kp.shape[2])
    qs = P(*([None] * (q.ndim - 2)), h, None)
    kv = P(None, None, h, None)
    specs = (qs, kv, kv, P()) + (P(),) * len(rows) + (P(None, h),) * len(
        scales)
    return _per_shard(kernel, mesh, specs, qs)(q, kp, vp, tables, *rows,
                                               *scales)


def paged_decode_attention(q, kp, vp, tables, pos, ks=None, vs=None, *,
                           mesh=None):
    """Paged decode: block-table indirection instead of dense slot rows.

    q: (B,1,HQ,dh) fresh query; kp/vp: (P+1,bs,HKV,dh) physical block pools
    (row P is the trash block); tables: (B,nb) int32 logical->physical map;
    pos: (B,) per-slot positions. Validity is logical-position order —
    ``arange(nb*bs) <= pos`` — since block chains are never circular, so
    the kernel forms it from the scalar-prefetched positions.
    ks/vs: optional (P+1,HKV) f32 per-block scales when the pools are
    quantized — dequant fuses into the kernel.
    """
    def kernel(*args):
        return _paged.paged_decode_attention(*args, interpret=_interpret())
    out = _paged_call(kernel, mesh, q[:, 0], kp, vp, tables, (pos,), ks, vs)
    return out[:, None]


def paged_prefill_attention(q, kp, vp, tables, start, ks=None, vs=None, *,
                            qlen=None, mesh=None):
    """Paged chunked-prefill: every slot's prompt chunk attends over its
    resident block chain (the rectangular generalization of paged decode).

    q: (B,W,HQ,dh) chunk queries (the chunk's own K/V already scattered into
    the pools); kp/vp: (P+1,bs,HKV,dh) physical pools; tables: (B,nb) int32
    logical->physical map; start: (B,) first chunk position per row.
    qlen: (B,) real queries per row (default W); each row walks its blocks
    only up to that of its last real query.
    ks/vs: optional (P+1,HKV) f32 per-block scales when the pools are
    quantized — dequant fuses into the kernel.
    """
    if qlen is None:
        qlen = jnp.full(start.shape, q.shape[1], jnp.int32)

    def kernel(q, kp, vp, tables, start, qlen, *scales):
        return _paged_pf.paged_prefill_attention(
            q, kp, vp, tables, start, *scales, qlen=qlen,
            interpret=_interpret())
    return _paged_call(kernel, mesh, q, kp, vp, tables, (start, qlen), ks,
                       vs)


def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 256,
            mesh=None):
    """Fused RMSNorm over the last axis; on a mesh every shard normalizes
    the whole (replicated) row block."""
    def kernel(x, scale):
        return _rms.rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                            interpret=_interpret())
    return _per_shard(kernel, mesh, (P(), P()), P())(x, scale)


def mamba_scan(a_unused, bx_unused, C_unused):  # pragma: no cover
    raise NotImplementedError(
        "use mamba_scan_fused(x, dt, A, Bv, Cv); the fused kernel computes "
        "the discretized gates internally")


def mamba_scan_fused(x, dt, A, Bv, Cv, *, chunk: int = 64, di_tile: int = 256):
    return _mamba.mamba_scan(x, dt, A, Bv, Cv, chunk=chunk, di_tile=di_tile,
                             interpret=_interpret())


def rwkv_scan(r, k, v, w, u, *, chunk: int = 256):
    return _rwkv.rwkv_scan(r, k, v, w, u, chunk=chunk, interpret=_interpret())


def moe_route(x, router, k: int, *, block_n: int = 1024):
    return _route.moe_route(x, router, k, block_n=block_n,
                            interpret=_interpret())
