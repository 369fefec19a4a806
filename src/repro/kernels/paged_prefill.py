"""Paged *prefill* attention: flash chunk-attention over a block-table cache.

The chunked-prefill serve step (``repro.core.step.build_serve_step``) hands
every slot a variable-length prompt chunk whose K/V was just scattered into
the slot's physical blocks. This kernel computes the chunk's queries against
the slot's whole resident prefix — the rectangular (W queries x resident
keys) generalization of ``paged_decode``, which is its W = 1 case:

  * the grid's inner axis walks the slot's *logical* blocks and a
    scalar-prefetched block table translates each step to a physical pool
    row in the BlockSpec index map (the gather happens in the DMA engine,
    never materialized in HBM);
  * a second scalar-prefetched operand carries each row's chunk start
    position, so the causal mask ``k_pos <= q_pos`` is computed from grid
    coordinates alone — tokens already resident are visible to every chunk
    query, later chunk positions are masked per query row. Garbage beyond a
    row's resident end always sits at positions above every real query, so
    it is masked by the same comparison (padding query rows are discarded
    by the caller);
  * a third scalar-prefetched operand carries each row's count of real
    queries, and the walk stops at the block of the row's last real query:
    past it the index maps repeat that block, so the pipeline fetches
    nothing new, and the flash step is skipped. Every real query masks such
    a block whole, so skipping it leaves their outputs bit for bit as they
    were; a row with no real query (a decoding or empty slot in the chunk
    pass) fetches one block, computes nothing and writes zeros;
  * quantized pools (int8 / fp8) carry per-(block, head) f32 scales. Each
    tile's scale pair is fetched into SMEM through the same ``tbl[b, i]``
    indirection as the tile itself, and the dequant fuses into the body.

All W queries of one (batch, kv-head) program are processed together
(W·G x bs score tiles), so each KV block is read exactly once per head —
one pass over the resident cache, the roofline minimum.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import flash_step


def _last_block(start, qlen, bs: int):
    """The last logical block a row's real queries can see: that of its
    last real query, ``start + qlen - 1``; -1 for a row with none."""
    return jnp.where(qlen > 0, lax.div(start + qlen - 1, bs), -1)


def _block(b, i, tbl, st, ql, *, bs: int):
    """Physical pool row of grid step ``i`` of row ``b``: past the row's
    last visible block the index repeats, so the pipeline fetches nothing
    new."""
    return tbl[b, jnp.minimum(i, jnp.maximum(_last_block(st[b], ql[b], bs),
                                             0))]


def _paged_kernel(tbl_ref, start_ref, qlen_ref, q_ref, k_ref, v_ref, *refs,
                  scale: float, nt: int, bs: int, G: int, quant: bool):
    if quant:
        ks_ref, vs_ref, *refs = refs
    o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    i = pl.program_id(2)
    start = start_ref[b]
    WG = q_ref.shape[2]

    def kv(ref, s_ref):
        t = ref[0, 0].astype(jnp.float32)              # (bs, dh)
        return t * s_ref[0, 0, 0, 0] if quant else t   # dequant in VMEM

    def live():
        # causal mask from grid coordinates: query w sits at start[b] + w,
        # key lane j of logical block i sits at i*bs + j
        q_pos = start + lax.broadcasted_iota(jnp.int32, (WG, bs), 0) // G
        k_pos = i * bs + lax.broadcasted_iota(jnp.int32, (WG, bs), 1)
        return k_pos <= q_pos

    # a block past the last real query's is masked for every real query
    # row: skipping it leaves their m, l and acc exactly as they were
    flash_step(lambda: q_ref[0, 0].astype(jnp.float32),     # (W*G, dh)
               lambda: kv(k_ref, ks_ref if quant else None),
               lambda: kv(v_ref, vs_ref if quant else None),
               live, o_ref, m_ref, l_ref, acc_ref, scale=scale, step=i,
               nt=nt, active=i <= _last_block(start, qlen_ref[b], bs))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention(q, kp, vp, tables, start, ks=None, vs=None, *,
                            qlen=None, interpret: bool = False):
    """q:(B,W,HQ,dh) chunk queries; kp,vp:(P+1,bs,HKV,dh) physical pools;
    tables:(B,nb) int32 logical->physical block map; start:(B,) int32 first
    position of each row's chunk; qlen:(B,) int32 real queries of each row
    (default W). -> (B,W,HQ,dh).

    The chunk's own K/V must already be scattered into the pools (the serve
    step writes before it attends). Each row walks its blocks only up to
    that of its last real query, ``(start + qlen - 1) // bs``: query rows
    past ``qlen`` produce garbage the caller discards, and a row with
    ``qlen = 0`` writes zeros.

    ``ks``/``vs`` (P+1, HKV) f32 mark the pools as per-block quantized: the
    dequant fuses into the flash body.
    """
    B, W, HQ, dh = q.shape
    bs, HKV = kp.shape[1], kp.shape[2]
    if qlen is None:
        qlen = jnp.full((B,), W, jnp.int32)
    nb = tables.shape[1]
    G = HQ // HKV
    scale = 1.0 / math.sqrt(dh)
    kT = kp.transpose(0, 2, 1, 3)                      # (P+1, HKV, bs, dh)
    vT = vp.transpose(0, 2, 1, 3)
    dhp = (-dh) % 128
    if dhp:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dhp)))
        kT = jnp.pad(kT, ((0, 0), (0, 0), (0, 0), (0, dhp)))
        vT = jnp.pad(vT, ((0, 0), (0, 0), (0, 0), (0, dhp)))
    dhf = dh + dhp
    # (B, W, HKV, G, dhf) -> (B, HKV, W*G, dhf): all of one KV head's chunk
    # queries ride one program
    qg = q.reshape(B, W, HKV, G, dhf).transpose(0, 2, 1, 3, 4) \
        .reshape(B, HKV, W * G, dhf)

    row = lambda b, h, i, tbl, st, ql: (b, h, 0, 0)
    blk = lambda b, h, i, tbl, st, ql: (_block(b, i, tbl, st, ql, bs=bs),
                                        h, 0, 0)
    in_specs = [
        pl.BlockSpec((1, 1, W * G, dhf), row),
        pl.BlockSpec((1, 1, bs, dhf), blk),
        pl.BlockSpec((1, 1, bs, dhf), blk),
    ]
    operands = [qg, kT, vT]
    quant = ks is not None
    if quant:
        # per-(block, head) scales ride the same table indirection, as
        # scalars in SMEM; the trailing unit dims make each (1, 1) tile span
        # the array's last two dims, as Mosaic's tiling rule asks
        in_specs += [pl.BlockSpec((1, 1, 1, 1), blk,
                                  memory_space=pltpu.SMEM)] * 2
        operands += [ks[..., None, None], vs[..., None, None]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, HKV, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, W * G, dhf), row),
        scratch_shapes=[
            pltpu.VMEM((W * G, 128), jnp.float32),
            pltpu.VMEM((W * G, 128), jnp.float32),
            pltpu.VMEM((W * G, dhf), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, nt=nb, bs=bs, G=G,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, HKV, W * G, dhf), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables, start.astype(jnp.int32), qlen.astype(jnp.int32), *operands)
    return out.reshape(B, HKV, W, G, dhf).transpose(0, 2, 1, 3, 4) \
        .reshape(B, W, HQ, dhf)[..., :dh]
