"""Paged one-token decode attention: flash-decode over a block-table cache.

The serving engine's paged KV backend keeps every slot's cache as a chain of
fixed-size blocks in one physical pool (``repro.serve.paging``). A decode
step is a one-token chunk at the slot's current position, so this is the
W = 1 case of the paged prefill kernel (``repro.kernels.paged_prefill``):
the grid's inner axis walks the slot's *logical* blocks, a scalar-prefetched
block table translates each step to a physical pool row in the BlockSpec
index map, and the per-row position rides the same scalar-prefetch lane, so
the live-lane mask ``k_pos <= pos`` is formed from grid coordinates and no
mask array is read from HBM. With one real query a row, the walk stops at
block ``pos // bs``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_prefill import paged_prefill_attention


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, kp, vp, tables, pos, ks=None, vs=None, *,
                           interpret: bool = False):
    """q:(B,HQ,dh); kp,vp:(P+1,bs,HKV,dh) physical pools; tables:(B,nb)
    int32 logical->physical block map; pos:(B,) int32 position of each
    row's query (logical positions ``<= pos`` are live). -> (B,HQ,dh).

    Dead slots point at the trash row; whatever they compute is discarded.
    ``ks``/``vs`` (P+1, HKV) f32 mark the pools as per-block quantized: each
    tile's scale is fetched through the same table indirection and the
    dequant fuses into the kernel.
    """
    return paged_prefill_attention(q[:, None], kp, vp, tables, pos, ks, vs,
                                   qlen=jnp.ones_like(pos),
                                   interpret=interpret)[:, 0]
