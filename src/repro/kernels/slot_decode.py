"""Slot-aware one-token decode attention (continuous-batching companion).

Identical math to ``decode_attention`` — one query token per sequence against
a circular KV cache — but every batch row is an independent *slot* of the
serving engine's cache, at its own sequence position. The only structural
difference from the uniform kernel is the validity mask: per-slot ``(B, T)``
instead of shared ``(T,)``, so the mask BlockSpec is indexed by the batch grid
axis. The kernel body itself is reused verbatim from ``decode_attention`` —
the online-softmax accumulation never cared which row the mask came from.

The mask is laid out as ``(B, nt, 1, bt)``: each grid step's ``(1, bt)`` tile
then spans the array's last two dims whole, which is what Mosaic's tiling
rule asks of a block whose last dims are not (8, 128) multiples.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import _decode_kernel


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def slot_decode_attention(q, k, v, valid, *, block_t: int = 512,
                          interpret: bool = False):
    """q:(B,HQ,dh); k,v:(B,T,HKV,dh); valid:(B,T) bool. -> (B,HQ,dh)."""
    B, HQ, dh = q.shape
    T, HKV = k.shape[1], k.shape[2]
    G = HQ // HKV
    scale = 1.0 / math.sqrt(dh)
    bt = min(block_t, T)
    pad = (-T) % bt
    padf = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else t
    kT = padf(k.transpose(0, 2, 1, 3))                 # (B,HKV,T,dh)
    vT = padf(v.transpose(0, 2, 1, 3))
    dhp = (-dh) % 128
    if dhp:
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, dhp)))
        kT = jnp.pad(kT, ((0, 0), (0, 0), (0, 0), (0, dhp)))
        vT = jnp.pad(vT, ((0, 0), (0, 0), (0, 0), (0, dhp)))
    else:
        qp = q
    dhf = dh + dhp
    qg = qp.reshape(B, HKV, G, dhf)
    nt = (T + pad) // bt
    mask = jnp.pad(valid.astype(jnp.int32), ((0, 0), (0, pad))) \
        .reshape(B, nt, 1, bt)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, nt=nt),
        grid=(B, HKV, nt),
        in_specs=[
            pl.BlockSpec((1, 1, G, dhf), lambda b, h, ti: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bt, dhf), lambda b, h, ti: (b, h, ti, 0)),
            pl.BlockSpec((1, 1, bt, dhf), lambda b, h, ti: (b, h, ti, 0)),
            pl.BlockSpec((1, 1, 1, bt), lambda b, h, ti: (b, ti, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dhf), lambda b, h, ti: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, HKV, G, dhf), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, dhf), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qg, kT, vT, mask)
    return out.reshape(B, HQ, dhf)[..., :dh]
