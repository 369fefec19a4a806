"""One-token decode attention Pallas kernel (flash-decode style).

The single query token attends to the whole KV cache. The cache axis is the
inner ("arbitrary") grid dimension; online-softmax state persists in VMEM
scratch. All query heads of one KV head (the GQA group) are processed together
so each cache tile is read exactly once — decode attention is purely
memory-bound, and this keeps the kernel at one pass over the cache (the
roofline minimum). Slot validity (circular-buffer occupancy + sliding-window
bounds) is precomputed by the wrapper into a (T,) mask.

``flash_step`` is the online-softmax accumulation shared by every decode and
paged-prefill kernel: the kernels differ only in where a KV tile is fetched
from and how its live-lane mask is formed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def flash_step(q, k, v, live, o_ref, m_ref, l_ref, acc_ref, *, scale: float,
               step, nt: int, active=None):
    """One online-softmax step of a (rows x tile) score block.

    q: (R, dh) f32 queries; k, v: (T, dh) f32 KV tile; live: bool mask
    broadcastable to (R, T). ``step`` is the grid index walking the KV tiles
    (0 .. nt-1): the first step resets the m/l/acc scratch, the last writes
    the normalized output to ``o_ref[0, 0]``.

    ``active`` (a traced bool) runs the score-and-update part only where it
    holds; the reset and the write still run on their steps. Without it
    every step updates. Each of ``q, k, v, live`` may be given as a
    function of no arguments, called only where the update runs, so that
    a skipped step skips its loads too.
    """
    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _update():
        qv, kv, vv, lv = (x() if callable(x) else x for x in (q, k, v, live))
        # f32 operands, f32 products: the contract precision is pinned
        # rather than left to the compiler's default
        hi = jax.lax.Precision.HIGHEST
        s = jax.lax.dot_general(qv, kv, (((1,), (1,)), ((), ())),
                                precision=hi,
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(lv, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.where(lv, jnp.exp(s - m_cur[:, None]), 0.0)
        l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, vv, (((1,), (0,)), ((), ())), precision=hi,
            preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_cur

    if active is None:
        _update()
    else:
        pl.when(active)(_update)

    @pl.when(step == nt - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _decode_kernel(q_ref, k_ref, v_ref, msk_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, nt: int):
    # the mask tile arrives as (1, bt): one row of the (B, nt, 1, bt) layout
    flash_step(q_ref[0, 0].astype(jnp.float32),        # (G, dh)
               k_ref[0, 0].astype(jnp.float32),        # (bt, dh)
               v_ref[0, 0].astype(jnp.float32),
               msk_ref[0, 0] != 0,                     # (1, bt)
               o_ref, m_ref, l_ref, acc_ref, scale=scale,
               step=pl.program_id(2), nt=nt)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def decode_attention(q, k, v, valid, *, block_t: int = 512,
                     interpret: bool = False):
    """q:(B,HQ,dh); k,v:(B,T,HKV,dh); valid:(T,) bool. -> (B,HQ,dh).

    The uniform case is the slot-aware kernel with the shared mask broadcast
    over the batch; the full wrapper (padding, tiling, pallas_call) lives in
    ``repro.kernels.slot_decode`` (imported lazily — it reuses this module's
    kernel body).
    """
    from repro.kernels.slot_decode import slot_decode_attention
    mask = jnp.broadcast_to(valid[None], (q.shape[0], valid.shape[0]))
    return slot_decode_attention(q, k, v, mask, block_t=block_t,
                                 interpret=interpret)
