"""RWKV-6 time-mix recurrence Pallas kernel.

TPU adaptation: the reference CUDA kernel assigns one thread per channel and
walks the sequence serially. Here one grid cell owns one (batch, head) pair,
holds the (hd, hd) state matrix in VMEM scratch, and walks the sequence as
chunked inner grid steps; within a chunk a fori_loop performs the exact
per-token outer-product recurrence on VMEM-resident tiles (hd=64 → the state
is a single 16 KB tile; r/k/v/w chunks are (cs, hd) tiles). All decay factors
w ∈ (0,1), so the recurrence is overflow-safe in fp32 — unlike the factorized
cumulative-decay matmul form, which is why we keep the sequential-in-chunk
formulation (the op is HBM-bound on r/k/v/w traffic, not FLOPs-bound, so the
serial inner loop does not move the roofline).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *, cs: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)                # (cs, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)                 # (1, hd); u.T is (hd,1)

    def step(t, carry):
        S, y = carry
        rt = lax.dynamic_slice_in_dim(r, t, 1, 0)      # (1, hd)
        kt = lax.dynamic_slice_in_dim(k, t, 1, 0)
        vt = lax.dynamic_slice_in_dim(v, t, 1, 0)
        wt = lax.dynamic_slice_in_dim(w, t, 1, 0)
        kv = kt.T @ vt                                 # (hd, hd) outer product
        yt = rt @ (S + u.T * kv)                       # (1, hd)
        S = wt.T * S + kv
        y = lax.dynamic_update_slice_in_dim(y, yt, t, 0)
        return S, y

    S0 = s_ref[...]
    y0 = jnp.zeros((cs, r.shape[1]), jnp.float32)
    S_fin, y = lax.fori_loop(0, cs, step, (S0, y0))
    s_ref[...] = S_fin
    o_ref[0, 0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv_scan(r, k, v, w, u, *, chunk: int = 256, interpret: bool = False):
    """r,k,v,w: (B,S,nh,hd); u: (nh,hd). Returns y: (B,S,nh,hd) fp32."""
    B, S, nh, hd = r.shape
    cs = min(chunk, S)
    pad = (-S) % cs
    tr = lambda t: jnp.pad(t.transpose(0, 2, 1, 3),
                           ((0, 0), (0, 0), (0, pad), (0, 0)),
                           constant_values=0.0)
    rT, kT, vT = tr(r), tr(k), tr(v)
    wT = jnp.pad(w.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pad), (0, 0)),
                 constant_values=1.0)
    Sp = S + pad
    ns = Sp // cs

    out = pl.pallas_call(
        functools.partial(_rwkv_kernel, cs=cs),
        grid=(B, nh, ns),
        in_specs=[
            pl.BlockSpec((1, 1, cs, hd), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, cs, hd), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, cs, hd), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, cs, hd), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, hd), lambda b, h, s: (h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, cs, hd), lambda b, h, s: (b, h, s, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nh, Sp, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rT, kT, vT, wT, u)
    return out.transpose(0, 2, 1, 3)[:, :S]
