"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py
oracles (assignment deliverable c). All kernels run their real Pallas body
under interpret=True on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as kref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_ssm import mamba_scan
from repro.kernels.moe_route import moe_route
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.rwkv6 import rwkv_scan

KEY = jax.random.PRNGKey(7)


def _tol(dt):
    return dict(atol=2.5e-2, rtol=2.5e-2) if dt == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=3e-5)


def _chunk_rows(starts, W):
    """A paged chunk case's rows: each entry of ``starts`` is a start (all
    W queries real) or a (start, real queries) pair. Returns the start
    array, the qlen array (None when every row is whole) and a (B, W) mask
    of the real query rows."""
    pairs = [s if isinstance(s, tuple) else (s, W) for s in starts]
    start = jnp.asarray(np.array([s for s, _ in pairs], np.int32))
    ql = np.array([n for _, n in pairs], np.int32)
    real = np.arange(W)[None] < ql[:, None]
    return start, (None if real.all() else jnp.asarray(ql)), real


def _assert_real_rows(out, ref, real, **tol):
    """Real query rows match the oracle; a row with none writes zeros
    (padding query rows are garbage by contract)."""
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(out[real], ref[real], **tol)
    for b in np.flatnonzero(~real.any(axis=1)):
        assert not out[b].any()


@pytest.mark.parametrize("B,S,HQ,HKV,dh,causal,window,dt", [
    (2, 128, 4, 2, 64, True, 0, jnp.float32),
    (1, 200, 8, 8, 80, True, 0, jnp.float32),      # ragged, MHA, odd dh
    (2, 256, 4, 1, 128, True, 64, jnp.bfloat16),   # MQA + sliding window
    (1, 96, 6, 2, 112, False, 0, jnp.float32),     # non-causal, dh=112
    (1, 64, 2, 2, 64, True, 16, jnp.float32),      # tight window
])
def test_flash_attention(B, S, HQ, HKV, dh, causal, window, dt):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, HQ, dh), dt)
    k = jax.random.normal(ks[1], (B, S, HKV, dh), dt)
    v = jax.random.normal(ks[2], (B, S, HKV, dh), dt)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = kref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dt))


@pytest.mark.parametrize("B,T,HQ,HKV,dh,live,dt", [
    (2, 100, 8, 2, 80, 77, jnp.float32),
    (1, 64, 4, 4, 64, 64, jnp.bfloat16),
    (3, 130, 8, 1, 128, 1, jnp.float32),           # single live slot
])
def test_decode_attention(B, T, HQ, HKV, dh, live, dt):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, HQ, dh), dt)
    k = jax.random.normal(ks[1], (B, T, HKV, dh), dt)
    v = jax.random.normal(ks[2], (B, T, HKV, dh), dt)
    valid = jnp.arange(T) < live
    out = decode_attention(q, k, v, valid, block_t=32, interpret=True)
    ref = kref.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dt))


@pytest.mark.parametrize("shape,dt,br", [
    ((3, 40, 96), jnp.float32, 16),
    ((2, 33, 256), jnp.bfloat16, 8),
    ((128, 64), jnp.float32, 128),
])
def test_rmsnorm(shape, dt, br):
    x = jax.random.normal(KEY, shape, dt)
    s = jax.random.normal(jax.random.PRNGKey(1), shape[-1:]) * 0.1 + 1.0
    out = rmsnorm(x, s, block_rows=br, interpret=True)
    ref = kref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dt))


@pytest.mark.parametrize("B,S,di,ds,chunk,dtile", [
    (2, 100, 96, 8, 16, 32),
    (1, 64, 64, 4, 64, 64),      # single chunk / single tile
    (2, 33, 128, 16, 8, 32),     # ragged seq
])
def test_mamba_scan(B, S, di, ds, chunk, dtile):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) - 1)
    A = -jnp.exp(jax.random.normal(ks[2], (di, ds)) * 0.3)
    Bv = jax.random.normal(ks[3], (B, S, ds))
    Cv = jax.random.normal(ks[4], (B, S, ds))
    out = mamba_scan(x, dt, A, Bv, Cv, chunk=chunk, di_tile=dtile,
                     interpret=True)
    ref = kref.mamba_scan_ref(x, dt, A, Bv, Cv)
    np.testing.assert_allclose(out, ref, atol=5e-4, rtol=5e-3)


@pytest.mark.parametrize("B,S,nh,hd,chunk", [
    (2, 70, 3, 16, 16),
    (1, 64, 2, 32, 64),
    (2, 31, 1, 64, 8),
])
def test_rwkv_scan(B, S, nh, hd, chunk):
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, nh, hd))
    k = jax.random.normal(ks[1], (B, S, nh, hd))
    v = jax.random.normal(ks[2], (B, S, nh, hd))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, nh, hd)) - 2))
    u = jax.random.normal(ks[4], (nh, hd)) * 0.5
    out = rwkv_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    ref = kref.rwkv_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(out, ref, atol=5e-4, rtol=5e-3)


@pytest.mark.parametrize("N,D,E,K,bn", [
    (100, 64, 16, 4, 32),
    (64, 32, 8, 1, 64),
    (33, 16, 4, 2, 16),
])
def test_moe_route(N, D, E, K, bn):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (N, D))
    router = jax.random.normal(ks[1], (D, E)) * 0.1
    g, i = moe_route(x, router, K, block_n=bn, interpret=True)
    gr, ir = kref.moe_route_ref(x, router, K)
    np.testing.assert_allclose(g, gr, atol=1e-5, rtol=1e-5)
    assert (np.asarray(i) == np.asarray(ir)).all()


# ---------------------------------------------------------------------------
# Serving decode kernels vs ref.py oracles: odd-shape parity sweep
# (non-power-of-two head counts, small block sizes, single-slot edges)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,HQ,HKV,dh,block_t,lives,dt", [
    (3, 32, 4, 2, 64, 16, (5, 20, 1), jnp.float32),    # mixed occupancy
    (2, 24, 6, 3, 64, 8, (24, 7), jnp.float32),        # non-pow2 heads (3)
    (2, 40, 8, 2, 80, 16, (33, 2), jnp.float32),       # odd dh=80, ragged T
    (1, 8, 4, 4, 64, 8, (1,), jnp.float32),            # single slot, 1 live
    (2, 32, 4, 1, 128, 16, (31, 16), jnp.bfloat16),    # MQA, bf16
])
def test_slot_decode_kernel_parity(B, T, HQ, HKV, dh, block_t, lives, dt):
    from repro.kernels.slot_decode import slot_decode_attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, HQ, dh), dt)
    k = jax.random.normal(ks[1], (B, T, HKV, dh), dt)
    v = jax.random.normal(ks[2], (B, T, HKV, dh), dt)
    valid = np.zeros((B, T), bool)
    for b, live in enumerate(lives):
        valid[b, :live] = True
    valid = jnp.asarray(valid)
    out = slot_decode_attention(q, k, v, valid, block_t=block_t,
                                interpret=True)
    ref = kref.slot_decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dt))


@pytest.mark.parametrize("P1,bs,nb,B,HQ,HKV,dh,lives,dt", [
    (7, 8, 3, 2, 4, 2, 64, (13, 1), jnp.float32),      # mid-block boundary
    (9, 16, 2, 2, 6, 3, 64, (17, 32), jnp.float32),    # bs=16, non-pow2 heads
    (5, 8, 2, 1, 8, 2, 80, (9,), jnp.float32),         # single slot, odd dh
    (4, 16, 1, 3, 4, 4, 64, (1, 16, 7), jnp.float32),  # one logical block
    (6, 8, 3, 2, 4, 1, 128, (23, 8), jnp.bfloat16),    # MQA, bf16
])
def test_paged_decode_kernel_parity(P1, bs, nb, B, HQ, HKV, dh, lives, dt):
    from repro.kernels.paged_decode import paged_decode_attention
    ks = jax.random.split(KEY, 4)
    kp = jax.random.normal(ks[0], (P1, bs, HKV, dh), dt)
    vp = jax.random.normal(ks[1], (P1, bs, HKV, dh), dt)
    q = jax.random.normal(ks[2], (B, HQ, dh), dt)
    # a deterministic permuted block table over the pool (no aliasing)
    rng = np.random.default_rng(P1 * bs + B)
    tables = jnp.asarray(np.stack(
        [rng.permutation(P1)[:nb] for _ in range(B)]).astype(np.int32))
    valid = np.zeros((B, nb * bs), bool)
    for b, live in enumerate(lives):
        valid[b, :live] = True
    pos = jnp.asarray(np.array(lives, np.int32) - 1)    # last live lane
    out = paged_decode_attention(q, kp, vp, tables, pos, interpret=True)
    ref = kref.paged_decode_attention_ref(q, kp, vp, tables,
                                          jnp.asarray(valid))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dt))


def test_flash_attention_grad_matches_ref():
    """The kernel must be differentiable (used in training at L4)."""
    B, S, H, dh = 1, 64, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, H, dh))
    v = jax.random.normal(ks[2], (B, S, H, dh))

    def f_kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                               interpret=True).sum()

    def f_ref(q, k, v):
        return kref.flash_attention_ref(q, k, v, causal=True).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-3)


# ---------------------------------------------------------------------------
# Paged *prefill* kernel (chunked-prefill serve step) vs ref.py oracle:
# non-pow2 heads, block sizes 8/16, chunk lengths 1 / 7 / bucket-boundary,
# resident prefixes 0 and mid-block
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("P1,bs,nb,B,W,HQ,HKV,dh,starts,dt", [
    (7, 8, 3, 2, 8, 4, 2, 64, (0, 13), jnp.float32),    # resident 0 + mid-blk
    (9, 16, 2, 2, 7, 6, 3, 64, (5, 17), jnp.float32),   # bs=16, HKV=3, W=7
    (5, 8, 2, 1, 1, 8, 2, 80, (9,), jnp.float32),       # chunk length 1
    (6, 8, 3, 3, 16, 4, 1, 128, (0, 8, 3), jnp.float32),  # MQA, W=2 blocks
    (7, 16, 2, 2, 16, 6, 3, 64, (16, 15), jnp.bfloat16),  # boundary starts
    # ragged real queries, (start, qlen): no chunk; last query on a block's
    # end with padding queries in the next block; mid-block; on a block's
    # first lane; in block nb-1
    (9, 8, 3, 5, 8, 4, 2, 64, ((0, 0), (5, 3), (9, 2), (10, 7), (17, 7)),
     jnp.float32),
    (7, 8, 3, 4, 1, 6, 3, 64, ((12, 1), (3, 0), (16, 1), (23, 1)),
     jnp.float32),                                       # W=1
    (7, 16, 2, 2, 16, 6, 3, 64, ((0, 16), (20, 5)), jnp.bfloat16),
])
def test_paged_prefill_kernel_parity(P1, bs, nb, B, W, HQ, HKV, dh, starts,
                                     dt):
    from repro.kernels.paged_prefill import paged_prefill_attention
    ks = jax.random.split(KEY, 3)
    kp = jax.random.normal(ks[0], (P1, bs, HKV, dh), dt)
    vp = jax.random.normal(ks[1], (P1, bs, HKV, dh), dt)
    q = jax.random.normal(ks[2], (B, W, HQ, dh), dt)
    # a deterministic permuted block table over the pool (no aliasing)
    rng = np.random.default_rng(P1 * bs + B + W)
    tables = jnp.asarray(np.stack(
        [rng.permutation(P1)[:nb] for _ in range(B)]).astype(np.int32))
    start, qlen, real = _chunk_rows(starts, W)
    out = paged_prefill_attention(q, kp, vp, tables, start, qlen=qlen,
                                  interpret=True)
    ref = kref.paged_prefill_attention_ref(q, kp, vp, tables, start)
    _assert_real_rows(out, ref, real, **_tol(dt))


@pytest.mark.parametrize("kvd", ["float32", "int8"])
def test_paged_prefill_walk_bound_is_exact(kvd):
    """Bounding the block walk by each row's real queries skips only blocks
    those queries mask whole: their rows equal the full walk's bit for
    bit."""
    from repro.kernels.paged_prefill import paged_prefill_attention
    P1, bs, nb, B, W, HQ, HKV, dh = 13, 8, 6, 4, 8, 4, 2, 64
    ks_ = jax.random.split(KEY, 3)
    kp = jax.random.normal(ks_[0], (P1, bs, HKV, dh), jnp.float32)
    vp = jax.random.normal(ks_[1], (P1, bs, HKV, dh), jnp.float32)
    q = jax.random.normal(ks_[2], (B, W, HQ, dh), jnp.float32)
    scales = ()
    if kvd == "int8":
        kp, k_s = _quantize_pool(kp, kvd)
        vp, v_s = _quantize_pool(vp, kvd)
        scales = (k_s, v_s)
    rng = np.random.default_rng(5)
    tables = jnp.asarray(np.stack(
        [rng.permutation(P1)[:nb] for _ in range(B)]).astype(np.int32))
    start, qlen, real = _chunk_rows(((3, 5), (17, 8), (30, 1), (40, 3)), W)
    full = paged_prefill_attention(q, kp, vp, tables, start, *scales,
                                   interpret=True)
    bound = paged_prefill_attention(q, kp, vp, tables, start, *scales,
                                    qlen=qlen, interpret=True)
    np.testing.assert_array_equal(np.asarray(bound)[real],
                                  np.asarray(full)[real])


def test_paged_prefill_width_one_matches_decode_kernel():
    """A width-1 chunk is a decode step: the prefill kernel must agree with
    the decode kernel on the same pool/table/position state."""
    from repro.kernels.paged_decode import paged_decode_attention
    from repro.kernels.paged_prefill import paged_prefill_attention
    P1, bs, nb, B, HQ, HKV, dh = 7, 8, 3, 2, 4, 2, 64
    ks = jax.random.split(KEY, 3)
    kp = jax.random.normal(ks[0], (P1, bs, HKV, dh), jnp.float32)
    vp = jax.random.normal(ks[1], (P1, bs, HKV, dh), jnp.float32)
    q = jax.random.normal(ks[2], (B, 1, HQ, dh), jnp.float32)
    tables = jnp.asarray(np.array([[0, 2, 5], [4, 1, 6]], np.int32))
    pos = jnp.asarray(np.array([12, 0], np.int32))      # mid-block + fresh
    out_pf = paged_prefill_attention(q, kp, vp, tables, pos, interpret=True)
    out_dec = paged_decode_attention(q[:, 0], kp, vp, tables, pos,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(out_pf[:, 0]), np.asarray(out_dec),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Speculative verify pass (draft-and-verify serve step): the verify program
# attends with W query positions per row at mid-generation starts over the
# same pool/table state as chunked prefill — kernel parity at verify-shaped
# geometries, then accept-boundary semantics vs the sequential decode oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("P1,bs,nb,B,W,HQ,HKV,dh,starts,dt", [
    (7, 8, 3, 2, 1, 6, 3, 64, (11, 20), jnp.float32),   # W=1: a decode row
    (9, 8, 4, 2, 3, 6, 3, 64, (17, 9), jnp.float32),    # W=3, non-pow2 HKV
    (7, 16, 2, 2, 8, 6, 3, 64, (15, 21), jnp.float32),  # W=8, bs=16, HKV=3
    (6, 16, 3, 3, 8, 6, 3, 64, (16, 31, 0), jnp.bfloat16),  # block-boundary
])
def test_verify_window_kernel_parity(P1, bs, nb, B, W, HQ, HKV, dh, starts,
                                     dt):
    """The verify window's attention is exactly a W-wide paged chunk at the
    row's committed position: kernel vs ref oracle at draft widths 1/3/8,
    block sizes 8/16, and starts on/off block boundaries."""
    from repro.kernels.paged_prefill import paged_prefill_attention
    ks = jax.random.split(KEY, 3)
    kp = jax.random.normal(ks[0], (P1, bs, HKV, dh), dt)
    vp = jax.random.normal(ks[1], (P1, bs, HKV, dh), dt)
    q = jax.random.normal(ks[2], (B, W, HQ, dh), dt)
    rng = np.random.default_rng(P1 * bs + B + W + 1)
    tables = jnp.asarray(np.stack(
        [rng.permutation(P1)[:nb] for _ in range(B)]).astype(np.int32))
    start = jnp.asarray(np.array(starts, np.int32))
    out = paged_prefill_attention(q, kp, vp, tables, start, interpret=True)
    ref = kref.paged_prefill_attention_ref(q, kp, vp, tables, start)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dt))


class _ScriptedProposer:
    """Drop-in DraftProposer whose drafts are scripted by the slot's
    ``produced`` count — lets a test place accept boundaries exactly."""

    def __init__(self, script):
        self.script = script
        self.proposed_tokens = 0
        self.lookups = 0
        self.hits = 0

    def propose(self, st):
        d = np.asarray(self.script.get(st.produced, []), np.int32)
        self.lookups += 1
        if d.size:
            self.hits += 1
            self.proposed_tokens += int(d.size)
        return d


@pytest.mark.parametrize("width", [3, 8])
def test_verify_accept_boundaries_match_decode_oracle(width):
    """Crafted drafts pin the accept boundary at full / zero / mid draft:
    the verify program must emit exactly the sequential decode oracle's
    tokens in every case (rejected tails rolled back, bonus token kept),
    with acceptance counters matching the crafted boundaries."""
    from repro.configs import get_config
    from repro.core import preset
    from repro.models import ModelOptions, decode_step, init_params, prefill
    from repro.serve import Request, ServeEngine
    cfg = get_config("tinyllama-1.1b").smoke()
    opts = ModelOptions(attn_impl="ref", scan_impl="ref", dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    max_len, max_new = 48, 16
    prompt = np.random.default_rng(11).integers(0, cfg.vocab_size, 8,
                                                dtype=np.int32)

    # the oracle: prefill + one-token decode loop
    logits, cache = jax.jit(
        lambda p, t: prefill(p, t, cfg, opts, max_len=max_len))(
            params, jnp.asarray(prompt)[None])
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    want = [int(nxt[0])]
    dec = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg, opts))
    for _ in range(max_new - 1):
        logits, cache = dec(params, cache, nxt)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        want.append(int(nxt[0]))

    t = want
    bad = lambda i: (t[i] + 1) % cfg.vocab_size     # ≠ the correct token
    if width == 3:
        script = {1: t[1:3],                        # full accept (+ bonus)
                  4: [bad(4)],                      # zero accept
                  5: [t[5], bad(6)]}                # mid: 1 of 2 accepted
        drafts, accepted = 5, 3
    else:
        script = {1: t[1:8],                        # full 7-draft window
                  9: [bad(9)],
                  10: [t[10], t[11], bad(12)]}
        drafts, accepted = 11, 9

    eng = ServeEngine(cfg, params, opts, preset("byp"), n_slots=1,
                      max_len=max_len, kv="paged", block_size=8,
                      spec_decode="ngram", spec_width=width)
    eng.proposer = _ScriptedProposer(script)
    req = Request(rid=0, prompt=prompt, max_new_tokens=max_new)
    comps, _ = eng.run([req], load="closed")
    assert comps[0].tokens.tolist() == want
    u = eng.utilization()
    assert u["spec_steps"] == 3
    assert u["spec_draft_tokens"] == drafts
    assert u["spec_accepted_tokens"] == accepted
    assert u["spec_wasted_tokens"] == drafts - accepted


# ---------------------------------------------------------------------------
# Quantized paged kernels (int8/fp8 block pools, fused dequant): the fused
# kernel must match the quantize-then-dequant ref oracle exactly (same math,
# different fetch path), and sit within an absolute error bound of the fp32
# oracle that reflects the format's precision. Geometry sweep mirrors the
# unquantized parity tests: HKV=3, block sizes 8/16, odd dh, W=1 decode edge.
# ---------------------------------------------------------------------------

def _quantize_pool(pool, kv_dtype):
    """Per-(block, head) symmetric quantization of an f32 pool — the same
    encoding the engine's write paths produce."""
    from repro.kernels import kv_quant
    dt = kv_quant.storage_dtype(kv_dtype, jnp.float32)
    amax = jnp.max(jnp.abs(pool), axis=(1, 3))
    s = kv_quant.block_scales(amax, dt)
    return kv_quant.quantize(pool, s[:, None, :, None], dt), s


# attention outputs are convex combinations of ~N(0,1) values, so the output
# error tracks the format's worst-case relative step at block amax
_QTOL = {"int8": 0.06, "fp8": 0.40}


@pytest.mark.parametrize("P1,bs,nb,B,HQ,HKV,dh,lives,kvd", [
    (7, 8, 3, 2, 4, 2, 64, (13, 1), "int8"),        # mid-block boundary
    (9, 16, 2, 2, 6, 3, 64, (17, 32), "int8"),      # bs=16, HKV=3
    (5, 8, 2, 1, 8, 2, 80, (9,), "int8"),           # odd dh
    (9, 16, 2, 2, 6, 3, 64, (17, 32), "fp8"),
    (5, 8, 2, 1, 8, 2, 80, (9,), "fp8"),
])
def test_paged_decode_quantized_parity(P1, bs, nb, B, HQ, HKV, dh, lives,
                                       kvd):
    from repro.kernels.paged_decode import paged_decode_attention
    ks_ = jax.random.split(KEY, 4)
    kpf = jax.random.normal(ks_[0], (P1, bs, HKV, dh), jnp.float32)
    vpf = jax.random.normal(ks_[1], (P1, bs, HKV, dh), jnp.float32)
    q = jax.random.normal(ks_[2], (B, HQ, dh), jnp.float32)
    kp, ks = _quantize_pool(kpf, kvd)
    vp, vs = _quantize_pool(vpf, kvd)
    rng = np.random.default_rng(P1 * bs + B)
    tables = jnp.asarray(np.stack(
        [rng.permutation(P1)[:nb] for _ in range(B)]).astype(np.int32))
    valid = np.zeros((B, nb * bs), bool)
    for b, live in enumerate(lives):
        valid[b, :live] = True
    valid = jnp.asarray(valid)
    pos = jnp.asarray(np.array(lives, np.int32) - 1)    # last live lane
    out = paged_decode_attention(q, kp, vp, tables, pos, ks, vs,
                                 interpret=True)
    # fused kernel == quantize-then-dequant oracle (same math, fused fetch)
    ref_q = kref.paged_decode_attention_ref(q, kp, vp, tables, valid,
                                            ks=ks, vs=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_q),
                               atol=3e-5, rtol=3e-5)
    # and within the format's error bound of the unquantized fp32 oracle
    ref_f = kref.paged_decode_attention_ref(q, kpf, vpf, tables, valid)
    assert float(np.max(np.abs(np.asarray(out) - np.asarray(ref_f)))) \
        <= _QTOL[kvd]


@pytest.mark.parametrize("P1,bs,nb,B,W,HQ,HKV,dh,starts,kvd", [
    (7, 8, 3, 2, 8, 4, 2, 64, (0, 13), "int8"),     # resident 0 + mid-block
    (9, 16, 2, 2, 7, 6, 3, 64, (5, 17), "int8"),    # bs=16, HKV=3
    (5, 8, 2, 1, 1, 8, 2, 80, (9,), "int8"),        # W=1 decode edge, odd dh
    (9, 16, 2, 2, 7, 6, 3, 64, (5, 17), "fp8"),
    (5, 8, 2, 1, 1, 8, 2, 80, (9,), "fp8"),
    # ragged real queries, (start, qlen), as in the unquantized cases
    (9, 8, 3, 4, 8, 4, 2, 64, ((0, 0), (5, 3), (10, 7), (17, 7)), "int8"),
    (5, 8, 2, 2, 1, 8, 2, 80, ((9, 1), (4, 0)), "int8"),          # W=1
])
def test_paged_prefill_quantized_parity(P1, bs, nb, B, W, HQ, HKV, dh,
                                        starts, kvd):
    from repro.kernels.paged_prefill import paged_prefill_attention
    ks_ = jax.random.split(KEY, 3)
    kpf = jax.random.normal(ks_[0], (P1, bs, HKV, dh), jnp.float32)
    vpf = jax.random.normal(ks_[1], (P1, bs, HKV, dh), jnp.float32)
    q = jax.random.normal(ks_[2], (B, W, HQ, dh), jnp.float32)
    kp, ks = _quantize_pool(kpf, kvd)
    vp, vs = _quantize_pool(vpf, kvd)
    rng = np.random.default_rng(P1 * bs + B + W)
    tables = jnp.asarray(np.stack(
        [rng.permutation(P1)[:nb] for _ in range(B)]).astype(np.int32))
    start, qlen, real = _chunk_rows(starts, W)
    out = paged_prefill_attention(q, kp, vp, tables, start, ks, vs,
                                  qlen=qlen, interpret=True)
    ref_q = kref.paged_prefill_attention_ref(q, kp, vp, tables, start,
                                             ks=ks, vs=vs)
    _assert_real_rows(out, ref_q, real, atol=3e-5, rtol=3e-5)
    ref_f = kref.paged_prefill_attention_ref(q, kpf, vpf, tables, start)
    assert float(np.max(np.abs(np.asarray(out)[real]
                               - np.asarray(ref_f)[real]))) <= _QTOL[kvd]


def test_paged_prefill_quantized_width_one_matches_decode_kernel():
    """The W=1 == decode-row edge holds for quantized pools too: both fused
    kernels dequantize through the same scale tables."""
    from repro.kernels.paged_decode import paged_decode_attention
    from repro.kernels.paged_prefill import paged_prefill_attention
    P1, bs, nb, B, HQ, HKV, dh = 7, 8, 3, 2, 6, 3, 64
    ks_ = jax.random.split(KEY, 3)
    kpf = jax.random.normal(ks_[0], (P1, bs, HKV, dh), jnp.float32)
    vpf = jax.random.normal(ks_[1], (P1, bs, HKV, dh), jnp.float32)
    q = jax.random.normal(ks_[2], (B, 1, HQ, dh), jnp.float32)
    kp, ks = _quantize_pool(kpf, "int8")
    vp, vs = _quantize_pool(vpf, "int8")
    tables = jnp.asarray(np.array([[0, 2, 5], [4, 1, 6]], np.int32))
    pos = jnp.asarray(np.array([12, 0], np.int32))
    out_pf = paged_prefill_attention(q, kp, vp, tables, pos, ks, vs,
                                     interpret=True)
    out_dec = paged_decode_attention(q[:, 0], kp, vp, tables, pos, ks, vs,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(out_pf[:, 0]), np.asarray(out_dec),
                               atol=2e-5, rtol=2e-5)


def test_quant_insert_untouched_blocks_bitwise_stable():
    """Repeated writes to one block must not drift any *other* block: the
    requantize-on-write masks untouched rows through bit-exactly."""
    from repro.kernels import kv_quant
    P1, bs, HKV, dh = 6, 8, 3, 16
    rng = np.random.default_rng(3)
    pool_f = jnp.asarray(rng.normal(size=(P1, bs, HKV, dh)).astype(np.float32))
    pool, scales = _quantize_pool(pool_f, "int8")
    p0, s0 = np.asarray(pool), np.asarray(scales)
    blk = jnp.asarray(np.array([2], np.int32))
    for step in range(5):
        off = jnp.asarray(np.array([step % bs], np.int32))
        vals = jnp.asarray(rng.normal(size=(1, HKV, dh)).astype(np.float32))
        pool, scales = kv_quant.quant_insert(pool, scales, blk, off, vals)
    p1, s1 = np.asarray(pool), np.asarray(scales)
    untouched = [i for i in range(P1) if i != 2]
    assert (p1[untouched] == p0[untouched]).all()
    assert (s1[untouched] == s0[untouched]).all()
