"""Paged KV memory subsystem: pool/table/index invariants and the engine's
token-identity guarantee under paging.

The load-bearing claim mirrors PR 1's: paging (demand-allocated blocks,
block tables, CoW prefix sharing, recompute preemption) changes *memory
layout and admission capacity only* — every request's token stream is
bit-identical to the slotted engine and to running it alone through prefill
+ sequential decode.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import L3_NSS, LinkageConfig, preset
from repro.models import ModelOptions, decode_step, init_params, prefill
from repro.serve import (BlockPool, PrefixIndex, Request, ServeEngine,
                         synthetic_requests)

CFG = get_config("tinyllama-1.1b").smoke()
OPTS = ModelOptions(attn_impl="ref", scan_impl="ref", dtype=jnp.float32)
MAX_LEN = 48


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def sequential_tokens(params, req, max_len=MAX_LEN, opts=OPTS):
    """Reference: the request alone, prefill + one-token decode loop (at the
    cell's own ModelOptions so shortcut presets lower like the engine)."""
    logits, cache = jax.jit(
        lambda p, t: prefill(p, t, CFG, opts, max_len=max_len))(
            params, jnp.asarray(req.prompt)[None])
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [int(nxt[0])]
    dec = jax.jit(lambda p, c, t: decode_step(p, c, t, CFG, opts))
    for _ in range(req.max_new_tokens - 1):
        logits, cache = dec(params, cache, nxt)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(int(nxt[0]))
    return out


def run_engine(params, linkage, requests, *, kv, n_slots=2, load="closed",
               **kw):
    eng = ServeEngine(CFG, params, OPTS, linkage, n_slots=n_slots,
                      max_len=MAX_LEN, kv=kv, **kw)
    comps, _ = eng.run(requests, load=load)
    assert len(comps) == len(requests)
    return {c.rid: c.tokens.tolist() for c in comps}, eng


def assert_paged_identical(params, linkage, requests, *, check_seq=True,
                           n_slots=2, **kw):
    slotted, _ = run_engine(params, linkage, requests, kv="slotted",
                            n_slots=n_slots)
    paged, eng = run_engine(params, linkage, requests, kv="paged",
                            n_slots=n_slots, **kw)
    assert slotted == paged, f"paged diverged:\n{slotted}\n{paged}"
    if check_seq:
        for req in requests:
            assert paged[req.rid] == sequential_tokens(params, req), req.rid
    return eng


# ---------------------------------------------------------------------------
# BlockPool / PrefixIndex invariants (host subsystem)
# ---------------------------------------------------------------------------

def test_block_pool_alloc_free_roundtrip():
    pool = BlockPool(4, block_size=8)
    a, b = pool.alloc(), pool.alloc()
    assert (a, b) == (0, 1) and pool.n_resident == 2 and pool.hwm == 2
    assert pool.free(a) is True                 # physically freed
    assert pool.alloc() == 0                    # lowest-first, deterministic
    pool.retain(b)
    assert pool.free(b) is False                # still referenced
    assert pool.free(b) is True
    assert pool.n_free == 3 and pool.hwm == 2


def test_block_pool_double_free_raises():
    pool = BlockPool(2, block_size=4)
    blk = pool.alloc()
    pool.free(blk)
    with pytest.raises(ValueError, match="double free"):
        pool.free(blk)
    with pytest.raises(ValueError, match="retain"):
        pool.retain(blk)


def test_block_pool_exhaustion_returns_none():
    pool = BlockPool(2, block_size=4)
    assert pool.alloc() is not None and pool.alloc() is not None
    assert pool.alloc() is None


def test_prefix_index_match_insert_evict():
    pool = BlockPool(8, block_size=4)
    idx = PrefixIndex(block_size=4)
    toks = np.arange(10, dtype=np.int32)        # 2 full blocks + tail
    blocks = [pool.alloc(), pool.alloc(), pool.alloc()]
    idx.insert(toks, blocks, n_full=2, pool=pool)
    assert len(idx) == 2
    assert pool.refs[blocks[0]] == 2            # caller + index
    assert idx.match(toks) == blocks[:2]
    assert idx.match(np.arange(4, dtype=np.int32)) == blocks[:1]
    assert idx.match(np.arange(1, 5, dtype=np.int32)) == []
    # caller drops its refs -> blocks become index-only -> evictable
    for b in blocks[:2]:
        pool.free(b)
    assert idx.n_evictable(pool) == 2
    assert idx.evict(pool, need=1) == 1         # LRU leaf first
    assert idx.match(toks) == blocks[:1]        # the chain shrank from the end
    assert idx.evict(pool, need=5) == 1
    assert len(idx) == 0 and pool.refs[blocks[0]] == 0


def test_prefix_index_interior_not_evictable_while_child_held():
    pool = BlockPool(8, block_size=2)
    idx = PrefixIndex(block_size=2)
    toks = np.arange(4, dtype=np.int32)
    blocks = [pool.alloc(), pool.alloc()]
    idx.insert(toks, blocks, n_full=2, pool=pool)
    pool.free(blocks[0])                        # parent: index-only
    # child still held by the caller: neither node can be freed
    assert idx.n_evictable(pool) == 0
    assert idx.evict(pool, need=2) == 0


# ---------------------------------------------------------------------------
# (Randomized BlockPool/CoW property tests live in tests/test_properties.py,
# which skips cleanly when the optional hypothesis dep is absent.)
# ---------------------------------------------------------------------------


def test_pool_scheduler_differential_deterministic():
    """Deterministic twin of test_properties.PoolSchedulerMachine (runs even
    without the optional hypothesis dep): a seeded random admit /
    demand-reserve / CoW-fork / finish / preempt sequence through a real
    BlockPool, differentially checked against a pure-Python model of
    refcounts and free-list size after every operation."""
    rng = np.random.default_rng(42)
    N = 12
    pool = BlockPool(N, block_size=4)
    refs = {}                       # blk -> modeled refcount
    chains = {}                     # slot -> [blk]
    order = []                      # admission order (youngest last)
    next_slot = [0]

    def alloc():
        blk = pool.alloc()
        if blk is None:
            assert pool.n_free == 0
            return None
        assert refs.get(blk, 0) == 0
        assert blk == min(set(range(N)) - set(refs))   # lowest-free-first
        refs[blk] = 1
        return blk

    def drop(blk):
        pool.free(blk)
        refs[blk] -= 1
        if refs[blk] == 0:
            del refs[blk]

    def teardown(slot):
        for b in chains.pop(slot):
            drop(b)
        order.remove(slot)

    for op in rng.integers(0, 5, size=400):
        if op == 0:                                    # admit (maybe shared)
            n = int(rng.integers(1, 5))
            chain = []
            if rng.random() < 0.5 and order:
                for blk in chains[order[0]][:n - 1]:
                    pool.retain(blk)
                    refs[blk] += 1
                    chain.append(blk)
            ok = True
            while len(chain) < n:
                blk = alloc()
                if blk is None:
                    for b in chain:
                        drop(b)
                    ok = False
                    break
                chain.append(blk)
            if ok:
                chains[next_slot[0]] = chain
                order.append(next_slot[0])
                next_slot[0] += 1
        elif op == 1 and chains:                       # demand-reserve
            slot = sorted(chains)[int(rng.integers(len(chains)))]
            blk = alloc()
            if blk is not None:
                chains[slot].append(blk)
        elif op == 2:                                  # CoW fork
            shared = [(s, i) for s, c in chains.items()
                      for i, b in enumerate(c) if pool.refs[b] > 1]
            if shared:
                slot, i = shared[int(rng.integers(len(shared)))]
                new = alloc()
                if new is not None:
                    drop(chains[slot][i])
                    chains[slot][i] = new
        elif op == 3 and chains:                       # finish
            teardown(sorted(chains)[int(rng.integers(len(chains)))])
        elif op == 4 and order:                        # preempt youngest
            teardown(order[-1])
        # differential invariants, every step
        for blk in range(N):
            assert pool.refs[blk] == refs.get(blk, 0), blk
        assert pool.n_free == N - len(refs)
        assert pool.n_resident == len(refs)
        assert pool.n_resident <= pool.hwm <= N
    for slot in list(order):                           # clean teardown
        teardown(slot)
    assert pool.n_free == N and (pool.refs == 0).all()


def test_pool_random_workload_refcounts_exact():
    """Deterministic version of the hypothesis pool property (runs even
    without the optional dep): random alloc/retain/free interleavings keep
    refcounts exact and capacity accounting consistent."""
    rng = np.random.default_rng(0)
    pool = BlockPool(6, block_size=4)
    live = []                                   # one entry per held reference
    for op in rng.integers(0, 3, size=200):
        if op == 0:
            blk = pool.alloc()
            if blk is None:
                assert pool.n_free == 0
            else:
                assert pool.refs[blk] == 1
                live.append(blk)
        elif op == 1 and live:
            blk = live[len(live) // 2]
            pool.retain(blk)
            live.append(blk)
        elif op == 2 and live:
            blk = live.pop()
            assert pool.free(blk) == (blk not in live)
        assert (pool.refs >= 0).all()
        assert pool.n_resident == len(set(live))
        for b in set(live):
            assert pool.refs[b] == live.count(b)
    assert pool.hwm <= 6


# ---------------------------------------------------------------------------
# Engine token identity under paging (the acceptance invariant)
# ---------------------------------------------------------------------------

def test_paged_identity_base_shared_prefix(params):
    """base preset, 4 requests CoW-sharing a 16-token prefix: paged ==
    slotted == sequential, and the index actually shared blocks."""
    reqs = synthetic_requests(4, prompt_len=24, max_new_tokens=5,
                              vocab_size=CFG.vocab_size, seed=7,
                              shared_prefix_len=16)
    eng = assert_paged_identical(params, preset("base"), reqs, block_size=8)
    u = eng.utilization()
    assert u["kv_prefix_shared_tokens"] >= 16 * 3   # rids 1..3 matched
    assert eng.sched.n_free == 2                    # everything evicted


def test_paged_identity_identical_prompts_cow(params):
    """Identical prompts (block-aligned): the full prefix is a radix hit, so
    later admissions prefill exactly one token and fork the tail block
    copy-on-write before writing it."""
    base = synthetic_requests(1, prompt_len=16, max_new_tokens=4,
                              vocab_size=CFG.vocab_size, seed=9)[0]
    reqs = [dataclasses.replace(base, rid=i) for i in range(3)]
    eng = assert_paged_identical(params, preset("byp"), reqs, block_size=8)
    u = eng.utilization()
    assert u["kv_cow_forks"] >= 2                   # rids 1,2 forked the tail
    assert u["kv_prefix_shared_tokens"] == 15 * 2   # P-1 shared each


def test_paged_identity_nss(params):
    """L3: multi-token fused programs over the paged cache, demand
    allocation crossing block boundaries mid-program."""
    lk = LinkageConfig(level=L3_NSS, ret_async=True, decode_steps=3)
    reqs = synthetic_requests(5, prompt_len=8, max_new_tokens=7,
                              vocab_size=CFG.vocab_size, seed=1,
                              shared_prefix_len=4)
    assert_paged_identical(params, lk, reqs, block_size=4)


def test_paged_identity_ret_byp_shortcut(params):
    """ret_byp_shortcut (blockwise-jnp kernels off-TPU) with a shared
    prefix: the suffix prefill lowers through the chunked attention form and
    the streams still match the slotted engine bit-for-bit."""
    lk = preset("ret_byp_shortcut")
    opts = lk.model_options(OPTS, on_tpu=False)
    reqs = synthetic_requests(3, prompt_len=16, max_new_tokens=5,
                              vocab_size=CFG.vocab_size, seed=5,
                              shared_prefix_len=8)
    eng = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=MAX_LEN,
                      kv="slotted")
    slotted, _ = eng.run(reqs, load="closed")
    eng2 = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=MAX_LEN,
                       kv="paged", block_size=8)
    paged, _ = eng2.run(reqs, load="closed")
    assert ({c.rid: c.tokens.tolist() for c in slotted}
            == {c.rid: c.tokens.tolist() for c in paged})


def test_paged_preemption_recompute(params):
    """A pool far smaller than worst-case forces recompute-preemption; the
    preempted requests replay bit-identically on re-admission."""
    reqs = synthetic_requests(4, prompt_len=8, max_new_tokens=12,
                              vocab_size=CFG.vocab_size, seed=3)
    eng = assert_paged_identical(params, preset("byp"), reqs, n_slots=3,
                                 check_seq=False, block_size=4, num_blocks=9)
    assert eng.preemptions > 0
    assert eng.kv.pool.hwm <= 9


def test_paged_admission_gated_on_blocks(params):
    """With blocks for only ~one sequence, free slots alone don't admit:
    the engine serializes on the block pool, not the slot count."""
    reqs = synthetic_requests(3, prompt_len=8, max_new_tokens=4,
                              vocab_size=CFG.vocab_size, seed=4)
    paged, eng = run_engine(params, preset("base"), reqs, kv="paged",
                            n_slots=3, block_size=4, num_blocks=5)
    for req in reqs:
        assert paged[req.rid] == sequential_tokens(params, req)
    assert eng.kv.pool.hwm <= 5


def test_paged_rejects_oversized_and_recurrent(params):
    eng = ServeEngine(CFG, params, OPTS, preset("base"), n_slots=1,
                      max_len=MAX_LEN, kv="paged", block_size=4, num_blocks=3)
    eng.sched.enqueue(Request(rid=0, prompt=np.zeros(8, np.int32),
                              max_new_tokens=8))
    with pytest.raises(ValueError, match="never fit"):
        eng._admit(lambda: 0.0)
    jamba = get_config("jamba-v0.1-52b").smoke()
    with pytest.raises(ValueError, match="plain-attention"):
        ServeEngine(jamba, init_params(jax.random.PRNGKey(1), jamba), OPTS,
                    preset("base"), n_slots=1, max_len=16, kv="paged")


@pytest.mark.slow
def test_paged_identity_open_loop(params):
    """Open-loop timed arrivals over the paged backend: admission timing
    changes, streams don't."""
    reqs = synthetic_requests(4, prompt_len=8, max_new_tokens=5,
                              vocab_size=CFG.vocab_size, seed=3, rate=500.0,
                              shared_prefix_len=4)
    assert_paged_identical(params, preset("byp"), reqs, load="open",
                           block_size=8)


@pytest.mark.slow
def test_paged_identity_bucketed_mixed_lengths(params):
    """Mixed prompt lengths + power-of-two bucketing + paging all compose
    without touching the streams."""
    reqs = synthetic_requests(6, prompt_len=0, max_new_tokens=4,
                              vocab_size=CFG.vocab_size, seed=11,
                              prompt_lens=[5, 9, 16, 23])
    assert_paged_identical(params, preset("byp"), reqs, block_size=8,
                           bucket_prompts=True)


# ---------------------------------------------------------------------------
# Paged decode-attention kernel (interpret mode = real kernel body)
# ---------------------------------------------------------------------------

def test_paged_decode_kernel_matches_gathered_ref():
    from repro.kernels.paged_decode import paged_decode_attention
    P1, bs, nb, B, HQ, HKV, dh = 7, 8, 3, 2, 4, 2, 64
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    kp = jax.random.normal(k1, (P1, bs, HKV, dh), jnp.float32)
    vp = jax.random.normal(k2, (P1, bs, HKV, dh), jnp.float32)
    q = jax.random.normal(k3, (B, HQ, dh), jnp.float32)
    tables = jnp.asarray(np.array([[0, 2, 5], [4, 1, 6]], np.int32))
    valid = np.zeros((B, nb * bs), bool)
    valid[0, :13] = True                       # mid-block boundary
    valid[1, :1] = True                        # freshly admitted
    pos = jnp.asarray(valid.sum(axis=1) - 1, jnp.int32)   # last live lane
    out = paged_decode_attention(q, kp, vp, tables, pos, interpret=True)

    kg = np.asarray(kp)[np.asarray(tables)].reshape(B, nb * bs, HKV, dh)
    vg = np.asarray(vp)[np.asarray(tables)].reshape(B, nb * bs, HKV, dh)
    qg = np.asarray(q).reshape(B, HKV, HQ // HKV, dh)
    s = np.einsum("bhgd,bthd->bhgt", qg, kg) / np.sqrt(dh)
    s = np.where(valid[:, None, None, :], s, -np.inf)
    p = np.asarray(jax.nn.softmax(jnp.asarray(s), axis=-1))
    ref = np.einsum("bhgt,bthd->bhgd", p, vg).reshape(B, HQ, dh)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


# ---------------------------------------------------------------------------
# Chunked prefill over the paged subsystem (PR 4 tentpole): prefix sharing,
# demand allocation per chunk, and mid-prefill recompute-preemption
# ---------------------------------------------------------------------------

def _chunked(params, linkage, requests, *, n_slots=2, budget=6, **kw):
    eng = ServeEngine(CFG, params, OPTS, linkage, n_slots=n_slots,
                      max_len=MAX_LEN, kv="paged", chunked=True,
                      chunk_budget=budget, **kw)
    comps, _ = eng.run(requests, load="closed")
    assert len(comps) == len(requests)
    return {c.rid: c.tokens.tolist() for c in comps}, eng


def test_chunked_paged_shared_prefix_identity(params):
    """Shared system prompt under chunked admission: the radix index still
    resolves the prefix once (prefill starts at ``shared``), suffix chunks
    split across several steps, and streams match two-phase + sequential."""
    reqs = synthetic_requests(4, prompt_len=12, max_new_tokens=6,
                              vocab_size=CFG.vocab_size, seed=7,
                              shared_prefix_len=8)
    two_phase, _ = run_engine(params, preset("byp"), reqs, kv="paged",
                              block_size=8)
    got, eng = _chunked(params, preset("byp"), reqs, budget=5, block_size=8)
    assert got == two_phase
    for req in reqs:
        assert got[req.rid] == sequential_tokens(params, req), req.rid
    u = eng.utilization()
    assert u["kv_prefix_shared_tokens"] > 0      # later rids shared 8 tokens


def test_chunked_paged_identical_prompts_cow(params):
    """Identical prompts: a full-prefix radix hit prefills one clipped chunk
    (the P-1 cap) whose final position CoW-forks the shared tail block, and
    every stream matches the first request's. Sharing semantics differ from
    two-phase by design: rids 0 and 1 admit in the same step, and
    non-blocking admission has nothing resident to share yet — only rid 2
    (admitted after a completion) hits the index. Streams are unchanged
    either way."""
    base = synthetic_requests(1, prompt_len=16, max_new_tokens=4,
                              vocab_size=CFG.vocab_size, seed=9)[0]
    reqs = [dataclasses.replace(base, rid=i) for i in range(3)]
    got, eng = _chunked(params, preset("byp"), reqs, budget=6, block_size=8)
    want = sequential_tokens(params, base)
    for rid in got:
        assert got[rid] == want, rid
    u = eng.utilization()
    assert u["kv_cow_forks"] >= 1
    assert u["kv_prefix_shared_tokens"] == 15           # P-1, rid 2 only


def test_chunked_paged_progressive_prefix_insert(params):
    """Full prompt blocks register in the radix index as their chunks land
    (not only at prefill completion), so a request admitted while another
    is mid-prefill shares everything already resident."""
    from repro.core import preset as _preset
    eng = ServeEngine(CFG, params, OPTS, _preset("byp"), n_slots=2,
                      max_len=MAX_LEN, kv="paged", block_size=8,
                      chunked=True, chunk_budget=8)
    kv = eng.kv
    prompt = np.arange(24, dtype=np.int32) % CFG.vocab_size
    key = eng.sampling.request_key(0)
    assert kv.admit_chunked(0, prompt, key) == 0
    # two chunks land 16 tokens = 2 full blocks; prompt NOT complete yet
    assert kv.append_chunk(0, 0, prompt[:8])
    assert kv.append_chunk(0, 8, prompt[8:16])
    assert len(kv.index) == 2
    # a mid-prefill admission of the same prompt shares those 16 tokens
    assert kv.admit_chunked(1, prompt, eng.sampling.request_key(1)) == 16
    assert kv.chains[1].blocks == kv.chains[0].blocks[:2]
    assert kv.pool.refs[kv.chains[0][0]] == 3           # 2 chains + index


def test_chunked_paged_mid_prefill_preemption(params):
    """Pool pressure while a slot is still absorbing its prompt: the victim
    may be mid-prefill (its chunks already in blocks). Recompute on
    re-admission must replay the stream bit-identically — the chunked
    analogue of two-phase recompute-preemption."""
    reqs = synthetic_requests(4, prompt_len=12, max_new_tokens=10,
                              vocab_size=CFG.vocab_size, seed=3)
    eng = ServeEngine(CFG, params, OPTS, preset("byp"), n_slots=3,
                      max_len=MAX_LEN, kv="paged", block_size=4,
                      num_blocks=11, chunked=True, chunk_budget=5)
    preempted_mid_prefill = []
    orig = eng._preempt

    def spy(slot):
        preempted_mid_prefill.append(eng.sched.active[slot].prefilling)
        orig(slot)

    eng._preempt = spy
    comps, _ = eng.run(reqs, load="closed")
    got = {c.rid: c.tokens.tolist() for c in comps}
    assert eng.preemptions > 0
    assert any(preempted_mid_prefill), "no mid-prefill preemption exercised"
    for req in reqs:
        assert got[req.rid] == sequential_tokens(params, req), req.rid


def test_chunked_paged_nss_shortcut_open_loop(params):
    """Open-loop arrivals + fused L3 shortcut decode + chunked admission:
    timing changes, streams don't."""
    lk = preset("nss_shortcut")
    opts = lk.model_options(OPTS, on_tpu=False)
    reqs = synthetic_requests(4, prompt_len=10, max_new_tokens=6,
                              vocab_size=CFG.vocab_size, seed=4, rate=400.0)
    eng = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=MAX_LEN,
                      kv="paged", block_size=8, chunked=True, chunk_budget=6)
    comps, _ = eng.run(reqs, load="open")
    got = {c.rid: c.tokens.tolist() for c in comps}
    eng2 = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=MAX_LEN,
                       kv="paged", block_size=8)
    comps2, _ = eng2.run(reqs, load="closed")
    assert got == {c.rid: c.tokens.tolist() for c in comps2}


# ---------------------------------------------------------------------------
# Two-tier KV hierarchy: host tier units, swap-out preemption identity,
# demote/promote, and restart-persistent prefix cache
# ---------------------------------------------------------------------------

def test_host_block_store_alloc_free_lru():
    from repro.serve import HostBlockStore
    host = HostBlockStore(3, block_size=4)          # allocator-only mode
    a, b = host.alloc(), host.alloc()
    assert (a, b) == (0, 1) and host.n_resident == 2 and host.hwm == 2
    assert host.tick[b] > host.tick[a]              # allocation touches
    host.touch(a)
    assert host.tick[a] > host.tick[b]              # LRU order flips
    assert host.free(a) is True
    assert host.alloc() == 0                        # lowest-first replay
    host.retain(b)
    assert host.free(b) is False
    assert host.free(b) is True
    with pytest.raises(ValueError, match="double free"):
        host.free(b)
    with pytest.raises(ValueError, match="retain"):
        host.retain(b)


def test_host_block_store_write_read_roundtrip():
    from repro.serve import HostBlockStore
    shape = (2, 4, 2, 8)                            # (L, bs, HKV, dh)
    host = HostBlockStore(2, block_size=4, group_shapes=[shape],
                          dtype=np.float32)
    n = int(np.prod(shape))
    kv = ({"k": np.arange(n, dtype=np.float32).reshape(shape),
           "v": -np.arange(n, dtype=np.float32).reshape(shape)},)
    blk = host.alloc()
    host.write(blk, kv)
    out = host.read(blk)
    np.testing.assert_array_equal(out[0]["k"], kv[0]["k"])
    np.testing.assert_array_equal(out[0]["v"], kv[0]["v"])
    out[0]["k"][:] = 0                              # read returns copies
    np.testing.assert_array_equal(host.read(blk)[0]["k"], kv[0]["k"])


def _swap_linkage(preset_name):
    lk = preset(preset_name)
    if lk.level == L3_NSS:
        # short fused programs so three decoding slots overlap under the
        # pressure geometry (K=32 would outlive the 12-token budgets)
        lk = dataclasses.replace(lk, decode_steps=4)
    opts = lk.model_options(OPTS, on_tpu=False) if lk.shortcut else OPTS
    return lk, opts


PRESSURE = dict(n_slots=3, block_size=4, num_blocks=9)


@pytest.mark.parametrize("preset_name",
                         ["base", "nss_shortcut", "ret_byp_shortcut"])
def test_swap_vs_recompute_identity(params, preset_name):
    """The acceptance matrix, 1x1 column: under a pool far smaller than
    worst-case, swap-preempted token streams are bit-identical to
    recompute-preempted and to sequential decode — and swaps actually
    happened (blocks moved out AND back in)."""
    lk, opts = _swap_linkage(preset_name)
    reqs = synthetic_requests(4, prompt_len=8, max_new_tokens=12,
                              vocab_size=CFG.vocab_size, seed=3)
    eng_r = ServeEngine(CFG, params, opts, lk, max_len=MAX_LEN, kv="paged",
                        preempt="recompute", **PRESSURE)
    rec = {c.rid: c.tokens.tolist()
           for c in eng_r.run(reqs, load="closed")[0]}
    eng_s = ServeEngine(CFG, params, opts, lk, max_len=MAX_LEN, kv="paged",
                        preempt="swap", **PRESSURE)
    swp = {c.rid: c.tokens.tolist()
           for c in eng_s.run(reqs, load="closed")[0]}
    assert swp == rec, f"{preset_name}: swap diverged from recompute"
    assert eng_r.preemptions > 0
    assert eng_s.swap_preemptions > 0 and eng_s.swap_resumes > 0
    u = eng_s.utilization()
    assert u["kv_swap_out_blocks"] > 0 and u["kv_swap_in_blocks"] > 0
    assert u["kv_host_bytes_moved"] > 0
    for req in reqs:
        assert swp[req.rid] == sequential_tokens(params, req, opts=opts), (
            preset_name, req.rid)


def test_chunked_swap_vs_recompute_identity(params):
    """Chunked engine under pool pressure with swap preemption: victims can
    be mid-prefill (partially landed chunks swap out with the chain and the
    prompt source rides the handle). Streams match the chunked recompute
    engine and sequential decode."""
    reqs = synthetic_requests(4, prompt_len=12, max_new_tokens=10,
                              vocab_size=CFG.vocab_size, seed=3)
    kw = dict(n_slots=3, max_len=MAX_LEN, kv="paged", block_size=4,
              num_blocks=11, chunked=True, chunk_budget=5)
    eng_r = ServeEngine(CFG, params, OPTS, preset("byp"), **kw)
    rec = {c.rid: c.tokens.tolist()
           for c in eng_r.run(reqs, load="closed")[0]}
    eng_s = ServeEngine(CFG, params, OPTS, preset("byp"), preempt="swap",
                        **kw)
    swp = {c.rid: c.tokens.tolist()
           for c in eng_s.run(reqs, load="closed")[0]}
    assert swp == rec
    assert eng_s.swap_preemptions > 0 and eng_s.swap_resumes > 0
    for req in reqs:
        assert swp[req.rid] == sequential_tokens(params, req), req.rid


def test_swap_lru_victim_identity(params):
    """Victim selection is a scheduler policy, not a correctness knob: the
    LRU policy preempts different slots but every stream still matches."""
    lk, opts = _swap_linkage("base")
    reqs = synthetic_requests(4, prompt_len=8, max_new_tokens=12,
                              vocab_size=CFG.vocab_size, seed=3)
    from repro.serve import PreemptionPolicy
    eng = ServeEngine(CFG, params, opts, lk, max_len=MAX_LEN, kv="paged",
                      preempt=PreemptionPolicy(mode="swap", victim="lru"),
                      **PRESSURE)
    got = {c.rid: c.tokens.tolist() for c in eng.run(reqs, load="closed")[0]}
    assert eng.swap_preemptions + eng.preemptions > 0
    for req in reqs:
        assert got[req.rid] == sequential_tokens(params, req), req.rid


def test_swapped_victims_resume_in_admission_order():
    """Regression: under ``--victim lru`` preemption order need not be
    admission order, and ``suspend_front`` parks the latest victim first —
    so parking order can INVERT admission order. ``resume_next`` must pop
    by original ``admit_seq``, not parking position (rid 1 resuming ahead
    of the earlier-admitted rid 0 was the observable bug)."""
    from repro.serve import SlotScheduler
    sched = SlotScheduler(3)
    for rid in range(3):
        sched.enqueue(Request(rid=rid, prompt=np.zeros(4, np.int32),
                              max_new_tokens=4))
    slots = {}
    for rid in range(3):
        slot, req = sched.admit_next(float(rid))
        slots[rid] = slot
    # emit recency ascending with rid: LRU victimizes rid 0 first, then
    # rid 1 — oldest admissions preempted first, the inversion case
    for rid in range(3):
        sched.active[slots[rid]].note_emit(10.0 + rid)
    v1 = sched.choose_victim("lru")
    assert sched.active[v1].req.rid == 0
    sched.suspend_front(sched.release(v1), "handle-0")
    v2 = sched.choose_victim("lru")
    assert sched.active[v2].req.rid == 1
    sched.suspend_front(sched.release(v2), "handle-1")
    # parked [rid 1, rid 0]; admission order is rid 0 first
    assert [st.req.rid for st, _ in sched.swapped] == [1, 0]
    head = sched.peek_swapped()
    assert head is not None and head[0].req.rid == 0
    _, st, handle = sched.resume_next()
    assert (st.req.rid, handle) == (0, "handle-0")
    _, st, handle = sched.resume_next()
    assert (st.req.rid, handle) == (1, "handle-1")
    # the resumed state is the youngest again (recompute-readmit parity)
    assert sched.active and not sched.swapped


def test_drop_swap_makes_handle_unresumable(params):
    """Regression: ``drop_swap`` used to empty the handle but leave it
    resumable-looking in the caller's hands — a later ``swap_in`` silently
    restored zero blocks. Dropped handles must refuse to resume, and the
    drop must return every host block exactly once."""
    eng = ServeEngine(CFG, params, OPTS, preset("byp"), n_slots=2,
                      max_len=MAX_LEN, kv="paged", block_size=8,
                      num_blocks=6, preempt="swap", host_blocks=6)
    prompt = (np.arange(16, dtype=np.int32) * 5 + 2) % CFG.vocab_size
    eng.sched.enqueue(Request(rid=0, prompt=prompt, max_new_tokens=4))
    eng._admit(lambda: 0.0)
    handle = eng.kv.swap_out(0)
    assert handle is not None and len(handle.hblks) == 2
    free_before = eng.kv.host.n_free
    eng.kv.drop_swap(handle)
    assert handle.dropped and handle.hblks == []
    assert eng.kv.host.n_free == free_before + 2
    with pytest.raises(RuntimeError, match="drop_swap"):
        eng.kv.swap_in(1, handle)
    eng.kv.drop_swap(handle)                    # idempotent, no double free
    assert eng.kv.host.n_free == free_before + 2


def test_prefix_demote_promote_roundtrip(params):
    """Index eviction under pool pressure demotes the block to the host
    tier instead of dropping it; a later admission of the same prompt
    promotes it back and shares — no re-prefill of the demoted prefix."""
    vocab = CFG.vocab_size
    pa = (np.arange(16, dtype=np.int32) * 7 + 1) % vocab
    pb = (np.arange(16, dtype=np.int32) * 11 + 3) % vocab
    reqs = [Request(rid=0, prompt=pa, max_new_tokens=4),
            Request(rid=1, prompt=pb, max_new_tokens=4),
            Request(rid=2, prompt=pa.copy(), max_new_tokens=4)]
    eng = ServeEngine(CFG, params, OPTS, preset("byp"), n_slots=1,
                      max_len=MAX_LEN, kv="paged", block_size=8,
                      num_blocks=4, host_blocks=8)
    comps, _ = eng.run(reqs, load="closed")
    got = {c.rid: c.tokens.tolist() for c in comps}
    assert got[2] == got[0]                     # same prompt, same stream
    u = eng.utilization()
    assert u["kv_prefix_demotions"] > 0         # rid 1 evicted rid 0's blocks
    assert u["kv_prefix_promotions"] > 0        # rid 2 pulled them back
    assert u["kv_prefix_shared_tokens"] == 15   # P-1 of rid 2's prompt


def test_prefix_cache_warm_start_restart(params, tmp_path):
    """The acceptance invariant: a restarted engine with ``warm_start``
    produces identical tokens with nonzero shared_tokens on its first
    batch — persisted prefixes are never re-prefilled."""
    reqs = synthetic_requests(4, prompt_len=24, max_new_tokens=5,
                              vocab_size=CFG.vocab_size, seed=7,
                              shared_prefix_len=16)
    kw = dict(n_slots=2, max_len=MAX_LEN, kv="paged", block_size=8)
    eng1 = ServeEngine(CFG, params, OPTS, preset("byp"), **kw)
    got1 = {c.rid: c.tokens.tolist()
            for c in eng1.run(reqs, load="closed")[0]}
    path = str(tmp_path / "prefix.npz")
    assert eng1.save_prefix_cache(path) > 0
    eng2 = ServeEngine(CFG, params, OPTS, preset("byp"), warm_start=path,
                       **kw)
    assert eng2.kv.restored_entries > 0
    got2 = {c.rid: c.tokens.tolist()
            for c in eng2.run(reqs, load="closed")[0]}
    assert got2 == got1
    u = eng2.utilization()
    # every request shares P-1 of its persisted prompt chain (the cap that
    # keeps the final prompt position computing its own logits)
    assert u["kv_prefix_shared_tokens"] == 23 * 4
    assert u["kv_prefix_promotions"] > 0


def test_warm_start_fingerprint_mismatch(params, tmp_path):
    reqs = synthetic_requests(2, prompt_len=16, max_new_tokens=3,
                              vocab_size=CFG.vocab_size, seed=1)
    eng1 = ServeEngine(CFG, params, OPTS, preset("byp"), n_slots=2,
                       max_len=MAX_LEN, kv="paged", block_size=8)
    eng1.run(reqs, load="closed")
    path = str(tmp_path / "prefix.npz")
    assert eng1.save_prefix_cache(path) > 0
    with pytest.raises(ValueError, match="different config"):
        ServeEngine(CFG, params, OPTS, preset("byp"), n_slots=2,
                    max_len=MAX_LEN, kv="paged", block_size=4,
                    warm_start=path)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(CFG, params, OPTS, preset("byp"), n_slots=2,
                    max_len=MAX_LEN, kv="slotted", warm_start=path)


def test_pool_scheduler_swap_differential_deterministic():
    """Deterministic twin of the PoolSchedulerMachine swap transitions
    (tests/test_properties.py; hypothesis is optional): random admit /
    reserve / CoW / finish / swap-out / swap-in sequences drive a real
    BlockPool + HostBlockStore pair while a pure-Python model mirrors every
    reference on both tiers."""
    from repro.serve import BlockPool, HostBlockStore
    rng = np.random.default_rng(7)
    N, H = 10, 6
    pool = BlockPool(N, block_size=4)
    host = HostBlockStore(H, block_size=4)
    refs, hrefs = {}, {}
    chains = {}                     # slot -> [device blk]
    swapped = {}                    # tag -> [host blk]
    order = []
    next_id = [0]

    def alloc():
        blk = pool.alloc()
        if blk is None:
            assert pool.n_free == 0
            return None
        assert refs.get(blk, 0) == 0
        refs[blk] = 1
        return blk

    def drop(blk):
        pool.free(blk)
        refs[blk] -= 1
        if refs[blk] == 0:
            del refs[blk]

    for op in rng.integers(0, 8, size=500):
        if op == 0:                                    # admit
            n = int(rng.integers(1, 4))
            chain, ok = [], True
            while len(chain) < n:
                blk = alloc()
                if blk is None:
                    for b in chain:
                        drop(b)
                    ok = False
                    break
                chain.append(blk)
            if ok:
                chains[next_id[0]] = chain
                order.append(next_id[0])
                next_id[0] += 1
        elif op == 1 and chains:                       # demand-reserve
            slot = sorted(chains)[int(rng.integers(len(chains)))]
            blk = alloc()
            if blk is not None:
                chains[slot].append(blk)
        elif op == 2:                                  # CoW-ish share+fork
            if order and rng.random() < 0.5:
                donor = chains[order[0]]
                pool.retain(donor[0])
                refs[donor[0]] += 1
                new = alloc()
                if new is None:
                    drop(donor[0])
                else:
                    drop(donor[0])
                    chains.setdefault(-next_id[0] - 1, []).append(new)
                    # fold the fork target into a fresh one-block chain
                    chains[next_id[0]] = chains.pop(-next_id[0] - 1)
                    order.append(next_id[0])
                    next_id[0] += 1
        elif op == 3 and chains:                       # finish
            slot = sorted(chains)[int(rng.integers(len(chains)))]
            for b in chains.pop(slot):
                drop(b)
            order.remove(slot)
        elif op == 4 and order:                        # preempt (recompute)
            for b in chains.pop(order[-1]):
                drop(b)
            order.pop()
        elif op == 5 and chains:                       # swap-out
            slot = sorted(chains)[int(rng.integers(len(chains)))]
            hblks, ok = [], True
            for _ in chains[slot]:
                h = host.alloc()
                if h is None:
                    assert host.n_free == 0
                    for hb in hblks:
                        host.free(hb)
                        del hrefs[hb]
                    ok = False
                    break
                assert hrefs.get(h, 0) == 0
                hrefs[h] = 1
                hblks.append(h)
            if ok:
                for b in chains.pop(slot):
                    drop(b)
                order.remove(slot)
                swapped[next_id[0]] = hblks
                next_id[0] += 1
        elif op == 6 and swapped:                      # swap-in
            tag = sorted(swapped)[int(rng.integers(len(swapped)))]
            dblks, ok = [], True
            for _ in swapped[tag]:
                b = alloc()
                if b is None:
                    for db in dblks:
                        drop(db)
                    ok = False
                    break
                dblks.append(b)
            if ok:
                for h in swapped.pop(tag):
                    host.free(h)
                    del hrefs[h]
                chains[next_id[0]] = dblks
                order.append(next_id[0])
                next_id[0] += 1
        elif op == 7 and chains:                       # spec verify roundtrip
            # draft-and-verify (PR 6): CoW-fork a shared tail, reserve the
            # draft span, then roll back to the accepted length — rejected
            # tail blocks free physically, sharers stay untouched
            slot = sorted(chains)[int(rng.integers(len(chains)))]
            chain = chains[slot]
            if pool.refs[chain[-1]] > 1:
                new = alloc()
                if new is None:
                    continue
                drop(chain[-1])
                chain[-1] = new
            width = int(rng.integers(1, 4))
            span, ok = [], True
            for _ in range(width):
                blk = alloc()
                if blk is None:                        # dry: roll span back
                    for b in span:
                        drop(b)
                    ok = False
                    break
                span.append(blk)
            if ok:
                chain.extend(span)
                keep = int(rng.integers(0, width + 1))
                for b in span[keep:]:
                    assert pool.refs[b] == 1   # never truncate into a share
                    drop(b)
                if width > keep:
                    del chain[-(width - keep):]
        # differential invariants on BOTH tiers, every step
        for blk in range(N):
            assert pool.refs[blk] == refs.get(blk, 0), blk
        assert pool.n_free == N - len(refs)
        for blk in range(H):
            assert host.refs[blk] == hrefs.get(blk, 0), blk
        assert host.n_free == H - len(hrefs)
        assert host.n_resident <= host.hwm <= H
    for slot in list(sorted(chains)):                  # clean teardown
        for b in chains.pop(slot):
            drop(b)
    for tag in list(sorted(swapped)):
        for h in swapped.pop(tag):
            host.free(h)
            del hrefs[h]
    assert pool.n_free == N and (pool.refs == 0).all()
    assert host.n_free == H and (host.refs == 0).all()


def test_swap_stream_differential_deterministic():
    """Deterministic twin of the PoolSchedulerMachine async-swap rules
    (tests/test_properties.py; hypothesis is optional): a seeded admit /
    swap-out / prefetch / drop / swap-in / drain sequence drives a real
    ``SwapStream``, asserting the drain discipline — every deferred
    device→host write lands exactly once on a still-referenced host block,
    draining moves no refcounts on either tier, and a prefetched resume
    cancelled by completion (swap-in) or second preemption (drop) leaves
    both pools exact."""
    from repro.serve import BlockPool, HostBlockStore, SwapStream
    rng = np.random.default_rng(13)
    N, H = 8, 5
    pool = BlockPool(N, block_size=4)
    host = HostBlockStore(H, block_size=4)
    refs, hrefs = {}, {}
    chains, swapped = {}, {}
    prefetched, pending, landed = set(), set(), set()
    nid = [0]

    def write(hblks, kvs):
        for h in hblks:
            assert h in pending, "write landed twice or unissued"
            pending.discard(h)
            assert hrefs.get(h, 0) == 1, "write landed on a freed block"
            landed.add(h)

    stream = SwapStream(write, depth=2)

    def drain():
        before = (dict(refs), dict(hrefs))
        stream.drain()
        assert not pending and before == (refs, hrefs)

    def alloc():
        blk = pool.alloc()
        if blk is None:
            return None
        refs[blk] = 1
        return blk

    def drop(blk):
        pool.free(blk)
        refs[blk] -= 1
        if refs[blk] == 0:
            del refs[blk]

    for op in rng.integers(0, 6, size=500):
        if op == 0:                                    # admit
            chain = []
            for _ in range(int(rng.integers(1, 4))):
                blk = alloc()
                if blk is None:
                    break
                chain.append(blk)
            if chain:
                chains[nid[0]] = chain
                nid[0] += 1
        elif op == 1 and chains:                       # async swap-out
            slot = sorted(chains)[int(rng.integers(len(chains)))]
            hblks, ok = [], True
            for _ in chains[slot]:
                h = host.alloc()
                if h is None:
                    for hb in hblks:
                        host.free(hb)
                        del hrefs[hb]
                    ok = False
                    break
                hrefs[h] = 1
                hblks.append(h)
            if ok:
                pending.update(hblks)
                stream.issue(hblks, ({"k": np.zeros(1, np.float32),
                                      "v": np.zeros(1, np.float32)},),
                             len(hblks) * 16)
                for b in chains.pop(slot):
                    drop(b)
                swapped[nid[0]] = hblks
                nid[0] += 1
        elif op == 2 and swapped:                      # prefetch resume head
            tag = min(swapped)
            drain()
            assert all(h in landed for h in swapped[tag])
            prefetched.add(tag)
        elif op == 3 and swapped:                      # drop (2nd preemption)
            tag = sorted(swapped)[int(rng.integers(len(swapped)))]
            drain()
            prefetched.discard(tag)
            for h in swapped.pop(tag):
                host.free(h)
                del hrefs[h]
                landed.discard(h)
        elif op == 4 and swapped:                      # swap-in (resume)
            tag = sorted(swapped)[int(rng.integers(len(swapped)))]
            dblks, ok = [], True
            for _ in swapped[tag]:
                b = alloc()
                if b is None:
                    for db in dblks:
                        drop(db)
                    ok = False
                    break
                dblks.append(b)
            if ok:
                drain()
                prefetched.discard(tag)
                for h in swapped.pop(tag):
                    assert h in landed
                    host.free(h)
                    del hrefs[h]
                    landed.discard(h)
                chains[nid[0]] = dblks
                nid[0] += 1
        elif op == 5 and chains:                       # finish
            slot = sorted(chains)[int(rng.integers(len(chains)))]
            for b in chains.pop(slot):
                drop(b)
        # differential invariants on both tiers + the stream, every step
        assert len(stream) <= 2
        for h in pending:
            assert hrefs.get(h, 0) == 1
        assert prefetched <= set(swapped)
        for blk in range(N):
            assert pool.refs[blk] == refs.get(blk, 0), blk
        assert pool.n_free == N - len(refs)
        for blk in range(H):
            assert host.refs[blk] == hrefs.get(blk, 0), blk
        assert host.n_free == H - len(hrefs)
    drain()
    for slot in sorted(chains):
        for b in chains[slot]:
            drop(b)
    for tag in sorted(swapped):
        for h in swapped[tag]:
            host.free(h)
            del hrefs[h]
    assert pool.n_free == N and (pool.refs == 0).all()
    assert host.n_free == H and (host.refs == 0).all()


def test_spec_rollback_pool_integrity_end_to_end(params):
    """The real PagedKV.rollback under a speculative workload with shared
    prefixes and pool pressure: every verify step truncates its rejected
    tail via pool.free, so after the run (and shedding the radix-held
    prefix entries) the pool must drain to empty — no leaked blocks, no
    double frees, no refcount drift — with streams identical to the plain
    paged engine."""
    rng = np.random.default_rng(9)
    core = rng.integers(0, CFG.vocab_size, 4, dtype=np.int32)
    shared = np.tile(core, 4)                       # 16 tokens, CoW-shared
    reqs = [Request(rid=i,
                    prompt=np.concatenate(
                        [shared,
                         rng.integers(0, CFG.vocab_size, 2, np.int32)]),
                    max_new_tokens=12) for i in range(4)]
    kw = dict(kv="paged", block_size=8, num_blocks=14)
    plain, _ = run_engine(params, preset("byp"), reqs, **kw)
    got, eng = run_engine(params, preset("byp"), reqs,
                          spec_decode="ngram", spec_width=6, **kw)
    assert got == plain
    u = eng.utilization()
    assert u["spec_steps"] > 0 and u["spec_accepted_tokens"] > 0
    assert u["spec_wasted_tokens"] > 0              # rollback actually ran
    assert u["kv_prefix_shared_tokens"] > 0         # under CoW sharing
    eng.kv.drop_prefix_cache()
    pool = eng.kv.pool
    assert pool.n_resident == 0 and (pool.refs == 0).all()
    assert pool.n_free == 14


# ---------------------------------------------------------------------------
# Quantized KV blocks (kv_dtype=int8/fp8): compression ratios, lifecycle
# (swap / warm-start / fingerprint), and the bf16 structural control
# ---------------------------------------------------------------------------

def test_bf16_control_cache_has_no_scale_leaves(params):
    """kv_dtype='bf16' must be the *structural* control: no scale tables in
    the cache tree, so every write path takes its original branch and the
    unquantized engine stays bit-identical to the pre-quantization code."""
    eng = ServeEngine(CFG, params, OPTS, preset("byp"), n_slots=2,
                      max_len=MAX_LEN, kv="paged", block_size=8)
    assert all("ks" not in g and "vs" not in g for g in eng.kv.cache)
    engq = ServeEngine(CFG, params, OPTS, preset("byp"), n_slots=2,
                       max_len=MAX_LEN, kv="paged", block_size=8,
                       kv_dtype="int8")
    assert all("ks" in g and "vs" in g for g in engq.kv.cache)
    assert all(g["kp"].dtype == jnp.int8 for g in engq.kv.cache)


def test_kv_dtype_rejected_on_slotted(params):
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(CFG, params, OPTS, preset("byp"), n_slots=2,
                    max_len=MAX_LEN, kv="slotted", kv_dtype="int8")


def test_quantized_block_bytes_compression(params):
    """int8 blocks must fit >=1.9x more resident tokens per HBM byte than
    the uncompressed pool (values 4x smaller; scales are the overhead)."""
    kw = dict(n_slots=2, max_len=MAX_LEN, kv="paged", block_size=8)
    b = ServeEngine(CFG, params, OPTS, preset("byp"), **kw).utilization()
    q = ServeEngine(CFG, params, OPTS, preset("byp"), kv_dtype="int8",
                    **kw).utilization()
    assert q["kv_dtype"] == "int8" and b["kv_dtype"] == "bf16"
    ratio = b["kv_bytes_per_block"] / q["kv_bytes_per_block"]
    assert ratio >= 1.9
    # at a fixed HBM budget the resident-block capacity scales by the same
    # ratio (blocks are the allocation granularity)
    budget = 64 * b["kv_bytes_per_block"]
    assert budget // q["kv_bytes_per_block"] >= 1.9 * 64


def test_quantized_greedy_flip_rate_small(params):
    """Acceptance gate: int8 greedy token-flip-rate <= 1% vs the bf16
    control on the smoke workload."""
    reqs = synthetic_requests(4, prompt_len=12, max_new_tokens=10,
                              vocab_size=CFG.vocab_size, seed=0,
                              shared_prefix_len=8)
    ref, _ = run_engine(params, preset("byp"), reqs, kv="paged",
                        block_size=8)
    got, _ = run_engine(params, preset("byp"), reqs, kv="paged",
                        block_size=8, kv_dtype="int8")
    total = sum(len(t) for t in ref.values())
    flips = sum(a != b for r in ref
                for a, b in zip(ref[r], got[r]))
    assert flips / total <= 0.01, f"{flips}/{total} tokens flipped"


@pytest.mark.parametrize("kvd", ["int8", "fp8"])
def test_quantized_prefix_cache_warm_start(params, kvd, tmp_path):
    """The persisted npz carries quantized bytes + scale tables *losslessly*
    (fp8 rides as a uint8 bitcast): restore -> save must reproduce every
    entry bit-exactly, and the restarted engine serves the workload with
    shared prefixes. Token streams are NOT asserted bit-identical here:
    warm-start changes each prompt's shared/suffix split, and a suffix
    recomputed over the *dequantized* prefix differs from one computed over
    the exact f32 prefill — inherent to lossy modes (the bf16 control keeps
    the bit-identity guarantee in test_prefix_cache_warm_start_restart)."""
    if kvd == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("no fp8 in this jax")
    reqs = synthetic_requests(4, prompt_len=24, max_new_tokens=5,
                              vocab_size=CFG.vocab_size, seed=7,
                              shared_prefix_len=16)
    kw = dict(n_slots=2, max_len=MAX_LEN, kv="paged", block_size=8,
              kv_dtype=kvd)
    eng1 = ServeEngine(CFG, params, OPTS, preset("byp"), **kw)
    comps1, _ = eng1.run(reqs, load="closed")
    assert len(comps1) == len(reqs)
    path = str(tmp_path / "prefix.npz")
    assert eng1.save_prefix_cache(path) > 0
    with np.load(path) as data:
        n = int(data["n"])
        # values persist quantized, not laundered through f32
        want_dt = np.uint8 if kvd == "fp8" else np.int8
        assert data["k_0_0"].dtype == want_dt
        assert data["ks_0_0"].dtype == np.float32
    eng2 = ServeEngine(CFG, params, OPTS, preset("byp"), warm_start=path,
                       **kw)
    assert eng2.kv.restored_entries == n
    # lossless roundtrip: a save right after restore reproduces every entry
    path2 = str(tmp_path / "prefix2.npz")
    assert eng2.save_prefix_cache(path2) == n
    with np.load(path) as a, np.load(path2) as b:
        ea = {a[f"tok_{i}"].tobytes(): i for i in range(n)}
        eb = {b[f"tok_{i}"].tobytes(): i for i in range(n)}
        assert ea.keys() == eb.keys()
        for key, i in ea.items():
            j = eb[key]
            for f in ("k", "v", "ks", "vs"):
                np.testing.assert_array_equal(a[f"{f}_{i}_0"],
                                              b[f"{f}_{j}_0"])
    comps2, _ = eng2.run(reqs, load="closed")
    assert len(comps2) == len(reqs)
    assert all(len(c.tokens) == 5 for c in comps2)
    assert eng2.utilization()["kv_prefix_shared_tokens"] > 0


def test_kv_dtype_fingerprint_mismatch(params, tmp_path):
    """Satellite fix: the prefix-cache fingerprint must cover kv_dtype — a
    quantized engine opening an uncompressed-era npz (or vice versa) raises
    instead of silently misreading the payload."""
    reqs = synthetic_requests(2, prompt_len=16, max_new_tokens=3,
                              vocab_size=CFG.vocab_size, seed=1)
    kw = dict(n_slots=2, max_len=MAX_LEN, kv="paged", block_size=8)
    eng1 = ServeEngine(CFG, params, OPTS, preset("byp"), **kw)
    eng1.run(reqs, load="closed")
    path = str(tmp_path / "prefix.npz")
    assert eng1.save_prefix_cache(path) > 0
    with pytest.raises(ValueError, match="different config"):
        ServeEngine(CFG, params, OPTS, preset("byp"), warm_start=path,
                    kv_dtype="int8", **kw)


def test_quantized_swap_moves_compressed_bytes(params):
    """Under pool pressure with kv_dtype=int8 the engine still completes
    every request (preempt/resume correctness on quantized blocks), the
    async and sync swap runtimes stay bit-identical to each other, and the
    tier traffic drops >=1.9x vs the uncompressed equivalent (the
    kv_host_bytes_moved_raw counter)."""
    reqs = synthetic_requests(4, prompt_len=8, max_new_tokens=12,
                              vocab_size=CFG.vocab_size, seed=3)
    kw = dict(kv="paged", block_size=8, num_blocks=5, host_blocks=12,
              kv_dtype="int8",
              preempt=__import__("repro.serve", fromlist=["PreemptionPolicy"]
                                 ).PreemptionPolicy(mode="swap"))
    lk = dataclasses.replace(preset("nss_shortcut"), decode_steps=4)
    opts = preset("nss_shortcut").model_options(OPTS, on_tpu=False)
    got_async, eng = {}, None
    for async_swap in (True, False):
        eng = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=MAX_LEN,
                          async_swap=async_swap, **kw)
        comps, _ = eng.run(reqs, load="closed")
        assert len(comps) == len(reqs)
        got = {c.rid: c.tokens.tolist() for c in comps}
        assert all(len(t) == 12 for t in got.values())
        if async_swap:
            got_async = got
        else:
            assert got == got_async   # same quantized bytes either way
    u = eng.utilization()
    assert u["kv_swap_out_blocks"] > 0 and u["kv_swap_in_blocks"] > 0
    assert u["kv_host_bytes_moved"] > 0
    assert u["kv_host_bytes_moved_raw"] >= 1.9 * u["kv_host_bytes_moved"]


def test_host_block_store_quantized_roundtrip():
    """HostBlockStore with scale_shapes stores quantized bytes + f32 scales
    and round-trips them exactly (no dtype laundering through f32)."""
    from repro.serve.paging import HostBlockStore
    L, bs, HKV, dh = 2, 8, 3, 16
    store = HostBlockStore(4, bs, group_shapes=[(L, bs, HKV, dh)],
                           dtype=np.int8, scale_shapes=[(L, HKV)])
    h = store.alloc()
    rng = np.random.default_rng(0)
    kv = {"k": rng.integers(-127, 128, (L, bs, HKV, dh)).astype(np.int8),
          "v": rng.integers(-127, 128, (L, bs, HKV, dh)).astype(np.int8),
          "ks": rng.random((L, HKV)).astype(np.float32),
          "vs": rng.random((L, HKV)).astype(np.float32)}
    store.write(h, (kv,))
    back = store.read(h)[0]
    for key in ("k", "v", "ks", "vs"):
        assert back[key].dtype == kv[key].dtype
        np.testing.assert_array_equal(back[key], kv[key])
