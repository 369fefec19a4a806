"""Sharded serving: the cross-backend identity matrix over a device mesh.

The load-bearing claim extends PR 1/2's: sharding the engine over a
``(data, model)`` mesh — weights tensor-parallel over "model", KV heads
per-shard resident (slot rows and physical block pools alike), slots over
"data" — changes *placement only*. Every request's token stream must be
bit-identical to the single-device engine and to running it alone through
prefill + sequential decode, across {slotted, paged} x {base, nss_shortcut,
ret_byp_shortcut} x {1x1, 1x2, 2x1}, including shared-prefix CoW and
recompute-preemption workloads.

The test process runs with 4 forced virtual host devices (tests/conftest.py)
so the meshes exist on CPU CI. Representatives run in tier-1; the exhaustive
matrix is marked ``slow`` (--runslow).

Note on "bit-identical": the guarantee is on *token streams*. Row-parallel
projections partial-sum over the model axis, so logits match the unsharded
program only to float accumulation order (~1e-7) — which greedy argmax and
the per-request sampling key chains are insensitive to at these margins.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import preset
from repro.launch.mesh import make_host_mesh
from repro.models import ModelOptions, decode_step, init_params, prefill
from repro.serve import ServeEngine, synthetic_requests

CFG = get_config("tinyllama-1.1b").smoke()
REF_OPTS = ModelOptions(attn_impl="ref", scan_impl="ref", dtype=jnp.float32)
MAX_LEN = 48

MESHES = {"1x1": None, "1x2": (1, 2), "2x1": (2, 1)}
PRESETS = ("base", "nss_shortcut", "ret_byp_shortcut")
BACKENDS = ("slotted", "paged")

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="mesh serving tests need >= 2 (virtual) devices; see conftest.py")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _linkage_opts(preset_name):
    lk = preset(preset_name)
    opts = lk.model_options(REF_OPTS, on_tpu=False) if lk.shortcut \
        else REF_OPTS
    return lk, opts


def _mesh(mesh_name):
    shape = MESHES[mesh_name]
    return None if shape is None else make_host_mesh(*shape)


# compiled-program and reference-stream caches: jitting inside helpers would
# recompile per call (new lambda identity), and the matrix reuses the same
# sequential references across many cells
_SEQ_FNS = {}
_SEQ_STREAMS = {}


def sequential_tokens(params, preset_name, req):
    """Reference: the request alone, prefill + one-token decode loop, at the
    cell's own ModelOptions (shortcut presets lower through the blockwise
    forms exactly like the engine does)."""
    key = (preset_name, req.rid, req.prompt.tobytes(), req.max_new_tokens)
    if key in _SEQ_STREAMS:
        return _SEQ_STREAMS[key]
    if preset_name not in _SEQ_FNS:
        _, opts = _linkage_opts(preset_name)
        _SEQ_FNS[preset_name] = (
            jax.jit(lambda p, t: prefill(p, t, CFG, opts, max_len=MAX_LEN)),
            jax.jit(lambda p, c, t: decode_step(p, c, t, CFG, opts)))
    pf, dec = _SEQ_FNS[preset_name]
    logits, cache = pf(params, jnp.asarray(req.prompt)[None])
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [int(nxt[0])]
    for _ in range(req.max_new_tokens - 1):
        logits, cache = dec(params, cache, nxt)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(int(nxt[0]))
    _SEQ_STREAMS[key] = out
    return out


def run_cell(params, kv, preset_name, mesh_name, reqs, *, n_slots=2, **kw):
    lk, opts = _linkage_opts(preset_name)
    eng = ServeEngine(CFG, params, opts, lk, n_slots=n_slots, max_len=MAX_LEN,
                      kv=kv, mesh=_mesh(mesh_name), **kw)
    comps, _ = eng.run(reqs, load="closed")
    assert len(comps) == len(reqs)
    return {c.rid: c.tokens.tolist() for c in comps}, eng


def _matrix_requests():
    """The identity workload: mixed slot reuse (4 requests, 2 slots) plus an
    8-token shared prefix so paged cells exercise prefix sharing too."""
    return synthetic_requests(4, prompt_len=12, max_new_tokens=6,
                              vocab_size=CFG.vocab_size, seed=7,
                              shared_prefix_len=8)


# ---------------------------------------------------------------------------
# Tier-1 representatives (one per mesh shape, spanning backends and presets)
# ---------------------------------------------------------------------------

REPRESENTATIVES = [("slotted", "nss_shortcut", "1x2"),
                   ("paged", "base", "1x2"),
                   ("paged", "ret_byp_shortcut", "2x1")]


@needs_devices
@pytest.mark.parametrize("kv,preset_name,mesh_name", REPRESENTATIVES)
def test_mesh_identity_representative(params, kv, preset_name, mesh_name):
    reqs = _matrix_requests()
    got, _ = run_cell(params, kv, preset_name, mesh_name, reqs, block_size=8)
    for req in reqs:
        want = sequential_tokens(params, preset_name, req)
        assert got[req.rid] == want, (
            f"{kv}/{preset_name}/{mesh_name} rid {req.rid}: "
            f"mesh {got[req.rid]} != sequential {want}")


# ---------------------------------------------------------------------------
# The full matrix (slow): mesh engine == 1-device engine == sequential
# ---------------------------------------------------------------------------

@pytest.mark.slow
@needs_devices
@pytest.mark.parametrize("mesh_name", [m for m in MESHES if m != "1x1"])
@pytest.mark.parametrize("preset_name", PRESETS)
@pytest.mark.parametrize("kv", BACKENDS)
def test_mesh_identity_matrix(params, kv, preset_name, mesh_name):
    reqs = _matrix_requests()
    # the 1x1 column of the matrix: the single-device engine every mesh cell
    # must reproduce (itself asserted against sequential below)
    one_dev, _ = run_cell(params, kv, preset_name, "1x1", reqs, block_size=8)
    got, eng = run_cell(params, kv, preset_name, mesh_name, reqs,
                        block_size=8)
    assert got == one_dev, f"{kv}/{preset_name}/{mesh_name} != 1-device"
    for req in reqs:
        assert got[req.rid] == sequential_tokens(params, preset_name, req), (
            kv, preset_name, mesh_name, req.rid)
    if kv == "paged":
        assert eng.utilization()["kv_prefix_shared_tokens"] > 0


# ---------------------------------------------------------------------------
# Chunked prefill on the mesh (PR 4): the unified serve step's prompt chunks
# ride the same (data, model) shardings as decode — there is no replicated
# batch-1 prefill program left. Identity bar unchanged.
# ---------------------------------------------------------------------------

CHUNKED_REPRESENTATIVES = [("slotted", "nss_shortcut", "1x2"),
                           ("paged", "base", "2x1"),
                           ("paged", "ret_byp_shortcut", "1x2")]


@needs_devices
@pytest.mark.parametrize("kv,preset_name,mesh_name", CHUNKED_REPRESENTATIVES)
def test_mesh_chunked_identity_representative(params, kv, preset_name,
                                              mesh_name):
    reqs = _matrix_requests()
    got, eng = run_cell(params, kv, preset_name, mesh_name, reqs,
                        block_size=8, chunked=True, chunk_budget=6)
    for req in reqs:
        want = sequential_tokens(params, preset_name, req)
        assert got[req.rid] == want, (
            f"chunked {kv}/{preset_name}/{mesh_name} rid {req.rid}: "
            f"mesh {got[req.rid]} != sequential {want}")
    assert eng.utilization()["step_mode"] == "chunked"


@pytest.mark.slow
@needs_devices
@pytest.mark.parametrize("mesh_name", [m for m in MESHES if m != "1x1"])
@pytest.mark.parametrize("preset_name", PRESETS)
@pytest.mark.parametrize("kv", BACKENDS)
def test_mesh_chunked_identity_matrix(params, kv, preset_name, mesh_name):
    """The full chunked matrix: chunked-mesh == chunked-1-device ==
    two-phase-1-device == sequential, across {slotted, paged} x {base,
    nss_shortcut, ret_byp_shortcut} x {1x2, 2x1} incl. the CoW shared
    prefix in the workload."""
    reqs = _matrix_requests()
    kw = dict(block_size=8, chunked=True, chunk_budget=6)
    one_dev, _ = run_cell(params, kv, preset_name, "1x1", reqs, **kw)
    two_phase, _ = run_cell(params, kv, preset_name, "1x1", reqs,
                            block_size=8)
    got, eng = run_cell(params, kv, preset_name, mesh_name, reqs, **kw)
    assert got == one_dev, f"chunked {kv}/{preset_name}/{mesh_name} != 1-dev"
    assert got == two_phase, (
        f"chunked {kv}/{preset_name}/{mesh_name} != two-phase")
    for req in reqs:
        assert got[req.rid] == sequential_tokens(params, preset_name, req), (
            kv, preset_name, mesh_name, req.rid)
    if kv == "paged":
        assert eng.utilization()["kv_prefix_shared_tokens"] > 0


# ---------------------------------------------------------------------------
# Shared-prefix CoW and recompute-preemption under sharding (tier-1)
# ---------------------------------------------------------------------------

@needs_devices
def test_mesh_paged_cow_identity(params):
    """Identical prompts on a 1x2 mesh: later admissions are full-prefix
    radix hits, prefill one token, and CoW-fork the tail block — each shard
    copying its own slice. Streams match the 1-device paged engine and
    sequential decode."""
    base = synthetic_requests(1, prompt_len=16, max_new_tokens=4,
                              vocab_size=CFG.vocab_size, seed=9)[0]
    reqs = [dataclasses.replace(base, rid=i) for i in range(3)]
    one_dev, _ = run_cell(params, "paged", "base", "1x1", reqs, block_size=8)
    got, eng = run_cell(params, "paged", "base", "1x2", reqs, block_size=8)
    assert got == one_dev
    u = eng.utilization()
    assert u["kv_cow_forks"] >= 2                   # rids 1,2 forked the tail
    assert u["kv_prefix_shared_tokens"] == 15 * 2   # P-1 shared each
    want = sequential_tokens(params, "base", base)
    for rid in got:
        assert got[rid] == want


@needs_devices
def test_mesh_paged_preemption_identity(params):
    """A pool far smaller than worst-case forces recompute-preemption on the
    mesh; preempted requests replay bit-identically on re-admission, same as
    on one device."""
    reqs = synthetic_requests(4, prompt_len=8, max_new_tokens=12,
                              vocab_size=CFG.vocab_size, seed=3)
    kw = dict(n_slots=3, block_size=4, num_blocks=9)
    one_dev, _ = run_cell(params, "paged", "base", "1x1", reqs, **kw)
    got, eng = run_cell(params, "paged", "base", "1x2", reqs, **kw)
    assert got == one_dev
    assert eng.preemptions > 0
    assert eng.kv.pool.hwm <= 9


# ---------------------------------------------------------------------------
# Sampling on the mesh: streams are a function of (request, seed) only
# ---------------------------------------------------------------------------

@needs_devices
def test_mesh_sampling_replays(params):
    """Per-request sampling key chains thread through the sharded decode
    program unchanged: sampled streams match the 1-device engine exactly."""
    from repro.core import SamplingConfig
    sc = SamplingConfig(temperature=0.7, top_k=16, seed=42)
    reqs = synthetic_requests(2, prompt_len=8, max_new_tokens=4,
                              vocab_size=CFG.vocab_size, seed=2)
    one_dev, _ = run_cell(params, "slotted", "base", "1x1", reqs,
                          sampling=sc)
    got, _ = run_cell(params, "slotted", "base", "1x2", reqs, sampling=sc)
    assert got == one_dev
    greedy, _ = run_cell(params, "slotted", "base", "1x2", reqs)
    assert got != greedy                            # it actually sampled


# ---------------------------------------------------------------------------
# The memory claim: per-shard KV residency (no decode run needed — engines
# build their sharded state eagerly, programs compile lazily)
# ---------------------------------------------------------------------------

@needs_devices
def test_mesh_shards_kv_memory_and_specs(params):
    from jax.sharding import PartitionSpec as P
    lk, opts = _linkage_opts("base")
    mesh = make_host_mesh(1, 2)

    eng = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=MAX_LEN,
                      kv="slotted", mesh=mesh)
    k = eng.kv.cache[0]["k"]                       # (L, B, T, HKV, dh)
    assert k.sharding.spec[3] == "model"           # KV heads tensor-parallel
    assert k.addressable_shards[0].data.nbytes == k.nbytes // 2
    # weights are tensor-parallel too (smoke tinyllama: 4 heads, 2 kv heads)
    wq = eng.kv.params["blocks"][0]["mixer"]["wq"]
    assert "model" in tuple(wq.sharding.spec)

    eng_p = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=MAX_LEN,
                        kv="paged", block_size=8, mesh=mesh)
    kp = eng_p.kv.cache[0]["kp"]                   # (L, P+1, bs, HKV, dh)
    assert kp.sharding.spec == P(None, None, None, "model", None)
    assert kp.addressable_shards[0].data.nbytes == kp.nbytes // 2
    # one *logical* block table drives the per-shard physical pools
    assert isinstance(eng_p.kv.tables_host, np.ndarray)

    # slots shard over "data" on a 2x1 mesh
    eng_d = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=MAX_LEN,
                        kv="slotted", mesh=make_host_mesh(2, 1))
    k = eng_d.kv.cache[0]["k"]
    slot_axis = k.sharding.spec[1]
    assert "data" in (slot_axis if isinstance(slot_axis, tuple)
                      else (slot_axis,))
    assert k.addressable_shards[0].data.nbytes == k.nbytes // 2


@needs_devices
def test_mesh_requires_jitted_linkage(params):
    with pytest.raises(ValueError, match="jitted linkage"):
        ServeEngine(CFG, params, REF_OPTS, preset("linux"), n_slots=1,
                    max_len=16, mesh=make_host_mesh(1, 2))


# ---------------------------------------------------------------------------
# Two-tier hierarchy on the mesh: swap-vs-recompute identity (per-shard
# device↔host block copies) and a warm-start restart. The 1x1 column of
# this matrix lives in tests/test_paging.py.
# ---------------------------------------------------------------------------

def _swap_cell(params, preset_name, mesh_name, reqs, *, preempt, **kw):
    lk, opts = _linkage_opts(preset_name)
    if lk.decode_steps > 4:
        # short fused programs so the pressure geometry overlaps decoders
        lk = dataclasses.replace(lk, decode_steps=4)
    eng = ServeEngine(CFG, params, opts, lk, n_slots=3, max_len=MAX_LEN,
                      kv="paged", block_size=4, num_blocks=9,
                      mesh=_mesh(mesh_name), preempt=preempt, **kw)
    comps, _ = eng.run(reqs, load="closed")
    return {c.rid: c.tokens.tolist() for c in comps}, eng


def _swap_requests():
    return synthetic_requests(4, prompt_len=8, max_new_tokens=12,
                              vocab_size=CFG.vocab_size, seed=3)


@needs_devices
def test_mesh_swap_vs_recompute_identity_representative(params):
    """1x2 nss_shortcut: swap-preempted streams == recompute-preempted ==
    the 1-device engine, with the host tier mirroring per-shard copies
    (each shard exports/imports only its slice of every block)."""
    reqs = _swap_requests()
    one_dev, _ = _swap_cell(params, "nss_shortcut", "1x1", reqs,
                            preempt="recompute")
    got, eng = _swap_cell(params, "nss_shortcut", "1x2", reqs,
                          preempt="swap")
    assert got == one_dev
    assert eng.swap_preemptions > 0 and eng.swap_resumes > 0
    u = eng.utilization()
    assert u["kv_swap_out_blocks"] > 0 and u["kv_swap_in_blocks"] > 0


@pytest.mark.slow
@needs_devices
@pytest.mark.parametrize("preset_name", PRESETS)
def test_mesh_swap_identity_matrix(params, preset_name):
    """The full 1x2 column: swap == recompute == 1-device across the three
    linkage presets."""
    reqs = _swap_requests()
    one_dev, _ = _swap_cell(params, preset_name, "1x1", reqs,
                            preempt="recompute")
    got, eng = _swap_cell(params, preset_name, "1x2", reqs, preempt="swap")
    assert got == one_dev, f"swap/{preset_name}/1x2 != 1-device recompute"
    assert eng.swap_preemptions > 0


@pytest.mark.slow
@needs_devices
def test_mesh_warm_start_identity(params, tmp_path):
    """Prefix-cache persistence composes with sharding: save on a 1x2 mesh,
    restart on the same mesh, identical streams with shared tokens on the
    first batch (host entries promote through per-shard imports)."""
    reqs = synthetic_requests(4, prompt_len=12, max_new_tokens=6,
                              vocab_size=CFG.vocab_size, seed=7,
                              shared_prefix_len=8)
    kw = dict(block_size=8)
    got1, eng1 = run_cell(params, "paged", "base", "1x2", reqs, **kw)
    path = str(tmp_path / "prefix.npz")
    assert eng1.save_prefix_cache(path) > 0
    got2, eng2 = run_cell(params, "paged", "base", "1x2", reqs,
                          warm_start=path, **kw)
    assert got2 == got1
    u = eng2.utilization()
    # one full 8-token block of each 12-token prompt persists (the radix
    # covers full blocks only): 8 shared tokens per request, first batch
    assert u["kv_prefix_shared_tokens"] == 8 * 4
    assert u["kv_prefix_promotions"] > 0


# ---------------------------------------------------------------------------
# Speculative decoding on the mesh: the verify pass is one more (B, W)
# chunk-shaped program under the same shardings — placement only, so greedy
# spec streams on any mesh match the plain mesh engine bit for bit.
# ---------------------------------------------------------------------------

def _spec_requests():
    """Repetitive prompts (a tiled core) so the n-gram proposer hits and
    verify windows actually accept drafts on every mesh cell."""
    from repro.serve import Request
    rng = np.random.default_rng(5)
    out = []
    for i in range(4):
        core = rng.integers(0, CFG.vocab_size, 6, dtype=np.int32)
        out.append(Request(rid=i, prompt=np.tile(core, 3),
                           max_new_tokens=14))
    return out


def _spec_cell(params, kv, preset_name, mesh_name, reqs, **kw):
    lk, opts = _linkage_opts(preset_name)
    if lk.decode_steps > 4:
        # preset K=32 would finish these budgets in one plain program
        # before any draft history exists
        lk = dataclasses.replace(lk, decode_steps=3)
    eng = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=MAX_LEN,
                      kv=kv, block_size=8, mesh=_mesh(mesh_name), **kw)
    comps, _ = eng.run(reqs, load="closed")
    return {c.rid: c.tokens.tolist() for c in comps}, eng


@needs_devices
def test_mesh_spec_identity_representative(params):
    """1x2 paged nss_shortcut: speculative streams == the plain mesh engine
    == the 1-device spec engine, with drafts accepted on the mesh."""
    reqs = _spec_requests()
    plain, _ = _spec_cell(params, "paged", "nss_shortcut", "1x2", reqs)
    spec_kw = dict(spec_decode="ngram", spec_width=6)
    one_dev, _ = _spec_cell(params, "paged", "nss_shortcut", "1x1", reqs,
                            **spec_kw)
    got, eng = _spec_cell(params, "paged", "nss_shortcut", "1x2", reqs,
                          **spec_kw)
    assert got == plain, "mesh spec diverged from mesh plain decode"
    assert got == one_dev, "mesh spec diverged from 1-device spec"
    u = eng.utilization()
    assert u["spec_steps"] > 0 and u["spec_accepted_tokens"] > 0


@pytest.mark.slow
@needs_devices
@pytest.mark.parametrize("mesh_name", [m for m in MESHES if m != "1x1"])
@pytest.mark.parametrize("preset_name", PRESETS)
@pytest.mark.parametrize("kv", BACKENDS)
def test_mesh_spec_identity_matrix(params, kv, preset_name, mesh_name):
    reqs = _spec_requests()
    plain, _ = _spec_cell(params, kv, preset_name, mesh_name, reqs)
    got, eng = _spec_cell(params, kv, preset_name, mesh_name, reqs,
                          spec_decode="ngram", spec_width=6)
    assert got == plain, f"spec {kv}/{preset_name}/{mesh_name} != plain"
    assert eng.utilization()["spec_steps"] > 0


# ---------------------------------------------------------------------------
# The shortcut kernels on a mesh. XLA cannot partition a Mosaic call, so on
# a mesh every kernel runs per shard under shard_map, heads split over
# "model" in whole GQA groups. Interpret mode runs the same kernel bodies on
# CPU, so the wrapping is checked here (tests/test_tpu_compile.py checks it
# compiles for four v5e chips).
# ---------------------------------------------------------------------------

# four KV heads, as TinyLlama has, so the heads split over model = 2
KV4 = dataclasses.replace(CFG, n_heads=8, n_kv_heads=4, d_head=8)
PALLAS_OPTS = dataclasses.replace(REF_OPTS, attn_impl="pallas",
                                  scan_impl="pallas", fused_norm=True)


def _kernel_call(name, mesh):
    from repro.kernels import kv_quant, ops
    B, W, HQ, HKV, dh, bs, nb = 2, 4, 8, 4, 16, 8, 3
    P1 = B * nb + 1
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    kp = jax.random.normal(ks[0], (P1, bs, HKV, dh), jnp.float32)
    vp = jax.random.normal(ks[1], (P1, bs, HKV, dh), jnp.float32)
    q = jax.random.normal(ks[2], (B, W, HQ, dh), jnp.float32)
    tables = jnp.asarray(np.arange(B * nb, dtype=np.int32).reshape(B, nb))
    pos = jnp.asarray(np.array([13, 5], np.int32))
    if name == "paged_decode":
        return ops.paged_decode_attention(q[:, :1], kp, vp, tables, pos,
                                          mesh=mesh)
    if name == "paged_prefill":
        return ops.paged_prefill_attention(q, kp, vp, tables, pos, mesh=mesh)
    if name == "paged_prefill_int8":
        amax = lambda p: jnp.max(jnp.abs(p), axis=(1, 3))
        s_k = kv_quant.block_scales(amax(kp), jnp.int8)
        s_v = kv_quant.block_scales(amax(vp), jnp.int8)
        kq = kv_quant.quantize(kp, s_k[:, None, :, None], jnp.int8)
        vq = kv_quant.quantize(vp, s_v[:, None, :, None], jnp.int8)
        return ops.paged_prefill_attention(q, kq, vq, tables, pos, s_k, s_v,
                                           mesh=mesh)
    if name == "slot_decode":
        T = nb * bs
        ck = kp[:B * nb].reshape(B, T, HKV, dh)
        cv = vp[:B * nb].reshape(B, T, HKV, dh)
        slot_pos = jnp.tile(jnp.arange(T, dtype=jnp.int32)[None], (B, 1))
        return ops.slot_decode_attention(q[:, :1], ck, cv, slot_pos, pos,
                                         block_t=8, mesh=mesh)
    if name == "flash":
        k = jax.random.normal(ks[3], (B, W, HKV, dh), jnp.float32)
        return ops.flash_attention(q, k, k, block_q=8, block_k=8, mesh=mesh)
    assert name == "rmsnorm"
    return ops.rmsnorm(q.reshape(B * W, HQ * dh),
                       jnp.ones((HQ * dh,), jnp.float32), mesh=mesh)


@needs_devices
@pytest.mark.parametrize("name", ["paged_decode", "paged_prefill",
                                  "paged_prefill_int8", "slot_decode",
                                  "flash", "rmsnorm"])
def test_kernel_per_shard_matches_single_device(name):
    want = _kernel_call(name, None)
    got = _kernel_call(name, make_host_mesh(1, 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


@needs_devices
@pytest.mark.parametrize("other", ["ref", "mesh"])
def test_teacher_forced_pallas_path(other):
    """The paged, chunked serving passes with the Pallas kernels give the
    reference path's logits, and the same logits on a 1x2 mesh — the CPU
    mirror of chip_smoke.py's two comparisons."""
    from repro.launch.serve import teacher_forced_logits
    params = init_params(jax.random.PRNGKey(0), KV4)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, KV4.vocab_size, 20, dtype=np.int32)
    forced = rng.integers(0, KV4.vocab_size, 6, dtype=np.int32)
    kw = dict(block_size=8, chunk=16)
    got = teacher_forced_logits(KV4, PALLAS_OPTS, params, prompt, forced,
                                **kw)
    want = (teacher_forced_logits(KV4, REF_OPTS, params, prompt, forced,
                                  **kw) if other == "ref" else
            teacher_forced_logits(KV4, PALLAS_OPTS, params, prompt, forced,
                                  mesh=make_host_mesh(1, 2), **kw))
    assert got.shape == (1 + len(forced), KV4.vocab_size)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
