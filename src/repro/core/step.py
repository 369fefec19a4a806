"""Step builders: train / prefill / decode programs at a given linkage level.

``build_train_step`` / ``build_decode_step`` return ``LinkedStep`` objects —
the "vmlinux binary" of UKL: the application (model) and the kernel (runtime:
optimizer, collectives, caches) linked into one compiled program, with the
boundary behavior dictated by ``LinkageConfig``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.linkage import L0_EAGER, L3_NSS, LinkageConfig
from repro.models import (init_params, loss_fn, prefill,
                          decode_step as model_decode,
                          decode_step_paged as model_decode_paged,
                          decode_step_slots as model_decode_slots,
                          serve_chunk_step as model_serve_chunk,
                          serve_chunk_step_paged as model_serve_chunk_paged,
                          serve_verify_step as model_serve_verify,
                          serve_verify_step_paged as model_serve_verify_paged)
from repro.models.layers import ModelOptions
from repro.optim import adamw
from repro.sharding.rules import ArchSharding, named


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState
    step: jax.Array


def init_train_state(key, cfg: ArchConfig, ocfg: adamw.AdamWConfig,
                     param_dtype=jnp.float32) -> TrainState:
    params = init_params(key, cfg, param_dtype)
    return TrainState(params=params, opt=adamw.init(ocfg, params),
                      step=jnp.zeros((), jnp.int32))


def make_train_step(cfg: ArchConfig, opts: ModelOptions,
                    ocfg: adamw.AdamWConfig) -> Callable:
    """The pure single-step function (microstep of every linkage level)."""

    def train_step(state: TrainState, batch: Dict[str, jax.Array]
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        def lf(p):
            return loss_fn(p, batch, cfg, opts)

        grads, metrics = jax.grad(lf, has_aux=True)(state.params)
        new_params, new_opt, om = adamw.update(ocfg, grads, state.opt,
                                               state.params)
        metrics = dict(metrics, **om)
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step


@dataclasses.dataclass
class LinkedStep:
    """A step program linked at some point of the UKL spectrum."""
    fn: Callable                   # python callable (jit'd unless L0)
    linkage: LinkageConfig
    in_shardings: Any = None
    out_shardings: Any = None
    _pending: Any = None           # RET: last un-synced metrics future

    def __call__(self, state, batch):
        state, metrics = self.fn(state, batch)
        if self.linkage.ret_async:
            # "ret": return without synchronizing; keep the future
            self._pending = metrics
            return state, None
        # "iret": full synchronization on every return
        metrics = jax.tree.map(lambda x: x.block_until_ready(), metrics)
        return state, metrics

    def sync(self):
        """RET mode: block on the most recent metrics future."""
        if self._pending is None:
            return None
        out = jax.tree.map(lambda x: jax.device_get(x), self._pending)
        self._pending = None
        return out


def build_train_step(cfg: ArchConfig, opts: ModelOptions,
                     ocfg: adamw.AdamWConfig, linkage: LinkageConfig,
                     mesh: Optional[Mesh] = None,
                     global_batch: Optional[int] = None) -> LinkedStep:
    linkage.validate()
    micro = make_train_step(cfg, opts, ocfg)

    if linkage.level == L3_NSS:
        # K microsteps fused in-graph: zero host transitions between steps.
        # batch leaves carry a leading K dim (the pre-staged NSS_PS buffer).
        def fused(state, batch_k):
            def body(s, b):
                s, m = micro(s, b)
                return s, m
            state, ms = lax.scan(body, state, batch_k)
            # return last-step metrics (cheap; full history stays on device)
            metrics = jax.tree.map(lambda m: m[-1], ms)
            return state, metrics
        step_fn = fused
    else:
        step_fn = micro

    if linkage.level == L0_EAGER:
        # op-at-a-time: every primitive is its own dispatch ("syscall")
        def eager(state, batch):
            with jax.disable_jit():
                return step_fn(state, batch)
        return LinkedStep(fn=eager, linkage=linkage)

    jit_kwargs: Dict[str, Any] = {}
    if linkage.donate:
        jit_kwargs["donate_argnums"] = (0,)
    fn = jax.jit(step_fn, **jit_kwargs)
    return LinkedStep(fn=fn, linkage=linkage)


def build_sharded_train_step(cfg: ArchConfig, opts: ModelOptions,
                             ocfg: adamw.AdamWConfig, linkage: LinkageConfig,
                             mesh: Mesh, state_like, global_batch: int,
                             ep_resident: bool = False):
    """Distributed variant: explicit in/out shardings over ``mesh``.

    ``state_like`` may be a TrainState of arrays *or* of ShapeDtypeStructs —
    only the tree structure and shapes are read, so the dry-run can build the
    fully-sharded program without allocating a single parameter.
    Returns (jitted_fn, state_shardings, batch_shardings).
    """
    linkage.validate()
    sh = ArchSharding(cfg, mesh, ep_resident=ep_resident)
    pspecs = sh.param_specs(state_like.params)
    state_specs = TrainState(
        params=pspecs,
        opt=adamw.AdamWState(count=P(), mu=pspecs, nu=pspecs),
        step=P(),
    )
    bspecs = sh.train_batch_specs(global_batch)
    if linkage.level == L3_NSS:
        bspecs = {k: P(None, *v) for k, v in bspecs.items()}
    metric_specs = None  # replicated outputs

    micro = make_train_step(cfg, opts, ocfg)
    if linkage.level == L3_NSS:
        def step_fn(state, batch_k):
            def body(s, b):
                return micro(s, b)
            state, ms = lax.scan(body, state, batch_k)
            return state, jax.tree.map(lambda m: m[-1], ms)
    else:
        step_fn = micro

    jit_kwargs: Dict[str, Any] = {}
    if linkage.donate:
        jit_kwargs["donate_argnums"] = (0,)
    fn = jax.jit(
        step_fn,
        in_shardings=(named(mesh, state_specs), named(mesh, bspecs)),
        out_shardings=(named(mesh, state_specs), None),
        **jit_kwargs,
    )
    return fn, state_specs, bspecs


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Token-sampling policy compiled into the decode program.

    ``temperature == 0`` is greedy argmax (the default, and the mode the
    token-identity tests pin down). Otherwise logits are divided by
    ``temperature``, optionally truncated to the ``top_k`` highest, and
    sampled with a per-slot PRNG key threaded through the decode program —
    each slot's key chain is seeded from (seed, request id) at admission, so
    a request's sampled stream depends only on the request and the seed,
    never on which slot it landed in or when it was admitted: schedules
    replay deterministically.
    """
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0

    def request_key(self, rid: int) -> jax.Array:
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), rid)


def make_sampler(sampling: Optional[SamplingConfig]) -> Callable:
    """(logits (B,V), keys (B,2) uint32) -> (tokens (B,) int32, new keys).
    The ops carry the ``sample`` scope."""
    if sampling is None or sampling.temperature <= 0.0:
        def greedy(logits, keys):
            with jax.named_scope("sample"):
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), keys
        return greedy

    def sample(logits, keys):
        with jax.named_scope("sample"):
            splits = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
            new_keys, subs = splits[:, 0], splits[:, 1]         # (B,2) each
            l = logits.astype(jnp.float32) / sampling.temperature
            if sampling.top_k > 0:
                kth = lax.top_k(l, sampling.top_k)[0][..., -1:]
                l = jnp.where(l >= kth, l, -jnp.inf)
            toks = jax.vmap(jax.random.categorical)(subs, l)
            return toks.astype(jnp.int32), new_keys
    return sample


def make_decode_fn(cfg: ArchConfig, opts: ModelOptions, linkage: LinkageConfig,
                   sample_greedy: bool = True) -> Callable:
    """Decode ``linkage.decode_steps`` tokens per program at L3, else one."""

    def one(params, cache, tokens):
        logits, cache = model_decode(params, cache, tokens, cfg, opts)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return cache, nxt

    if linkage.level == L3_NSS:
        def many(params, cache, tokens):
            def body(carry, _):
                cache, toks = carry
                cache, nxt = one(params, cache, toks)
                return (cache, nxt), nxt
            (cache, last), seq = lax.scan(body, (cache, tokens), None,
                                          length=linkage.decode_steps)
            return cache, seq.swapaxes(0, 1)     # (B, K)
        return many

    def single(params, cache, tokens):
        cache, nxt = one(params, cache, tokens)
        return cache, nxt[:, None]
    return single


def program_label(cfg: ArchConfig, linkage: LinkageConfig,
                  kind: str) -> str:
    """A stable human-readable label for a compiled serving program —
    ``kind`` is the program family the engine dispatched ("decode",
    "serve_chunk", "verify", "prefill_admit"). Telemetry stamps it on
    ``engine_step`` trace events so a timeline names which linked program
    each step ran (the trace-side analogue of a kernel symbol name)."""
    tag = linkage.level
    if linkage.level == L3_NSS:
        tag += f"x{linkage.decode_steps}"
    if linkage.ret_async:
        tag += "+ret"
    if linkage.shortcut:
        tag += "+shortcut"
    return f"{kind}/{tag}/d{cfg.d_model}L{cfg.num_blocks}"


def _serve_jit_kwargs(linkage: LinkageConfig, mesh: Optional[Mesh],
                      param_sharding, cache_sharding,
                      n_extra: int = 0) -> Dict[str, Any]:
    """jit kwargs for a serving decode program.

    With ``mesh`` the program is compiled with explicit in/out shardings —
    params tensor-parallel, the engine cache per-shard resident, everything
    else (tokens, keys, block tables) replicated — so one mesh shape jits
    exactly one decode program and the cache never migrates between calls.
    """
    kwargs: Dict[str, Any] = {}
    if linkage.donate:
        kwargs["donate_argnums"] = (1,)
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        kwargs["in_shardings"] = ((param_sharding, cache_sharding, repl, repl)
                                  + (repl,) * n_extra)
        kwargs["out_shardings"] = (cache_sharding, repl, repl)
    return kwargs


def _link_decode_fn(fn: Callable, linkage: LinkageConfig) -> Callable:
    """Apply the linkage boundary to a decode fn: eager at L0, jit (with the
    cache donated at L2+) otherwise."""
    if linkage.level == L0_EAGER:
        def eager(params, cache, tokens):
            with jax.disable_jit():
                return fn(params, cache, tokens)
        return eager
    kwargs = {"donate_argnums": (1,)} if linkage.donate else {}
    return jax.jit(fn, **kwargs)


def build_decode_step(cfg: ArchConfig, opts: ModelOptions,
                      linkage: LinkageConfig) -> Callable:
    linkage.validate()
    return _link_decode_fn(make_decode_fn(cfg, opts, linkage), linkage)


def _serving_decode_fn(one: Callable, linkage: LinkageConfig) -> Callable:
    """Lift a one-token serving microstep ``(params, cache, tokens, keys) ->
    (cache, nxt, keys)`` over the linkage spectrum: at L3 ``decode_steps``
    tokens are fused in-graph per program (one host transition per K tokens
    for the whole continuously-batched slot set), else one per program.
    Returns ``(params, cache, tokens (B,), keys (B,2)) ->
    (cache, tokens (B,K), keys)``.
    """
    if linkage.level == L3_NSS:
        def many(params, cache, tokens, keys):
            def body(carry, _):
                cache, toks, ks = carry
                cache, nxt, ks = one(params, cache, toks, ks)
                return (cache, nxt, ks), nxt
            with jax.named_scope("decode_microsteps"):
                (cache, _, keys), seq = lax.scan(
                    body, (cache, tokens, keys), None,
                    length=linkage.decode_steps)
            return cache, seq.swapaxes(0, 1), keys   # (n_slots, K)
        return many

    def single(params, cache, tokens, keys):
        cache, nxt, keys = one(params, cache, tokens, keys)
        return cache, nxt[:, None], keys
    return single


def make_slot_decode_fn(cfg: ArchConfig, opts: ModelOptions,
                        linkage: LinkageConfig,
                        sampling: Optional[SamplingConfig] = None) -> Callable:
    """Slot-layout decode for the serving engine: every batch row is an
    independent in-flight sequence at its own position, with its own
    sampling-key chain."""
    sampler = make_sampler(sampling)

    def one(params, cache, tokens, keys):
        logits, cache = model_decode_slots(params, cache, tokens, cfg, opts)
        nxt, keys = sampler(logits, keys)
        return cache, nxt, keys

    return _serving_decode_fn(one, linkage)


def build_slot_decode_step(cfg: ArchConfig, opts: ModelOptions,
                           linkage: LinkageConfig,
                           sampling: Optional[SamplingConfig] = None, *,
                           mesh: Optional[Mesh] = None,
                           param_sharding=None, cache_sharding=None
                           ) -> Callable:
    """(params, slot_cache, tokens (B,), keys (B,2)) ->
    (slot_cache, tokens (B, K), keys).

    With ``mesh`` (+ NamedSharding trees for params and the slot cache) the
    decode program is compiled tensor-parallel over the ``"model"`` axis and
    slot-parallel over ``"data"``: one jit per mesh shape, cache resident
    per shard (see ``ArchSharding.serve_slot_cache_specs``).
    """
    linkage.validate()
    fn = make_slot_decode_fn(cfg, opts, linkage, sampling)
    if linkage.level == L0_EAGER:
        if mesh is not None:
            raise ValueError("mesh serving needs a jitted linkage level")

        def eager(params, cache, tokens, keys):
            with jax.disable_jit():
                return fn(params, cache, tokens, keys)
        return eager

    def decode_program(params, cache, tokens, keys):
        return fn(params, cache, tokens, keys)
    return jax.jit(decode_program, **_serve_jit_kwargs(
        linkage, mesh, param_sharding, cache_sharding))


def make_paged_decode_fn(cfg: ArchConfig, opts: ModelOptions,
                         linkage: LinkageConfig, max_len: int,
                         sampling: Optional[SamplingConfig] = None
                         ) -> Callable:
    """Paged-KV decode: the cache is a physical block pool and each slot's
    logical view is assembled through its block table (passed per call — the
    engine demand-allocates / CoW-forks blocks between programs, so the
    table is host state, not program state)."""
    sampler = make_sampler(sampling)

    def one_with_tables(tables):
        def one(params, cache, tokens, keys):
            logits, cache = model_decode_paged(params, cache, tokens, tables,
                                               cfg, opts, max_len)
            nxt, keys = sampler(logits, keys)
            return cache, nxt, keys
        return one

    def decode_program(params, cache, tokens, keys, tables):
        return _serving_decode_fn(one_with_tables(tables), linkage)(
            params, cache, tokens, keys)
    return decode_program


def build_paged_decode_step(cfg: ArchConfig, opts: ModelOptions,
                            linkage: LinkageConfig, max_len: int,
                            sampling: Optional[SamplingConfig] = None, *,
                            mesh: Optional[Mesh] = None,
                            param_sharding=None, cache_sharding=None
                            ) -> Callable:
    """(params, paged_cache, tokens (B,), keys (B,2), tables (B, nb)) ->
    (paged_cache, tokens (B, K), keys).

    The program is named ``decode_program`` (``jit_decode_program`` in a
    profiler trace). With ``mesh`` the physical block pools are per-shard
    resident (KV heads over ``"model"``) while the block table stays one
    replicated *logical* map — each shard resolves the same
    logical->physical translation against its own slice of every block
    (``ArchSharding.serve_paged_cache_specs``).
    """
    linkage.validate()
    fn = make_paged_decode_fn(cfg, opts, linkage, max_len, sampling)
    if linkage.level == L0_EAGER:
        if mesh is not None:
            raise ValueError("mesh serving needs a jitted linkage level")

        def eager(params, cache, tokens, keys, tables):
            with jax.disable_jit():
                return fn(params, cache, tokens, keys, tables)
        return eager
    return jax.jit(fn, **_serve_jit_kwargs(linkage, mesh, param_sharding,
                                           cache_sharding, n_extra=1))


def build_block_export_fn(mesh: Optional[Mesh] = None, cache_sharding=None,
                          block_sharding=None) -> Callable:
    """Jitted ``(paged_cache, blk) -> tuple of {"k","v"}``: read one physical
    block's K/V out of the device pool — (L, bs, HKV, dh) per layer group —
    the device half of a device→host block copy (the host tier's copy is
    ``jax.device_get`` of the result). Used by swap-out preemption and
    prefix-cache demotion/persistence (repro.serve.paging).

    With ``mesh`` the output keeps the pool's KV-head sharding
    (``ArchSharding.serve_swap_block_specs``): each shard reads only its own
    slice of the block — no collective — so the host tier mirrors the
    physical shard layout on ``(data, model)`` meshes.
    """

    def export(cache, blk):
        out = []
        for g in cache:
            kv = {"k": g["kp"][:, blk], "v": g["vp"][:, blk]}
            if "ks" in g:              # quantized pool: scales ride along
                kv["ks"] = g["ks"][:, blk]
                kv["vs"] = g["vs"][:, blk]
            out.append(kv)
        return tuple(out)

    kwargs: Dict[str, Any] = {}
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        kwargs = dict(in_shardings=(cache_sharding, repl),
                      out_shardings=block_sharding)
    return jax.jit(export, **kwargs)


def build_block_import_fn(mesh: Optional[Mesh] = None, cache_sharding=None,
                          block_sharding=None) -> Callable:
    """Jitted ``(paged_cache, kvs, blk) -> paged_cache``: write one block's
    K/V back into the device pool — the host→device half (swap-in resume,
    host-tier prefix promotion, warm-start restore). The pool is donated.

    With ``mesh`` the incoming block carries the pool's KV-head sharding, so
    host data placed per-shard (``repro.sharding.rules.host_to_mesh``) lands in
    each shard's slice without resharding.
    """

    def imp(cache, kvs, blk):
        out = []
        for g, kv in zip(cache, kvs):
            d = dict(g,
                     kp=g["kp"].at[:, blk].set(kv["k"].astype(g["kp"].dtype)),
                     vp=g["vp"].at[:, blk].set(kv["v"].astype(g["vp"].dtype)))
            if "ks" in g:
                d["ks"] = g["ks"].at[:, blk].set(kv["ks"])
                d["vs"] = g["vs"].at[:, blk].set(kv["vs"])
            out.append(d)
        return tuple(out)

    kwargs: Dict[str, Any] = {"donate_argnums": (0,)}
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        kwargs.update(in_shardings=(cache_sharding, block_sharding, repl),
                      out_shardings=cache_sharding)
    return jax.jit(imp, **kwargs)


def build_chain_export_fn(mesh: Optional[Mesh] = None, cache_sharding=None,
                          chain_sharding=None) -> Callable:
    """Jitted ``(paged_cache, blks (n,) i32) -> tuple of {"k","v"}``: gather
    a whole block chain's K/V out of the device pool in one program —
    (L, n, bs, HKV, dh) per layer group. The chain-at-once counterpart of
    ``build_block_export_fn``: one dispatch per swapped sequence instead of
    one per block, and the result is a *fresh* array (the gather copies out
    of the pool), so the transfer can be drained asynchronously
    (``copy_to_host_async``) after the pool blocks are already reused.

    This pair is also the fleet's prefill→decode handoff lane
    (``repro.serve.fleet``): a finished prompt's chain exports out of the
    prefill cell's pool into the shared host tier and imports into a
    *different* engine's pool — disaggregation is a swap-out whose
    swap-in lands elsewhere, no third program needed.

    Retraces once per chain length n — chain lengths are small and heavily
    repeated under steady swap pressure, so the jit cache stays tiny.

    With ``mesh`` the output keeps the pool's KV-head sharding
    (``ArchSharding.serve_swap_chain_specs``).
    """

    def export(cache, blks):
        out = []
        for g in cache:
            kv = {"k": g["kp"][:, blks], "v": g["vp"][:, blks]}
            if "ks" in g:
                kv["ks"] = g["ks"][:, blks]
                kv["vs"] = g["vs"][:, blks]
            out.append(kv)
        return tuple(out)

    kwargs: Dict[str, Any] = {}
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        kwargs = dict(in_shardings=(cache_sharding, repl),
                      out_shardings=chain_sharding)
    return jax.jit(export, **kwargs)


def build_chain_import_fn(mesh: Optional[Mesh] = None, cache_sharding=None,
                          chain_sharding=None) -> Callable:
    """Jitted ``(paged_cache, kvs, blks (n,) i32) -> paged_cache``: scatter
    a whole chain's K/V back into the device pool in one donated program —
    the host→device half of swap-in resume, prefix promotion, and
    warm-start restore, chain-at-once. See ``build_chain_export_fn``.
    """

    def imp(cache, kvs, blks):
        out = []
        for g, kv in zip(cache, kvs):
            d = dict(g,
                     kp=g["kp"].at[:, blks].set(
                         kv["k"].astype(g["kp"].dtype)),
                     vp=g["vp"].at[:, blks].set(
                         kv["v"].astype(g["vp"].dtype)))
            if "ks" in g:
                d["ks"] = g["ks"].at[:, blks].set(kv["ks"])
                d["vs"] = g["vs"].at[:, blks].set(kv["vs"])
            out.append(d)
        return tuple(out)

    kwargs: Dict[str, Any] = {"donate_argnums": (0,)}
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        kwargs.update(in_shardings=(cache_sharding, chain_sharding, repl),
                      out_shardings=cache_sharding)
    return jax.jit(imp, **kwargs)


def build_serve_step(cfg: ArchConfig, opts: ModelOptions,
                     linkage: LinkageConfig, max_len: int,
                     sampling: Optional[SamplingConfig] = None, *,
                     kv_kind: str = "slotted", mesh: Optional[Mesh] = None,
                     param_sharding=None, cache_sharding=None) -> Callable:
    """The *unified* serve program: one jitted entry per engine step.

    Chunked-prefill serving has no separate prefill phase — every program
    is [chunk pass] + [K fused decode microsteps]:

      1. Chunk pass: each slot absorbs its own variable-length prompt chunk
         (decode/empty slots carry a zero-length chunk), K/V written then
         attended with per-row positions; rows whose chunk completes their
         prompt sample their first token from the chunk's last-position
         logits (``emit0`` gates the sampling-key advance).
      2. Decode scan: the linkage level's K microsteps — exactly the
         two-phase engine's decode body — advance the rows already past
         prefill (``dec_mask`` gates their key chains; other rows' garbage
         writes land beyond their resident positions / in the trash block
         and are invisible to the chunk pass's causal mask).

    Signature (slotted):
      (params, cache, chunk_tokens (B,W) i32, clen (B,) i32, start (B,) i32,
       reset (B,) bool, emit0 (B,) bool, dec_tok (B,) i32, dec_mask (B,)
       bool, keys (B,2) u32) -> (cache, t0 (B,) i32, seq (B,K) i32, keys)
    paged adds trailing ``tables (B,nb)`` (chunk pass) and ``scan_tables``
    (decode scan: mid-prefill/empty rows redirected wholesale to trash).

    The program is named ``serve_step``; its two halves carry the
    ``chunk_pass`` and ``decode_microsteps`` scopes, so a profiler trace
    tells them apart by op.

    W (the compiled chunk width) is implicit in the traced shapes — the
    engine pads every step to one fixed width, so this program jits a
    single shape where the two-phase engine compiled one prefill per
    bucket (the engine dispatches the plain decode program instead when no
    slot is mid-prefill, so steady-state decode pays no chunk pass; masked
    rows must therefore leave the cache — including per-row positions —
    bit-exact, which the selects below enforce). With ``mesh``, prefill
    chunks ride
    the same (data, model) shardings as decode: weights tensor-parallel,
    cache per-shard resident, every host-built operand replicated
    (``ArchSharding.serve_chunk_operand_specs``) — there is no replicated
    batch-1 prefill program left.
    """
    linkage.validate()
    sampler = make_sampler(sampling)
    K = linkage.decode_steps if linkage.level == L3_NSS else 1
    paged = kv_kind == "paged"
    if kv_kind not in ("slotted", "paged"):
        raise ValueError(f"unknown kv_kind {kv_kind!r}")

    def serve_step(params, cache, chunk_toks, clen, start, reset, emit0,
                   dec_tok, dec_mask, keys, *tabs):
        with jax.named_scope("chunk_pass"):
            if paged:
                tables, scan_tables = tabs
                logits, cache = model_serve_chunk_paged(
                    params, cache, chunk_toks, tables, start, clen, cfg,
                    opts, max_len)
            else:
                logits, cache = model_serve_chunk(
                    params, cache, chunk_toks, start, clen, reset, cfg,
                    opts)
            t0, keys_c = sampler(logits, keys)
            keys = jnp.where(emit0[:, None], keys_c, keys)

        def body(carry, _):
            c, toks, ks = carry
            if paged:
                # non-decode rows were redirected wholesale to the trash
                # block via scan_tables — their garbage never lands — but
                # they must also keep their per-row position: the engine's
                # pure-decode fast path trusts device pos between programs
                lg, c2 = model_decode_paged(params, c, toks, scan_tables,
                                            cfg, opts, max_len)
                c = tuple(dict(g2, pos=jnp.where(dec_mask[None, :],
                                                 g2["pos"], g["pos"]))
                          for g2, g in zip(c2, c))
            else:
                # non-decode rows keep their cache bit-exact: a garbage
                # microstep write would wrap the circular row (pos % T) and
                # clobber resident prefill state whenever pos + K > T
                lg, c2 = model_decode_slots(params, c, toks, cfg, opts)
                c = jax.tree.map(
                    lambda new, old: jnp.where(
                        dec_mask.reshape((1, -1) + (1,) * (new.ndim - 2)),
                        new, old), c2, c)
            nxt, ks2 = sampler(lg, ks)
            ks = jnp.where(dec_mask[:, None], ks2, ks)
            return (c, nxt, ks), nxt

        with jax.named_scope("decode_microsteps"):
            (cache, _, keys), seq = lax.scan(body, (cache, dec_tok, keys),
                                             None, length=K)
        return cache, t0, seq.swapaxes(0, 1), keys

    if linkage.level == L0_EAGER:
        if mesh is not None:
            raise ValueError("mesh serving needs a jitted linkage level")

        def eager(*args):
            with jax.disable_jit():
                return serve_step(*args)
        return eager

    kwargs: Dict[str, Any] = {}
    if linkage.donate:
        kwargs["donate_argnums"] = (1,)
    if mesh is not None:
        operand_specs = ArchSharding(cfg, mesh).serve_chunk_operand_specs(
            paged)
        kwargs["in_shardings"] = (param_sharding, cache_sharding) + tuple(
            NamedSharding(mesh, s) for s in operand_specs)
        repl = NamedSharding(mesh, P())
        kwargs["out_shardings"] = (cache_sharding, repl, repl, repl)
    return jax.jit(serve_step, **kwargs)


def build_verify_step(cfg: ArchConfig, opts: ModelOptions,
                      linkage: LinkageConfig, max_len: int,
                      sampling: Optional[SamplingConfig] = None, *,
                      kv_kind: str = "slotted", mesh: Optional[Mesh] = None,
                      param_sharding=None, cache_sharding=None) -> Callable:
    """The speculative *verify* program: one draft-widened decode step.

    Each decode row feeds ``toks[s] = [next_token, d_1 .. d_m]`` (clen =
    m + 1 — its committed next token plus m proposed drafts) through the
    serve-chunk machinery at its own position, getting logits at every fed
    position. An in-graph accept scan then resolves the longest accepted
    prefix per row:

      position j's logits condition on the fed prefix toks[:, :j+1] — all
      committed-or-still-accepted tokens — so the sampled token ``t_j`` is
      exactly what plain decode would have produced there. Row s emits t_j
      while it is still accepting; it keeps accepting past j iff t_j equals
      the token it fed at j + 1 (the draft the cache write already assumed).
      n_emit = 1 + accepted drafts, and out[s, n_emit-1] is the row's new
      committed next token. Greedy verify is therefore bit-identical to
      plain decode by construction, and sampled verify is distribution-
      and key-chain-exact (keys advance once per *emitted* token only).

    The cache is repaired in-graph so rejected draft writes are
    indistinguishable from never-written state: per-row ``pos`` returns to
    ``start + n_emit`` (both backends), and slotted ``slot_pos`` marks at
    or beyond it are invalidated (every pre-existing live entry sits below
    ``start``, so only this program's rejected writes match). Paged block
    residency is host-side state; its tail truncation is the backend's
    ``rollback`` (freed-by-truncation blocks can never be CoW-shared or
    radix-registered — they lie beyond the prompt blocks the index covers).

    Signature (slotted):
      (params, cache, toks (B,W) i32, clen (B,) i32, start (B,) i32,
       vmask (B,) bool, keys (B,2) u32) -> (cache, out (B,W) i32,
       n_emit (B,) i32, keys)
    paged adds trailing ``tables (B,nb)``. Rows with vmask False (free /
    swapped slots) carry clen 0, write nothing, and emit nothing.
    """
    linkage.validate()
    sampler = make_sampler(sampling)
    paged = kv_kind == "paged"
    if kv_kind not in ("slotted", "paged"):
        raise ValueError(f"unknown kv_kind {kv_kind!r}")

    def verify_step(params, cache, toks, clen, start, vmask, keys, *tabs):
        if paged:
            (tables,) = tabs
            logits, cache = model_serve_verify_paged(
                params, cache, toks, tables, start, clen, cfg, opts, max_len)
        else:
            logits, cache = model_serve_verify(
                params, cache, toks, start, clen, cfg, opts)
        B, W = toks.shape
        fed_next = jnp.concatenate(
            [toks[:, 1:], jnp.zeros((B, 1), toks.dtype)], axis=1)

        def body(carry, j):
            ks, accepting, n_emit = carry
            t, ks2 = sampler(logits[:, j], ks)
            emit = vmask & accepting & (j < clen)
            ks = jnp.where(emit[:, None], ks2, ks)
            n_emit = n_emit + emit.astype(jnp.int32)
            accepting = emit & (j + 1 < clen) & (t == fed_next[:, j])
            return (ks, accepting, n_emit), t

        (keys, _, n_emit), out = lax.scan(
            body, (keys, jnp.ones((B,), bool), jnp.zeros((B,), jnp.int32)),
            jnp.arange(W))
        out = out.swapaxes(0, 1)                               # (B, W)
        new_pos = start + n_emit
        if paged:
            cache = tuple(
                dict(g, pos=jnp.where(vmask[None, :], new_pos[None, :],
                                      g["pos"]))
                for g in cache)
        else:
            cache = tuple(
                dict(g,
                     slot_pos=jnp.where(
                         vmask[None, :, None]
                         & (g["slot_pos"] >= new_pos[None, :, None]),
                         -1, g["slot_pos"]),
                     pos=jnp.where(vmask[None, :], new_pos[None, :],
                                   g["pos"]))
                for g in cache)
        return cache, out, n_emit, keys

    if linkage.level == L0_EAGER:
        if mesh is not None:
            raise ValueError("mesh serving needs a jitted linkage level")

        def eager(*args):
            with jax.disable_jit():
                return verify_step(*args)
        return eager

    kwargs: Dict[str, Any] = {}
    if linkage.donate:
        kwargs["donate_argnums"] = (1,)
    if mesh is not None:
        operand_specs = ArchSharding(cfg, mesh).serve_verify_operand_specs(
            paged)
        kwargs["in_shardings"] = (param_sharding, cache_sharding) + tuple(
            NamedSharding(mesh, s) for s in operand_specs)
        repl = NamedSharding(mesh, P())
        kwargs["out_shardings"] = (cache_sharding, repl, repl, repl)
    return jax.jit(verify_step, **kwargs)


def build_prefill_fn(cfg: ArchConfig, opts: ModelOptions, max_len: int, *,
                     bucket_fn: Optional[Callable[[int], int]] = None,
                     mesh: Optional[Mesh] = None,
                     param_sharding=None) -> Callable:
    """Jitted full-prompt admission prefill shared by both KV backends
    (identical program => trivially bit-identical admissions across
    backends). Returns ``prefill_prompt(params, prompt (P,) np.int32) ->
    (logits, cache)``.

    With ``bucket_fn`` the prompt is right-padded to its bucket and
    prefilled with a traced ``true_len`` — one compile per bucket, not per
    length. Prompts must be non-empty: ``true_len == 0`` would silently
    clamp the logit slice to position 0 of pure padding, so it is guarded
    here instead.

    With ``mesh`` the program takes tensor-parallel weights and returns a
    replicated batch-1 cache (the slot/scatter writers reshard it into the
    engine's per-shard resident cache).
    """
    import numpy as np

    jit_kwargs: Dict[str, Any] = {}
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        n_in = 2 if bucket_fn is None else 3
        jit_kwargs["in_shardings"] = (param_sharding,) + (repl,) * (n_in - 1)
        jit_kwargs["out_shardings"] = repl

    if bucket_fn is None:
        fn = jax.jit(lambda p, t: prefill(p, t, cfg, opts, max_len=max_len),
                     **jit_kwargs)

        def prefill_prompt(params, prompt):
            if int(prompt.shape[0]) < 1:
                raise ValueError("cannot prefill an empty prompt")
            return fn(params, jnp.asarray(prompt)[None])
    else:
        fn = jax.jit(lambda p, t, n: prefill(p, t, cfg, opts,
                                             max_len=max_len, true_len=n),
                     **jit_kwargs)

        def prefill_prompt(params, prompt):
            P_ = int(prompt.shape[0])
            if P_ < 1:
                raise ValueError("cannot prefill an empty prompt")
            bucket = bucket_fn(P_)
            if bucket < P_:
                raise ValueError(
                    f"bucket_fn({P_}) = {bucket} is smaller than the prompt "
                    "— buckets must cover the prompt length")
            padded = np.zeros((bucket,), np.int32)
            padded[:P_] = prompt
            return fn(params, jnp.asarray(padded)[None],
                      jnp.asarray(P_, jnp.int32))
    return prefill_prompt
