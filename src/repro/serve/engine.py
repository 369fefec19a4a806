"""Continuous-batching serving engine over the UKL linkage spectrum.

One persistent KV store lives on device; between decode programs the engine
evicts finished sequences and prefills newly admitted prompts into the freed
slots, so the device never idles while work exists. The decode program is
built by ``repro.core`` at whatever linkage level the preset names:

  L1/L2      one token per program for the whole slot set; L2 donates the
             cache (no realloc at the boundary).
  L3 (NSS)   ``decode_steps`` tokens fused in-graph per program — one host
             transition per K tokens for all slots.
  ret_async  RET: generated-token arrays stay on device as futures; the host
             synchronizes only when a request *finishes* (completion is
             length-based, so the host can detect it without reading token
             values). Timestamps are dispatch-time, matching RET semantics.
  shortcut   specialized kernels, including the slot-aware and paged
             decode-attention paths in ``repro.kernels``.

Device memory is owned by a pluggable ``KVBackend`` (``--kv``):

  slotted    one dense ``max_len`` row per slot — admission capacity is
             bounded by worst-case length (``repro.serve.cache.SlottedKV``).
  paged      virtual memory for the cache: demand-allocated fixed-size
             blocks, per-slot block tables, copy-on-write prefix sharing,
             and — under pool pressure — recompute- or swap-out preemption
             against a host block tier (``PreemptionPolicy``; swapped
             sequences resume without re-prefill, evicted shared prefixes
             demote to host and persist across restarts via
             ``save_prefix_cache``/``warm_start``)
             (``repro.serve.paging.PagedKV``). Admission is gated on free
             *blocks*, so capacity follows tokens actually resident.

The engine is deterministic for a fixed request list: admission is FIFO,
slots are assigned lowest-index-first, eviction happens only at program
boundaries, and sampling keys are derived from (seed, request id) — so its
token output is bit-identical to running each request alone through prefill
+ decode, whichever backend serves it (asserted in tests/test_serve.py and
tests/test_paging.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.coprocess import AdmissionWorker
from repro.core.linkage import L3_NSS, LinkageConfig
from repro.core.step import SamplingConfig, program_label
from repro.serve.cache import KVBackend, SlottedKV
from repro.serve.scheduler import (MIN_BUCKET, BudgetTuner, Completion,
                                   DraftProposer, PreemptionPolicy, Request,
                                   SlotScheduler, SlotState, bucket_len,
                                   pack_chunks)
from repro.serve.telemetry import NULL_TELEMETRY, Telemetry

KV_BACKENDS = ("slotted", "paged")
SPEC_MODES = ("none", "ngram")


class ServeEngine:
    """Request-level continuous batching over a fixed slot pool.

    Two step disciplines:

    two-phase (default)  admission runs a blocking full-prompt prefill
                         program, then occupied slots decode together —
                         every admission stalls every decoding slot for a
                         whole prompt.
    chunked              (``chunked=True``) there is no prefill phase: every
                         engine step is ONE program with a fixed token
                         budget, filled with decode tokens from occupied
                         slots first and prompt *chunks* from admitting
                         requests after (Sarathi-style chunked prefill);
                         pure-decode steps dispatch the plain decode
                         program. Admission never stalls decode (queue
                         wait and worst inter-token stall drop, admissions
                         batch into one program), and the per-bucket
                         compiled-prefill zoo collapses to one serve-step
                         shape. Token streams are bit-identical to the
                         two-phase engine and to sequential decode
                         (tests/test_serve.py, tests/test_paging.py).
    """

    #: smallest admission bucket (re-exported from the scheduler, which owns
    #: the bucketing/empty-prompt guards for every admission path)
    MIN_BUCKET = MIN_BUCKET

    def __init__(self, cfg: ArchConfig, params, opts, linkage: LinkageConfig,
                 n_slots: int, max_len: int, *, kv: str = "slotted",
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 sampling: Optional[SamplingConfig] = None,
                 bucket_prompts: bool = False, mesh=None,
                 chunked: bool = False, chunk_budget: int = 256,
                 chunk_width: int = 0, preempt="recompute",
                 host_blocks: Optional[int] = 0,
                 warm_start: Optional[str] = None,
                 ttft_slo_s: Optional[float] = None,
                 spec_decode: str = "none", spec_width: int = 0,
                 telemetry: Optional[Telemetry] = None,
                 async_swap: bool = True, kv_dtype: str = "bf16",
                 shared_host=None):
        linkage.validate()
        if cfg.embeds_in:
            raise ValueError("serving engine takes token ids, not embeddings")
        if n_slots < 1:
            raise ValueError("serving engine needs n_slots >= 1")
        if mesh is not None:
            # the shortcut kernels run per shard on the serving mesh
            opts = dataclasses.replace(opts, mesh=mesh)
        self.cfg = cfg
        self.params = params
        self.opts = opts
        self.linkage = linkage
        self.n_slots = n_slots
        self.max_len = max_len
        self.mesh = mesh
        self.sampling = sampling or SamplingConfig()
        self.tokens_per_program = (linkage.decode_steps
                                   if linkage.level == L3_NSS else 1)
        self.chunked = chunked
        if chunked:
            if chunk_budget < 1:
                raise ValueError("chunked serving needs chunk_budget >= 1")
            self.chunk_budget = chunk_budget
            # W: the compiled per-row chunk width — every step pads to this
            # one shape, so the whole engine jits a single serve program
            self.chunk_width = chunk_width or min(chunk_budget, max_len)
            if not 1 <= self.chunk_width <= max_len:
                raise ValueError(f"chunk_width must be in [1, max_len] "
                                 f"(got {self.chunk_width})")
        self.preempt = PreemptionPolicy.parse(preempt)
        if ttft_slo_s is not None and not chunked:
            raise ValueError("ttft_slo_s tunes the chunked token budget — "
                             "it needs chunked=True")
        # speculative decode: a scheduler-side DraftProposer feeds W-wide
        # draft-and-verify programs; "none" never builds the verify program
        if spec_decode not in SPEC_MODES:
            raise ValueError(f"unknown spec_decode {spec_decode!r}; known: "
                             f"{SPEC_MODES}")
        self.proposer: Optional[DraftProposer] = None
        self.spec_width = 0
        if spec_decode != "none":
            self.spec_width = spec_width or 4
            if not 1 <= self.spec_width <= max_len:
                raise ValueError(f"spec_width must be in [1, max_len] "
                                 f"(got {self.spec_width})")
            self.proposer = DraftProposer(self.spec_width)
        bucket_fn = self._bucket if bucket_prompts else None
        if kv == "slotted":
            # host_blocks=None means "auto-size the host tier" on paged —
            # reject it here too, not just explicit sizes
            if warm_start or host_blocks != 0:
                raise ValueError("the host tier (host_blocks / warm_start) "
                                 "needs kv='paged': dense slot rows have no "
                                 "block structure to spill")
            if kv_dtype != "bf16":
                raise ValueError("kv_dtype quantization needs kv='paged': "
                                 "dense slot rows have no per-block scale "
                                 "tables")
            if shared_host is not None:
                raise ValueError("a shared host tier (shared_host) needs "
                                 "kv='paged': dense slot rows have no block "
                                 "structure to publish")
            self.kv: KVBackend = SlottedKV(cfg, params, opts, linkage,
                                           n_slots, max_len, self.sampling,
                                           bucket_fn, mesh=mesh,
                                           chunked=chunked,
                                           spec=self.proposer is not None)
        elif kv == "paged":
            from repro.serve.paging import PagedKV
            hb = host_blocks
            if hb in (0, None) and (self.preempt.mode == "swap"
                                    or warm_start):
                hb = None            # auto: mirror the device pool (and grow
                                     # to fit the warm-start file)
            self.kv = PagedKV(cfg, params, opts, linkage, n_slots, max_len,
                              self.sampling, bucket_fn,
                              block_size=block_size, num_blocks=num_blocks,
                              mesh=mesh, chunked=chunked, host_blocks=hb,
                              warm_start=warm_start,
                              spec=self.proposer is not None,
                              async_swap=async_swap, kv_dtype=kv_dtype,
                              shared_host=shared_host)
        else:
            raise ValueError(f"unknown kv backend {kv!r}; known: "
                             f"{KV_BACKENDS}")
        # telemetry: NULL_TELEMETRY is the zero-cost disabled bundle (every
        # hook a no-op, now() never reads a clock); the backend shares the
        # engine's bundle so tier movement lands in the same trace
        self.tel = telemetry if telemetry is not None else NULL_TELEMETRY
        self.kv.tel = self.tel
        # program-family labels stamped on engine_step trace events — the
        # trace-side analogue of a kernel symbol name for each linked program
        self._labels = {k: program_label(cfg, linkage, k)
                        for k in ("decode", "serve_chunk", "verify",
                                  "prefill_admit")}
        self.tuner = None
        if ttft_slo_s is not None:
            self.tuner = BudgetTuner(
                slo_s=ttft_slo_s, budget=self.chunk_budget,
                floor=max(1, self.tokens_per_program),
                cap=(self.tokens_per_program + self.chunk_width) * n_slots)
        self._next = jnp.zeros((n_slots,), jnp.int32)
        self.sched = SlotScheduler(n_slots)
        # dispatch pipelining: next-step chunk grants computed in the
        # overlap window, keyed on the exact pack_chunks inputs — consumed
        # by _plan_chunks only on an exact key match (pack_chunks is pure,
        # so a hit is bit-identical to recomputing)
        self._pack_memo: Optional[Tuple[tuple, List[int]]] = None
        self.programs_run = 0
        self.tokens_wasted = 0       # decoded past a request's budget/EOS
        self.preemptions = 0         # paged: recompute-preempted admissions
        self.swap_preemptions = 0    # paged: swap-out preempted (host tier)
        self.swap_resumes = 0        # swapped slots resumed via swap-in
        self.prefill_tokens = 0      # prompt tokens admitted (incl. shared)
        self.decode_tokens = 0       # decode tokens produced
        self.spec_steps = 0          # verify programs run
        self.spec_draft_tokens = 0   # drafts fed into verify programs
        self.spec_accepted_tokens = 0   # ...that the model confirmed
        self.spec_wasted_tokens = 0  # ...that it rejected (verify compute
                                     # spent on positions never emitted)
        self.spec_emitted_tokens = 0    # tokens emitted by verify programs
        self.handoffs_out = 0        # fleet: chains handed to a decode cell
        self.handoffs_in = 0         # fleet: chains adopted from a prefill
                                     # cell (swap-in landed in this pool)
        # token positions the dispatched programs computed (slots x (chunk
        # width + K) a serve step, slots x K a decode program, slots x spec
        # width a verify program) and those of them that did useful work
        # (prompt positions absorbed, decode microsteps making a kept token,
        # verify positions emitting one)
        self.positions_computed = 0
        self.positions_useful = 0
        # KV blocks of the paged attention kernel's grid in the chunk and
        # verify passes (slots x blocks a row), and those of them at or
        # below a row's last real query, the blocks it attends
        self.chunk_blocks_grid = 0
        self.chunk_blocks_live = 0
        self._waited = 0.0           # this step's blocking waits on results

    def _bucket(self, n: int) -> int:
        """Power-of-two admission bucket (owned by the scheduler module —
        see ``repro.serve.scheduler.bucket_len`` for the guards)."""
        return bucket_len(n, self.max_len)

    # -- admission ----------------------------------------------------------

    def _admit(self, now_fn: Callable[[], float]) -> List[Completion]:
        tel = self.tel
        adm = now_fn()
        slot, req = self.sched.admit_next(adm)
        if req.prompt.shape[0] + req.max_new_tokens > self.max_len:
            self.sched.release(slot)
            raise ValueError(
                f"request {req.rid}: prompt+budget exceeds max_len "
                f"{self.max_len}")
        if not self.kv.fits(int(req.prompt.shape[0]), req.max_new_tokens):
            self.sched.release(slot)
            raise ValueError(
                f"request {req.rid}: prompt+budget can never fit the "
                f"{self.kv.kind} KV store (pool too small)")
        tel.admit(req.rid, slot, int(req.prompt.shape[0]), adm)
        t0 = tel.now()
        first = self.kv.admit(slot, np.asarray(req.prompt, np.int32),
                              self.sampling.request_key(req.rid))
        t1 = tel.now()
        self.prefill_tokens += int(req.prompt.shape[0])
        tel.prefill_tokens(int(req.prompt.shape[0]))
        self._next = self._next.at[slot].set(first[0])
        st = self.sched.active[slot]
        # the prefill sample is generated token #1 of the budget
        if self.linkage.ret_async:
            st.chunks.append(first)                 # stays a device future
            t2 = t1
        else:
            f = np.asarray(first)                   # "iret": sync now
            t2 = tel.now()
            st.chunks.append(f)
            if req.eos_id is not None and int(f[0]) == req.eos_id:
                st.eos_seen = True
        st.first_token_s = st.prefill_done_s = now_fn()
        st.note_emit(st.first_token_s)
        st.prefill_pos = int(req.prompt.shape[0])   # two-phase: all at once
        st.fresh = False
        st.produced = 1
        tel.state(req.rid, "decoding", st.first_token_s)
        tel.step("prefill_admit", self.programs_run, t0, 0.0, t1 - t0,
                 t2 - t1, tel.now() - t2, queued=self.sched.n_queued,
                 active=len(self.sched.active),
                 swapped=len(self.sched.swapped),
                 program=self._labels["prefill_admit"])
        if st.remaining == 0 or st.eos_seen:
            return [self._finalize(slot, now_fn)]
        return []

    # -- decode -------------------------------------------------------------

    def _reserve_all(self) -> None:
        """Demand-allocate the blocks this program will write, preempting
        the youngest slot (recompute on re-admission) when the pool is dry.
        Oldest-first order keeps the head of the line progressing."""
        K = self.tokens_per_program
        while True:
            order = sorted(self.sched.active,
                           key=lambda s: self.sched.active[s].admit_seq)
            if all(self.kv.reserve(slot, K) for slot in order):
                return
            if len(self.sched.active) == 1:
                raise RuntimeError(
                    "paged KV pool cannot hold a single active request; "
                    "fits() should have rejected it")
            self._preempt(self.sched.choose_victim(self.preempt.victim))

    def _preempt(self, slot: int) -> None:
        """Evict ``slot`` under pool pressure, per the PreemptionPolicy:
        swap parks the slot state + its host-tier KV for an exact resume;
        recompute (or a failed swap: no host tier / pinned full) releases
        everything and requeues the request at the head of the line."""
        rid = self.sched.active[slot].req.rid
        if self.preempt.mode == "swap":
            handle = self.kv.swap_out(slot)
            if handle is not None:
                st = self.sched.release(slot)
                st.pending_drafts = None     # drafts die with the victim's
                                             # step; resume re-proposes
                self.sched.suspend_front(st, (handle, self._next[slot]))
                self.swap_preemptions += 1
                now = self.tel.now()
                self.tel.preempt(rid, slot, "swap", now)
                self.tel.state(rid, "swapped", now)
                # start staging the resume-head victim's host→device copy
                # while the victim's device blocks are still being recycled
                self._prefetch_head()
                return
        st = self.sched.release(slot)
        self.kv.release(slot)
        self.sched.requeue_front(st.req)
        self.preemptions += 1
        now = self.tel.now()
        self.tel.preempt(rid, slot, "recompute", now)
        self.tel.state(rid, "preempted", now)
        self.tel.state(rid, "queued", now)

    def _resume_swapped(self) -> None:
        """Swap suspended slot states back in, oldest first — they are the
        head of the FIFO line, so fresh admissions wait behind them (the
        same discipline recompute's requeue_front imposes). Stops at the
        first one the device pool cannot hold yet."""
        while self.sched.can_resume():
            handle, nxt = self.sched.peek_swapped()[1]
            if not self.kv.can_swap_in(handle):
                break                # FIFO: wait for blocks, don't skip ahead
            slot, st, _ = self.sched.resume_next()
            if not self.kv.swap_in(slot, handle):
                # can_swap_in raced nothing (single-threaded) — belt and
                # braces: fall back to recompute for this request, and free
                # the handle's host blocks so the tier cannot leak
                self.kv.drop_swap(handle)
                self.sched.release(slot)
                self.sched.requeue_front(st.req)
                self.preemptions += 1
                now = self.tel.now()
                self.tel.preempt(st.req.rid, slot, "recompute", now)
                self.tel.state(st.req.rid, "queued", now)
                continue
            self._next = self._next.at[slot].set(nxt)
            self.swap_resumes += 1
            self.tel.state(st.req.rid,
                           "prefilling" if st.prefilling else "decoding",
                           self.tel.now())
        # whatever still waits (pool not ready / no free slot): stage the
        # new resume head's copy so its eventual swap-in is a prefetch hit
        self._prefetch_head()

    # -- async runtime: drain / prefetch / overlapped host work -------------

    def _drain_swaps(self) -> int:
        """Complete in-flight async swap transfers (no-op for backends
        without a stream). Called at step boundaries and in the overlap
        window, so deferred device→host copies never pile past the step."""
        drain = getattr(self.kv, "drain_swaps", None)
        return drain() if drain is not None else 0

    def _prefetch_head(self) -> bool:
        """Speculatively stage the host→device copy for the resume-head
        swapped victim (smallest original admit_seq — the one
        ``_resume_swapped`` will pop first). Pure data staging on the
        handle: no refcounts move until the actual swap-in, and the
        synchronous path (``--sync-swap``) makes this a no-op."""
        pf = getattr(self.kv, "prefetch_swap_in", None)
        if pf is None:
            return False
        head = self.sched.peek_swapped()
        if head is None:
            return False
        return pf(head[1][0])

    def _overlap_host_work(self) -> None:
        """Host-side work pipelined under the just-dispatched device step:
        drain the swap stream and stage the resume-head prefetch. Runs
        between dispatch and the commit, inside the ``engine.overlap`` span;
        the step event counts it as host time."""
        tel = self.tel
        t = tel.now()
        if self._drain_swaps():
            tel.overlap("drain", tel.now() - t)
        if self.sched.swapped:
            t = tel.now()
            if self._prefetch_head():
                tel.overlap("prefetch", tel.now() - t)

    def step(self, now_fn: Callable[[], float]) -> List[Completion]:
        """Run one decode program; harvest tokens; evict finished slots.

        With speculative decoding enabled, a draft-and-verify program runs
        instead whenever the proposer has drafts for any slot; steps where
        every slot draws a blank fall through to the plain decode program
        (zero overhead relative to the spec-off engine).

        Internally split at the blocking host sync so a fleet driver can
        dispatch every replica's program before committing any of them
        (``tick_dispatch``/``tick_commit``); run back to back the two
        halves ARE this method — the 1-replica fleet is bit-identical to
        the bare engine by construction."""
        return self._step_end(self._step_begin(now_fn), now_fn)

    def _step_begin(self, now_fn: Callable[[], float]):
        """Dispatch half of ``step``: reserve, launch the decode program,
        run the overlap-window host work — everything up to (excluding) the
        blocking ``np.asarray`` sync. Returns a tagged pending ticket for
        ``_step_end``. The spec-decode path resolves accept counts on the
        host, so it runs whole here and returns its completions directly."""
        if self.proposer is not None:
            spec = self._step_spec(now_fn)
            if spec is not None:
                return ("done", spec)
        tel = self.tel
        K = self.tokens_per_program
        with tel.span("engine.plan") as plan:
            self._reserve_all()
        self._count_positions(
            sum(min(K, st.remaining) for st in self.sched.active.values()),
            self.n_slots * K)
        with tel.span("engine.dispatch", kind="decode",
                      program=self.programs_run) as disp:
            toks = self.kv.decode(self._next)
            self._next = toks[:, -1]
            self.programs_run += 1
        with tel.span("engine.overlap"):
            self._overlap_host_work()  # under the dispatched device step
        return ("decode", (toks, plan, disp))

    def _step_end(self, pending, now_fn: Callable[[], float]
                  ) -> List[Completion]:
        """Commit half: block on the device result, harvest tokens, evict
        finished slots, stamp the step trace event. Dispatches on the
        ticket tag from ``_step_begin`` / ``_chunk_begin``."""
        tag, data = pending
        if tag == "done":
            return data
        if tag == "chunk":
            return self._chunk_end(data, now_fn)
        tel = self.tel
        toks, plan, disp = data
        with tel.span("engine.harvest") as harvest:
            self._waited = 0.0
            toks_host = None
            if not self.linkage.ret_async:
                t = tel.now()
                toks_host = np.asarray(toks)        # "iret": sync every program
                self._waited += tel.now() - t
            slots = sorted(self.sched.active)
            tel.decode_microsteps(len(slots), self.tokens_per_program,
                                  disp.t0)
            finished = self._harvest_decode(slots, toks, toks_host, now_fn)
        self._step_event("decode", plan, disp, harvest)
        return finished

    def _count_positions(self, useful: int, computed: int) -> None:
        self.positions_useful += useful
        self.positions_computed += computed

    def _count_blocks(self, start: np.ndarray, clen: np.ndarray) -> None:
        """A paged chunk or verify pass's KV blocks: the kernel's whole
        grid, and the blocks up to each row's last real query,
        ``ceil((start + clen) / bs)`` over the rows with a chunk."""
        if self.kv.kind != "paged":
            return
        real = clen > 0
        ends = start[real].astype(np.int64) + clen[real]
        self.chunk_blocks_live += int((-(-ends // self.kv.bs)).sum())
        self.chunk_blocks_grid += len(clen) * self.kv.nb

    def _step_event(self, kind: str, plan, disp, harvest) -> None:
        """The ``engine_step`` event of one program, from its spans: pack is
        everything before the dispatch, device the blocking waits on
        results (a non-``ret_async`` commit's sync, ``_finalize``'s), host
        the rest up to the end of the harvest (the overlap work included)."""
        tel = self.tel
        tel.step(kind, self.programs_run, plan.t0, disp.t0 - plan.t0,
                 disp.t1 - disp.t0, self._waited,
                 harvest.t1 - disp.t1 - self._waited,
                 queued=self.sched.n_queued, active=len(self.sched.active),
                 swapped=len(self.sched.swapped),
                 program=self._labels[kind])

    # -- speculative decode: draft-and-verify -------------------------------

    def _reserve_spec(self) -> None:
        """Per-row verify reservations: row s writes 1 + |drafts| positions
        this program (its committed next token plus the draft window).
        Same preemption discipline as ``_reserve_all``."""
        while True:
            order = sorted(self.sched.active,
                           key=lambda s: self.sched.active[s].admit_seq)
            if all(self.kv.reserve(
                    s, 1 + int(self.sched.active[s].pending_drafts.size))
                    for s in order):
                return
            if len(self.sched.active) == 1:
                raise RuntimeError(
                    "paged KV pool cannot hold a single active request; "
                    "fits() should have rejected it")
            self._preempt(self.sched.choose_victim(self.preempt.victim))

    def _step_spec(self, now_fn: Callable[[], float]
                   ) -> Optional[List[Completion]]:
        """One draft-and-verify program, or None to fall back to plain
        decode (no slot drew a draft this step).

        Every active slot rides the verify program: drafted rows at width
        1 + |drafts|, draft-less rows at width 1 — a width-1 verify row IS
        a decode step (same write, same attend, same sample), so no row
        falls behind. Note the RET caveat: resolving accept lengths needs
        the accept counts AND token values on the host, so a verify program
        synchronizes even under ``ret_async`` (drafting from the produced
        history already synced the slot's futures); plain-decode fallback
        steps keep RET's once-per-request sync."""
        # propose before reserving: reservations depend on draft lengths
        order = sorted(self.sched.active)
        if not all(self.sched.active[s].produced > 0 for s in order):
            return None                   # a slot with no committed token
                                          # yet cannot feed a verify row
        tel = self.tel
        with tel.span("engine.plan") as plan:
            any_draft = False
            for s in order:
                st = self.sched.active[s]
                st.pending_drafts = self.proposer.propose(st)
                any_draft = any_draft or st.pending_drafts.size > 0
            if not any_draft:
                for s in order:
                    self.sched.active[s].pending_drafts = None
                return None
            self._reserve_spec()
            order = sorted(self.sched.active)   # preemption may have evicted
            B, W = self.n_slots, self.spec_width
            toks = np.zeros((B, W), np.int32)
            clen = np.zeros(B, np.int32)
            start = np.zeros(B, np.int32)
            vmask = np.zeros(B, bool)
            nxt_host = np.asarray(self._next)
            for s in order:
                st = self.sched.active[s]
                m = int(st.pending_drafts.size)
                toks[s, 0] = nxt_host[s]
                toks[s, 1:1 + m] = st.pending_drafts
                clen[s] = 1 + m
                start[s] = st.prompt_len + st.produced - 1   # next write pos
                vmask[s] = True

        with tel.span("engine.dispatch", kind="verify",
                      program=self.programs_run) as disp:
            out, n_emit = self.kv.verify_step(toks, clen, start, vmask)
            self.programs_run += 1
            self.spec_steps += 1
        with tel.span("engine.harvest") as harvest:
            finished = self._harvest_spec(order, start, nxt_host, out,
                                          n_emit, now_fn)
        self._count_positions(int(np.asarray(n_emit).sum()), B * W)
        self._count_blocks(start, clen)
        self._step_event("verify", plan, disp, harvest)
        return finished

    def _harvest_spec(self, order, start, nxt_host, out, n_emit,
                      now_fn: Callable[[], float]) -> List[Completion]:
        """Sync a verify program's accept counts and tokens, commit each
        row's accepted prefix, and finalize finished rows."""
        tel = self.tel
        self._waited = 0.0
        t = tel.now()
        out_host, n_host = np.asarray(out), np.asarray(n_emit)
        self._waited += tel.now() - t
        nxt = nxt_host.copy()
        for s in order:
            nxt[s] = out_host[s, int(n_host[s]) - 1]
        self._next = jnp.asarray(nxt)

        now = now_fn()
        finished = []
        for s in order:
            st = self.sched.active[s]
            m = int(st.pending_drafts.size)
            st.pending_drafts = None
            a = int(n_host[s])              # emitted = 1 + accepted drafts
            self.spec_draft_tokens += m
            self.spec_accepted_tokens += a - 1
            self.spec_wasted_tokens += m - (a - 1)
            self.spec_emitted_tokens += a
            tel.verify_window(s, st.req.rid, m, a - 1, now)
            chunk = out_host[s, :a]
            st.chunks.append(chunk)
            st.produced += a                # clamped drafting: never > budget
            self.decode_tokens += a
            if st.last_emit_s is not None:
                tel.emit_gap(now - st.last_emit_s)
            st.note_emit(now)
            if st.first_decode_s is None:
                st.first_decode_s = now
            if st.req.eos_id is not None and st.req.eos_id in chunk:
                st.eos_seen = True          # EOS inside the accepted window
            # commit = rollback to the accepted length: frees draft-tail
            # blocks (paged) and rewinds the host position
            self.kv.rollback(s, int(start[s]) + a)
            if st.produced >= st.req.max_new_tokens or st.eos_seen:
                finished.append(self._finalize(s, now_fn))
        return finished

    def _harvest_decode(self, slots, toks, toks_host,
                        now_fn: Callable[[], float]) -> List[Completion]:
        """Collect this program's decode tokens for ``slots``: append (up to
        the request budget), check EOS at the sync point, finalize finished.
        Shared by the two-phase step and the chunked step's decode half."""
        now = now_fn()
        finished = []
        for slot in slots:
            st = self.sched.active[slot]
            take = min(self.tokens_per_program, st.remaining)
            self.tokens_wasted += self.tokens_per_program - take
            if take == 0:
                continue
            chunk = (toks[slot, :take] if toks_host is None
                     else toks_host[slot, :take])
            st.chunks.append(chunk)
            st.produced += take
            self.decode_tokens += take
            if st.last_emit_s is not None:
                self.tel.emit_gap(now - st.last_emit_s)
            st.note_emit(now)
            if st.first_decode_s is None:
                st.first_decode_s = now
            if (toks_host is not None and st.req.eos_id is not None
                    and st.req.eos_id in chunk):
                st.eos_seen = True                  # stop at the sync point
            if st.produced >= st.req.max_new_tokens or st.eos_seen:
                finished.append(self._finalize(slot, now_fn))
        return finished

    # -- chunked prefill: the unified serve step ---------------------------

    def _admit_chunked(self, now_fn: Callable[[], float]) -> None:
        """Chunked admission is pure host bookkeeping — no program runs, so
        admission can never stall occupied decode slots. The prompt enters
        the device chunk by chunk through subsequent serve steps."""
        slot, req = self.sched.admit_next(now_fn())
        if req.prompt.shape[0] + req.max_new_tokens > self.max_len:
            self.sched.release(slot)
            raise ValueError(
                f"request {req.rid}: prompt+budget exceeds max_len "
                f"{self.max_len}")
        if not self.kv.fits(int(req.prompt.shape[0]), req.max_new_tokens):
            self.sched.release(slot)
            raise ValueError(
                f"request {req.rid}: prompt+budget can never fit the "
                f"{self.kv.kind} KV store (pool too small)")
        self.tel.admit(req.rid, slot, int(req.prompt.shape[0]),
                       self.sched.active[slot].admit_s)
        shared = self.kv.admit_chunked(slot, np.asarray(req.prompt, np.int32),
                                       self.sampling.request_key(req.rid))
        # count the radix-shared prefix so prefill_tokens means the same
        # thing in both step modes (prompt tokens admitted, shared or
        # computed — two-phase _admit counts the full prompt length too;
        # computed-vs-shared is broken out by kv_prefix_shared_tokens)
        self.prefill_tokens += shared
        self.tel.prefill_tokens(shared)
        st = self.sched.active[slot]
        st.prefill_pos = shared          # radix-shared prefix already resident

    def _plan_chunks(self):
        """Pack this step's token budget and reserve the memory it needs,
        preempting the youngest slot (recompute on re-admission) while the
        paged pool is dry. Returns (decode slots, prefill slots, grants) in
        FIFO admission order."""
        K = self.tokens_per_program
        while True:
            order = sorted(self.sched.active,
                           key=lambda s: self.sched.active[s].admit_seq)
            dec = [s for s in order if not self.sched.active[s].prefilling]
            pre = [s for s in order if self.sched.active[s].prefilling]
            remaining = [self.sched.active[s].prompt_len
                         - self.sched.active[s].prefill_pos for s in pre]
            key = (self.chunk_budget, self.chunk_width, K * len(dec),
                   tuple(remaining))
            if self._pack_memo is not None and self._pack_memo[0] == key:
                grants = self._pack_memo[1]
            else:
                grants = pack_chunks(self.chunk_budget, self.chunk_width,
                                     K * len(dec), remaining)
            self._pack_memo = None       # single-shot; replans recompute
            ok = all(self.kv.reserve(s, K) for s in dec)
            if ok:
                for s, g in zip(pre, grants):
                    st = self.sched.active[s]
                    if g and not self.kv.append_chunk(
                            s, st.prefill_pos,
                            st.req.prompt[st.prefill_pos:st.prefill_pos + g]):
                        ok = False
                        break
            if ok:
                return dec, pre, grants
            if len(self.sched.active) == 1:
                raise RuntimeError(
                    "paged KV pool cannot hold a single active request; "
                    "fits() should have rejected it")
            self._preempt(self.sched.choose_victim(self.preempt.victim))

    def _step_chunked(self, now_fn: Callable[[], float]) -> List[Completion]:
        """One unified serve program: decode tokens for occupied slots plus
        budget-packed prompt chunks; harvest both halves; evict finished.

        Pure-decode steps (no slot mid-prefill) dispatch the two-phase
        decode program instead — no dead chunk pass, so steady-state decode
        throughput is the two-phase engine's by construction. The unified
        program runs whenever ANY slot is mid-prefill, even on a step whose
        budget grants it zero chunk tokens: the plain decode path would
        harvest mid-prefill slots as decode rows and write their garbage
        through real block tables / circular rows, so only the masked serve
        step may run while a prompt is partially resident."""
        return self._step_end(self._chunk_begin(now_fn), now_fn)

    def _chunk_begin(self, now_fn: Callable[[], float]):
        """Dispatch half of the chunked serve step (see ``_step_begin`` for
        the split discipline). Pure-decode steps fall through to the plain
        decode dispatch."""
        if not any(self.sched.active[s].prefilling for s in self.sched.active):
            return self._step_begin(now_fn)
        tel = self.tel
        B, W, K = self.n_slots, self.chunk_width, self.tokens_per_program
        with tel.span("engine.plan") as plan:
            dec, pre, grants = self._plan_chunks()
            tel.pack(self.chunk_budget, K * len(dec), int(sum(grants)),
                     plan.t0)
            toks = np.zeros((B, W), np.int32)
            clen = np.zeros(B, np.int32)
            start = np.zeros(B, np.int32)
            reset = np.zeros(B, bool)
            emit0 = np.zeros(B, bool)
            dec_mask = np.zeros(B, bool)
            for s in dec:
                st = self.sched.active[s]
                start[s] = st.prompt_len + st.produced - 1   # next write pos
                dec_mask[s] = True
            for s, g in zip(pre, grants):
                st = self.sched.active[s]
                start[s] = st.prefill_pos
                clen[s] = g
                toks[s, :g] = st.req.prompt[st.prefill_pos:st.prefill_pos + g]
                if g:
                    reset[s] = st.fresh
                    st.fresh = False
                    emit0[s] = st.prefill_pos + g == st.prompt_len
                    tel.prefill_chunk(s, st.req.rid, st.prefill_pos, g,
                                      plan.t0)
        self._count_positions(
            int(clen.sum()) + sum(min(K, self.sched.active[s].remaining)
                                  for s in dec),
            B * (W + K))
        self._count_blocks(start, clen)
        with tel.span("engine.dispatch", kind="serve",
                      program=self.programs_run) as disp:
            t0, seq = self.kv.serve_step(toks, clen, start, reset, emit0,
                                         dec_mask, self._next)
            self._next = jnp.where(jnp.asarray(emit0), t0, seq[:, -1])
            self.programs_run += 1
            self.prefill_tokens += int(clen.sum())
        tel.decode_microsteps(len(dec), K, disp.t0)
        with tel.span("engine.overlap"):
            self._overlap_host_work()  # under the dispatched device step
            # pack next step's chunk grants now, keyed on the exact inputs
            # _plan_chunks will see; a key hit is bit-identical to
            # recomputing (pack_chunks is pure), a miss (admission/preemption
            # changed the picture) silently falls through to the normal
            # recompute
            nxt_rem = [r for s, g in zip(pre, grants)
                       for r in [self.sched.active[s].prompt_len
                                 - (self.sched.active[s].prefill_pos + g)]
                       if r > 0]
            if nxt_rem:
                t = tel.now()
                ndec = len(dec) + sum(1 for s, g in zip(pre, grants)
                                      if emit0[s])
                key = (self.chunk_budget, self.chunk_width, K * ndec,
                       tuple(nxt_rem))
                self._pack_memo = (key, pack_chunks(
                    self.chunk_budget, self.chunk_width, K * ndec,
                    list(nxt_rem)))
                tel.overlap("pack", tel.now() - t)
        return ("chunk", (pre, grants, dec, emit0, t0, seq, plan, disp))

    def _chunk_end(self, data, now_fn: Callable[[], float]
                   ) -> List[Completion]:
        """Commit half of the chunked serve step: sync, harvest prefill
        first-tokens and decode tokens, evict finished slots."""
        pre, grants, dec, emit0, t0, seq, plan, disp = data
        tel = self.tel
        with tel.span("engine.harvest") as harvest:
            self._waited = 0.0
            t0_host = seq_host = None
            if not self.linkage.ret_async:
                t = tel.now()
                t0_host, seq_host = np.asarray(t0), np.asarray(seq)
                self._waited += tel.now() - t
            now = now_fn()
            finished = []
            for s, g in zip(pre, grants):
                st = self.sched.active[s]
                st.prefill_pos += g
                if not emit0[s]:
                    continue
                # the chunk that completed the prompt yields generated
                # token #1
                first = t0[s:s + 1] if t0_host is None else t0_host[s:s + 1]
                st.chunks.append(first)
                if (t0_host is not None and st.req.eos_id is not None
                        and int(first[0]) == st.req.eos_id):
                    st.eos_seen = True
                st.first_token_s = st.prefill_done_s = now
                st.note_emit(now)
                st.produced = 1
                tel.state(st.req.rid, "decoding", now)
                if st.remaining == 0 or st.eos_seen:
                    finished.append(self._finalize(s, now_fn))
            finished += self._harvest_decode(dec, seq, seq_host, now_fn)
        self._step_event("serve_chunk", plan, disp, harvest)
        return finished

    def _finalize(self, slot: int,
                  now_fn: Callable[[], float]) -> Completion:
        st = self.sched.release(slot)
        self.kv.release(slot)                       # paged: free blocks now
        # RET mode synchronizes here, once per completed request
        with self.tel.span("engine.finalize", rid=st.req.rid) as sync:
            tokens = np.concatenate([np.asarray(c) for c in st.chunks])
        self._waited += sync.t1 - sync.t0
        if st.req.eos_id is not None:
            hits = np.nonzero(tokens == st.req.eos_id)[0]
            if hits.size:
                self.tokens_wasted += len(tokens) - (int(hits[0]) + 1)
                tokens = tokens[:int(hits[0]) + 1]
        done = now_fn()
        fd = st.first_decode_s if st.first_decode_s is not None else done
        c = Completion(
            rid=st.req.rid, prompt_len=int(st.req.prompt.shape[0]),
            tokens=tokens, arrival_s=st.req.arrival_s, admit_s=st.admit_s,
            first_token_s=st.first_token_s, done_s=done,
            prefill_done_s=st.prefill_done_s, first_decode_s=fd,
            max_stall_s=st.max_stall_s)
        self.tel.complete(c, done)
        return c

    # -- driving loops ------------------------------------------------------

    def _admit_and_step(self, now_fn) -> List[Completion]:
        return self.tick_commit(self.tick_dispatch(now_fn), now_fn)

    def tick_dispatch(self, now_fn) -> Tuple[List[Completion],
                                             Optional[tuple]]:
        """Dispatch half of one engine tick: resume/admit bookkeeping plus
        the step's dispatch half. Returns (completions so far, pending
        ticket) for ``tick_commit``. A fleet driver calls every replica's
        dispatch before any replica's commit, so all device programs are in
        flight before the first blocking sync — the same overlap discipline
        ``_overlap_host_work`` applies within one step, lifted across
        replicas. ``tick_commit(tick_dispatch(now))`` run back to back is
        exactly the single-engine tick."""
        finished = []
        tel = self.tel
        tel.profile_tick(self.programs_run)
        # the tick's span opens here and closes in tick_commit
        tick = tel.span("engine.tick").__enter__()
        with tel.span("engine.admit"):
            self._drain_swaps()      # step boundary: complete deferred copies
            self._resume_swapped()
            while self.sched.can_admit() and not self.sched.swapped:
                # swapped slots are the head of the line: fresh admissions
                # wait
                head = self.sched.peek()
                if not self.kv.has_room(int(head.prompt.shape[0])):
                    break            # FIFO: wait for blocks, don't skip ahead
                if self.chunked:
                    self._admit_chunked(now_fn)  # bookkeeping only
                else:
                    finished += self._admit(now_fn)
        pend = None
        if self.sched.active:
            pend = (self._chunk_begin(now_fn) if self.chunked
                    else self._step_begin(now_fn))
        return finished, pend, tick

    def tick_commit(self, ticket, now_fn) -> List[Completion]:
        """Commit half of one engine tick: block on the dispatched program,
        harvest, and feed the TTFT tuner."""
        finished, pend, tick = ticket
        finished = list(finished)
        if pend is not None:
            finished += self._step_end(pend, now_fn)
        if self.tuner is not None:
            for c in finished:
                old = self.chunk_budget
                self.chunk_budget = self.tuner.observe(c.ttft_s)
                self.tel.budget_adjust(old, self.chunk_budget,
                                       self.tel.now())
        tick.__exit__(None, None, None)
        return finished

    # -- fleet: prefill/decode disaggregation handoff -----------------------

    def extract_handoffs(self) -> List[tuple]:
        """Harvest every decode-ready slot for a fleet prefill→decode
        handoff: the prompt is fully resident and generated token #1 is
        committed, so a decode cell can continue the stream exactly where
        this (prefill) cell left off. The transfer rides the swap lane —
        ``swap_out`` exports the slot's chain through the host tier, and
        the decode cell's ``swap_in`` imports it into its own pool; swap
        round-trip identity (tests/test_paging.py) is what makes the
        disaggregated stream bit-identical to the colocated one.

        Slots whose chain cannot reach the host tier (no tier / tier full)
        simply stay and decode locally — values unchanged, retried never
        (this cell finishes them). Returns [(SlotState, SwapHandle,
        next-token device scalar), ...] in slot order."""
        out = []
        for slot in sorted(self.sched.active):
            st = self.sched.active[slot]
            if st.prefilling or st.produced < 1:
                continue
            nxt = self._next[slot]
            handle = self.kv.swap_out(slot)
            if handle is None:
                continue             # no host room: decode locally instead
            st2 = self.sched.release(slot)
            st2.pending_drafts = None    # drafts die with the handoff; the
                                         # decode cell re-proposes
            self.handoffs_out += 1
            out.append((st2, handle, nxt))
        return out

    def inject_handoff(self, st: SlotState, handle, next_token) -> bool:
        """Adopt a prefill cell's finished chain into this engine: claim a
        slot, swap the chain into this pool, and resume decoding from the
        carried next token. Returns False (nothing consumed) when no slot
        is free or the pool cannot hold the chain yet — the fleet retries
        or leaves the stream on its prefill cell."""
        if self.sched.n_free == 0 or not self.kv.can_swap_in(handle):
            return False
        slot = self.sched.adopt(st)
        if not self.kv.swap_in(slot, handle):
            # can_swap_in raced nothing (single-threaded) — belt and braces,
            # mirroring _resume_swapped: recompute the request from scratch
            # here (deterministic sampling replays the identical stream)
            self.kv.drop_swap(handle)
            self.sched.release(slot)
            self.sched.requeue_front(st.req)
            self.preemptions += 1
            now = self.tel.now()
            self.tel.preempt(st.req.rid, slot, "recompute", now)
            self.tel.state(st.req.rid, "queued", now)
            return True                  # consumed (as a requeue)
        self._next = self._next.at[slot].set(next_token)
        self.handoffs_in += 1
        return True

    def run(self, requests: List[Request], *, load: str = "closed",
            concurrency: Optional[int] = None,
            clock: Callable[[], float] = time.monotonic
            ) -> Tuple[List[Completion], float]:
        """Serve ``requests`` to completion. Returns (completions, wall_s).

        load="open":   requests arrive at their ``arrival_s`` timestamps via
                       an AdmissionWorker co-process, regardless of server
                       speed (open loop — queueing delay shows up in latency).
        load="closed": at most ``concurrency`` requests are outstanding; a
                       completion immediately issues the next (closed loop).
        """
        n = len(requests)
        completions: List[Completion] = []
        t0 = clock()
        rel = lambda: clock() - t0
        # trace timestamps share the run's relative clock, so span-derived
        # TTFT/latency and Completion timestamps are the same timeline
        self.tel.set_clock(rel)
        if load == "open":
            worker = AdmissionWorker(requests, clock=clock)
            while len(completions) < n:
                for r in worker.poll():
                    self.sched.enqueue(r)
                    self.tel.state(r.rid, "queued", r.arrival_s)
                if (not self.sched.active and not self.sched.can_admit()
                        and not self.sched.swapped and not worker.exhausted):
                    r = worker.wait(timeout=0.05)   # device idle: block
                    if r is not None:
                        self.sched.enqueue(r)
                        self.tel.state(r.rid, "queued", r.arrival_s)
                    continue
                completions += self._admit_and_step(rel)
        elif load == "closed":
            conc = concurrency or self.n_slots
            issued = 0
            outstanding = 0
            while len(completions) < n:
                while outstanding < conc and issued < n:
                    req = dataclasses.replace(requests[issued],
                                              arrival_s=rel())
                    self.sched.enqueue(req)
                    self.tel.state(req.rid, "queued", req.arrival_s)
                    issued += 1
                    outstanding += 1
                done = self._admit_and_step(rel)
                outstanding -= len(done)
                completions += done
        else:
            raise ValueError(f"unknown load mode {load!r}")
        return completions, rel()

    # -- prefix-cache persistence -------------------------------------------

    def save_prefix_cache(self, path: str) -> int:
        """Persist the KV hierarchy's prefix cache (host tier + shared
        device prefixes) so a restarted engine (``warm_start=path``) serves
        the same prompts without re-prefilling them. Paged backend only."""
        return self.kv.save(path)

    # -- reporting ----------------------------------------------------------

    def utilization(self) -> dict:
        """Engine + backend utilization counters (merged into serve_report)."""
        u = {
            "kv_backend": self.kv.kind,
            "step_mode": "chunked" if self.chunked else "two_phase",
            "programs_run": self.programs_run,
            "tokens_wasted": self.tokens_wasted,
            "preemptions": self.preemptions,
            "preempt_policy": f"{self.preempt.mode}/{self.preempt.victim}",
            "swap_preemptions": self.swap_preemptions,
            "swap_resumes": self.swap_resumes,
            # the step batch mix: how the budget split between absorbing
            # prompts and producing tokens (chunked scheduling observable)
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "positions_useful": self.positions_useful,
            "positions_computed": self.positions_computed,
            "chunk_blocks_live": self.chunk_blocks_live,
            "chunk_blocks_grid": self.chunk_blocks_grid,
        }
        if self.programs_run:
            u["prefill_tokens_per_step"] = round(
                self.prefill_tokens / self.programs_run, 2)
            u["decode_tokens_per_step"] = round(
                self.decode_tokens / self.programs_run, 2)
        if self.chunked:
            u["chunk_budget"] = self.chunk_budget
            u["chunk_width"] = self.chunk_width
        if self.proposer is not None:
            u["spec_decode"] = "ngram"
            u["spec_width"] = self.spec_width
            u["spec_steps"] = self.spec_steps
            u["spec_draft_tokens"] = self.spec_draft_tokens
            u["spec_accepted_tokens"] = self.spec_accepted_tokens
            u["spec_wasted_tokens"] = self.spec_wasted_tokens
            if self.spec_draft_tokens:
                u["spec_acceptance_rate"] = round(
                    self.spec_accepted_tokens / self.spec_draft_tokens, 4)
            if self.spec_steps:
                u["spec_tokens_per_step"] = round(
                    self.spec_emitted_tokens / self.spec_steps, 2)
        if self.tuner is not None:
            u["ttft_slo_s"] = self.tuner.slo_s
            u["budget_adjustments"] = self.tuner.adjustments
        if self.handoffs_out or self.handoffs_in:
            u["handoffs_out"] = self.handoffs_out
            u["handoffs_in"] = self.handoffs_in
        u.update(self.kv.utilization())
        # on one device the single shard holds the whole store, so this
        # doubles as total KV residency — the equal-block-budget bytes the
        # kv_dtype axis compresses
        u["kv_bytes_per_shard"] = _kv_bytes_per_shard(self.kv.cache)
        if "kv_blocks_hwm" in u:
            # resident high-watermark in per-shard bytes (+1: trash row)
            u["kv_hwm_bytes_per_shard"] = int(
                u["kv_bytes_per_shard"] * u["kv_blocks_hwm"]
                / (u["kv_blocks_total"] + 1))
        if self.mesh is not None:
            u["mesh"] = "x".join(str(self.mesh.shape[a])
                                 for a in self.mesh.axis_names)
        return u

    def reset_counters(self) -> None:
        """Zero the utilization counters (after a compile-warmup run)."""
        self.programs_run = 0
        self.tokens_wasted = 0
        self.preemptions = 0
        self.swap_preemptions = 0
        self.swap_resumes = 0
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self.positions_useful = 0
        self.positions_computed = 0
        self.chunk_blocks_live = 0
        self.chunk_blocks_grid = 0
        self.spec_steps = 0
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_wasted_tokens = 0
        self.spec_emitted_tokens = 0
        self.handoffs_out = 0
        self.handoffs_in = 0
        if self.proposer is not None:
            self.proposer.proposed_tokens = 0
            self.proposer.lookups = 0
            self.proposer.hits = 0
        if self.tuner is not None:
            self.tuner.adjustments = 0
        self.kv.reset_counters()
        self.tel.reset()                 # warmup events don't belong in the
                                         # trace or the metrics


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------

def _kv_bytes_per_shard(cache) -> int:
    """Device bytes one mesh shard holds for the KV store (what "per-shard
    KV residency" buys: the sharded leaves divide by the model axis)."""
    import jax
    total = 0
    for leaf in jax.tree.leaves(cache):
        shards = getattr(leaf, "addressable_shards", None)
        total += shards[0].data.nbytes if shards else leaf.nbytes
    return int(total)


def serve_report(completions: List[Completion], wall_s: float,
                 utilization: Optional[dict] = None) -> dict:
    """Summarize a serve run. Well-defined for every completion count:

    - zero completions (a mid-run snapshot before anything finishes):
      returns the partial report — ``requests``/``total_tokens`` 0, the
      rates 0.0, utilization merged — with every percentile/latency field
      *omitted* (there is no sample to summarize; consumers must treat the
      keys as optional, not read NaNs).
    - small samples: percentiles are ``np.percentile`` over the observed
      completions, so with n < 100 the p99 equals the sample maximum (with
      n == 1, every percentile is that single observation). They are exact
      order statistics of what was measured, not population estimates.
    - ``wall_s == 0`` (frozen or zero-resolution clocks): the throughput
      rates are 0.0 rather than a division error.
    """
    if not completions:
        rep = {
            "requests": 0,
            "wall_s": wall_s,
            "total_tokens": 0,
            "tokens_per_s": 0.0,
            "requests_per_s": 0.0,
        }
        if utilization:
            rep.update(utilization)
        return rep
    lats = np.array([c.latency_s for c in completions])
    ttfts = np.array([c.ttft_s for c in completions])
    queue = np.array([c.queue_wait_s for c in completions])
    pfill = np.array([c.prefill_s for c in completions])
    fdec = np.array([c.first_decode_gap_s for c in completions])
    total_tokens = int(sum(len(c.tokens) for c in completions))
    rep = {
        "requests": len(completions),
        "wall_s": wall_s,
        "total_tokens": total_tokens,
        # rates are 0.0 on a zero-length wall clock (e.g. a frozen test
        # clock), not a ZeroDivisionError — the counts still carry the data
        "tokens_per_s": total_tokens / wall_s if wall_s else 0.0,
        "requests_per_s": len(completions) / wall_s if wall_s else 0.0,
        "mean_latency_s": float(lats.mean()),
        "p50_latency_s": float(np.percentile(lats, 50)),
        "p99_latency_s": float(np.percentile(lats, 99)),
        "p50_ttft_s": float(np.percentile(ttfts, 50)),
        "p99_ttft_s": float(np.percentile(ttfts, 99)),
        # TTFT breakdown: time queued for a slot, time absorbing the prompt
        # (admission -> first token), and the gap to the first decode-phase
        # tokens — what the chunked budget knob trades against throughput
        "p50_queue_wait_s": float(np.percentile(queue, 50)),
        "p99_queue_wait_s": float(np.percentile(queue, 99)),
        "p50_prefill_s": float(np.percentile(pfill, 50)),
        "p99_prefill_s": float(np.percentile(pfill, 99)),
        "p50_first_decode_gap_s": float(np.percentile(fdec, 50)),
        # worst inter-token stall across requests: in the two-phase engine
        # this is dominated by blocking admission prefills; chunked bounds
        # it at one budget-packed step
        "max_decode_stall_s": float(max(c.max_stall_s for c in completions)),
    }
    if utilization:
        rep.update(utilization)
    return rep
