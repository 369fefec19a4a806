"""One measured window: drive the engine with a traffic mix for a fixed
number of seconds, and the arithmetic of the end-to-end metrics.

The engine (``repro.serve.ServeEngine``) is driven tick by tick through its
public ``tick_dispatch`` / ``tick_commit`` pair. Its KV backend's two
program entries, ``serve_step`` and ``decode``, are wrapped on the instance
so that each program run is recorded with the work its rows really do. The
engine does not wait for a program before it plans the next one (the
``ret_async`` linkage), so a watcher thread waits on each program's output
and stamps when it was ready: a token counts as delivered then.

The window opens at ``t0`` and closes at ``end``: no program is dispatched
after ``end``. A metric counts what was delivered by ``end``:

- ``tokens_per_s``: tokens of programs ready by ``end``, over the window,
  unfinished requests included;
- ``ttft_p95_s``: over every request due by ``end``, first token's delivery
  minus the time it was due; a request with no first token by ``end``
  counts with the wait so far;
- ``tpot_p95_ms``: over requests finished by ``end``, (last delivery -
  first delivery) / (tokens - 1);
- ``queue_wait_p95_s``: admission minus due, censored at ``end`` likewise.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Record:
    """One request as the window saw it. Times are host-clock seconds."""
    rid: int
    due: float
    prompt_len: int
    max_new: int
    admit: Optional[float] = None
    first_prog: Optional[int] = None      # program that made token 1
    last_prog: Optional[int] = None       # program that finished it
    tokens: Optional[np.ndarray] = None   # served tokens, once finished


@dataclasses.dataclass
class Program:
    """One program run: its kind, the tokens it delivers, and its rows'
    real attention work: ``chunks`` as (start, n) per row of the chunk pass,
    ``decode`` as (position, useful microsteps) per decoding row."""
    kind: str
    dispatched: float
    tokens: int
    chunks: List[tuple]
    decode: List[tuple]
    ready: Optional[float] = None
    emitted: List[int] = dataclasses.field(default_factory=list)


def p95(xs) -> Optional[float]:
    xs = list(xs)
    return float(np.percentile(xs, 95)) if xs else None


def end_to_end(records: List[Record], progs: List[Program], t0: float,
               end: float) -> Dict[str, Optional[float]]:
    """The window's end-to-end numbers (None where nothing was measured)."""
    ready = lambda i: progs[i].ready if i is not None else None
    delivered = sum(p.tokens for p in progs
                    if p.ready is not None and p.ready <= end)
    due = [r for r in records if r.due <= end]
    ttft = []
    queue_wait = []
    for r in due:
        f = ready(r.first_prog)
        ttft.append((f if f is not None and f <= end else end) - r.due)
        a = r.admit if r.admit is not None else end
        queue_wait.append(min(a, end) - r.due)
    tpot = []
    for r in records:
        if r.tokens is None or len(r.tokens) < 2:
            continue
        f, last = ready(r.first_prog), ready(r.last_prog)
        if f is None or last is None or last > end:
            continue
        tpot.append((last - f) / (len(r.tokens) - 1) * 1e3)
    return {
        "tokens_per_s": delivered / (end - t0),
        "ttft_p95_s": p95(ttft),
        "tpot_p95_ms": p95(tpot),
        "queue_wait_p95_s": p95(queue_wait),
        "attempted": len(due),
        "finished": len(tpot),
    }


class _Watcher(threading.Thread):
    """Stamps the host time at which each program's output is ready."""

    def __init__(self, progs: List[Program], clock: Callable[[], float]):
        super().__init__(daemon=True)
        self.progs, self.clock = progs, clock
        self.q: "queue.Queue" = queue.Queue()
        self.error: Optional[BaseException] = None

    def run(self):
        while True:
            item = self.q.get()
            if item is None:
                return
            i, out = item
            try:
                out.block_until_ready()
            except BaseException as e:       # surfaced by the window
                self.error = e
            self.progs[i].ready = self.clock()


class Window:
    """Drive ``eng`` with ``reqs`` (``bench.traffic.Requests``) as ``mix``
    says, for ``seconds``."""

    def __init__(self, eng, reqs, mix: dict, seconds: float,
                 clock: Callable[[], float] = time.monotonic):
        self.eng, self.reqs, self.mix = eng, reqs, mix
        self.seconds, self.clock = seconds, clock
        self.records: Dict[int, Record] = {}
        self.progs: List[Program] = []
        self.late: List[float] = []        # open loop: enqueue - due
        self.preemptions = 0
        self._wrap(eng)

    # -- instrumenting the backend's two program entries ------------------
    def _wrap(self, eng):
        kv, K = eng.kv, eng.tokens_per_program
        serve_step, decode = kv.serve_step, kv.decode

        def take(s):
            return min(K, eng.sched.active[s].remaining)

        def serve(toks, clen, start, reset, emit0, dec_mask, dec_tok):
            clen_h, start_h = np.asarray(clen), np.asarray(start)
            emit_h, dec_h = np.asarray(emit0), np.asarray(dec_mask)
            chunks = [(int(start_h[s]), int(clen_h[s]))
                      for s in range(len(clen_h)) if clen_h[s] > 0]
            dec = [(int(start_h[s]), take(s))
                   for s in range(len(dec_h)) if dec_h[s]]
            emitted = [eng.sched.active[s].req.rid
                       for s in range(len(emit_h)) if emit_h[s]]
            out = serve_step(toks, clen, start, reset, emit0, dec_mask,
                             dec_tok)
            self._record("serve", len(emitted) + sum(n for _, n in dec),
                         chunks, dec, emitted, out[1])
            return out

        def dec_only(next_tokens):
            dec = [(int(kv.pos_host[s]), take(s))
                   for s in sorted(eng.sched.active)]
            out = decode(next_tokens)
            self._record("decode", sum(n for _, n in dec), [], dec, [], out)
            return out

        kv.serve_step, kv.decode = serve, dec_only

    def _record(self, kind, tokens, chunks, dec, emitted, out):
        i = len(self.progs)
        self.progs.append(Program(kind, self.clock(), tokens, chunks, dec,
                                  emitted=emitted))
        for rid in emitted:
            r = self.records[rid]
            if r.first_prog is None:
                r.first_prog = i
        self._watcher.q.put((i, out))

    # -- the loop ----------------------------------------------------------
    def _issue(self, req, due):
        from repro.serve.scheduler import Request
        self.records[req.rid] = Record(req.rid, due, len(req.prompt),
                                       req.max_new)
        self.eng.sched.enqueue(Request(rid=req.rid, prompt=req.prompt,
                                       max_new_tokens=req.max_new,
                                       arrival_s=due - self.t0))

    def run(self, on_open: Callable[[], None] = lambda: None) -> None:
        eng, clock = self.eng, self.clock
        self._watcher = _Watcher(self.progs, clock)
        self._watcher.start()
        closed = self.mix["loop"] == "closed"
        clients = int(self.mix.get("clients", 0))
        outstanding, nxt = 0, 0
        pre0 = eng.preemptions
        on_open()
        self.t0 = t0 = clock()
        self.end = end = t0 + self.seconds
        rel = lambda: clock() - t0
        while True:
            now = clock()
            if now >= end:
                break
            if closed:
                while outstanding < clients:
                    self._issue(self.reqs[nxt], now)
                    nxt += 1
                    outstanding += 1
            else:
                while t0 + self.reqs[nxt].arrival_s <= now:
                    due = t0 + self.reqs[nxt].arrival_s
                    self._issue(self.reqs[nxt], due)
                    self.late.append(now - due)
                    nxt += 1
            if not eng.sched.active and not eng.sched.n_queued:
                wake = (t0 + self.reqs[nxt].arrival_s) if not closed else now
                time.sleep(max(0.0, min(wake, end) - clock()))
                continue
            done = eng.tick_commit(eng.tick_dispatch(rel), rel)
            last = len(self.progs) - 1
            for st in eng.sched.active.values():
                r = self.records[st.req.rid]
                if r.admit is None:
                    r.admit = t0 + st.admit_s
            for c in done:
                r = self.records[c.rid]
                if r.admit is None:
                    r.admit = t0 + c.admit_s
                r.tokens = np.asarray(c.tokens)
                r.last_prog = last
                outstanding -= 1
            if self._watcher.error is not None:
                raise self._watcher.error
        self.preemptions = eng.preemptions - pre0

    def drain(self) -> float:
        """Wait for every dispatched program; returns when the last was
        ready (or ``end``, whichever is later)."""
        self._watcher.q.put(None)
        self._watcher.join()
        if self._watcher.error is not None:
            raise self._watcher.error
        last = max((p.ready for p in self.progs), default=self.end)
        return max(last, self.end)

    def metrics(self) -> Dict[str, Optional[float]]:
        return end_to_end(list(self.records.values()), self.progs, self.t0,
                          self.end)
