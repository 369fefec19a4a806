"""Telemetry: trace schema, span state machine, metrics, zero-cost-off.

Three load-bearing claims:

* every trace event type survives the JSONL and Chrome-trace exports
  (``load_trace`` reconstructs the raw stream from either file);
* a request's lifecycle spans are exactly the scheduler's legal
  transitions — including swap and mid-prefill preemption — and the
  span-derived TTFT equals the engine's ``Completion`` timestamps;
* a disabled recorder is a no-op: token streams and ``serve_report``
  bit-identical with telemetry on vs off.
"""
import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import MetricWriter, preset
from repro.models import ModelOptions, init_params
from repro.serve import (EVENT_SCHEMA, NULL_TELEMETRY, SPAN_TRANSITIONS,
                         MetricsRegistry, Request, ServeEngine, Telemetry,
                         TraceRecorder, load_trace, phase_breakdown,
                         serve_report, span_latencies, synthetic_requests,
                         validate_events, validate_spans)

CFG = get_config("tinyllama-1.1b").smoke()
OPTS = ModelOptions(attn_impl="ref", scan_impl="ref", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _traced_engine(params, tel, **kw):
    lk = preset("nss_shortcut")
    opts = lk.model_options(OPTS, on_tpu=False)
    base = dict(n_slots=2, max_len=32, kv="paged", block_size=8)
    base.update(kw)
    return ServeEngine(CFG, params, opts, lk, telemetry=tel, **base)


# ---------------------------------------------------------------------------
# TraceRecorder: schema + export round-trip
# ---------------------------------------------------------------------------

def _one_of_everything() -> TraceRecorder:
    """A recorder holding at least one event of every schema type."""
    rec = TraceRecorder()
    rec.span(7, "queued", 0.0)
    rec.emit("admit", 0.1, rid=7, slot=0, prompt_len=16)
    rec.span(7, "prefilling", 0.1)
    rec.step("serve_chunk", 1, 0.2, 0.01, 0.02, 0.03, 0.04)
    rec.emit("host_span", 0.2, dur=0.01, name="engine.plan")
    rec.emit("prefill_chunk", 0.2, slot=0, rid=7, start=0, len=6)
    rec.emit("pack", 0.2, budget=8, decode_tokens=2, granted=6)
    rec.emit("decode_microsteps", 0.3, slots=2, k=4)
    rec.span(7, "decoding", 0.35)
    rec.emit("verify_window", 0.4, slot=0, rid=7, drafted=4, accepted=2)
    rec.emit("swap_out", 0.5, slot=1, blocks=3, bytes=3072)
    rec.emit("preempt", 0.5, rid=9, slot=1, mode="swap")
    rec.emit("swap_in", 0.6, slot=1, blocks=3, bytes=3072)
    rec.emit("swap_fail", 0.6, slot=1, blocks=3, op="swap_out")
    rec.emit("swap_stream", 0.65, transfers=2, blocks=5, bytes=5120)
    rec.emit("prefetch", 0.65, blocks=3, status="issued")
    rec.emit("overlap", 0.65, kind="drain", hidden_s=0.002)
    rec.emit("demote", 0.7, blocks=1, bytes=1024)
    rec.emit("handoff", 0.75, rid=7, src=0, dst=1, blocks=3, bytes=3072)
    rec.emit("promote", 0.8, blocks=1, bytes=1024)
    rec.emit("budget", 0.9, old=8, new=12)
    rec.emit("complete", 1.0, rid=7, tokens=8, ttft_s=0.35)
    rec.span(7, "done", 1.0)
    return rec


def test_every_event_type_round_trips(tmp_path):
    rec = _one_of_everything()
    types = {e["type"] for e in rec.events}
    assert types == set(EVENT_SCHEMA), "fixture must cover the whole schema"
    validate_events(rec.events)

    jl, ch = tmp_path / "t.jsonl", tmp_path / "t.json"
    assert rec.export_jsonl(str(jl)) == len(rec.events)
    assert rec.export_chrome(str(ch)) == len(rec.events)

    # JSONL is the exact raw stream
    back = load_trace(str(jl))
    assert back == rec.events
    # Chrome reconstructs every event (µs timestamps: compare to 1e-9 s)
    back = load_trace(str(ch))
    assert {e["type"] for e in back} == types
    assert len(back) == len(rec.events)
    for raw, got in zip(sorted(rec.events, key=lambda e: e["ts"]), back):
        assert got["type"] == raw["type"]
        assert abs(got["ts"] - raw["ts"]) < 1e-9
        if raw["type"] != "span":
            assert got["args"] == raw["args"]
    validate_events(back)


def test_chrome_trace_is_wellformed(tmp_path):
    """The export is the Chrome trace-event format Perfetto loads: one
    traceEvents list, X duration events on the engine process, b/e async
    pairs per request span, M process-name metadata."""
    rec = _one_of_everything()
    doc = rec.chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert all(set(e) >= {"ph", "pid"} for e in evs)
    phases = {e["ph"] for e in evs}
    assert phases == {"M", "X", "b", "e", "i"}
    names = {e["name"] for e in evs if e["ph"] == "M"}
    assert names == {"process_name"}
    # every async begin has a matching end with the same (cat, id, name)
    opens = [(e["cat"], e["id"], e["name"]) for e in evs if e["ph"] == "b"]
    closes = [(e["cat"], e["id"], e["name"]) for e in evs if e["ph"] == "e"]
    assert sorted(opens) == sorted(closes)
    # duration events carry µs ts/dur and nest under the engine pid
    for e in evs:
        if e["ph"] == "X":
            assert e["pid"] == 1 and e["dur"] >= 0
    json.dumps(doc)        # serializable as-is


def test_validate_events_rejects_malformed():
    with pytest.raises(ValueError, match="unknown type"):
        validate_events([{"type": "warp_core", "ts": 0.0, "args": {}}])
    with pytest.raises(ValueError, match="missing args"):
        validate_events([{"type": "swap_out", "ts": 0.0,
                          "args": {"slot": 1}}])
    with pytest.raises(ValueError, match="bad ts"):
        validate_events([{"type": "pack", "ts": None,
                          "args": {"budget": 1, "decode_tokens": 0,
                                   "granted": 1}}])
    with pytest.raises(ValueError, match="bad span state"):
        validate_events([{"type": "span", "rid": 0, "state": "limbo",
                          "ts": 0.0}])


# ---------------------------------------------------------------------------
# Span state machine
# ---------------------------------------------------------------------------

def _span_stream(rid, states):
    return [{"type": "span", "rid": rid, "state": s, "ts": float(i)}
            for i, s in enumerate(states)]


def test_span_transition_map_accepts_legal_paths():
    legal = [
        ["queued", "prefilling", "decoding", "done"],
        ["queued", "prefilling", "done"],                    # 1-token budget
        ["queued", "prefilling", "preempted", "queued",      # mid-prefill
         "prefilling", "decoding", "done"],                  # recompute
        ["queued", "prefilling", "swapped", "prefilling",    # mid-prefill
         "decoding", "done"],                                # swap
        ["queued", "prefilling", "decoding", "swapped",
         "decoding", "done"],
        ["queued", "prefilling", "decoding", "swapped",      # failed swap-in
         "queued", "prefilling", "decoding", "done"],        # falls back
    ]
    for i, path in enumerate(legal):
        assert validate_spans(_span_stream(i, path)) == {i: path}


def test_span_transition_map_rejects_illegal_paths():
    illegal = [
        ["prefilling"],                          # must start queued
        ["queued", "decoding"],                  # skipped prefill
        ["queued", "prefilling", "decoding", "done", "decoding"],  # revived
        ["queued", "swapped"],                   # swap needs a slot
        ["queued", "prefilling", "preempted", "decoding"],  # must requeue
    ]
    for path in illegal:
        with pytest.raises(ValueError, match="illegal span transition"):
            validate_spans(_span_stream(0, path))


def test_span_transitions_match_exhaustively():
    """Every pair NOT in SPAN_TRANSITIONS is rejected, every pair in it is
    accepted — the validator IS the documented state machine."""
    states = list(SPAN_TRANSITIONS)
    for cur in states:
        prefix = [] if cur is None else ["queued", "prefilling",
                                         "decoding", "swapped", "preempted",
                                         "done"]
        # build a legal prefix ending at `cur` by brute force
        if cur is not None:
            found = None
            def dfs(path):
                if path and path[-1] == cur:
                    return path
                last = path[-1] if path else None
                for nxt in SPAN_TRANSITIONS[last]:
                    if nxt in path and nxt != "queued":
                        continue
                    r = dfs(path + [nxt])
                    if r:
                        return r
                return None
            found = dfs([])
            assert found, f"no legal path reaches {cur}"
            prefix = found
        for nxt in ["queued", "prefilling", "decoding", "swapped",
                    "preempted", "done"]:
            stream = _span_stream(0, (prefix if cur else []) + [nxt])
            if nxt in SPAN_TRANSITIONS[cur]:
                validate_spans(stream)
            else:
                with pytest.raises(ValueError):
                    validate_spans(stream)


# ---------------------------------------------------------------------------
# Engine integration: real traces obey the machine, TTFT matches
# ---------------------------------------------------------------------------

def test_engine_trace_spans_and_ttft(params):
    tel = Telemetry()
    eng = _traced_engine(params, tel, chunked=True, chunk_budget=6)
    reqs = synthetic_requests(4, prompt_len=16, max_new_tokens=8,
                              vocab_size=CFG.vocab_size, seed=0,
                              shared_prefix_len=8)
    comps, wall = eng.run(reqs, load="closed")
    evs = tel.trace.events
    validate_events(evs)
    paths = validate_spans(evs)
    assert set(paths) == {r.rid for r in reqs}
    assert all(p[-1] == "done" for p in paths.values())
    # span-derived TTFT/latency == the engine's own Completion timestamps
    lat = span_latencies(evs)
    for c in comps:
        assert lat[c.rid]["ttft_s"] == pytest.approx(c.ttft_s, abs=1e-12)
        assert lat[c.rid]["latency_s"] == pytest.approx(c.latency_s,
                                                        abs=1e-12)
    # the step-phase breakdown covers every program the engine ran
    pb = phase_breakdown(evs)
    assert pb["all"]["steps"] == eng.programs_run
    assert pb["all"]["total_s"] > 0


def test_engine_trace_swap_preemption_spans(params):
    """Pool pressure with swap preemption (the paged_smoke geometry): the
    trace must show swapped spans and legal resume transitions, including
    mid-prefill victims under chunked admission."""
    lk = dataclasses.replace(preset("nss_shortcut"), decode_steps=4)
    opts = lk.model_options(OPTS, on_tpu=False)
    reqs = synthetic_requests(4, prompt_len=8, max_new_tokens=12,
                              vocab_size=CFG.vocab_size, seed=0)
    tel = Telemetry()
    eng = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=32,
                      kv="paged", block_size=8, num_blocks=4,
                      preempt="swap", chunked=True, chunk_budget=6,
                      telemetry=tel)
    eng.run(reqs, load="closed")
    assert eng.swap_preemptions > 0, "geometry must force swap preemption"
    evs = tel.trace.events
    validate_events(evs)
    paths = validate_spans(evs)
    assert any("swapped" in p for p in paths.values())
    # block movement shows up with real sizes
    outs = [e for e in evs if e["type"] == "swap_out"]
    ins = [e for e in evs if e["type"] == "swap_in"]
    assert outs and ins
    assert all(e["args"]["blocks"] > 0 and e["args"]["bytes"] > 0
               for e in outs + ins)
    assert tel.metrics.snapshot()['kv_tier_blocks_total{op="swap_out"}'] \
        == eng.kv.swap_out_blocks
    # the async runtime leaves its own trail: every deferred device→host
    # transfer is completed by a drain (swap_stream), and the resume head
    # gets its host→device copy staged ahead of the swap-in (prefetch)
    streams = [e for e in evs if e["type"] == "swap_stream"]
    assert streams and all(e["args"]["transfers"] > 0 for e in streams)
    assert sum(e["args"]["transfers"] for e in streams) \
        == eng.kv.stream_transfers
    pf = [e for e in evs if e["type"] == "prefetch"]
    assert any(e["args"]["status"] == "issued" for e in pf)
    assert eng.kv.prefetch_hits + eng.kv.prefetch_cancels \
        <= eng.kv.prefetch_issued


def test_swap_fail_event_and_counter(params):
    """A swap_out that dies mid-chain (host tier too small for the victim's
    chain) must emit a swap_fail event and bump the failure counter — the
    silent None return used to make failed swaps indistinguishable from
    a recompute-policy preemption in every trace and metric."""
    lk = dataclasses.replace(preset("nss_shortcut"), decode_steps=4)
    opts = lk.model_options(OPTS, on_tpu=False)
    # 16-token prompts at block_size=8 with a budget that grants the whole
    # prompt in one chunk: every victim chain spans >= 2 blocks, so the
    # 1-block host tier allocates the first block and dies on the second —
    # the exact mid-chain rollback the event reports
    reqs = synthetic_requests(4, prompt_len=16, max_new_tokens=12,
                              vocab_size=CFG.vocab_size, seed=0)
    tel = Telemetry()
    eng = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=32,
                      kv="paged", block_size=8, num_blocks=5,
                      preempt="swap", host_blocks=1, chunked=True,
                      chunk_budget=24, telemetry=tel)
    eng.run(reqs, load="closed")
    fails = [e for e in tel.trace.events if e["type"] == "swap_fail"]
    assert fails, "a 1-block host tier must fail a multi-block swap_out"
    assert all(e["args"]["op"] == "swap_out" and e["args"]["blocks"] > 0
               for e in fails)
    assert eng.kv.swap_fails == len(fails)
    assert tel.metrics.snapshot()['kv_swap_failures_total{op="swap_out"}'] \
        == len(fails)
    # failed swaps degrade to recompute preemption; spans stay legal
    validate_spans(tel.trace.events)


def test_engine_trace_recompute_preemption_spans(params):
    lk = dataclasses.replace(preset("nss_shortcut"), decode_steps=4)
    opts = lk.model_options(OPTS, on_tpu=False)
    reqs = synthetic_requests(4, prompt_len=8, max_new_tokens=12,
                              vocab_size=CFG.vocab_size, seed=0)
    tel = Telemetry()
    eng = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=32,
                      kv="paged", block_size=8, num_blocks=5,
                      preempt="recompute", telemetry=tel)
    eng.run(reqs, load="closed")
    assert eng.preemptions > 0
    paths = validate_spans(tel.trace.events)
    assert any("preempted" in p for p in paths.values())


def test_spec_decode_verify_windows(params):
    """Speculative engines emit verify_window events whose accept counts
    sum to the engine's own counters."""
    lk = dataclasses.replace(preset("nss_shortcut"), decode_steps=3)
    opts = lk.model_options(OPTS, on_tpu=False)
    rng = np.random.default_rng(5)
    reqs = []
    for i in range(4):
        core = rng.integers(0, CFG.vocab_size, 6, dtype=np.int32)
        reqs.append(Request(rid=i, prompt=np.tile(core, 3),
                            max_new_tokens=14))
    tel = Telemetry()
    eng = ServeEngine(CFG, params, opts, lk, n_slots=2, max_len=48,
                      kv="paged", block_size=8, spec_decode="ngram",
                      spec_width=6, telemetry=tel)
    eng.run(reqs, load="closed")
    assert eng.spec_steps > 0
    wins = [e for e in tel.trace.events if e["type"] == "verify_window"]
    assert wins
    assert sum(w["args"]["drafted"] for w in wins) == eng.spec_draft_tokens
    assert sum(w["args"]["accepted"] for w in wins) \
        == eng.spec_accepted_tokens
    validate_spans(tel.trace.events)


def _counting_clock(step_s: float = 1e-3):
    """A clock that advances ``step_s`` at every reading."""
    n = iter(range(1 << 40))
    return lambda: next(n) * step_s


def test_host_spans_nest_in_the_tick_and_phases_add_up(params):
    """Every engine_step's phases add up to the step; under ret_async its
    device phase is exactly the engine.finalize spans inside it (the only
    blocking waits on results), so zero on a step that finished nothing;
    each phase span lies inside an engine.tick span."""
    tel = Telemetry()
    eng = _traced_engine(params, tel, chunked=True, chunk_budget=6)
    assert eng.linkage.ret_async
    reqs = synthetic_requests(4, prompt_len=16, max_new_tokens=8,
                              vocab_size=CFG.vocab_size, seed=0)
    eng.run(reqs, load="closed", clock=_counting_clock())
    evs = tel.trace.events
    validate_events(evs)
    spans = [e for e in evs if e["type"] == "host_span"]
    by = lambda n: [(e["ts"], e["ts"] + e.get("dur", 0.0)) for e in spans
                    if e["args"]["name"] == n]
    ticks, finals = by("engine.tick"), by("engine.finalize")
    assert len(ticks) == len(by("engine.admit")) >= eng.programs_run
    for name in ("engine.admit", "engine.plan", "engine.dispatch",
                 "engine.overlap", "engine.harvest", "engine.finalize"):
        assert by(name), name
        for s, e in by(name):
            assert any(a <= s and e <= b for a, b in ticks), name
    steps = [e for e in evs if e["type"] == "engine_step"]
    assert len(steps) == eng.programs_run
    waited = 0
    for st in steps:
        a = st["args"]
        phases = a["pack_s"] + a["dispatch_s"] + a["device_s"] + a["host_s"]
        assert phases == pytest.approx(st["dur"], abs=1e-9)
        assert min(a["pack_s"], a["dispatch_s"], a["device_s"],
                   a["host_s"]) >= -1e-12
        lo, hi = st["ts"], st["ts"] + st["dur"]
        inside = sum(e - s for s, e in finals if lo <= s and e <= hi)
        assert a["device_s"] == pytest.approx(inside, abs=1e-9)
        waited += a["device_s"] > 0
    assert 0 < waited < len(steps)


def test_a_profiler_session_leaves_token_streams_unchanged(params, tmp_path):
    """The engine's spans are profiler annotations even with telemetry off
    (NULL_TELEMETRY): a profiler session records them, on the host plane,
    and serving under one gives the same tokens."""
    import glob
    reqs = synthetic_requests(4, prompt_len=16, max_new_tokens=8,
                              vocab_size=CFG.vocab_size, seed=0,
                              shared_prefix_len=8)

    def serve():
        eng = _traced_engine(params, None, chunked=True, chunk_budget=6)
        assert eng.tel is NULL_TELEMETRY
        comps, _ = eng.run(reqs, load="closed")
        return {c.rid: c.tokens.tolist() for c in comps}

    off = serve()
    with jax.profiler.trace(str(tmp_path)):
        on = serve()
    assert on == off
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    names = {e.name.split("#")[0] for plane in pd.planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for e in line.events}
    assert {"engine.tick", "engine.plan", "engine.dispatch",
            "engine.harvest", "kv.publish"} <= names


# ---------------------------------------------------------------------------
# Zero-cost disabled: identical streams, identical report
# ---------------------------------------------------------------------------

def test_disabled_recorder_is_identity(params):
    """With telemetry off (the default NULL_TELEMETRY) and a frozen clock,
    the whole serve_report — tokens, counters, timings — is bit-identical
    to the traced run: recording must never perturb scheduling."""
    reqs = synthetic_requests(5, prompt_len=16, max_new_tokens=8,
                              vocab_size=CFG.vocab_size, seed=0,
                              shared_prefix_len=8)
    frozen = lambda: 0.0
    reports = []
    for tel in (None, Telemetry()):
        eng = _traced_engine(params, tel, chunked=True, chunk_budget=6)
        comps, wall = eng.run(reqs, load="closed", clock=frozen)
        rep = serve_report(comps, wall, utilization=eng.utilization())
        rep["_streams"] = {c.rid: c.tokens.tolist() for c in comps}
        reports.append(rep)
    assert reports[0] == reports[1]


def test_null_telemetry_never_reads_a_clock():
    assert NULL_TELEMETRY.now() == 0.0
    NULL_TELEMETRY.set_clock(lambda: (_ for _ in ()).throw(
        AssertionError("disabled telemetry must not adopt a clock")))
    assert NULL_TELEMETRY.now() == 0.0
    # every hook is a no-op
    NULL_TELEMETRY.step("decode", 0, 0, 0, 0, 0, 0)
    NULL_TELEMETRY.state(0, "queued", 0.0)
    NULL_TELEMETRY.swap_out(0, 1, 1024)
    NULL_TELEMETRY.reset()
    NULL_TELEMETRY.close()
    assert NULL_TELEMETRY.trace is None and NULL_TELEMETRY.metrics is None


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

def test_registry_families_and_exposition():
    reg = MetricsRegistry(const_labels={"backend": "paged"})
    c = reg.counter("requests_total", "requests", labels=("kind",))
    c.labels(kind="ok").inc()
    c.labels(kind="ok").inc(2)
    c.labels(kind="err").inc()
    g = reg.gauge("queue_depth", "waiting")
    g.set(3)
    h = reg.histogram("ttft_seconds", "ttft", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 20.0):
        h.observe(v)

    text = reg.render()
    assert '# TYPE requests_total counter' in text
    assert 'requests_total{kind="ok",backend="paged"} 3.0' in text
    assert 'queue_depth{backend="paged"} 3.0' in text
    assert 'ttft_seconds_bucket{backend="paged",le="0.1"} 1' in text
    assert 'ttft_seconds_bucket{backend="paged",le="1.0"} 3' in text
    assert 'ttft_seconds_bucket{backend="paged",le="+Inf"} 4' in text
    assert 'ttft_seconds_count{backend="paged"} 4' in text

    snap = reg.snapshot()
    assert snap['requests_total{kind="ok"}'] == 3.0
    assert snap["ttft_seconds_count"] == 4.0
    assert reg.quantile("ttft_seconds", 0.5) == 1.0

    reg.reset()
    assert reg.snapshot()['requests_total{kind="ok"}'] == 0.0
    assert reg.snapshot()["ttft_seconds_count"] == 0.0


def test_registry_guards():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="increasing"):
        reg.histogram("h", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError, match="bad metric name"):
        reg.counter("nope nope")
    c = reg.counter("ok_total", labels=("a",))
    with pytest.raises(ValueError, match="expected labels"):
        c.labels(b="x")
    with pytest.raises(ValueError, match="only go up"):
        c.labels(a="x").inc(-1)
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("ok_total")
    g = reg.gauge("depth")
    with pytest.raises(TypeError):
        g.inc()


def test_periodic_log_line():
    lines = []
    tel = Telemetry(trace=False, log_interval=1.0, log_fn=lines.append)
    t = [0.0]
    tel.set_clock(lambda: t[0])
    tel.step("decode", 0, 0.0, 0, 0, 0, 0)        # first: always logs
    tel.step("decode", 1, 0.0, 0, 0, 0, 0)        # same instant: suppressed
    t[0] = 1.5
    tel.step("decode", 2, 0.0, 0, 0, 0, 0)        # past interval: logs
    assert len(lines) == 2
    assert "engine_steps_total" in lines[0]


# ---------------------------------------------------------------------------
# MetricWriter as the registry sink (the co-process contract)
# ---------------------------------------------------------------------------

def test_metric_writer_consumes_registry_snapshots():
    got = []
    writer = MetricWriter(lambda step, m: got.append((step, m)))
    reg = MetricsRegistry()
    reg.counter("steps_total").inc(3)
    writer.submit(7, reg.snapshot())
    writer.close()
    assert got == [(7, {"steps_total": 3.0})]


def test_metric_writer_sink_errors_still_reraise():
    """The unification must keep the co-process error contract: a crashed
    sink fed registry snapshots re-raises on the next submit or close."""
    def sink(step, metrics):
        raise RuntimeError("sink crashed")
    writer = MetricWriter(sink)
    reg = MetricsRegistry()
    writer.submit(0, reg.snapshot())
    with pytest.raises(RuntimeError, match="sink crashed"):
        writer.close()


def test_telemetry_pushes_snapshots_to_sink():
    got = []
    writer = MetricWriter(lambda step, m: got.append((step, m)))
    tel = Telemetry(trace=False, sink=writer)
    tel.set_clock(lambda: 0.0)
    tel.step("decode", 3, 0.0, 0, 0, 0, 0)
    tel.close()
    assert len(got) == 1
    assert got[0][0] == 3
    assert got[0][1]['engine_steps_total{kind="decode"}'] == 1.0


# ---------------------------------------------------------------------------
# serve_report edge cases
# ---------------------------------------------------------------------------

def test_serve_report_zero_completions():
    rep = serve_report([], 2.0, utilization={"programs_run": 0})
    assert rep["requests"] == 0
    assert rep["total_tokens"] == 0
    assert rep["tokens_per_s"] == 0.0
    assert rep["programs_run"] == 0
    assert "p99_ttft_s" not in rep          # omitted, not NaN


def test_serve_report_single_completion_percentiles(params):
    """n=1: every percentile is the single observation (documented small-
    sample semantics: exact order statistics, p99 == max for n < 100)."""
    reqs = synthetic_requests(1, prompt_len=8, max_new_tokens=4,
                              vocab_size=CFG.vocab_size, seed=0)
    eng = _traced_engine(params, None)
    comps, wall = eng.run(reqs, load="closed")
    rep = serve_report(comps, wall)
    c = comps[0]
    assert rep["p50_ttft_s"] == rep["p99_ttft_s"] == c.ttft_s
    assert rep["p99_latency_s"] == c.latency_s
