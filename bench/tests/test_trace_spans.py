"""The engine's host spans joined with the device ops (bench/trace_spans.py):
idle gaps named by what the host was doing, on synthetic intervals, and on
the trace of one tiny traced run on the CPU."""
from types import SimpleNamespace

import jax
import pytest

from bench import harness, spec, trace_reduce
from bench import trace_spans as ts
from bench.tests import tiny

MS = 1_000_000
SEED = 2 ** 31 + 977


def _busy(*spans):
    return [(s * MS, e * MS, name) for name, s, e in spans]


def _spans(*spans):
    return [(name, s * MS, e * MS) for name, s, e in spans]


def test_a_gap_under_a_span_is_named_by_it():
    busy = _busy(("fusion.1", 0, 10), ("copy.87", 20, 30))
    spans = _spans(("engine.tick", 5, 40), ("engine.plan", 12, 19))
    gaps = ts.named_gaps(busy, 0, 30 * MS, spans)
    assert gaps == [("engine.plan after fusion.1", 0.01, "engine.plan")]


def test_a_gap_under_no_span_is_named_so():
    busy = _busy(("fusion.1", 5, 10), ("copy.87", 20, 30))
    spans = _spans(("engine.tick", 0, 11), ("engine.tick", 19, 40))
    gaps = ts.named_gaps(busy, 0, 40 * MS, spans)
    assert [g[0] for g in gaps] == ["no engine span after fusion.1",
                                    "engine.tick after copy.87",
                                    "engine.tick after window start"]
    assert [g[1] for g in gaps] == [0.01, 0.01, 0.005]


def test_a_gap_a_compile_overlaps_is_a_compile():
    busy = _busy(("fusion.1", 0, 10), ("copy.87", 20, 30))
    spans = _spans(("engine.dispatch", 9, 21))
    gaps = ts.named_gaps(busy, 0, 30 * MS, spans, [(15 * MS, 16 * MS)])
    assert gaps[0][0] == "compile after fusion.1"


def test_the_innermost_span_is_the_one_opened_last():
    spans = _spans(("engine.tick", 0, 100), ("engine.harvest", 10, 90),
                   ("engine.finalize", 20, 30))
    assert ts.innermost(spans) == _spans(
        ("engine.tick", 0, 10), ("engine.harvest", 10, 20),
        ("engine.finalize", 20, 30), ("engine.harvest", 30, 90),
        ("engine.tick", 90, 100))


def test_idle_in_and_outside_spans_add_up_to_the_idle_time():
    busy = _busy(("a", 2, 10), ("b", 20, 30), ("c", 31, 45))
    spans = _spans(("engine.tick", 8, 25), ("engine.plan", 12, 14),
                   ("engine.tick", 44, 60))
    inside, outside = ts.split_idle(busy, 0, 50 * MS, ts.innermost(spans))
    assert inside == (10 + 5) * MS            # [10, 20) and [45, 50)
    assert outside == (2 + 1) * MS            # [0, 2) and [30, 31)
    idle = 50 * MS - sum(e - s for s, e, _ in busy)
    assert inside + outside == idle


@pytest.mark.parametrize("scope,copy", [
    ("chunk_pass/layer_scan", True),
    ("decode_microsteps/layer_scan", True),
    ("decode_microsteps/layer_scan/attn", False),
    ("decode_microsteps/layer_scan/attn/kv_write", False),
    ("decode_microsteps/layer_scan/norm", False),
    ("decode_microsteps", False),
    ("chunk_pass/head", False),
])
def test_pool_copies_are_under_layer_scan_alone(scope, copy):
    assert ts.is_pool_copy(scope) is copy


def test_scope_path_keeps_the_serving_scopes_in_order():
    op = ("jit(serve_step)/decode_microsteps/while/body/closed_call/"
          "layer_scan/while/body/closed_call/attn/jit(paged_decode_attention)"
          "/transpose:")
    assert ts.scope_path(op) == "decode_microsteps/layer_scan/attn"


# -- one tiny traced run ------------------------------------------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced run of the tiny closed-loop cell, its per-layer readings
    taken (which extends the reduced trace), the trace file kept."""
    from repro.launch import compile_cache
    keep = compile_cache.enable_compile_cache
    compile_cache.enable_compile_cache = lambda: ""
    before = jax.config.jax_default_matmul_precision
    try:
        root = tiny.make_root(tmp_path_factory.mktemp("root"))
        cell = harness.Cell(spec.benchmark(root), "tiny.closed", SEED, 2.0,
                            trace=True, root=root)
        cell.setup(jax.devices()[:1])
        cell.measure()
        layers = cell.per_layer({"bf16_flops": 197e12,
                                 "hbm_bytes_per_s": 819e9})
    finally:
        compile_cache.enable_compile_cache = keep
        jax.config.update("jax_default_matmul_precision", before)
    return SimpleNamespace(cell=cell, layers=layers)


def test_the_reduced_trace_keeps_its_keys(traced):
    red = traced.cell.reduced
    fresh = trace_reduce.reduce(traced.cell.trace_path)
    for key in fresh:
        if key != "idle_gaps":
            assert red[key] == fresh[key], key
    assert [g[1] for g in red["idle_gaps"]] == \
        [g[1] for g in fresh["idle_gaps"]]
    whats = {"compile", ts.NO_SPAN} | set(red["host_spans"])
    assert all(any(g[0].startswith(w + " after ") for w in whats)
               for g in red["idle_gaps"])


def test_the_host_plane_holds_the_engine_spans_nested(traced):
    pd = trace_reduce._load(traced.cell.trace_path)
    lo, hi = trace_reduce._window(pd)
    spans, _ = ts._host_events(pd, lo, hi)
    names = {n for n, _, _ in spans}
    assert {"engine.tick", "engine.admit", "engine.plan", "engine.dispatch",
            "engine.overlap", "engine.harvest",
            "engine.finalize"} <= names
    ticks = [(s, e) for n, s, e in spans if n == "engine.tick"]
    harvests = [(s, e) for n, s, e in spans if n == "engine.harvest"]
    inside = lambda s, e, outer: any(a <= s and e <= b for a, b in outer)
    for n, s, e in spans:
        if n == "engine.finalize":
            assert inside(s, e, harvests)
        elif n != "engine.tick" and e < hi:
            assert inside(s, e, ticks), n


def test_the_programs_carry_their_names(traced):
    mods = traced.cell.reduced["modules_by_name"]
    assert {"jit_serve_step", "jit_decode_program"} <= set(mods)
    kinds = [p.kind for p in traced.cell.win.progs]
    assert mods["jit_serve_step"]["count"] == kinds.count("serve")
    assert mods["jit_decode_program"]["count"] == kinds.count("decode")
    assert traced.layers["decode_program_ms"]["value"] > 0


def test_engine_token_fill_is_step_token_fill_arithmetic(traced):
    """The engine's counters over the same programs the window recorded,
    under step_token_fill's definition (every program, ready or not)."""
    e = traced.cell.cfg["engine"]
    B, W, K = e["slots"], e["chunk_width"], e["decode_steps"]
    useful = computed = 0
    for p in traced.cell.win.progs:
        useful += sum(n for _, n in p.chunks) + sum(n for _, n in p.decode)
        computed += B * ((W + K) if p.kind == "serve" else K)
    c = traced.cell.counters
    assert (c["positions_useful"], c["positions_computed"]) == \
        (useful, computed)
    assert traced.layers["engine_token_fill"]["value"] == pytest.approx(
        100.0 * useful / computed)


def test_idle_host_bound_and_outside_add_up_to_device_idle(traced):
    red, layers = traced.cell.reduced, traced.layers
    outside = 100.0 * red["idle_outside_span_s"] / red["window_s"]
    assert layers["idle_host_bound_share"]["value"] + outside == \
        pytest.approx(layers["device_idle_share"]["value"], abs=1e-6)
