"""The engine's chunk-pass block counters (``chunk_blocks_live`` /
``chunk_blocks_grid``) and their reader ``chunk_attn_live_share``: counted
against the chunks one tiny run on the CPU dispatched, and read as null
where an engine keeps no such counters."""
from types import SimpleNamespace

import jax
import pytest

from bench import harness, spec
from bench.tests import tiny

SEED = 2 ** 31 + 1013


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """An untraced run of the tiny closed-loop cell, measured to drain."""
    from repro.launch import compile_cache
    keep = compile_cache.enable_compile_cache
    compile_cache.enable_compile_cache = lambda: ""
    before = jax.config.jax_default_matmul_precision
    try:
        root = tiny.make_root(tmp_path_factory.mktemp("root"))
        cell = harness.Cell(spec.benchmark(root), "tiny.closed", SEED, 2.0,
                            trace=False, root=root)
        cell.setup(jax.devices()[:1])
        cell.measure()
    finally:
        compile_cache.enable_compile_cache = keep
        jax.config.update("jax_default_matmul_precision", before)
    return SimpleNamespace(cell=cell, root=root)


def test_chunk_blocks_count_the_dispatched_chunks(run):
    """The engine's block counters against a count by hand from each serve
    program's dispatched chunks: the blocks up to each row's last real
    query, over slots x blocks a row."""
    e, mix = run.cell.cfg["engine"], run.cell.mix
    bs = e["block_size"]
    nb = -(-mix["max_len"] // bs)
    live = grid = 0
    for p in run.cell.win.progs:
        if p.kind == "serve":
            live += sum(-(-(start + n) // bs) for start, n in p.chunks)
            grid += e["slots"] * nb
    c = run.cell.counters
    assert (c["chunk_blocks_live"], c["chunk_blocks_grid"]) == (live, grid)
    assert 0 < live < grid
    read = spec.reader("chunk_attn_live_share", run.root)
    assert read(SimpleNamespace(counters=c)) == pytest.approx(
        100.0 * live / grid)


def test_chunk_attn_live_share_reads_null_without_the_counters():
    """An engine that keeps no block counters reads null, and so does one
    that dispatched no chunk pass."""
    read = spec.reader("chunk_attn_live_share")
    assert read(SimpleNamespace(counters={"positions_computed": 8})) is None
    assert read(SimpleNamespace(counters={"chunk_blocks_live": 0,
                                          "chunk_blocks_grid": 0})) is None
