"""A configuration brings its model description as a file,
``bench/models/<name>.py``: the harness takes its architecture, its
reference and its work counts from that file alone. On the CPU at a tiny
size."""
import dataclasses
import shutil

import jax
import pytest

from bench import flops, harness, reference, spec, trace_reduce
from bench.tests import tiny

SEED = 2 ** 31 + 1515
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}

#: the dense description, its reference one layer short
ONE_SHORT = '''
from bench import reference, spec

_dense = spec.model({})
arch_config, sizes, work = _dense.arch_config, _dense.sizes, _dense.work


def logits(cfg, seed, seqs, rows, mode="f32"):
    s = sizes(cfg)
    return reference.forward(s, seed, seqs, rows, mode,
                             [(reference.layer, reference.layer_shapes(s))],
                             s["layers"] - 1)
'''

#: the dense decoder as a period of two layers: num_blocks is half the
#: depth, and layer i takes its weights from blocks/{i % 2} at i // 2
PERIOD_TWO = '''
import dataclasses

from bench import reference, spec

_dense = spec.model({})
sizes, work = _dense.sizes, _dense.work


def arch_config(cfg):
    arch = _dense.arch_config(cfg)
    return dataclasses.replace(arch, block_pattern=arch.block_pattern * 2,
                               num_blocks=cfg["num_hidden_layers"] // 2)


def logits(cfg, seed, seqs, rows, mode="f32"):
    s = sizes(cfg)
    period = [(reference.layer, reference.layer_shapes(s))] * 2
    return reference.forward(s, seed, seqs, rows, mode, period,
                             s["layers"] // 2)
'''


def _root(tmp_path, cfg, module=None, text=None):
    root = tiny.make_root(tmp_path, cfgs=[cfg])
    models = root / "bench" / "models"
    if text is not None:
        (models / f"{module}.py").write_text(text)
    elif module is not None:
        shutil.copy(models / "dense.py", models / f"{module}.py")
    return root


def _run(root, trace=False):
    c = harness.Cell(spec.benchmark(root), "tiny.closed", SEED, 2.0,
                     trace=trace, root=root)
    c.setup(jax.devices()[:1])
    c.measure()
    layers = c.per_layer(PEAKS) if trace else None
    c.release()
    return c, layers, c.check()


def test_a_named_copy_of_the_dense_description_reads_as_the_default(
        tmp_path, monkeypatch):
    """A faithful copy, named by ``model``, reads ``correct``, and its
    work counts give the default's readings on the same run."""
    root = _root(tmp_path, {**tiny.config("tiny-named"),
                            "model": "dense_copy"}, "dense_copy")
    # no Pallas kernel runs on the CPU: give the trace one, so that the
    # kernel's roofline share reads
    reduce = trace_reduce.reduce

    def with_kernel(path):
        red = reduce(path)
        red["ops"]["paged_prefill_attention.1"] = 0.25
        return red

    monkeypatch.setattr(trace_reduce, "reduce", with_kernel)
    cell, named, (ok, checks, n) = _run(root, trace=True)
    assert ok and n > 0, checks
    assert cell.desc.__file__.endswith("dense_copy.py")
    cell.desc = spec.model({}, root)
    default = cell.per_layer(PEAKS)
    for name in ("step_mfu", "paged_attn_roofline"):
        assert named[name]["value"] == default[name]["value"] > 0


def test_the_harness_checks_against_the_named_reference(tmp_path):
    """A description whose reference leaves out the last layer reads
    not ``correct``: the check takes the named file's reference."""
    root = _root(tmp_path, {**tiny.config("tiny-short"),
                            "model": "one_short"}, "one_short", ONE_SHORT)
    _, _, (ok, checks, _) = _run(root)
    assert not ok
    assert checks["max_logit_gap"]["value"] > 1e-3


def test_a_two_layer_period_runs_through_the_engine(tmp_path):
    """The tiny dense model described as a period of two layers, four
    deep: the program holds two stacks of two, the reference takes layer i
    from ``blocks/{i % 2}`` at ``i // 2``, and the served tokens read
    ``correct``. The dense description's weights, all from ``blocks/0``,
    do not."""
    cfg = {**tiny.config("tiny-period"), "model": "period_two"}
    cfg["num_hidden_layers"] = cfg["published"]["num_hidden_layers"] = 4
    root = _root(tmp_path, cfg, "period_two", PERIOD_TWO)
    cell, _, (ok, checks, n) = _run(root)
    assert len(cell.arch.block_pattern) == 2 and cell.arch.num_blocks == 2
    assert ok and n > 0, checks
    cell.desc = spec.model({}, root)
    ok, checks, _ = cell.check()
    assert not ok and checks["max_logit_gap"]["value"] > 1e-3


def test_an_unknown_description_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.model({"model": "no_such_model"})


def test_two_layer_kinds_count_at_the_keys_each_sees():
    """Hand counts for a model of three window layers (16 keys) and
    one full layer: each kind is counted at the keys it reads, and the
    kernel's least time is the sum over the kinds."""
    win = flops.Kind(layers=3, hq=4, hkv=2, dh=8, matmul_params=1000,
                     window=16)
    full = flops.Kind(layers=1, hq=4, hkv=2, dh=8, matmul_params=1000)
    m = flops.Stack((win, full), head_params=500)
    # queries at 20..23: the window layers see 16 keys each, keys 5..23
    # in all; the full layer sees 21, 22, 23 and 24
    assert win.keys_seen(20, 4) == 64 and full.keys_seen(20, 4) == 90
    # queries at 10..19 straddle the window: 11..16 keys, then 16
    assert win.keys_seen(10, 10) == 11 + 12 + 13 + 14 + 15 + 16 * 5
    assert win.keys_seen(0, 3) == full.keys_seen(0, 3) == 1 + 2 + 3
    assert win.kernel_row(20, 4) == (4 * 4 * 8 * 64,
                                     2 * 19 * 2 * 8 * 4 + 2 * 4 * 4 * 8 * 4)
    assert full.kernel_row(20, 4) == (4 * 4 * 8 * 90,
                                      2 * 24 * 2 * 8 * 4 + 2 * 4 * 4 * 8 * 4)
    assert m.prefill_flops(20, 4, True) == (
        2 * 4 * 1000 * 4 + 4 * 4 * 8 * (3 * 64 + 90) + 2 * 500)
    assert m.decode_flops(30) == (2 * 500 + 2 * 4 * 1000
                                  + 4 * 4 * 8 * (3 * 16 + 31))
    calls = [[(20, 4)], [(10, 10), (0, 3)]]
    pf, bw = 1e9, 1e7

    def least(k, rows):
        fl = sum(k.kernel_row(s, n)[0] for s, n in rows)
        by = sum(k.kernel_row(s, n)[1] for s, n in rows)
        return k.layers * max(fl / 2 / pf, by / 2 / bw)

    want = sum(least(k, rows) for rows in calls for k in (win, full))
    got = flops.kernel_min_seconds(m, calls, pf, bw, chips=2)
    assert got == want
    # four full layers read more than three windowed and one full
    assert got < flops.kernel_min_seconds(
        flops.Stack((dataclasses.replace(full, layers=4),), 500), calls, pf,
        bw, chips=2)
    # one kind: the dense model's value
    one = flops.Model(d=32, hq=4, hkv=2, dh=8, f=64, v=100, layers=4)
    kind = flops.Kind(4, 4, 2, 8, one.layer_matmul_params)
    assert flops.kernel_min_seconds(flops.Stack((kind,), 3200), calls, pf,
                                    bw) == flops.kernel_min_seconds(
        one, calls, pf, bw)


def _parent_counts(s):
    """The dense counts as closed forms, written out from the sizes."""
    q, kv = s["hq"] * s["dh"], s["hkv"] * s["dh"]
    lmp = s["d"] * (q + 2 * kv) + q * s["d"] + 3 * s["d"] * s["f"]
    head, L, att = s["d"] * s["v"], s["layers"], 4 * s["hq"] * s["dh"]

    def prefill(start, n, emits):
        ctx = n * start + n * (n + 1) // 2
        return (2 * L * lmp * n + L * att * ctx
                + (2 * head if emits else 0))

    def decode(pos):
        return 2 * (L * lmp + head) + L * att * (pos + 1)

    def row(start, n):
        ctx = n * start + n * (n + 1) // 2
        return (att * ctx, 2 * (start + n) * kv * 4 + 2 * n * q * 4)

    def least(calls, pf, bw, chips):
        total = 0.0
        for rows in calls:
            fl = sum(row(st, n)[0] for st, n in rows)
            by = sum(row(st, n)[1] for st, n in rows)
            total += L * max(fl / chips / pf, by / chips / bw)
        return total

    return prefill, decode, row, least


@pytest.mark.parametrize("name", ["qwen2-7b.pp7-stage", "qwen2-7b.tp4"])
def test_the_default_description_counts_as_the_dense_model(name):
    """For each committed configuration, the default description's
    counts equal ``flops.Model``'s and the dense closed forms on a grid of
    (start, n), and so does the kernel's least time."""
    cfg = spec.load_config(spec.benchmark(), name)
    work = spec.model(cfg).work(cfg)
    model = flops.Model.of(reference.sizes(cfg))
    prefill, decode, row, least = _parent_counts(reference.sizes(cfg))
    grid = [(s, n) for s in (0, 1, 15, 16, 255, 1024, 6143)
            for n in (1, 2, 7, 16, 256)]
    for s, n in grid:
        for emits in (False, True):
            assert work.prefill_flops(s, n, emits) == \
                model.prefill_flops(s, n, emits) == prefill(s, n, emits)
        assert work.decode_flops(s + n) == model.decode_flops(s + n) \
            == decode(s + n)
        assert work.kinds[0].kernel_row(s, n) == model.kernel_row(s, n) \
            == row(s, n)
    assert work.head_params == model.head_params
    calls = [grid[i:i + 5] for i in range(0, len(grid), 5)] + [[(7, 1)]]
    for chips in (1, 4):
        assert flops.kernel_min_seconds(work, calls, 197e12, 819e9, chips) \
            == least(calls, 197e12, 819e9, chips)
