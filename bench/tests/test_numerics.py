"""The yardstick's arithmetic: operation counts, the reference, the weights
and the trace reduction."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness, reference, spec, trace_reduce, weights
from bench.tests import tiny


@pytest.mark.parametrize("name", ["qwen2-7b.pp7-stage", "qwen2-7b.tp4"])
def test_flops_against_hand_counts(name):
    cfg = spec.load_config(spec.benchmark(), name)
    m = flops.Model.of(reference.sizes(cfg))
    one = dataclasses.replace(m, layers=1)
    # one Qwen2-7B layer: q and o 3584x3584, k and v 3584x512, three
    # 3584x18944 MLP matrices
    params = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert one.layer_matmul_params == params == 233_046_016
    assert one.head_params == 3584 * 152064
    # a lone prompt token at position 0 sees one key: 28 heads x 128 x 4
    assert one.prefill_flops(0, 1, False) == 2 * params + 4 * 28 * 128
    # positions 10..12 see 11, 12 and 13 keys
    assert one.prefill_flops(10, 3, True) == (
        3 * 2 * params + 4 * 28 * 128 * (11 + 12 + 13) + 2 * 3584 * 152064)
    assert one.decode_flops(99) == 2 * (params + 3584 * 152064) \
        + 4 * 28 * 128 * 100
    assert m.prefill_flops(0, 1, False) == \
        cfg["num_hidden_layers"] * one.prefill_flops(0, 1, False)
    # the kernel reads each key and value of the row once (float32), the
    # query and writes the output
    fl, by = one.kernel_row(100, 4)
    assert fl == 4 * 28 * 128 * (101 + 102 + 103 + 104)
    assert by == 2 * 104 * 4 * 128 * 4 + 2 * 4 * 28 * 128 * 4
    t = flops.kernel_min_seconds(one, [[(100, 4)]], 1e12, 1e9, chips=2)
    assert t == pytest.approx(max(fl / 2 / 1e12, by / 2 / 1e9))


def test_program_weights_equal_the_reference_weights():
    """The program's tree, made in one call, and the reference's layers,
    made one at a time, hold the same values."""
    cfg = tiny.config()
    s = reference.sizes(cfg)
    seed = 2 ** 31 + 5
    params = harness.program_params(harness.arch_config(cfg), seed)
    key = weights.base_key(seed)
    shapes = tuple(sorted(reference.layer_shapes(s).items()))
    for i in range(s["layers"]):
        w = reference._layer_weights(key, i, shapes, jnp.float32)
        for name, _ in shapes:
            a, b = name.split("/")
            np.testing.assert_array_equal(
                np.asarray(params["blocks"][0][a][b][i]), np.asarray(w[name]))
    np.testing.assert_array_equal(
        np.asarray(params["lm_head"]),
        np.asarray(reference._leaf(key, "lm_head", (s["d"], s["v"]),
                                   jnp.float32)))
    assert not np.array_equal(np.asarray(params["blocks"][0]["mlp"]["wi"][0]),
                              np.asarray(params["blocks"][0]["mlp"]["wi"][1]))


def test_reference_matches_the_model_code():
    """At a tiny size on the CPU, under Precision.HIGHEST, the reference's
    logits equal those of ``repro.models`` given the same weights."""
    from repro.models import ModelOptions
    from repro.models.transformer import backbone, embed, unembed_logits
    cfg = tiny.config()
    arch = harness.arch_config(cfg)
    seed = 2 ** 31 + 99
    params = harness.program_params(arch, seed)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 20)]
    opts = ModelOptions(attn_impl="ref", scan_impl="ref", dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = []
        for s in seqs:
            h = embed(params, jnp.asarray(s)[None], arch, opts)
            h, _ = backbone(params, h, arch, opts,
                            jnp.arange(len(s), dtype=jnp.int32), None)
            want.append(np.asarray(unembed_logits(params, h, arch))[0])
    rows = [(0, 5), (0, 36), (1, 0), (1, 19)]
    got = np.asarray(reference.logits(cfg, seed, seqs, rows, "f32"))
    for g, (i, p) in zip(got, rows):
        np.testing.assert_allclose(g, want[i][p], rtol=1e-4, atol=1e-4)
    # the control is a different computation
    ctl = np.asarray(reference.logits(cfg, seed, seqs, rows, "bf16"))
    assert np.abs(ctl - got).max() > 1e-3


def _record_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    return trace_reduce.find(str(tmp_path))


def test_trace_reduction_adds_up(tmp_path):
    path = _record_trace(tmp_path)
    red = trace_reduce.reduce(path)
    assert red is not None and red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    # every instant of the busy time belongs to exactly one operation
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"], rel=1e-9)
    idle = red["window_s"] - red["busy_s"]
    gaps = sum(s for _, s in red["idle_gaps"])
    assert gaps <= idle * (1 + 1e-9)
    assert any(v["count"] == 3 for v in red["modules"].values())


def test_self_times_split_nested_operations():
    ops = [("outer", 0, 100), ("inner", 20, 50), ("next", 120, 130)]
    selft, busy = trace_reduce.self_times(ops)
    assert selft == {"outer": 70, "inner": 30, "next": 10}
    assert [(s, e) for s, e, _ in busy] == [(0, 100), (120, 130)]
