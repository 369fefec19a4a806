"""Sharding rules + dry-run machinery. The production 512-device dry-run runs
via subprocess (XLA_FLAGS must be set before jax init — the test process
keeps its real device count, per the assignment)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, all_cells, get_config, list_archs, shape_applicable
from repro.launch import hlo_analysis
from repro.models import cache_spec, init_params
from repro.sharding.rules import ArchSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    """Axis-name/shape stand-in so rules can be tested without 256 devices."""

    def __init__(self, shape_by_axis):
        self.axis_names = tuple(shape_by_axis)
        self.shape = dict(shape_by_axis)

    @property
    def devices(self):
        import numpy as _np
        return _np.empty(tuple(self.shape.values()))


MESH1 = FakeMesh({"data": 16, "model": 16})
MESH2 = FakeMesh({"pod": 2, "data": 16, "model": 16})


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("mesh", [MESH1, MESH2], ids=["16x16", "2x16x16"])
def test_param_specs_cover_tree_and_rank(arch, mesh):
    cfg = get_config(arch)
    # smoke-size params have identical tree structure to full-size
    params = init_params(jax.random.PRNGKey(0), cfg.smoke())
    sh = ArchSharding(cfg, mesh)
    specs = sh.param_specs(params)
    leaves_p = jax.tree.leaves(params)
    leaves_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves_p) == len(leaves_s)
    for p, s in zip(leaves_p, leaves_s):
        assert isinstance(s, P)
        assert len(s) <= p.ndim, (s, p.shape)


@pytest.mark.parametrize("arch", list_archs())
def test_every_big_param_is_fsdp_sharded(arch):
    """No parameter matrix may be fully replicated (1000-node posture)."""
    cfg = get_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg.smoke())
    sh = ArchSharding(cfg, MESH2)
    specs = sh.param_specs(params)

    def check(path, p, s):
        # true matrices only: at least two non-trivial dims (the stacked
        # blocks dim and per-channel vectors don't count)
        if p.ndim >= 2 and sorted(p.shape)[-2] >= 32:
            axes = [a for dim in s if dim for a in
                    (dim if isinstance(dim, tuple) else (dim,))]
            assert axes, f"{arch}: replicated matrix at {path} spec={s}"

    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for (path, p), s in zip(flat_p, flat_s):
        check(path, p, s)


def test_qwen2_heads_not_tp_sharded_but_ff_is():
    """28 heads % 16 != 0 -> attention TP off; d_ff/vocab TP on."""
    sh = ArchSharding(get_config("qwen2-7b"), MESH1)
    rep = sh.tp_report()
    assert not rep["tp_heads"]
    assert rep["tp_ff"] and rep["tp_vocab"]


def test_kimi_experts_tp_sharded():
    sh = ArchSharding(get_config("kimi-k2-1t-a32b"), MESH1)
    assert sh.tp_report()["tp_experts"]


def test_batch_spec_fallbacks():
    sh = ArchSharding(get_config("tinyllama-1.1b"), MESH2)
    assert sh.batch_spec(256) == P(("pod", "data"))
    assert sh.batch_spec(16) == P("data")      # not divisible by 32
    assert sh.batch_spec(1) == P(None)


def test_cache_specs_long_context_shards_time_axis():
    cfg = get_config("h2o-danube-1.8b")
    sh = ArchSharding(cfg, MESH1)
    cspec = cache_spec(cfg, 1, 524288, jnp.bfloat16)
    specs = sh.cache_specs(cspec, global_batch=1)
    k_spec = specs[0]["k"]
    # batch=1 + kv-heads not TP-divisible: time axis sharded over BOTH the
    # idle data axis (context parallel) and the model axis (flash-decode)
    t_axes = k_spec[2] if isinstance(k_spec[2], tuple) else (k_spec[2],)
    assert "data" in t_axes and "model" in t_axes


def test_shape_applicability_matrix():
    runnable = dict((a, [s for s in SHAPES
                         if shape_applicable(get_config(a), SHAPES[s])])
                    for a in list_archs())
    for a in ("rwkv6-7b", "jamba-v0.1-52b", "h2o-danube-1.8b"):
        assert "long_500k" in runnable[a]
    for a in ("tinyllama-1.1b", "qwen2-7b", "mistral-large-123b",
              "kimi-k2-1t-a32b", "moonshot-v1-16b-a3b", "musicgen-medium",
              "llama-3.2-vision-11b"):
        assert "long_500k" not in runnable[a]
    total = sum(len(v) for v in runnable.values())
    assert total == 33                          # 10*4 - 7 skips


# ---------------------------------------------------------------------------
# hlo_analysis
# ---------------------------------------------------------------------------

def test_hlo_flops_match_xla_on_loop_free():
    def f(a, b):
        return jnp.tanh(a @ b).sum()

    a = jnp.ones((64, 128))
    b = jnp.ones((128, 32))
    c = jax.jit(f).lower(a, b).compile()
    st = hlo_analysis.analyze(c.as_text())
    want = 2 * 64 * 128 * 32
    assert abs(st.flops - want) / want < 0.05


def test_hlo_bytes_calibration_band_vs_xla_loop_free():
    """On loop-free programs the raw parsed byte count matches XLA's
    bytes-accessed within a program-dependent factor in [1.0, 2.0]
    (fusion granularity); the calibrated (×0.5) value therefore lands in
    [0.5×, 1.0×] of XLA's number. The loop-corrected extension to while
    bodies (which XLA counts once) inherits the same band."""
    def f(a, b):
        h = jnp.tanh(a @ b)
        return (h @ b.T).sum()

    a = jnp.ones((256, 512))
    b = jnp.ones((512, 256))
    c = jax.jit(f).lower(a, b).compile()
    st = hlo_analysis.analyze(c.as_text())
    xla = float(c.cost_analysis()["bytes accessed"])
    assert 0.4 <= st.hbm_bytes / xla <= 1.1, (st.hbm_bytes, xla)


def test_hlo_loop_multiplier():
    from jax import lax

    def f(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = lax.scan(body, x, ws)
        return h.sum()

    ws = jnp.ones((8, 32, 32))
    x = jnp.ones((4, 32))
    c = jax.jit(f).lower(ws, x).compile()
    st = hlo_analysis.analyze(c.as_text())
    want = 8 * 2 * 4 * 32 * 32
    assert abs(st.flops - want) / want < 0.05
    assert st.while_loops and st.while_loops[0][1] == 8


def test_collective_accounting_conventions():
    txt = """
HloModule m

ENTRY %main (p: f32[16,16]) -> f32[16,16] {
  %p = f32[16,16]{1,0} parameter(0)
  %ag = f32[64,16]{1,0} all-gather(%p), replica_groups=[2,4]<=[8], dimensions={0}
  %ar = f32[16,16]{1,0} all-reduce(%p), replica_groups=[1,8]<=[8], to_apply=%add
  ROOT %cp = f32[16,16]{1,0} collective-permute(%p), source_target_pairs={{0,1}}
}
"""
    st = hlo_analysis.analyze(txt)
    ag = 64 * 16 * 4 * (4 - 1) / 4
    ar = 2 * 16 * 16 * 4 * (8 - 1) / 8
    cp = 16 * 16 * 4
    assert abs(st.coll_wire_bytes - (ag + ar + cp)) < 1.0


# ---------------------------------------------------------------------------
# the real dry-run, via subprocess (small + fast cell on the 512-dev mesh)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_dryrun_subprocess_single_pod():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "tinyllama-1.1b", "--shape", "decode_32k"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")),
        cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK    16x16 tinyllama-1.1b × decode_32k" in out.stdout


@pytest.mark.slow
def test_dryrun_subprocess_multi_pod():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "rwkv6-7b", "--shape", "long_500k", "--multi-pod"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")),
        cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK    2x16x16 rwkv6-7b × long_500k" in out.stdout


def test_input_specs_are_abstract():
    """input_specs never allocates: everything is ShapeDtypeStruct."""
    from repro.launch.cells import input_specs
    specs = input_specs("qwen2-7b", "decode_32k")
    for leaf in jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)):
        assert isinstance(leaf, jax.ShapeDtypeStruct)
    assert specs["cache"][0]["k"].shape[2] == 32768
