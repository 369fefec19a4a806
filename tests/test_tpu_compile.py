"""The serving kernels and programs compile for a TPU v5e, at TinyLlama width.

Interpret-mode parity tests cannot see what the chip's compiler refuses — a
block that is neither an (8, 128) tile multiple nor the whole array, too
much VMEM, a custom call XLA would have to partition. These tests compile
for a *described* v5e (``jax.experimental.topologies``): nothing runs, so
they say nothing about results or time, only that the compiler accepts the
code. The widths are TinyLlama-1.1B's (32 query / 4 KV heads of 64) at the
served shapes: 8 slots, 16-token blocks, 40 blocks per row, chunk widths 16
and 256.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file. Keep all such tests in this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, HQ, HKV, DH, BS, NB = 8, 32, 4, 64, 16, 40
P1 = B * NB + 1                      # pool rows, the last one the trash row
D_MODEL = 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args) -> str:
    """Compile for the described chip; returns the compiled HLO text."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text      # the Mosaic kernel is in there
    return text


def _kv_operands(sd, kv_dtype):
    """(q dtype, pool shapes, scale shapes) for a pool stored in kv_dtype:
    int8 pools carry f32 per-(block, head) scales and f32 queries."""
    if kv_dtype == "int8":
        pool = sd((P1, BS, HKV, DH), jnp.int8)
        scales = (sd((P1, HKV), jnp.float32),) * 2
        return jnp.float32, pool, scales
    dt = jnp.dtype(kv_dtype)
    return dt, sd((P1, BS, HKV, DH), dt), ()


@pytest.fixture(scope="module")
def sd(one_chip):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "float32", "int8"])
def test_paged_decode_compiles(sd, kv_dtype):
    from repro.kernels.paged_decode import paged_decode_attention
    qdt, pool, scales = _kv_operands(sd, kv_dtype)
    _compile(lambda q, kp, vp, t, p, *s: paged_decode_attention(
                 q, kp, vp, t, p, *s),
             sd((B, HQ, DH), qdt), pool, pool, sd((B, NB), jnp.int32),
             sd((B,), jnp.int32), *scales)


@pytest.mark.parametrize("width", [16, 256])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "float32", "int8"])
def test_paged_prefill_compiles(sd, kv_dtype, width):
    from repro.kernels.paged_prefill import paged_prefill_attention
    qdt, pool, scales = _kv_operands(sd, kv_dtype)
    _compile(lambda q, kp, vp, t, st, ql, *s: paged_prefill_attention(
                 q, kp, vp, t, st, *s, qlen=ql),
             sd((B, width, HQ, DH), qdt), pool, pool,
             sd((B, NB), jnp.int32), sd((B,), jnp.int32),
             sd((B,), jnp.int32), *scales)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_slot_decode_compiles(sd, dtype):
    from repro.kernels.slot_decode import slot_decode_attention
    T = NB * BS
    kv = sd((B, T, HKV, DH), jnp.dtype(dtype))
    _compile(lambda q, k, v, m: slot_decode_attention(q, k, v, m),
             sd((B, HQ, DH), jnp.dtype(dtype)), kv, kv,
             sd((B, T), jnp.bool_))


@pytest.mark.parametrize("rows", [B, B * 256])   # decode rows, chunk rows
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_rmsnorm_compiles(sd, dtype, rows):
    from repro.kernels.rmsnorm import rmsnorm
    _compile(lambda x, s: rmsnorm(x, s),
             sd((rows, D_MODEL), jnp.dtype(dtype)),
             sd((D_MODEL,), jnp.float32))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_attention_compiles(sd, dtype):
    from repro.kernels.flash_attention import flash_attention
    S = 512
    dt = jnp.dtype(dtype)
    _compile(lambda q, k, v: flash_attention(q, k, v),
             sd((1, S, HQ, DH), dt), sd((1, S, HKV, DH), dt),
             sd((1, S, HKV, DH), dt))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("chips", [1, 4])
def test_serving_programs_compile(topo, monkeypatch, chips, kv_dtype):
    """The engine's paged decode and chunked serve-step programs, with the
    shortcut kernels, at TinyLlama's full width: on one chip, and on a
    (data 1, model 4) mesh where each kernel runs per shard; with the
    unquantized pools ("bf16" stores the activation dtype, f32 here) and
    with int8 pools. Every program fits the 16 GB of one v5e."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.core import SamplingConfig, preset
    from repro.core.step import build_paged_decode_step, build_serve_step
    from repro.kernels import ops
    from repro.models import ModelOptions, init_params
    from repro.serve.paging import init_paged_cache
    from repro.sharding.rules import ArchSharding, named

    # ops picks interpret mode from the process's backend, which is the CPU
    # here: steer it to the Mosaic kernels the chip runs
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("tinyllama-1.1b")
    lk = dataclasses.replace(preset("nss_shortcut"), decode_steps=32)
    opts = lk.model_options(ModelOptions(dtype=jnp.float32), on_tpu=True)
    max_len, W = 512 + 64 + 8, 256
    nb = -(-max_len // BS)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, B * nb + 1, BS, B, jnp.float32, kv_dtype=kv_dtype))
    mesh = psh = csh = None
    if chips == 1:
        one = SingleDeviceSharding(topo.devices[0])
        place = lambda t, s=None: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
        rep = one
    else:
        mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4),
                    ("data", "model"))
        sh = ArchSharding(cfg, mesh)
        psh = named(mesh, sh.serve_param_specs(params))
        csh = named(mesh, sh.serve_paged_cache_specs(cache))
        place = lambda t, s: jax.tree.map(
            lambda a, x: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=x),
            t, s)
        rep = NamedSharding(mesh, P())
        opts = dataclasses.replace(opts, mesh=mesh)
    params, cache = place(params, psh), place(cache, csh)
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    i32, no = (lambda *s: arg(s, jnp.int32)), arg((B,), jnp.bool_)
    keys, tables = arg((B, 2), jnp.uint32), i32(B, nb)

    dec = build_paged_decode_step(cfg, opts, lk, max_len, SamplingConfig(),
                                  mesh=mesh, param_sharding=psh,
                                  cache_sharding=csh)
    srv = build_serve_step(cfg, opts, lk, max_len, SamplingConfig(),
                           kv_kind="paged", mesh=mesh, param_sharding=psh,
                           cache_sharding=csh)
    for compiled in (
            dec.lower(params, cache, i32(B), keys, tables).compile(),
            srv.lower(params, cache, i32(B, W), i32(B), i32(B), no, no,
                      i32(B), no, keys, tables, tables).compile()):
        assert "tpu_custom_call" in compiled.as_text()
        ma = compiled.memory_analysis()
        used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        assert used < 16e9, used
