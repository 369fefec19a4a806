"""The serving kernels compile for a TPU v5e at every cell's own shapes.

A kernel whose VMEM blocks outgrow the chip's scoped limit at a cell's
widths would only fail on the chip; this compiles the fused RMSNorm over a
serve step's rows and the paged attention kernel (chunk pass and decode) for
a described v5e, per chip of each cell, without running anything. The
topology is described inside a fixture, never at import.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import reference, spec

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(cell):
    bench = spec.benchmark()
    w = spec.workload(bench, cell)
    cfg = spec.load_config(bench, w["config"])
    e = cfg["engine"]
    s = reference.sizes(cfg)
    model = e["mesh"][1]          # heads split over the "model" axis
    nb = -(-spec.load_traffic(w["traffic"])["max_len"] // e["block_size"])
    return (e["slots"], e["chunk_width"], s["hq"] // model,
            s["hkv"] // model, s["dh"], s["d"], e["block_size"], nb,
            e["num_blocks"] + 1)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_kernels_compile_for_v5e(chip, cell):
    from repro.kernels.paged_prefill import paged_prefill_attention
    from repro.kernels.rmsnorm import rmsnorm
    B, W, hq, hkv, dh, d, bs, nb, pool = _shapes(cell)
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=chip)
    for rows in (B, B * W):
        c = jax.jit(lambda x, g: rmsnorm(x, g)).lower(
            sd((rows, d)), sd((d,))).compile()
        assert "tpu_custom_call" in c.as_text()
    kv = sd((pool, bs, hkv, dh))
    for width in (W, 1):
        c = jax.jit(lambda q, k, v, t, st: paged_prefill_attention(
            q, k, v, t, st)).lower(sd((B, width, hq, dh)), kv, kv,
                                   sd((B, nb), jnp.int32),
                                   sd((B,), jnp.int32)).compile()
        assert "tpu_custom_call" in c.as_text()
