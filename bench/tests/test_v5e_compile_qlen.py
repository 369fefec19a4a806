"""The paged attention kernel compiles for a TPU v5e at every cell's own
shapes with its per-row query counts (``qlen``), the operand that bounds
each row's walk over its KV blocks: the chunk pass and decode, per chip of
each cell, for a described v5e, without running anything."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests.test_v5e_compile import CELLS, _shapes, chip  # noqa: F401


@pytest.mark.parametrize("cell", CELLS)
def test_paged_attention_with_qlen_compiles_for_v5e(chip, cell):
    from repro.kernels.paged_prefill import paged_prefill_attention
    B, W, hq, hkv, dh, d, bs, nb, pool = _shapes(cell)
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=chip)
    kv = sd((pool, bs, hkv, dh))
    for width in (W, 1):
        c = jax.jit(lambda q, k, v, t, st, ql: paged_prefill_attention(
            q, k, v, t, st, qlen=ql)).lower(sd((B, width, hq, dh)), kv, kv,
                                            sd((B, nb), jnp.int32),
                                            sd((B,), jnp.int32),
                                            sd((B,), jnp.int32)).compile()
        assert "tpu_custom_call" in c.as_text()
