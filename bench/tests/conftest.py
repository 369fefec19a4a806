"""A run of the harness sets process-wide JAX options (the matmul precision
of the timed path, the persistent compilation cache), as the benchmark's own
process should. Tests share worker processes with the rest of the suite:
here the cache stays off, and each test gets the precision back as it found
it."""
import pytest


@pytest.fixture(autouse=True)
def _keep_jax_options(monkeypatch):
    import jax
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    before = jax.config.jax_default_matmul_precision
    yield
    if jax.config.jax_default_matmul_precision != before:
        jax.config.update("jax_default_matmul_precision", before)
