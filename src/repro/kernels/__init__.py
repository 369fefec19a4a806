"""Pallas TPU kernels for the hot paths the UKL shortcut level bypasses.

Each kernel ships three artifacts (assignment contract):
  <name>.py -- pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py    -- jit'd public wrappers (backend dispatch, mask precompute)
  ref.py    -- pure-jnp oracles, asserted against in tests
"""
