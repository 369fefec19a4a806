"""Weights from the seed, one leaf at a time, by the leaf's name.

Both the program's weights (``harness.program_params``) and the plain
reference's (``reference``) come from ``leaf``: the same seed, name, layer
and shape give the same values, on one chip or sharded over four, in one
jitted call or one layer at a time. This module imports nothing of the
serving program.

Leaf names follow the program's parameter tree, as paths such as
``blocks/0/mixer/wq``. Stacked per-layer leaves are made one layer at a time
from ``fold_in(key, layer)``, so the reference can make layer ``i`` alone.
"""
from __future__ import annotations

import zlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from bench.traffic import seed_words


def base_key(seed: int) -> jax.Array:
    words = seed_words(seed)
    key = jax.random.PRNGKey(words[0])
    for w in words[1:]:
        key = jax.random.fold_in(key, w)
    return key


def _stats(name: str, shape: Sequence[int]):
    """(mean, std) of a leaf, by its last name. Matrices take 1/sqrt(fan-in)
    (the fan-in is the second-to-last axis); norm scales sit near 1; the
    q/k/v biases are far enough from 0 that dropping one shows. A nonzero
    mean comes with a power-of-two std, so that ``mean + std * x`` rounds
    the same whether or not the compiler fuses it."""
    if name == "scale":
        return 1.0, 0.125
    if name in ("bq", "bk", "bv"):
        return 0.0, 0.5
    if name == "embed":
        return 0.0, 1.0
    return 0.0, float(shape[-2]) ** -0.5


def leaf(key: jax.Array, path: str, shape: Sequence[int],
         layer: Optional[int] = None) -> jax.Array:
    """One float32 leaf (or one layer's slice of a stacked leaf)."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    mean, std = _stats(path.rsplit("/", 1)[-1], shape)
    return mean + std * jax.random.normal(k, tuple(shape), jnp.float32)


def stacked(key: jax.Array, path: str, shape: Sequence[int]) -> jax.Array:
    """A (layers, ...) leaf: layer ``i`` equals ``leaf(key, path, shape[1:],
    layer=i)``."""
    return jax.vmap(lambda i: leaf(key, path, shape[1:], layer=i))(
        jnp.arange(shape[0]))
