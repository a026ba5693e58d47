"""Weights from the seed, leaf by leaf, in one jitted call on the device.

Both the system under test and the plain reference take their weights
from here, so neither takes anything the other made.  A leaf is a pure
function of (seed, its path, its shape), so the reference can make the
same leaf again at any time, and the harness can make the weights again
to measure how far training moved them.

Rules by path, following the usual initialisation of such a model:
norm scales are ones, the embedding is N(0, 0.02), every other matrix is
a truncated normal with standard deviation 1/sqrt(fan-in), fan-in being
the second-to-last axis.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Shapes = Dict[str, Tuple[Tuple[int, ...], str]]   # path -> (shape, dtype)


def seed_key(seed: int):
    """A PRNG key for any whole number up to 2**63 (seeds pass 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def leaf(key, path: str, shape, dtype):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if path.endswith("scale"):
        x = jnp.ones(shape, jnp.float32)
    elif path == "embed":
        x = jax.random.normal(k, shape, jnp.float32) * 0.02
    else:
        std = 1.0 / math.sqrt(shape[-2])
        x = jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                        jnp.float32) * std
    return x.astype(dtype)


@functools.lru_cache(maxsize=8)
def _maker(items):
    def make(key):
        return {p: leaf(key, p, s, jnp.dtype(d)) for p, (s, d) in items}
    return jax.jit(make)


def make(seed: int, shapes: Shapes) -> Dict[str, jax.Array]:
    """Every leaf of ``shapes`` in its stated dtype, in one call."""
    items = tuple(sorted((p, (tuple(s), str(d))) for p, (s, d)
                         in shapes.items()))
    return _maker(items)(seed_key(seed))
