"""Small pytree helpers used across the framework (pure JAX, no deps)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def tree_size(tree) -> int:
    """Total number of scalar parameters in a pytree."""
    return sum(int(x.size) for x in jax.tree.leaves(tree))


def tree_bytes(tree) -> int:
    """Total bytes of a pytree (respects per-leaf dtype)."""
    return sum(int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_average(trees):
    """Element-wise average of a list of identically-structured pytrees.

    This is the FedAvg / proxy-model operator (paper Fig. 4 and Eq. 13).
    Leaves held in host memory are moved to the device one at a time
    and averaged there, so host-resident models never make the host do
    the arithmetic.
    """
    n = len(trees)
    if n == 0:
        raise ValueError("tree_average of empty list")
    if n == 1:
        return trees[0]
    return jax.tree.map(
        lambda *xs: (sum(jnp.asarray(x).astype(jnp.float32) for x in xs)
                     / n).astype(xs[0].dtype),
        *trees,
    )


def tree_zeros_like(tree, dtype=None):
    return jax.tree.map(
        lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree
    )


def tree_cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def tree_norm(tree) -> jax.Array:
    """Global L2 norm of a pytree."""
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def flatten_with_paths(tree):
    """Returns [(path_str, leaf)] for a pytree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(path_str(path), leaf) for path, leaf in flat]


def path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)
