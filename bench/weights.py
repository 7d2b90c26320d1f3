"""Seeded random weights, made on the device in one jitted call.

The tree and each leaf's shape and distribution are the model family's
``layout`` (``bench/families``), the tree the served program reads; the
family's reference reads the same tree.  Weights are made in the type
they are served in (bf16 for the published checkpoints).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.families import dense


def layout(m: dict) -> dict:
    """The dense family's leaves ``(shape, mean, std)``."""
    return dense.layout(m)


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def key_data(seed: int, which: int = 0) -> np.ndarray:
    """A raw uint32[2] PRNG key drawn from any whole-number seed."""
    rng = np.random.default_rng([int(seed), 1000 + which])
    return rng.integers(0, 2 ** 32, size=2, dtype=np.uint32)


def from_layout(tree: dict, seed: int, dtype: str) -> dict:
    """Every leaf ``(shape, mean, std)`` of a family's ``layout(m)`` drawn
    from ``seed`` on the device, in ``dtype``."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_leaf)
    dt = jnp.dtype(dtype)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = [(mean + std * jax.random.normal(k, shape, jnp.float32)).astype(dt)
               for (shape, mean, std), k in zip(leaves, keys)]
        return jax.tree.unflatten(treedef, out)

    return build(jnp.asarray(key_data(seed)))


def make(m: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The dense family's weights."""
    return from_layout(layout(m), seed, dtype)


def n_params(m: dict) -> int:
    """Parameters of the dense family's layout."""
    leaves = jax.tree.leaves(layout(m), is_leaf=_is_leaf)
    return int(sum(np.prod(shape) for shape, _, _ in leaves))
