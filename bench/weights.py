"""Seeded random weights, made on the device in one jitted call.

The layout is the one the served transformer reads (``embedding``,
stacked ``layers``, ``final_norm``, ``lm_head`` when untied); the plain
reference in ``bench/reference`` reads the same tree.  Weights are made in
the type they are served in (bf16 for the published checkpoints).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BIAS_STD = 0.5     # qkv biases: large enough that a dropped bias shows
NORM_STD = 0.1     # norm scales around 1
EMBED_STD = 0.02


def layout(m: dict) -> dict:
    """Leaves are ``(shape, mean, std)``."""
    d, L, f, V = m["d_model"], m["n_layers"], m["d_ff"], m["vocab_size"]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]

    def lin(i, o, bias=False):
        leaf = {"w": ((L, i, o), 0.0, i ** -0.5)}
        if bias:
            leaf["b"] = ((L, o), 0.0, BIAS_STD)
        return leaf

    def norm(*lead):
        return {"scale": ((*lead, d), 1.0, NORM_STD)}

    qkv_bias = bool(m.get("qkv_bias", False))
    tree = {
        "embedding": {"table": ((V, d), 0.0, EMBED_STD)},
        "layers": {
            "attn_norm": norm(L),
            "attn": {"wq": lin(d, hq * hd, qkv_bias),
                     "wk": lin(d, hkv * hd, qkv_bias),
                     "wv": lin(d, hkv * hd, qkv_bias),
                     "wo": lin(hq * hd, d)},
            "ffn_norm": norm(L),
            "ffn": {"gate": lin(d, f), "up": lin(d, f), "down": lin(f, d)},
        },
        "final_norm": norm(),
    }
    if not m.get("tie_embeddings", False):
        tree["lm_head"] = {"table": ((V, d), 0.0, EMBED_STD)}
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def key_data(seed: int, which: int = 0) -> np.ndarray:
    """A raw uint32[2] PRNG key drawn from any whole-number seed."""
    rng = np.random.default_rng([int(seed), 1000 + which])
    return rng.integers(0, 2 ** 32, size=2, dtype=np.uint32)


def make(m: dict, seed: int, dtype: str = "bfloat16") -> dict:
    leaves, treedef = jax.tree.flatten(layout(m), is_leaf=_is_leaf)
    dt = jnp.dtype(dtype)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = [(mean + std * jax.random.normal(k, shape, jnp.float32)).astype(dt)
               for (shape, mean, std), k in zip(leaves, keys)]
        return jax.tree.unflatten(treedef, out)

    return build(jnp.asarray(key_data(seed)))


def n_params(m: dict) -> int:
    leaves = jax.tree.leaves(layout(m), is_leaf=_is_leaf)
    return int(sum(np.prod(shape) for shape, _, _ in leaves))
