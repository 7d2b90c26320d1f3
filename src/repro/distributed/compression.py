"""Gradient compression for bandwidth-bound reductions.

Two mechanisms:

* ``compressed_psum`` — a drop-in collective: reduce-scatter at full (or
  bf16) precision for exact summation, then int8-quantize the *scattered*
  shard and all-gather it compressed.  Per-device bytes vs plain f32
  all-reduce (ring):  RS_f32 + AG_int8 = 1.25×size  vs  2×size  (1.6×
  reduction; 2.7× with bf16 RS).  Intended deployment: the cross-pod
  ("pod" axis) gradient reduction, where inter-pod links are the scarce
  resource at 1000+ node scale.

* ``ef_quantize`` — error-feedback int8 quantize/dequantize used as a
  ``grad_transform`` hook in the train step to study compression's effect
  on convergence without rewiring XLA's automatic intra-pod reductions.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quantize_int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum_local(x, axis: str, *, rs_dtype=jnp.float32):
    """Runs inside shard_map. x: any shape, identical on all shards of
    ``axis`` only in *shape*. Returns the full psum result (replicated)."""
    n = jax.lax.axis_size(axis)
    flat = x.reshape(-1).astype(rs_dtype)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))
    # exact-sum reduce-scatter (each shard owns 1/n of the summed vector)
    shard = jax.lax.psum_scatter(flat, axis, scatter_dimension=0, tiled=True)
    # compress the broadcast half: int8 + one scale per shard
    q, scale = _quantize_int8(shard.astype(jnp.float32))
    q_all = jax.lax.all_gather(q, axis, axis=0, tiled=True)      # int8 bytes
    s_all = jax.lax.all_gather(scale, axis, axis=0)              # n scalars
    idx = jnp.repeat(jnp.arange(n), shard.shape[0])
    full = q_all.astype(jnp.float32) * s_all[idx]
    full = full[: flat.shape[0] - pad] if pad else full
    return full.reshape(x.shape).astype(x.dtype)


def compressed_psum(tree, mesh, axis: str = "pod", *, rs_dtype=jnp.float32):
    """Apply compressed_psum_local leaf-wise under shard_map (inputs
    replicated along ``axis``; result = sum over that axis)."""

    def local(args):
        return jax.tree.map(
            lambda x: compressed_psum_local(x, axis, rs_dtype=rs_dtype), args)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(),), out_specs=P(), check_vma=False)
    return fn(tree)


# ---------------------------------------------------------------------------
# Error-feedback quantization (train-step grad_transform hook)
# ---------------------------------------------------------------------------


def make_ef_state(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def ef_quantize(grads, ef_state=None):
    """int8 quantize/dequantize with error feedback.

    Returns (compressed_grads, new_ef_state). With ef_state=None behaves as
    stateless quantization.
    """

    def one(g, e):
        x = g.astype(jnp.float32) + (e if e is not None else 0.0)
        q, s = _quantize_int8(x)
        deq = _dequantize_int8(q, s)
        return deq.astype(g.dtype), x - deq

    if ef_state is None:
        out = jax.tree.map(lambda g: one(g, None)[0], grads)
        return out, None
    pairs = jax.tree.map(one, grads, ef_state)
    comp = jax.tree.map(lambda t: t[0], pairs,
                        is_leaf=lambda x: isinstance(x, tuple))
    new_ef = jax.tree.map(lambda t: t[1], pairs,
                          is_leaf=lambda x: isinstance(x, tuple))
    return comp, new_ef
