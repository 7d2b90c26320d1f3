"""Pallas TPU kernel: on-device tile-group quantization (Q4_0 grid).

The paper quantizes offline; this kernel exists for the cases where weights
are produced on-device (e.g. checkpoint-load-time quantization of a trained
model) so the fp weights never have to round-trip through HBM twice.
Geometry matches ``quant.tile_quant.quantize(scheme='tile')``: (2, 16)
groups, scale = absmax/8, codes packed two-per-byte along N.

The kernel runs interpreted only; compiling it raises.  Mosaic refuses
its fp16 scale output (f32 -> fp16 casts), its (bk/2, bn/16) scales
block, and the lane-strided slices that pack two codes per byte.  No
serving path calls it: ``quant.qlinear.quantize_model_params`` quantizes
in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import require_interpret, resolve_interpret


def _kernel(w_ref, codes_ref, scales_ref, *, group_size: int):
    w = w_ref[...].astype(jnp.float32)           # (bk, bn)
    bk, bn = w.shape
    gr, gc = 2, group_size // 2
    wg = w.reshape(bk // gr, gr, bn // gc, gc)
    absmax = jnp.max(jnp.abs(wg), axis=(1, 3))   # (bk//2, bn//16)
    scales = absmax / 8.0
    scales_ref[...] = scales.astype(scales_ref.dtype)
    sc = jnp.repeat(jnp.repeat(jnp.maximum(scales, 1e-8), gr, axis=0), gc, axis=1)
    q = jnp.clip(jnp.round(w / sc), -8, 7) + 8   # [0, 15]
    q = q.astype(jnp.uint8)
    lo = q[:, 0::2]
    hi = q[:, 1::2]
    codes_ref[...] = lo | (hi << 4)


@functools.partial(jax.jit, static_argnames=("group_size", "bk", "bn", "interpret"))
def tile_quantize(w, *, group_size: int = 32, bk: int = 128, bn: int = 256,
                  interpret: bool | None = None):
    """w: (K, N) -> (codes (K, N//2) uint8, scales (K//2, N//16) f16).
    ``interpret=None`` follows the backend."""
    interpret = resolve_interpret(interpret)
    require_interpret(interpret, "tile_quantize",
                      "Mosaic refuses f32 -> fp16 casts, its (bk/2, bn/16) "
                      "scales block and lane-strided packing slices")
    K, N = w.shape
    bk, bn = min(bk, K), min(bn, N)
    assert K % bk == 0 and N % bn == 0
    g = group_size
    return pl.pallas_call(
        functools.partial(_kernel, group_size=g),
        grid=(K // bk, N // bn),
        in_specs=[pl.BlockSpec((bk, bn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bk, bn // 2), lambda i, j: (i, j)),
            pl.BlockSpec((bk // 2, bn // (g // 2)), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, N // 2), jnp.uint8),
            jax.ShapeDtypeStruct((K // 2, N // (g // 2)), jnp.float16),
        ],
        interpret=interpret,
    )(w)
