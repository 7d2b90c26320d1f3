"""Pallas TPU kernel: mixed-precision GEMM with in-kernel LUT dequantization.

The TPU adaptation of the paper's §5.1 + §5.2.2 pipeline:

* weights arrive as packed int4 codes (two per byte) in the tile-group
  layout produced offline by ``repro.quant.tile_quant`` — codes and scales
  are unit-stride for every (bk, bn) VMEM block (no scatter, the Fig. 6
  mismatch is designed away);
* dequantization inside the kernel is a 16-entry codebook lookup — the
  ``vlut16`` analogue, a select chain on TPU — so swapping the table
  supports Q4_0 / NF4 / FP4 / IQ4_NL with zero code changes;
* scale broadcast is two cheap in-register repeats (2× along sublanes,
  16× along lanes), the analogue of the paper's scale-broadcast-via-LUT;
  the fp16 scales enter as bf16-typed bits and are decoded in-kernel
  (``repro.kernels.common``), since Mosaic cannot load fp16 vectors;
* the MXU consumes the dequantized (bk, bn) tile immediately — FP16/BF16
  weights never round-trip through HBM.

Block shapes obey Mosaic's rule that a block's last two dims are (8, 128)
multiples or whole; ``kernels/autotune.gemm_axes`` encodes it.  A tile-
scheme scales block (bk/2, bn/16) breaks that rule for any bn narrower
than 2048, and full-width blocks take minutes to compile (the unrolled
dequant grows with bn), so the wrapper hands the kernel the scales as
(N/bn, K/2, bn/16) column panels, whose (bk/2, bn/16) blocks are whole
along lanes.  That relayout is one small XLA transpose per call (1/16 of
the packed codes' bytes, read and written).

Grid: (M/bm, N/bn, K/bk), K innermost; f32 accumulation in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (as_bf16_bits, codebook_lookup,
                                  f16_bits_to_f32, resolve_interpret,
                                  unpack_codes)


def _kernel(x_ref, codes_ref, scales_ref, cb_ref, o_ref, acc_ref,
            *, nk: int, scheme: str, group_size: int):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = unpack_codes(codes_ref[...], packed=True)      # (bk, bn) int32
    vals = codebook_lookup(cb_ref[...], idx)
    if scheme == "tile":                                 # (bk//2, bn//16)
        s = f16_bits_to_f32(scales_ref[0])
        s = jnp.repeat(jnp.repeat(s, 2, axis=0), group_size // 2, axis=1)
    else:                                                # common: (bk//g, bn)
        s = jnp.repeat(f16_bits_to_f32(scales_ref[...]), group_size, axis=0)
    w = (vals * s).astype(x_ref.dtype)

    acc_ref[...] += jnp.dot(x_ref[...], w, preferred_element_type=jnp.float32)

    @pl.when(k_idx == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scheme", "group_size", "bm", "bn",
                                             "bk", "interpret", "out_dtype"))
def lut_dequant_gemm(x, codes, scales, codebook, *, scheme: str = "tile",
                     group_size: int = 32, bm: int = 128, bn: int = 256,
                     bk: int = 128, interpret: bool | None = None,
                     out_dtype=None):
    """x: (M, K) @ dequant(codes, scales, codebook): (K, N) -> (M, N).

    Block sizes are clipped to the GEMM shape and must divide it; pick
    Mosaic-legal ones with ``kernels/autotune.gemm_blocks``.
    ``interpret=None`` follows the backend.
    """
    interpret = resolve_interpret(interpret)
    M, K = x.shape
    Kc, Nh = codes.shape
    N = Nh * 2
    if Kc != K:
        raise ValueError(
            f"lut_dequant_gemm: codes have {Kc} rows but x has K={K} "
            f"columns (x {x.shape} vs codes {codes.shape})")
    out_dtype = out_dtype or x.dtype
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(
            f"lut_dequant_gemm: block sizes must divide the GEMM shape, "
            f"got (M, N, K) = ({M}, {N}, {K}) with "
            f"(bm, bn, bk) = ({bm}, {bn}, {bk})")
    nk = K // bk
    g = group_size
    scales = as_bf16_bits(scales)
    if scheme == "tile":     # column panels: (N/bn, K/2, bn/(g/2))
        sn = bn // (g // 2)
        scales = scales.reshape(K // 2, N // bn, sn).transpose(1, 0, 2)
        s_spec = pl.BlockSpec((1, bk // 2, sn), lambda i, j, k: (j, k, 0))
    else:
        s_spec = pl.BlockSpec((bk // g, bn), lambda i, j, k: (k, j))

    grid = (M // bm, N // bn, nk)
    kern = functools.partial(_kernel, nk=nk, scheme=scheme, group_size=g)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn // 2), lambda i, j, k: (k, j)),
            s_spec,
            pl.BlockSpec((1, 16), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, codes, scales, codebook.reshape(1, 16))


# ---------------------------------------------------------------------------
# Dequant-only variant over KV token slabs (the vlut16 story applied to
# gathered quantized-KV views, e.g. the partial-prefill prefix gather)
# ---------------------------------------------------------------------------


def _kv_kernel(codes_ref, scales_ref, cb_ref, o_ref, *, mode: str,
               n_heads: int, gr: int, gc: int):
    """One (br, Hkv·Dc) slab block -> (br, Hkv·D), head by head: each head's
    codes and its covering scale-tile row are lane slices of the 2-D
    view."""
    dc = codes_ref.shape[1] // n_heads
    d = o_ref.shape[1] // n_heads
    sd = d // gc
    for h in range(n_heads):
        vals = unpack_codes(codes_ref[:, h * dc:(h + 1) * dc],
                            packed=mode == "q4")
        if mode == "q4":
            vals = codebook_lookup(cb_ref[...], vals)
        else:
            vals = vals.astype(jnp.float32)
        hs = h // gr
        s = f16_bits_to_f32(scales_ref[:, hs * sd:(hs + 1) * sd])
        o_ref[:, h * d:(h + 1) * d] = (
            vals * jnp.repeat(s, gc, axis=-1)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "gr", "gc", "br",
                                             "interpret", "out_dtype"))
def lut_dequant_kv(codes, scales, codebook, *, mode: str, gr: int, gc: int,
                   br: int = 256, interpret: bool | None = None,
                   out_dtype=jnp.float32):
    """Dequantize (R, Hkv, Dc) KV token-slab codes with (R, Hkv//gr,
    D//gc) fp16 tile scales to (R, Hkv, D) — the kernel twin of
    ``repro.serving.kv_quant.dequantize_kv`` (same unpack, codebook
    lookup, scale broadcast and multiply per element, so the outputs are
    bit-identical).  The kernel sees 2-D (R, Hkv·Dc) / (R, Hs·Ds) views,
    whose whole-width lane dims satisfy Mosaic's block rule; the grid
    walks R in ``br``-row blocks (a multiple of 8, or all of R).
    ``interpret=None`` follows the backend.
    """
    interpret = resolve_interpret(interpret)
    R, H, Dc = codes.shape
    D = Dc * 2 if mode == "q4" else Dc
    Hs, Ds = scales.shape[-2:]
    br = min(br, R)
    if R % br:
        raise ValueError(f"lut_dequant_kv: row block br={br} must divide "
                         f"the {R} gathered token slabs")
    kern = functools.partial(_kv_kernel, mode=mode, n_heads=H, gr=gr, gc=gc)
    out = pl.pallas_call(
        kern,
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, H * Dc), lambda i: (i, 0)),
            pl.BlockSpec((br, Hs * Ds), lambda i: (i, 0)),
            pl.BlockSpec((1, 16), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, H * D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, H * D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(codes.reshape(R, H * Dc), as_bf16_bits(scales).reshape(R, Hs * Ds),
      codebook.reshape(1, 16))
    return out.reshape(R, H, D)
