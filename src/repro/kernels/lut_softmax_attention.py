"""Pallas TPU kernel: FP16 FlashAttention with LUT-based exp (paper Alg. 1).

Faithful port of the paper's §5.2.1 design:

* S, P, m, l are FP16; QKᵀ / rowsum(P) / O-accumulation are FP32
  (``AccumType=FP32`` in Alg. 1);
* ``exp`` is a table lookup into a 2^15-entry FP16 table: safe softmax
  guarantees the argument x = s − m ≤ 0, so the sign bit is constant and
  the low 15 bits of the FP16 pattern index the table (the paper's
  "ignore the MSB, left-shift by one" trick, §5.2.1);
* the same table also yields the correction factor e^{m_prev − m_new}
  (Alg. 1 lines 5–6);
* the table is precomputed once at FP32+ precision (paper: "floating-point
  numbers with a width of 32 bits or higher"), so LUT-exp is *more*
  accurate than an in-kernel FP16 polynomial.

The kernel also exposes ``exp_mode='poly'|'exact'`` re-implementing the
paper's Fig. 14 ablation baselines (FP16 polynomial exp2, FP32 exp).

Grid: (B*Hq, nq, nkv), kv innermost; m/l/acc live in VMEM scratch.
The table (64 KiB) sits in VMEM — 0.05% of a v5e core's ~128 MiB, the
analogue of the paper's 0.8%-of-TCM budget.

The kernel runs interpreted only.  Mosaic on TPU v5e refuses what Alg. 1
is built from: fp16 vector loads, f32 -> fp16 casts and fp16 max
reductions ("Unsupported element type for the selected reduction"), and
a gather from a 32768-entry table (only 2-D gathers within one vreg
exist: "Only 2D gather is supported").  Compiling it therefore raises
(``FP16_LUT_REFUSED``) instead of substituting another softmax.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import require_interpret, resolve_interpret

NEG_CAP = -30000.0  # finite "-inf" in fp16 range; LUT(e^{-30000}) == 0

LUT_SIZE = 32768


# why compiling the fp16 / LUT-softmax recurrence raises
FP16_LUT_REFUSED = ("Mosaic on TPU refuses fp16 vectors (loads, f32->fp16 "
                    "casts, max reductions) and gathers from the "
                    "32768-entry exp table (only in-vreg 2-D gathers are "
                    "supported)")


def build_exp_lut(dtype=jnp.float16) -> jnp.ndarray:
    """LUT[i] = exp(x) where x is the fp16 with bit pattern (0x8000 | i).

    Index = low 15 bits of the fp16 argument (which is ≤ 0 under safe
    softmax). Entries whose pattern decodes to -inf/NaN hold 0 — exp(-inf).
    Intermediates are computed in f32 (the paper's accuracy argument).
    """
    bits = (jnp.arange(LUT_SIZE, dtype=jnp.uint32) | 0x8000).astype(jnp.uint16)
    x = jax.lax.bitcast_convert_type(bits, jnp.float16).astype(jnp.float32)
    vals = jnp.exp(x)
    vals = jnp.where(jnp.isfinite(x), vals, 0.0)  # -inf and NaN patterns -> 0
    return vals.astype(dtype).reshape(1, LUT_SIZE)


def _lut_exp(lut, x16):
    """x16: fp16 (≤ 0). Returns fp16 exp via 15-bit table index."""
    bits = jax.lax.bitcast_convert_type(x16, jnp.uint16)
    idx = jnp.bitwise_and(bits.astype(jnp.int32), 0x7FFF)
    return jnp.take(lut[0], idx, axis=0)


def _poly_exp(x16):
    """FP16 polynomial exp2 baseline (paper's conventional approach):
    exp(x) = 2^{x·log2e}; split y into integer k and fraction f, 2^f by a
    degree-4 Taylor/minimax polynomial, scale by 2^k."""
    y = x16.astype(jnp.float32) * 1.4426950408889634
    k = jnp.floor(y)
    f = y - k
    ln2 = 0.6931471805599453
    t = f * ln2
    p = 1.0 + t * (1.0 + t * (0.5 + t * (1.0 / 6.0 + t * (1.0 / 24.0))))
    return jnp.ldexp(p, k.astype(jnp.int32)).astype(jnp.float16)


def _kernel(q_ref, k_ref, v_ref, lut_ref, o_ref, acc_ref, m_ref, l_ref,
            *, nkv: int, scale: float, causal: bool, bq: int, bkv: int,
            exp_mode: str):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_CAP)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                    # (bq, d)
    k = k_ref[0]                                    # (bkv, d)
    v = v_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qi = pl.program_id(1)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = jnp.where(kpos <= qpos, s, NEG_CAP)

    s16 = s.astype(jnp.float16)                     # S in FP16 (Alg. 1)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s16, axis=-1, keepdims=True))
    x = s16 - m_new                                 # ≤ 0 by construction
    if exp_mode == "lut":
        p = _lut_exp(lut_ref, x)                    # FP16 P via table
        corr = _lut_exp(lut_ref, m_prev - m_new)
    elif exp_mode == "poly":
        p = _poly_exp(x)
        corr = _poly_exp(m_prev - m_new)
    else:  # exact f32
        p = jnp.exp(x.astype(jnp.float32)).astype(jnp.float16)
        corr = jnp.exp((m_prev - m_new).astype(jnp.float32)).astype(jnp.float16)

    corr_f = corr.astype(jnp.float32)
    l_ref[...] = (l_ref[...] * corr_f +
                  jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True))
    pv = jax.lax.dot_general(p, v.astype(jnp.float16),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr_f + pv
    m_ref[...] = m_new

    @pl.when(j == nkv - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bkv",
                                             "interpret", "exp_mode"))
def lut_softmax_attention(q, k, v, lut, *, causal: bool = True, bq: int = 128,
                          bkv: int = 128, interpret: bool | None = None,
                          exp_mode: str = "lut"):
    """q: (BH, Sq, D) fp16; k, v: (BH, Skv, D) fp16 (kv heads pre-expanded).

    Returns (BH, Sq, D) fp16. GQA head mapping is done by the ops wrapper.
    ``interpret=None`` follows the backend; compiling raises (see the
    module docstring).
    """
    interpret = resolve_interpret(interpret)
    require_interpret(interpret, "lut_softmax_attention", FP16_LUT_REFUSED)
    BH, Sq, D = q.shape
    _, Skv, _ = k.shape
    bq, bkv = min(bq, Sq), min(bkv, Skv)
    if Sq % bq or Skv % bkv:
        raise ValueError(
            f"lut_softmax_attention: block sizes must divide the sequence "
            f"lengths, got Sq={Sq} with bq={bq} (Sq % bq = {Sq % bq}) and "
            f"Skv={Skv} with bkv={bkv} (Skv % bkv = {Skv % bkv})")
    nq, nkv = Sq // bq, Skv // bkv
    scale = 1.0 / math.sqrt(D)

    kern = functools.partial(_kernel, nkv=nkv, scale=scale, causal=causal,
                             bq=bq, bkv=bkv, exp_mode=exp_mode)
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, LUT_SIZE), lambda b, i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), jnp.float16),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float16),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, lut)
