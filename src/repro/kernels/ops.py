"""Jit'd public wrappers around the Pallas kernels.

Every kernel follows the backend (``repro.kernels.common.resolve_interpret``):
interpreted on CPU, where the tests check it against ``kernels/ref.py``,
and compiled by Mosaic on TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import autotune as _autotune
from repro.kernels import lut_dequant_gemm as _gemm
from repro.kernels import lut_softmax_attention as _attn
from repro.kernels import paged_attention as _paged
from repro.kernels import tile_quantize as _tq
from repro.quant import tile_quant as TQ

# Kernel-dispatch recording hook (serving/profiling.KernelProfiler).
# ``hook(name, flops, hbm_bytes)`` fires once per wrapper call with the
# analytic cost from ``kernels/autotune`` — for jitted callers that means
# at *trace* time, which is exactly what the profiler wants: it caches
# each phase's op roster at trace time and replays it on cached-
# executable steps.  None (the default) is zero overhead.
_OP_HOOK = None


def set_op_hook(hook):
    """Install the dispatch-layer cost hook; returns the previous one so
    callers can restore it (``set_op_hook(None)`` disables)."""
    global _OP_HOOK
    prev, _OP_HOOK = _OP_HOOK, hook
    return prev


def record_op(name: str, flops: float, hbm_bytes: float) -> None:
    """Report one op's analytic (flops, hbm_bytes) to the installed hook.
    Public so dispatch sites outside this module — e.g. the XLA fallback
    branch of ``layers.paged_decode_attention`` — attribute through the
    same funnel."""
    if _OP_HOOK is not None:
        _OP_HOOK(name, float(flops), float(hbm_bytes))


def pool_slab_bytes(pool_leaf) -> float:
    """Storage bytes of one token's (Hkv, D) slab in a per-layer pool
    leaf ``(n_blocks, bs, Hkv, D)`` — codes + scales for quantized
    {"codes", "scales"} leaves, dtype bytes for fp arrays."""
    if isinstance(pool_leaf, dict):
        c, s = pool_leaf["codes"], pool_leaf["scales"]
        return float(c.shape[-2] * c.shape[-1] * c.dtype.itemsize
                     + s.shape[-2] * s.shape[-1] * s.dtype.itemsize)
    return float(pool_leaf.shape[-2] * pool_leaf.shape[-1]
                 * pool_leaf.dtype.itemsize)


_EXP_LUT = None


def exp_lut():
    global _EXP_LUT
    if _EXP_LUT is None:
        # built eagerly even when first requested under a jit trace (e.g.
        # inside the engine's scanned decode step) — caching a traced
        # value here would leak the tracer into every later caller
        with jax.ensure_compile_time_eval():
            _EXP_LUT = _attn.build_exp_lut()
    return _EXP_LUT


def plan_lut_dequant_matmul(qw: dict, *, m: int, group_size: int = 32):
    """Resolve scheme inference and block-size selection once for a fixed
    (M, K, N) and return a callable ``x -> x @ dequant(qw)``.

    The returned closure goes straight to the jitted kernel — no per-call
    Python scheme/shape work, which is what hot loops (and fair timed
    ablations, see ``benchmarks/kernel_ablation.fig15_dequant_gemm``)
    should pay."""
    codes, scales, codebook = qw["codes"], qw["scales"], qw["codebook"]
    scheme = TQ.infer_scheme(qw, group_size)
    K = codes.shape[0]
    N = codes.shape[1] * 2
    bm, bn, bk = _autotune.gemm_blocks(m, K, N, scheme=scheme,
                                       group_size=group_size)

    def run(x):
        record_op("lut_dequant_matmul", *_autotune.gemm_cost(m, K, N))
        return _gemm.lut_dequant_gemm(
            x, codes, scales, codebook, scheme=scheme,
            group_size=group_size, bm=bm, bn=bn, bk=bk)

    return run


def lut_dequant_matmul(x, qw: dict, *, group_size: int = 32):
    """x: (M, K); qw: quantized-weight leaf dict -> (M, N)."""
    return plan_lut_dequant_matmul(qw, m=x.shape[0],
                                   group_size=group_size)(x)


def flash_attention(q, k, v, *, causal: bool = True, exp_mode: str = "lut",
                    bq: int = 128, bkv: int = 128):
    """LUT-softmax FlashAttention with GQA support.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) — any fp dtype, computed in
    fp16 per Alg. 1. Returns (B, Sq, Hq, D) in q.dtype.
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    qt = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D).astype(jnp.float16)
    kt = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(
        B * Hq, Skv, D).astype(jnp.float16)
    vt = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(
        B * Hq, Skv, D).astype(jnp.float16)
    bq_pick, bkv_pick = _autotune.attn_blocks(B * Hq, Sq, Skv, D,
                                              bq_target=bq, bkv_target=bkv)
    record_op("flash_attention", *_autotune.attn_cost(B * Hq, Sq, Skv, D))
    o = _attn.lut_softmax_attention(
        qt, kt, vt, exp_lut(), causal=causal,
        bq=bq_pick, bkv=bkv_pick, exp_mode=exp_mode)
    return o.reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3).astype(q.dtype)


def paged_flash_decode(q, k_pool, v_pool, table, cache_len, *,
                       window: int = 0, softcap: float = 0.0,
                       exp_mode: str = "exact"):
    """Paged decode attention through the block-table-walking kernel.

    q: (B, 1, Hq, D); pools: (n_blocks, bs, Hkv, D) fp arrays *or*
    tile-quantized {"codes", "scales"} leaf dicts (``repro.serving.
    kv_quant``), which route to the fused-dequant kernel; table: (B, W)
    int32; cache_len: (B,) int32 including the current token.  Returns
    (B, 1, Hq, D) in q.dtype — drop-in for ``layers.paged_decode_attention``
    (the XLA gather fallback) on the TPU hot path.

    ``exp_mode='lut'`` runs the fp16 LUT-softmax recurrence (Alg. 1)
    inside the same table walk — block gather + VMEM dequant + LUT exp in
    one pass; ``'exact'`` keeps the f32 recurrence.
    """
    B, _, Hq, D = q.shape
    quantized = isinstance(k_pool, dict)
    Hkv = (k_pool["codes"] if quantized else k_pool).shape[2]
    G = Hq // Hkv
    if _OP_HOOK is not None:
        record_op("paged_flash_decode", *_autotune.paged_attn_cost(
            B, Hq, table.shape[1],
            (k_pool["codes"] if quantized else k_pool).shape[1], D,
            slab_bytes=pool_slab_bytes(k_pool)))
    qg = q.reshape(B, Hkv, G, D)
    lut = exp_lut() if exp_mode == "lut" else None
    fn = _paged.quant_paged_attention if quantized else _paged.paged_attention
    o = fn(qg, k_pool, v_pool, table, cache_len, lut, window=window,
           softcap=softcap, exp_mode=exp_mode)
    return o.reshape(B, 1, Hq, D)


def lut_dequant_gather(gathered):
    """Dequantize a gathered quantized-pool view through the vlut16
    dequant kernel (identity on fp arrays).

    ``gathered``: {"codes", "scales"} leaf dict with arbitrary leading
    dims over the (Hkv, D) token slab — e.g. the (L, B, P, ...) prefix
    view of the engine's partial prefill.  Bit-identical to
    ``repro.serving.kv_quant.dequantize_kv`` (same unpack, codebook take,
    scale broadcast and multiply, per element), so swapping it into read
    paths cannot change greedy outputs.
    """
    if not isinstance(gathered, dict):
        return gathered
    from repro.quant.codebooks import get_codebook
    from repro.serving.kv_quant import Q4_CODEBOOK, kv_geometry

    mode, gr, gc, d = kv_geometry(gathered)
    codes, scales = gathered["codes"], gathered["scales"]
    lead = codes.shape[:-2]
    r = math.prod(lead) if lead else 1
    br = _autotune.dequant_rows(r, codes.shape[-2], d, mode)
    record_op("lut_dequant_kv",
              *_autotune.dequant_kv_cost(r, codes.shape[-2], d, mode))
    out = _gemm.lut_dequant_kv(
        codes.reshape(r, *codes.shape[-2:]),
        scales.reshape(r, *scales.shape[-2:]),
        get_codebook(Q4_CODEBOOK), mode=mode, gr=gr, gc=gc, br=br)
    return out.reshape(*lead, codes.shape[-2], d)


def tile_quantize_op(w, *, group_size: int = 32):
    """Kernel-quantize a (K, N) weight -> quantized leaf dict."""
    K, N = w.shape
    bk, bn = _autotune.quantize_blocks(K, N)
    record_op("tile_quantize", *_autotune.quantize_cost(K, N))
    codes, scales = _tq.tile_quantize(
        w, group_size=group_size, bk=bk, bn=bn)
    from repro.quant.codebooks import get_codebook

    return {"codes": codes, "scales": scales, "codebook": get_codebook("q4_0")}
