"""Pallas TPU kernel: gather-based paged decode attention.

One query token per sequence attends against a *paged* KV cache: KV bytes
live in a shared block pool ``(n_blocks, bs, Hkv, D)`` and each sequence
maps logical positions to pool blocks through a block table (block ``w``
of a row holds positions ``[w·bs, (w+1)·bs)``).

The block table, the per-row lengths and the sliding window ride in as
**scalar-prefetch** arguments (``pltpu.PrefetchScalarGridSpec``; the
window is dynamic because the model threads per-layer windows through
the layer scan as traced int32): the grid walks ``(B, W)`` with the block
index innermost, and the K/V BlockSpec index maps dereference
``table[b, j]`` so the DMA engine fetches exactly the row's j-th block,
all ``Hkv`` heads of it at once (Mosaic requires a block's last two dims
to be (8, 128)-aligned or whole, and ``(Hkv, D)`` is whole); a static
loop over heads runs inside the kernel.  No (B, W·bs, …) gather is ever
materialized, which is the point: HBM traffic per step is the *live* KV,
not the ``max_len`` reservation.  Table padding points at the reserved
scratch block 0; blocks past a row's length skip their compute.

Online-softmax accumulation (m/l/acc in VMEM scratch) is plain FP32 by
default; ``exp_mode='lut'`` instead runs the fp16 LUT-softmax recurrence
of ``lut_softmax_attention`` (paper Alg. 1) inside the same table walk,
the exp LUT riding in as a broadcast input.  LUT mode runs interpreted
only: Mosaic refuses its fp16 vectors and its 32768-entry table gather,
so compiling it raises (``lut_softmax_attention.FP16_LUT_REFUSED``).
The identical-semantics XLA fallback is
``repro.models.layers.paged_decode_attention``; the pure-jnp oracles are
``repro.kernels.ref.paged_decode_attention_ref`` (exact) and
``ref.lut_paged_decode_attention_ref`` (fp16/LUT recurrence).

Fully-masked positions are guarded: ``p`` is zeroed on them, so
``m_new == m_prev == -inf`` can never turn ``exp(0) == 1`` into
scratch-garbage accumulation — a zero-length row returns exactly 0.

:func:`quant_paged_attention` is the same walk over a *tile-quantized*
pool (``repro.serving.kv_quant``): the BlockSpec index maps dereference
the table for the codes **and** the per-(2, 16)-tile scales — both
unit-stride by construction, the §5.1 layout story applied to KV — and
dequantization happens per block in VMEM (int8 scale-multiply for Q8, a
16-entry codebook select chain for packed Q4, the vlut16 analogue) right
before the Q·Kᵀ dot.  The fp16 scales enter as bf16-typed bits and are
decoded in-kernel (``repro.kernels.common``).  HBM traffic per step is
therefore the *quantized* live KV.  Oracle:
``ref.quant_paged_decode_attention_ref``; XLA fallback: the same
``layers.paged_decode_attention`` dispatching on the pool's leaf dicts.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (as_bf16_bits, codebook_lookup,
                                  f16_bits_to_f32, require_interpret,
                                  resolve_interpret, unpack_codes)
from repro.kernels.lut_softmax_attention import (FP16_LUT_REFUSED, NEG_CAP,
                                                 _lut_exp)

NEG_INF = -1e30


def _block_mask(shape, seq_len, w, j, block_size):
    """(G, bs) validity of block ``j``'s kv positions for a row of length
    ``seq_len``.  The window ``w`` rides in as a scalar-prefetch value
    (<= 0 = unbounded) because the model threads per-layer windows
    through the layer scan as traced int32."""
    q_pos = seq_len - 1
    kv_pos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    valid = kv_pos < seq_len
    valid &= (w <= 0) | (q_pos - kv_pos < w)
    return valid


def _softmax_update(s, valid, v, lut_ref, m_prev, l_prev, acc_prev, *,
                    exp_mode: str):
    """One head's online-softmax accumulation over one block; returns the
    new (m, l, acc).

    ``'exact'`` is the f32 recurrence; ``'lut'`` the fp16 Alg. 1
    recurrence with table-lookup exp (m is fp16 there).  Both zero ``p``
    on masked positions.
    """
    if exp_mode == "lut":
        s16 = jnp.where(valid, s, NEG_CAP).astype(jnp.float16)
        m_new = jnp.maximum(m_prev, jnp.max(s16, axis=-1, keepdims=True))
        p = _lut_exp(lut_ref, s16 - m_new)
        corr = _lut_exp(lut_ref, m_prev - m_new).astype(jnp.float32)
        v = v.astype(jnp.float16)
    else:
        sm = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(sm, axis=-1, keepdims=True))
        p = jnp.exp(sm - m_new)
        corr = jnp.exp(m_prev - m_new)
        v = v.astype(jnp.float32)
    p = jnp.where(valid, p, jnp.zeros_like(p))
    l_new = l_prev * corr + jnp.sum(p.astype(jnp.float32), axis=-1,
                                    keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return m_new, l_new, acc_prev * corr + pv


def _walk(len_ref, win_ref, q_ref, lut_ref, o_ref, acc_ref, m_ref, l_ref,
          kv_fn, *, n_blk: int, block_size: int, scale: float,
          softcap: float, exp_mode: str):
    """Grid step (b, j): fold pool block ``table[b, j]`` into every head's
    running softmax.  ``kv_fn(h)`` loads head ``h``'s (bs, D) K and V."""
    b, j = pl.program_id(0), pl.program_id(1)
    n_heads, G, _ = acc_ref.shape

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(
            m_ref, NEG_CAP if exp_mode == "lut" else NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = len_ref[b]

    @pl.when(j * block_size < seq_len)
    def _block():
        valid = _block_mask((G, block_size), seq_len, win_ref[0], j,
                            block_size)
        for h in range(n_heads):
            k, v = kv_fn(h)
            q = q_ref[0, h]                              # (G, D)
            if k.dtype == jnp.float32:
                q = q.astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            m, l, acc = _softmax_update(s, valid, v, lut_ref, m_ref[h],
                                        l_ref[h], acc_ref[h],
                                        exp_mode=exp_mode)
            m_ref[h], l_ref[h], acc_ref[h] = m, l, acc

    @pl.when(j == n_blk - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _kernel(table_ref, len_ref, win_ref, q_ref, k_ref, v_ref, *rest,
            exp_mode: str, **kw):
    lut_ref, rest = (rest[0], rest[1:]) if exp_mode == "lut" else (None,
                                                                   rest)

    def kv(h):
        return k_ref[0, :, h, :], v_ref[0, :, h, :]

    _walk(len_ref, win_ref, q_ref, lut_ref, *rest, kv, exp_mode=exp_mode,
          **kw)


def _check_exp_mode(lut, exp_mode: str, interpret: bool) -> list:
    """The extra kernel operands for ``exp_mode``: ``[lut]`` under
    ``'lut'`` (required, interpret only), none under ``'exact'``."""
    if exp_mode not in ("exact", "lut"):
        raise ValueError(f"exp_mode must be 'exact' or 'lut', "
                         f"got {exp_mode!r}")
    if exp_mode == "exact":
        return []
    require_interpret(interpret, "paged decode attention with "
                      "exp_mode='lut'", FP16_LUT_REFUSED)
    if lut is None:
        raise ValueError("exp_mode='lut' needs the exp LUT "
                         "(repro.kernels.ops.exp_lut())")
    return [lut]


def _call(kern, scalars, q, operands, specs, *, n_blk: int,
          exp_mode: str, interpret: bool):
    """Shared pallas_call over the (B, W) table walk."""
    B, Hkv, G, D = q.shape
    head_spec = pl.BlockSpec((1, Hkv, G, D),
                             lambda b, j, tbl, lens, win: (b, 0, 0, 0))
    if exp_mode == "lut":
        specs = specs + [pl.BlockSpec(
            (1, operands[-1].shape[1]),
            lambda b, j, tbl, lens, win: (0, 0))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_blk),
        in_specs=[head_spec] + specs,
        out_specs=head_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, 1),
                       jnp.float16 if exp_mode == "lut" else jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*scalars, q, *operands)


def _scalars(table, lengths, window):
    return (table.astype(jnp.int32), lengths.astype(jnp.int32),
            jnp.asarray(window, jnp.int32).reshape(1))


def _pool_spec(block_shape):
    """A per-block operand spec: the index map dereferences the table."""
    return pl.BlockSpec((1, *block_shape),
                        lambda b, j, tbl, lens, win: (tbl[b, j], 0, 0, 0))


@functools.partial(jax.jit, static_argnames=("softcap", "interpret",
                                             "exp_mode"))
def paged_attention(q, k_pool, v_pool, table, lengths, lut=None, *,
                    window=0, softcap: float = 0.0,
                    interpret: bool | None = None, exp_mode: str = "exact"):
    """q: (B, Hkv, G, D); pools: (n_blocks, bs, Hkv, D); table: (B, W)
    int32 block ids (padding = scratch block 0); lengths: (B,) int32
    including the current token.  Returns (B, Hkv, G, D) in q.dtype.
    ``window`` may be a python int or a traced int32 scalar (the model's
    per-layer windows ride through the layer scan); <= 0 = unbounded.

    ``exp_mode='lut'`` runs the fp16 LUT-softmax recurrence; ``lut`` is
    then the (1, 32768) exp table (``lut_softmax_attention.build_exp_lut``)
    riding in as a broadcast input.  ``interpret=None`` follows the
    backend (``repro.kernels.common.resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    extra = _check_exp_mode(lut, exp_mode, interpret)
    D = q.shape[-1]
    _, bs, Hkv, _ = k_pool.shape
    W = table.shape[1]
    kern = functools.partial(_kernel, n_blk=W, block_size=bs,
                             scale=1.0 / math.sqrt(D), softcap=softcap,
                             exp_mode=exp_mode)
    kv_spec = _pool_spec((bs, Hkv, D))
    return _call(kern, _scalars(table, lengths, window), q,
                 [k_pool, v_pool, *extra], [kv_spec, kv_spec], n_blk=W,
                 exp_mode=exp_mode, interpret=interpret)


# ---------------------------------------------------------------------------
# Quantized-pool variant: per-block VMEM dequant fused into the table walk
# ---------------------------------------------------------------------------


def _dequant_head(codes_ref, scales_ref, cb_ref, h: int, *, mode: str,
                  gr: int, gc: int):
    """Head ``h`` of one pool block -> (bs, D) f32.  The head's codes and
    its covering tile row of scales are strided loads of the block; the
    scale broadcast along dims is one lane repeat — no scatter, by
    construction."""
    vals = unpack_codes(codes_ref[0, :, h, :], packed=mode == "q4")
    if mode == "q4":
        vals = codebook_lookup(cb_ref[...], vals)
    else:
        vals = vals.astype(jnp.float32)
    s = f16_bits_to_f32(scales_ref[0, :, h // gr, :])     # (bs, D // gc)
    return vals * jnp.repeat(s, gc, axis=-1)


def _quant_kernel(table_ref, len_ref, win_ref, q_ref, kc_ref, ks_ref, vc_ref,
                  vs_ref, cb_ref, *rest, mode: str, gr: int, gc: int,
                  exp_mode: str, **kw):
    lut_ref, rest = (rest[0], rest[1:]) if exp_mode == "lut" else (None,
                                                                   rest)
    deq = functools.partial(_dequant_head, cb_ref=cb_ref, mode=mode, gr=gr,
                            gc=gc)

    def kv(h):
        return deq(kc_ref, ks_ref, h=h), deq(vc_ref, vs_ref, h=h)

    _walk(len_ref, win_ref, q_ref, lut_ref, *rest, kv, exp_mode=exp_mode,
          **kw)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret",
                                             "exp_mode"))
def quant_paged_attention(q, k_pool, v_pool, table, lengths, lut=None, *,
                          window=0, softcap: float = 0.0,
                          interpret: bool | None = None,
                          exp_mode: str = "exact"):
    """Paged decode attention over a tile-quantized block pool.

    q: (B, Hkv, G, D); ``k_pool``/``v_pool``: {"codes", "scales"} leaf
    dicts per ``repro.serving.kv_quant`` — codes (n_blocks, bs, Hkv, Dc)
    int8 (q8) or packed uint8 (q4), scales (n_blocks, bs, Hkv//gr, D//gc)
    fp16; table: (B, W) int32 block ids; lengths: (B,) int32 including the
    current token.  Returns (B, Hkv, G, D) in q.dtype.  Geometry is
    inferred from the leaf shapes (static under jit).

    ``exp_mode='lut'`` fuses the fp16 LUT softmax onto the same walk
    (interpret only, see :func:`paged_attention`).
    """
    from repro.quant.codebooks import get_codebook
    from repro.serving.kv_quant import Q4_CODEBOOK, kv_geometry

    interpret = resolve_interpret(interpret)
    extra = _check_exp_mode(lut, exp_mode, interpret)
    D = q.shape[-1]
    codes = k_pool["codes"]
    _, bs, Hkv, dc = codes.shape
    mode, gr, gc, _ = kv_geometry(k_pool)
    hs, sd = k_pool["scales"].shape[-2:]
    W = table.shape[1]
    cb = get_codebook(Q4_CODEBOOK).reshape(1, 16)    # unused under q8
    kern = functools.partial(_quant_kernel, n_blk=W, block_size=bs,
                             scale=1.0 / math.sqrt(D), softcap=softcap,
                             mode=mode, gr=gr, gc=gc, exp_mode=exp_mode)
    code_spec = _pool_spec((bs, Hkv, dc))
    scale_spec = _pool_spec((bs, hs, sd))
    cb_spec = pl.BlockSpec((1, 16), lambda b, j, tbl, lens, win: (0, 0))
    return _call(kern, _scalars(table, lengths, window), q,
                 [k_pool["codes"], as_bf16_bits(k_pool["scales"]),
                  v_pool["codes"], as_bf16_bits(v_pool["scales"]), cb,
                  *extra],
                 [code_spec, scale_spec, code_spec, scale_spec, cb_spec],
                 n_blk=W, exp_mode=exp_mode, interpret=interpret)
