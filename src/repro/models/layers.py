"""Shared model building blocks.

Everything is expressed as pure functions over parameter pytrees (nested
dicts of jnp arrays), so the same definitions serve training, prefill and
decode, and lower cleanly under pjit on the production mesh.

Attention is implemented as a *chunked online-softmax* ("flash"-style) scan
so that prefill_32k / train_4k never materialize S×S score matrices in the
lowered HLO.  The Pallas LUT-softmax kernel (`repro.kernels.
lut_softmax_attention`) is the TPU hot path with identical semantics; this
file is the XLA path used for dry-runs and CPU execution.

Each layer of a program runs under a ``jax.named_scope`` (``attention``
with its ``kv_write``, ``mlp``; ``lm_head`` and ``sampler`` outside the
layer loop), so its operations carry the scope in their HLO op names and
a profiler trace can split device time by layer.  Scopes change only
metadata: the computation is the same.
"""
from __future__ import annotations

import functools
import math
import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig



def scoped(name: str):
    """Decorator: run the function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


# ---------------------------------------------------------------------------
# Initialization helpers
# ---------------------------------------------------------------------------


def _dense_init(key, in_dim: int, out_shape, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(in_dim)
    return jax.random.normal(key, (in_dim, *out_shape), dtype=jnp.float32).astype(dtype) * scale


def init_linear(key, in_dim: int, out_dim: int, *, bias: bool, dtype) -> dict:
    p = {"w": _dense_init(key, in_dim, (out_dim,), dtype)}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def linear(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Dense layer. ``p`` may hold a plain weight or a quantized weight.

    Quantized weights (produced by ``repro.quant``) are dicts with a
    ``codes`` entry; they are dequantized in-graph (XLA path) or via the
    Pallas LUT kernel (TPU path) by ``repro.quant.qlinear.apply``.
    """
    w = p["w"]
    if isinstance(w, dict):  # quantized
        from repro.quant.qlinear import quantized_matmul

        y = quantized_matmul(x, w)
    else:
        y = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding. x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (XLA path)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _attn_mask(q_pos, kv_pos, *, causal: bool, window, kv_len=None):
    """(..., Sq, Skv) boolean validity mask.

    ``window`` may be a Python int or a traced scalar (per-layer windows are
    threaded through the layer scan); window <= 0 means unbounded.
    """
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], kv_pos.shape[-1]), bool)
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    if causal:
        m &= kp <= qp
    w = jnp.asarray(window, jnp.int32)
    m &= (w <= 0) | (qp - kp < w)
    if kv_len is not None:
        m &= kp < kv_len[..., None, None]
    return m


def _gqa_scores(q, k, scale):
    """q: (B, Sq, Hkv, G, D); k: (B, Skv, Hkv, D) -> (B, Hkv, G, Sq, Skv) f32."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32) * scale


def _softcap(s, cap: float):
    if cap:
        s = jnp.tanh(s / cap) * cap
    return s


def chunked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    kv_len: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Flash-style attention via a double scan over q- and kv-chunks.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D). Returns (B, Sq, Hq, D).
    Never materializes more than (B, Hq, q_chunk, kv_chunk) scores.
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    # pad to chunk multiples (e.g. whisper's 1500 encoder frames); padded
    # KV is masked via kv_len, padded Q rows are sliced off the output.
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    Sq_orig, Skv_orig = Sq, Skv
    q_positions = jnp.broadcast_to(q_positions, (B, Sq))
    kv_positions = jnp.broadcast_to(kv_positions, (B, Skv))
    if Sq % q_chunk:
        pq = q_chunk - Sq % q_chunk
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pq)))
        Sq += pq
    if Skv % kv_chunk:
        pk = kv_chunk - Skv % kv_chunk
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        # pad kv positions with a sentinel past every reachable position
        # (not 0: callers pass *semantic* positions — a 0-padded slot would
        # alias the real position 0 and slip through the kv_len mask)
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pk)),
                               constant_values=jnp.iinfo(jnp.int32).max // 2)
        Skv += pk
        if kv_len is None:
            kv_len = jnp.full((B,), Skv_orig, jnp.int32)
        else:
            kv_len = jnp.minimum(kv_len, Skv_orig)
    nq = Sq // q_chunk
    nkv = Skv // kv_chunk

    qg = q.reshape(B, nq, q_chunk, Hkv, G, D)
    qg = jnp.moveaxis(qg, 1, 0)  # (nq, B, qc, Hkv, G, D)
    qp = jnp.moveaxis(q_positions.reshape(B, nq, q_chunk), 1, 0)  # (nq, B, qc)

    kg = jnp.moveaxis(k.reshape(B, nkv, kv_chunk, Hkv, D), 1, 0)
    vg = jnp.moveaxis(v.reshape(B, nkv, kv_chunk, Hkv, D), 1, 0)
    kp = jnp.moveaxis(kv_positions.reshape(B, nkv, kv_chunk), 1, 0)  # (nkv, B, kc)

    def q_step(_, qc):
        qi, qpi = qc  # (B, qc, Hkv, G, D), (B, qc)

        def kv_step(carry, kc):
            o, m, l = carry
            ki, vi, kpi = kc
            s = _gqa_scores(qi, ki, scale)  # (B, Hkv, G, qc, kc) f32
            s = _softcap(s, softcap)
            mask = _attn_mask(qpi, kpi, causal=causal, window=window, kv_len=kv_len)
            s = jnp.where(mask[:, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vi.dtype), vi,
                            preferred_element_type=jnp.float32)
            o = o * corr[..., None] + pv
            return (o, m_new, l), None

        o0 = jnp.zeros((B, Hkv, G, qi.shape[1], D), jnp.float32)
        m0 = jnp.full((B, Hkv, G, qi.shape[1]), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qi.shape[1]), jnp.float32)
        # Checkpoint each KV block: backward recomputes the (qc, kc) score
        # tile instead of saving it — the flash-attention backward memory
        # pattern (saved state per block = the small (o, m, l) carry only).
        (o, m, l), _ = jax.lax.scan(jax.checkpoint(kv_step), (o0, m0, l0),
                                    (kg, vg, kp))
        o = o / jnp.maximum(l[..., None], 1e-30)
        # (B, Hkv, G, qc, D) -> (B, qc, Hkv*G, D)
        o = jnp.moveaxis(o, 3, 1).reshape(B, qi.shape[1], Hq, D)
        return None, o.astype(q.dtype)

    if nq == 1:
        _, o = q_step(None, (qg[0], qp[0]))
        return o[:, :Sq_orig]
    _, os = jax.lax.scan(q_step, None, (qg, qp))
    return jnp.moveaxis(os, 0, 1).reshape(B, Sq, Hq, D)[:, :Sq_orig]


def ring_slot_positions(slots, cache_len, ring_size: int):
    """Token position held by each ring-cache slot.

    slot i of a ring of W entries holds the most recent position p ≤
    cache_len-1 with p ≡ i (mod W); p < 0 means "slot not yet written".
    slots: (..., S) int; cache_len: (...,) broadcastable."""
    last = cache_len - 1
    return last - ((last - slots) % ring_size)


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    *,
    cache_len: jnp.ndarray,
    window: int = 0,
    softcap: float = 0.0,
    ring: bool = False,
) -> jnp.ndarray:
    """Single-step attention against a KV cache.

    q: (B, 1, Hq, D); caches: (B, S, Hkv, D); cache_len: (B,) int32 (length
    *including* the current token, whose K/V has already been written).
    ``ring``: cache is a circular buffer of S slots (slot = pos % S).
    """
    B, _, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, 1, Hkv, G, D)
    s = _gqa_scores(qg, k_cache, scale)[..., 0, :]  # (B, Hkv, G, S)
    s = _softcap(s, softcap)
    q_pos = (cache_len - 1)[:, None]
    if ring:
        kv_pos = ring_slot_positions(jnp.arange(S)[None], cache_len[:, None], S)
        valid = kv_pos >= 0
    else:
        kv_pos = jnp.arange(S)[None]
        valid = kv_pos < cache_len[:, None]
    w = jnp.asarray(window, jnp.int32)
    valid &= (w <= 0) | (q_pos - kv_pos < w)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, D).astype(q.dtype)


PAGED_ATTN_IMPLS = ("xla", "kernel", "kernel_lut")
_PAGED_ATTN_IMPL = os.environ.get("REPRO_PAGED_ATTN", "xla")


def set_paged_attention_impl(impl: str) -> str:
    """Select the decode-attention backend for paged KV caches.

    ``"xla"`` (default): gather-then-attend fallback below, and XLA
    dequantization of the partial-prefill prefix gather.  ``"kernel"``:
    fused Pallas block-table walk (``repro.kernels.paged_attention``), and
    the prefix gather dequantized by ``lut_dequant_kv``.  ``"kernel_lut"``:
    the same with the fp16 LUT softmax (Alg. 1) fused in (interpreter
    only).  Returns the previous impl so callers can restore it.  Engines jit
    their step functions at construction time, so set this *before*
    building the engine (or via ``REPRO_PAGED_ATTN``).
    """
    global _PAGED_ATTN_IMPL
    if impl not in PAGED_ATTN_IMPLS:
        raise ValueError(f"unknown paged-attention impl {impl!r}; "
                         f"expected one of {PAGED_ATTN_IMPLS}")
    prev, _PAGED_ATTN_IMPL = _PAGED_ATTN_IMPL, impl
    return prev


def paged_attention_impl() -> str:
    """The selected paged-KV read backend (see
    :func:`set_paged_attention_impl`)."""
    return _PAGED_ATTN_IMPL


def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    *,
    table: jnp.ndarray,
    cache_len: jnp.ndarray,
    window: int = 0,
    softcap: float = 0.0,
) -> jnp.ndarray:
    """Single-step attention against a paged (block-pooled) KV cache.

    q: (B, 1, Hq, D); pools: (n_blocks, bs, Hkv, D); table: (B, W) int32
    block ids, position-ordered (block w of a row holds positions
    [w*bs, (w+1)*bs)); cache_len: (B,) int32 including the current token.

    XLA path: gather the row's blocks into a contiguous (B, W*bs, Hkv, D)
    view and reuse :func:`decode_attention` — padding entries point at the
    scratch block and land beyond ``cache_len``, so the standard length
    mask hides them.  Quantized pools ({"codes", "scales"} leaf dicts,
    see ``repro.serving.kv_quant``) gather codes and scales through the
    same table and dequantize the contiguous view before attending.  The
    Pallas kernel (`repro.kernels.paged_attention`) walks the table via
    scalar prefetch and dequantizes per block in VMEM instead of
    materializing the gather; this is the identical-semantics XLA
    fallback.
    """
    from repro.serving.kv_quant import dequantize_for_pool, pool_block_size

    impl = _PAGED_ATTN_IMPL
    if impl != "xla":
        if impl not in PAGED_ATTN_IMPLS:
            raise ValueError(f"unknown paged-attention impl {impl!r}; "
                             f"expected one of {PAGED_ATTN_IMPLS}")
        from repro.kernels import ops as _kops

        return _kops.paged_flash_decode(
            q, k_pool, v_pool, table, cache_len, window=window,
            softcap=softcap,
            exp_mode="lut" if impl == "kernel_lut" else "exact")

    B = q.shape[0]
    W = table.shape[1]
    bs = pool_block_size(k_pool)
    from repro.kernels import ops as _kops

    # attribute the gather+attend fallback through the same dispatch-hook
    # funnel as the kernels (the kernel branches above record inside
    # paged_flash_decode, so each call is counted exactly once)
    from repro.kernels import autotune as _autotune

    _kops.record_op("paged_attention_xla", *_autotune.paged_attn_cost(
        B, q.shape[2], W, bs, q.shape[3],
        slab_bytes=_kops.pool_slab_bytes(k_pool)))

    def gather(pool):
        seq = jax.tree.map(
            lambda a: a[table].reshape(B, W * bs, *a.shape[2:]), pool)
        return dequantize_for_pool(seq)

    return decode_attention(q, gather(k_pool), gather(v_pool),
                            cache_len=cache_len, window=window,
                            softcap=softcap)


def decode_attention_partial(q, k_cache, v_cache, *, valid, softcap=0.0):
    """Per-shard partial decode attention for sequence-parallel KV.

    Returns (o_unnormalized f32 (B,1,Hq,D), m (B,Hq), l (B,Hq)) so that the
    caller can combine shards with the distributed safe-softmax merge:
      m* = max_i m_i;  l* = sum_i l_i e^{m_i-m*};  o* = sum_i o_i e^{m_i-m*} / l*.
    """
    B, _, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, 1, Hkv, G, D)
    s = _gqa_scores(qg, k_cache, scale)[..., 0, :]  # (B, Hkv, G, S)
    s = _softcap(s, softcap)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return (o.reshape(B, 1, Hq, D),
            m.reshape(B, Hq),
            l.reshape(B, Hq))


# ---------------------------------------------------------------------------
# Attention block (projections + rope + attention)
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim()
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], d, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": init_linear(ks[1], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_linear(ks[2], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_linear(ks[3], cfg.n_heads * hd, d, bias=False, dtype=dtype),
    }


@scoped("attention")
def attention_block(
    p: dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    positions: jnp.ndarray,
    window: int,
    cache: Optional[dict] = None,
    cache_len: Optional[jnp.ndarray] = None,
    cross_kv: Optional[tuple] = None,
    causal: bool = True,
    prefix_kv: Optional[tuple] = None,
    prefix_positions: Optional[jnp.ndarray] = None,
):
    """Full attention block. Returns (out, new_cache_kv or None).

    - training/prefill: cache is None, chunked attention over x itself.
    - partial prefill (cross-request prefix cache): additionally
      prefix_kv = (k, v) of shape (B, P, Hkv, D) — already-rope'd KV of a
      cached prompt prefix — and prefix_positions (B, P), the prefix
      token positions with invalid slots pushed past every query position
      so the causal mask hides them.  ``x`` then holds only the uncached
      suffix (its ``positions`` start at the cached length) and queries
      attend over the concatenated prefix + suffix keys; only the
      suffix's K/V is returned for caching.  The prefix may be *ragged*
      across B rows (per-row cached lengths, P = the batch-max padded
      width): each row's offsets and masked prefix slots are independent,
      which is what lets the scheduler admit a whole batch of cache-hit
      requests through one call.
    - decode: cache = {"k","v"} (B, S, Hkv, D); writes current K/V at
      cache_len-1 then attends (batch-sharded layout).
    - paged decode: cache additionally holds "table" (B, W) int32 and the
      k/v leaves are block pools (n_blocks, bs, Hkv, D); the current K/V
      is scattered into (table[b, (cache_len-1)//bs], (cache_len-1)%bs)
      and attention gathers through the table.
    - cross attention (whisper decoder): cross_kv = (k, v) precomputed.
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim()
    q = linear(p["wq"], x).reshape(B, S, cfg.n_heads, hd)

    if cross_kv is not None:
        k, v = cross_kv
        q = q  # no rope in whisper cross-attn
        o = chunked_attention(
            q, k, v,
            q_positions=positions, kv_positions=jnp.arange(k.shape[1])[None],
            causal=False, window=0, softcap=cfg.logit_softcap,
        ) if cache is None else decode_attention(
            q, k, v, cache_len=jnp.full((B,), k.shape[1], jnp.int32),
            softcap=cfg.logit_softcap)
        out = linear(p["wo"], o.reshape(B, S, cfg.n_heads * hd))
        return out, None

    k = linear(p["wk"], x).reshape(B, S, cfg.n_kv_heads, hd)
    v = linear(p["wv"], x).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    if cache is None:
        if prefix_kv is not None:
            pk, pv = prefix_kv  # (B, P, Hkv, D), rope'd at pool-write time
            kv_pos = jnp.concatenate(
                [prefix_positions,
                 jnp.broadcast_to(positions, (B, S))], axis=1)
            o = chunked_attention(
                q, jnp.concatenate([pk.astype(k.dtype), k], axis=1),
                jnp.concatenate([pv.astype(v.dtype), v], axis=1),
                q_positions=positions, kv_positions=kv_pos,
                causal=causal, window=window, softcap=cfg.logit_softcap,
            )
        else:
            o = chunked_attention(
                q, k, v,
                q_positions=positions, kv_positions=positions,
                causal=causal, window=window, softcap=cfg.logit_softcap,
            )
        new_kv = (k, v)
    elif "table" in cache:
        # paged decode: route the write through the block table.  A done
        # row arrives with cache_len == max_len == W*bs; its write lands at
        # the last table slot's final offset — either the scratch block
        # (table padding) or a position >= the row's usable length, never
        # attended either way (the paged analogue of the dense scratch
        # slot).
        from repro.serving.kv_quant import pool_block_size, quantize_for_pool

        table = cache["table"]
        bs = pool_block_size(cache["k"])
        idx = cache_len - 1  # (B,)
        b_idx = jnp.arange(B)
        blk = table[b_idx, idx // bs]
        off = idx % bs

        def upd(pool, new_row):
            # quantize-on-write: the (B, Hkv, D) token slab becomes
            # code+scale leaves for quantized pools (identity on fp) and
            # scatters leaf-wise at the same (block, offset)
            payload = quantize_for_pool(new_row[:, 0], pool)
            return jax.tree.map(
                lambda p, x: p.at[blk, off].set(x.astype(p.dtype)),
                pool, payload)

        with jax.named_scope("kv_write"):
            ck = upd(cache["k"], k)
            cv = upd(cache["v"], v)
        o = paged_decode_attention(q, ck, cv, table=table,
                                   cache_len=cache_len, window=window,
                                   softcap=cfg.logit_softcap)
        new_kv = (ck, cv)
    else:
        # decode: scatter K/V of the current token into the cache
        ring = getattr(cfg, "ring_cache", False)
        S_cache = cache["k"].shape[1]
        idx = (cache_len - 1) % S_cache if ring else cache_len - 1  # (B,)

        def upd(cache_arr, new_row):
            # cache_arr: (B, S, Hkv, D); new_row: (B, 1, Hkv, D)
            b_idx = jnp.arange(B)
            return cache_arr.at[b_idx, idx].set(new_row[:, 0].astype(cache_arr.dtype))

        with jax.named_scope("kv_write"):
            ck = upd(cache["k"], k)
            cv = upd(cache["v"], v)
        o = decode_attention(q, ck, cv, cache_len=cache_len, window=window,
                             softcap=cfg.logit_softcap, ring=ring)
        new_kv = (ck, cv)

    out = linear(p["wo"], o.reshape(B, S, cfg.n_heads * hd))
    return out, new_kv


# ---------------------------------------------------------------------------
# FFN (SwiGLU) and classic MLP
# ---------------------------------------------------------------------------


def init_swiglu(key, d: int, f: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "gate": init_linear(ks[0], d, f, bias=False, dtype=dtype),
        "up": init_linear(ks[1], d, f, bias=False, dtype=dtype),
        "down": init_linear(ks[2], f, d, bias=False, dtype=dtype),
    }


@scoped("mlp")
def swiglu(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    return linear(p["down"], jax.nn.silu(linear(p["gate"], x)) * linear(p["up"], x))


def init_mlp(key, d: int, f: int, dtype) -> dict:
    ks = jax.random.split(key, 2)
    return {
        "fc1": init_linear(ks[0], d, f, bias=True, dtype=dtype),
        "fc2": init_linear(ks[1], f, d, bias=True, dtype=dtype),
    }


@scoped("mlp")
def mlp(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    return linear(p["fc2"], jax.nn.gelu(linear(p["fc1"], x)))


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d: int, dtype) -> dict:
    return {"table": jax.random.normal(key, (vocab, d), jnp.float32).astype(dtype) * 0.02}


def embed(p: dict, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    return p["table"].astype(dtype)[tokens]


def lm_logits(p: dict, x: jnp.ndarray, softcap: float = 0.0) -> jnp.ndarray:
    logits = jnp.einsum("...d,vd->...v", x, p["table"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return _softcap(logits, softcap)
