"""Sequence-parallel (flash-decoding style) KV attention.

The KV cache is sharded along the *sequence* dimension over a configurable
mesh axis (``par.kv_seq_axis``); each shard computes a partial safe-softmax
attention (unnormalized out, running max m, running sum l) over its KV
slice and shards combine with the distributed softmax merge:

    m* = pmax(m);   l* = psum(l e^{m-m*});   o* = psum(o e^{m-m*}) / l*

Axis choice (configs/inputs.py):
  * decode_32k  — seq over **model** (batch occupies data); the per-step
    collective is one psum of (B,1,Hq,D) — bytes ~1000× smaller than the
    involuntary cache reshards GSPMD inserts otherwise;
  * long_500k   — seq over **data** (batch=1 cannot use it).

This is the TPU-native answer to the paper's concern that softmax/attention
dominates as context grows (§5.2.1): O(S) work spreads across an axis and
only O(heads·head_dim) crosses the interconnect.  Head projections stay
tensor-parallel outside the shard_map; only the tiny (B, 1) q/k/v rows
enter it, so no head-divisibility constraints apply (gemma3 has 1 KV head —
it cannot shard 16-way over ``model``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParallelContext
from repro.models import layers as L


def _seq_shard_attention(q, new_k, new_v, cache_k, cache_v, cache_len, window,
                         *, axis: str, softcap: float, ring_size: int = 0):
    """Runs inside shard_map. cache_*: (B_loc, S_loc, Hkv, D) local slice.
    ``ring_size``: total ring slots (0 = linear cache)."""
    B, S_loc, Hkv, D = cache_k.shape
    shard = jax.lax.axis_index(axis)
    base = shard * S_loc  # global slot index of local row 0

    # -- write the current token's K/V into whichever shard owns the target
    #    slot (one scatter; other shards are masked no-ops).
    target = cache_len - 1  # (B,) position of the current token
    slot = target % ring_size if ring_size else target
    local_idx = jnp.clip(slot - base, 0, S_loc - 1)
    owns = (slot >= base) & (slot < base + S_loc)  # (B,)
    b_idx = jnp.arange(B)

    def write(cache, new):
        cur = cache[b_idx, local_idx]  # (B, Hkv, D)
        upd = jnp.where(owns[:, None, None], new[:, 0].astype(cache.dtype), cur)
        return cache.at[b_idx, local_idx].set(upd)

    ck = write(cache_k, new_k)
    cv = write(cache_v, new_v)

    # -- partial attention over the local slice
    slots = base + jnp.arange(S_loc)[None]           # (1, S_loc) global slots
    q_pos = target[:, None]
    if ring_size:
        kv_pos = L.ring_slot_positions(slots, cache_len[:, None], ring_size)
        valid = kv_pos >= 0
    else:
        kv_pos = slots
        valid = kv_pos < cache_len[:, None]
    w = jnp.asarray(window, jnp.int32)
    valid &= (w <= 0) | (q_pos - kv_pos < w)
    o, m, l = L.decode_attention_partial(q, ck, cv, valid=valid, softcap=softcap)

    # -- distributed softmax merge (§Perf iteration: one fused psum in the
    #    cache dtype — bf16 in production — instead of separate f32 psums:
    #    halves merge bytes on the wire; normalization stays local f32).
    m_star = jax.lax.pmax(m, axis)                   # (B, Hq)
    corr = jnp.exp(m - m_star)
    Bq, _, Hq, D = o.shape
    payload = jnp.concatenate(
        [(o * corr[:, None, :, None]).reshape(Bq, Hq * D),
         (l * corr).reshape(Bq, Hq)], axis=-1).astype(cache_k.dtype)
    merged = jax.lax.psum(payload, axis).astype(jnp.float32)
    o_star = merged[:, : Hq * D].reshape(Bq, 1, Hq, D)
    l_star = merged[:, Hq * D:].reshape(Bq, Hq)
    o_star = o_star / jnp.maximum(l_star[:, None, :, None], 1e-30)
    return o_star.astype(q.dtype), ck, cv


def seq_parallel_attention(q, new_k, new_v, cache_k, cache_v, cache_len,
                           window, cfg: ModelConfig, par: ParallelContext):
    """q: (B,1,Hq,D); new_k/v: (B,1,Hkv,D); cache: (B,S,Hkv,D) seq-sharded
    over par.kv_seq_axis; batch sharded over the remaining batch axes."""
    axis = par.kv_seq_axis
    ring = getattr(cfg, "ring_cache", False)
    ring_size = cache_k.shape[1] if ring else 0
    if par.mesh is None or axis is None or axis not in par.axes:
        # single-device fallback: behave like the dense decode path
        B = q.shape[0]
        idx = (cache_len - 1) % ring_size if ring else cache_len - 1
        b_idx = jnp.arange(B)
        ck = cache_k.at[b_idx, idx].set(new_k[:, 0].astype(cache_k.dtype))
        cv = cache_v.at[b_idx, idx].set(new_v[:, 0].astype(cache_v.dtype))
        o = L.decode_attention(q, ck, cv, cache_len=cache_len, window=window,
                               softcap=cfg.logit_softcap, ring=ring)
        return o, ck, cv

    B = q.shape[0]
    # batch axes must not collide with the seq axis
    batch_ax = par.batch_axes_for(B)
    if batch_ax is not None:
        bt = (batch_ax,) if isinstance(batch_ax, str) else tuple(batch_ax)
        bt = tuple(a for a in bt if a != axis)
        batch_ax = (bt if len(bt) > 1 else (bt[0] if bt else None))

    act4 = P(batch_ax, None, None, None)
    vec = P(batch_ax)
    cache_spec = P(batch_ax, axis, None, None)
    fn = jax.shard_map(
        lambda *a: _seq_shard_attention(*a, axis=axis,
                                        softcap=cfg.logit_softcap,
                                        ring_size=ring_size),
        mesh=par.mesh,
        in_specs=(act4, act4, act4, cache_spec, cache_spec, vec, P()),
        out_specs=(act4, cache_spec, cache_spec),
        check_vma=False,
    )
    return fn(q, new_k, new_v, cache_k, cache_v, cache_len,
              jnp.asarray(window, jnp.int32))


def seq_parallel_decode_layer(lp, x, cfg: ModelConfig, par: ParallelContext,
                              *, cache_k, cache_v, cache_len, window):
    """Full transformer layer for the sequence-parallel decode path.

    Mirrors models.transformer._layer but routes attention through the
    seq-sharded cache. Returns (x, new_cache_k, new_cache_v).
    """
    from repro.models.moe import moe_ffn

    B, S, _ = x.shape
    hd = cfg.resolved_head_dim()
    hn = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
    q = L.linear(lp["attn"]["wq"], hn).reshape(B, S, cfg.n_heads, hd)
    k = L.linear(lp["attn"]["wk"], hn).reshape(B, S, cfg.n_kv_heads, hd)
    v = L.linear(lp["attn"]["wv"], hn).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.rope_theta:
        positions = (cache_len - 1)[:, None]
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
    o, ck, cv = seq_parallel_attention(q, k, v, cache_k, cache_v, cache_len,
                                       window, cfg, par)
    x = x + L.linear(lp["attn"]["wo"], o.reshape(B, S, cfg.n_heads * hd))
    hn = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
    if cfg.moe:
        h, _ = moe_ffn(lp["moe"], hn, cfg, par)
    else:
        h = L.swiglu(lp["ffn"], hn)
    return x + h, ck, cv
