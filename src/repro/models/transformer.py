"""Unified decoder-only transformer covering the dense / GQA / bias / SWA /
local:global / MoE members of the architecture pool.

Layers are scanned (`jax.lax.scan` over stacked params) so the lowered HLO —
and therefore dry-run compile time — is independent of depth.  Per-layer
attention-pattern variation (gemma3's 5 local : 1 global) is expressed as a
per-layer window array threaded through the scan.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParallelContext
from repro.models import layers as L
from repro.models.moe import init_moe, moe_ffn


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer sliding window sizes (0 = unbounded full attention)."""
    if cfg.attn_pattern.startswith("local_global"):
        ratio = int(cfg.attn_pattern.split(":")[1])
        w = [cfg.window_size if (i % (ratio + 1)) != ratio else 0
             for i in range(cfg.n_layers)]
        return jnp.array(w, jnp.int32)
    if cfg.window_size:
        return jnp.full((cfg.n_layers,), cfg.window_size, jnp.int32)
    return jnp.zeros((cfg.n_layers,), jnp.int32)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: ModelConfig, dtype) -> dict:
    ka, kf = jax.random.split(key)
    p = {
        "attn_norm": L.init_rmsnorm(cfg.d_model),
        "attn": L.init_attention(ka, cfg, dtype),
        "ffn_norm": L.init_rmsnorm(cfg.d_model),
    }
    if cfg.moe:
        p["moe"] = init_moe(kf, cfg, dtype)
    else:
        p["ffn"] = L.init_swiglu(kf, cfg.d_model, cfg.d_ff, dtype)
    return p


def init_params(key, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    ke, kl, kh, kp = jax.random.split(key, 4)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    params = {
        "embedding": L.init_embedding(ke, cfg.vocab_size, cfg.d_model, dtype),
        "layers": jax.vmap(partial(_init_layer, cfg=cfg, dtype=dtype))(layer_keys),
        "final_norm": L.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_embedding(kh, cfg.vocab_size, cfg.d_model, dtype)
    if cfg.frontend == "patch_stub":
        params["patch_proj"] = L.init_linear(kp, cfg.d_model, cfg.d_model,
                                             bias=False, dtype=dtype)
    return params


def abstract_params(cfg: ModelConfig) -> dict:
    return jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))


# ---------------------------------------------------------------------------
# Layer body
# ---------------------------------------------------------------------------


def _layer(p, x, cfg, par, *, positions, window, cache=None, cache_len=None,
           prefix_kv=None, prefix_positions=None):
    h, new_kv = L.attention_block(
        p["attn"], L.rmsnorm(p["attn_norm"], x, cfg.norm_eps), cfg,
        positions=positions, window=window, cache=cache, cache_len=cache_len,
        prefix_kv=prefix_kv, prefix_positions=prefix_positions)
    x = x + h
    hn = L.rmsnorm(p["ffn_norm"], x, cfg.norm_eps)
    if cfg.moe:
        h, aux = moe_ffn(p["moe"], hn, cfg, par)
    else:
        h, aux = L.swiglu(p["ffn"], hn), jnp.zeros((), jnp.float32)
    if par is not None:
        # act_seq: the layer-boundary residual (which remat saves) is
        # sequence-sharded over the model axis (no-op unless enabled).
        x = par.constrain(x + h, "batch", "act_seq", None)
    else:
        x = x + h
    return x, new_kv, aux


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def forward(params, tokens, cfg: ModelConfig, par: ParallelContext = None,
            *, embeddings: Optional[jnp.ndarray] = None, return_kv: bool = False,
            logit_positions: Optional[jnp.ndarray] = None,
            prefix: Optional[dict] = None):
    """Full-sequence forward (training / prefill). Returns (logits, kv, aux).

    tokens: (B, S) int32.  ``embeddings``: optional (B, P, d) modality-stub
    prefix (VLM patches / audio frames) that replaces the embedding of the
    first P positions.

    ``prefix``: optional cached-prefix handle for *partial prefill* —
    {"k", "v": (L, B, P, Hkv, D) already-rope'd per-layer prefix KV,
    "len": (B,) int32 cached lengths}.  ``tokens`` then holds only the
    uncached suffix: token j of row b sits at global position
    ``len[b] + j``, queries attend over prefix + suffix, and the returned
    KV covers the suffix alone.  Prefix slots at or past a row's cached
    length get their position pushed past every query so the causal mask
    hides them (rows with len == 0 attend to none of the prefix).  The
    per-row ``len`` makes the prefix *ragged-batch* capable: B rows with
    different cached lengths (P is the batch-max padded width) run in one
    pass — the batched cache-aware admission path.
    """
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(params["embedding"], tokens, dtype)
    if embeddings is not None:
        if prefix is not None:
            raise NotImplementedError(
                "modality-stub embeddings cannot be combined with a cached "
                "prefix (the patch positions would be ambiguous)")
        pre = L.linear(params["patch_proj"], embeddings.astype(dtype))
        x = jnp.concatenate([pre, x[:, embeddings.shape[1]:]], axis=1)
    if par is not None:
        x = par.constrain(x, "batch", "act_seq", None)
    B, S = tokens.shape
    if prefix is not None:
        offset = prefix["len"].astype(jnp.int32)  # (B,)
        positions = offset[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        P = prefix["k"].shape[2]
        parr = jnp.arange(P, dtype=jnp.int32)[None]
        # invalid prefix slots -> position P + S: strictly past any query
        # (queries reach at most offset + S - 1 <= P + S - 2), so both the
        # causal mask and the chunked kv_len mask drop them
        prefix_positions = jnp.where(parr < offset[:, None], parr, P + S)
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        prefix_positions = None
    windows = layer_windows(cfg)

    def body(carry, xs):
        x, aux = carry
        if prefix is None:
            (lp, w), pkv = xs, None
        else:
            lp, w, pk, pv = xs
            pkv = (pk, pv)
        x, kv, a = _layer(lp, x, cfg, par, positions=positions, window=w,
                          prefix_kv=pkv, prefix_positions=prefix_positions)
        return (x, aux + a), (kv if return_kv else None)

    body = jax.checkpoint(body) if cfg.remat == "full" else body
    scan_xs = ((params["layers"], windows) if prefix is None else
               (params["layers"], windows, prefix["k"], prefix["v"]))
    (x, aux), kvs = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                 scan_xs)
    with jax.named_scope("lm_head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if logit_positions is not None:
            # gather the true last position per sequence before the (large)
            # lm_head matmul — avoids materializing (B, S, V) logits in
            # prefill
            x = x[jnp.arange(B), logit_positions]
        head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
        logits = L.lm_logits(head, x, cfg.logit_softcap)
    return logits, kvs, aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim()
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim()
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def init_paged_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                     dtype=None) -> dict:
    """Block-pool KV storage: (L, n_blocks, block_size, Hkv, D) per leaf.

    Unlike :func:`init_cache` there is no batch or max_len dimension — rows
    map positions to blocks through per-sequence block tables (see
    ``repro.serving.kv_pool``)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim()
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quantize_tokens(pool, kvs):
    """Match prefill KV (L, B, S, Hkv, D) to a pool's storage: identity
    for fp pools, tile quantize-on-write ({"codes", "scales"} leaves with
    the same (L, B, S, ...) leading layout) for quantized pools — fused
    into the same jitted scatter, so fp KV never round-trips through HBM.
    """
    from repro.serving.kv_quant import quantize_for_pool

    return quantize_for_pool(kvs, pool)


@L.scoped("kv_write")
def _scatter_prefill_blocks(pool, kvs, table, block_size: int):
    """Write prefill KV (L, B, S, Hkv, D) into pool blocks via the table.

    S is padded up to a block multiple; chunk j of row b goes to block
    ``table[b, j]``.  Chunks past a row's true block count carry padding
    and target the scratch block (table padding = 0), whose contents are
    never attended.  Quantized pools scatter the quantized code and scale
    leaves through the identical index math (the trailing token-slab dims
    are free).
    """
    kvs = _quantize_tokens(pool, kvs)

    def leaf(p, x):
        L, B, S = x.shape[:3]
        nS = -(-S // block_size)
        pad = nS * block_size - S
        if pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)) +
                        ((0, 0),) * (x.ndim - 3))
        chunks = x.reshape(L, B * nS, block_size, *x.shape[3:])
        blocks = table[:, :nS].reshape(-1)
        return p.at[:, blocks].set(chunks.astype(p.dtype))

    return jax.tree.map(leaf, pool, kvs)


@L.scoped("kv_write")
def _scatter_suffix_blocks(pool, kvs, table, block_size: int, start):
    """Write suffix KV (L, B, S, Hkv, D) into pool blocks at a per-row
    positional offset: row b's token j lands at global position
    ``start[b] + j``, i.e. pool[table[b, pos//bs], pos % bs].

    Unlike :func:`_scatter_prefill_blocks` this writes position-by-position
    (not whole blocks), because a misaligned cached prefix leaves the first
    suffix tokens *inside* a partially-filled tail block whose earlier
    offsets must survive.  Positions past the table's range (padding rows)
    are clamped to the last slot — an un-attended offset or the scratch
    block, mirroring the dense scratch-slot convention.  Groups of the
    quantized layout never span tokens, so per-position writes are exact
    on code+scale leaves too.
    """
    kvs = _quantize_tokens(pool, kvs)
    W = table.shape[1]

    def leaf(p, x):
        S = x.shape[2]
        pos = start.astype(jnp.int32)[:, None] + jnp.arange(S,
                                                            dtype=jnp.int32)
        pos = jnp.minimum(pos, W * block_size - 1)       # (B, S)
        blk = jnp.take_along_axis(table, pos // block_size, axis=1)
        return p.at[:, blk, pos % block_size].set(x.astype(p.dtype))

    return jax.tree.map(leaf, pool, kvs)


def prefill(params, tokens, cfg: ModelConfig, par: ParallelContext = None,
            *, max_len: int, embeddings=None, lengths=None, paged=None,
            prefix=None):
    """Run the prompt, build the KV cache. Returns (next_logits, cache).

    ``lengths``: (B,) true prompt lengths for right-padded batches; the
    returned logits are taken at each sequence's true last position.
    ``paged``: optional {"k", "v", "table"} handle — block pools
    (L, n_blocks, bs, Hkv, D) plus a (B, W) block table; prompt KV is
    scattered into the rows' blocks instead of a fresh dense cache and the
    returned cache carries the updated pools.
    ``prefix``: optional cached-prefix handle (see :func:`forward`) for
    *partial prefill* — requires ``paged``; ``tokens``/``lengths`` then
    describe only the uncached suffix, whose KV is scattered into the
    table at offset ``prefix["len"]`` while the prompt's cached positions
    stay untouched.
    """
    B, S = tokens.shape
    pos = (lengths - 1) if lengths is not None else jnp.full((B,), S - 1)
    if prefix is not None and paged is None:
        raise ValueError("partial prefill over a cached prefix requires the "
                         "paged cache layout")
    logits, kvs, _ = forward(params, tokens, cfg, par, embeddings=embeddings,
                             return_kv=True, logit_positions=pos,
                             prefix=prefix)
    k, v = kvs  # (L, B, S, Hkv, D)
    if paged is not None:
        from repro.serving.kv_quant import pool_block_size

        bs = pool_block_size(paged["k"], axis=2)
        if prefix is not None:
            start = prefix["len"]
            return logits, {
                "k": _scatter_suffix_blocks(paged["k"], k, paged["table"],
                                            bs, start),
                "v": _scatter_suffix_blocks(paged["v"], v, paged["table"],
                                            bs, start),
                "table": paged["table"],
            }
        return logits, {
            "k": _scatter_prefill_blocks(paged["k"], k, paged["table"], bs),
            "v": _scatter_prefill_blocks(paged["v"], v, paged["table"], bs),
            "table": paged["table"],
        }
    cache = init_cache(cfg, B, max_len)
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, 0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, 0, 0, 0, 0)),
    }
    return logits, cache


def decode_step(params, tokens, cache, cache_len, cfg: ModelConfig,
                par: ParallelContext = None):
    """One decode step.

    tokens: (B, 1) int32 — current token.  cache: stacked (L, B, S, Hkv, D),
    or a paged handle additionally carrying "table" (B, W) int32 with k/v
    leaves shaped (L, n_blocks, bs, Hkv, D).
    cache_len: (B,) int32 — sequence length *after* this token is appended.
    Returns (logits (B, vocab) f32, new_cache).
    """
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(params["embedding"], tokens, dtype)
    if par is not None:
        x = par.constrain(x, "batch", "act_seq", None)
    positions = (cache_len - 1)[:, None]
    windows = layer_windows(cfg)

    seq_par = par is not None and par.kv_seq_axis is not None
    table = cache.get("table") if isinstance(cache, dict) else None
    if table is not None and seq_par:
        raise NotImplementedError(
            "paged KV cache is not supported with sequence-parallel decode")

    def body(x, xs):
        lp, w, ck, cv = xs
        if seq_par:
            from repro.serving.seq_parallel import seq_parallel_decode_layer
            x, nk, nv = seq_parallel_decode_layer(
                lp, x, cfg, par, cache_k=ck, cache_v=cv,
                cache_len=cache_len, window=w)
        else:
            layer_cache = {"k": ck, "v": cv}
            if table is not None:
                layer_cache["table"] = table
            x, (nk, nv), _ = _layer(lp, x, cfg, par, positions=positions,
                                    window=w, cache=layer_cache,
                                    cache_len=cache_len)
        return x, (nk, nv)

    x, (nk, nv) = jax.lax.scan(
        body, x, (params["layers"], windows, cache["k"], cache["v"]))
    with jax.named_scope("lm_head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
        logits = L.lm_logits(head, x[:, 0], cfg.logit_softcap)
    new_cache = {"k": nk, "v": nv}
    if table is not None:
        new_cache["table"] = table
    return logits, new_cache
