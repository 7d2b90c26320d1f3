"""Mixture-of-Experts FFN.

Dispatch is the sort-by-expert / capacity scheme: per data shard, tokens are
routed top-k, sorted by expert id, packed into an (E, C, d) buffer
(C = capacity), run through a batched expert einsum, and combined back with
the router weights.  Compute cost is ~capacity_factor × the *active* FLOPs
(6·N_active·D), never the dense all-experts cost.

Token routing stays local to each data shard (no global sort); d_ff is
tensor-parallel over the ``model`` axis with one psum after the down
projection — the same collective pattern as the dense FFN.  When a mesh is
present the layer runs under shard_map; without a mesh it runs the same code
on the full array.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.distributed.sharding import ParallelContext
from repro.models.layers import scoped


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    m = cfg.moe
    d, f, E = cfg.d_model, m.expert_d_ff, m.n_experts
    ks = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    return {
        "router": {"w": jax.random.normal(ks[0], (d, E), jnp.float32).astype(dtype) * s_in},
        "experts": {
            "gate": jax.random.normal(ks[1], (E, d, f), jnp.float32).astype(dtype) * s_in,
            "up": jax.random.normal(ks[2], (E, d, f), jnp.float32).astype(dtype) * s_in,
            "down": jax.random.normal(ks[3], (E, f, d), jnp.float32).astype(dtype) * s_out,
        },
    }


def _route(logits: jnp.ndarray, m: MoEConfig):
    """logits: (T, E) -> (weights (T,k), ids (T,k), aux_loss)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(probs, m.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load balancing loss.
    T, E = probs.shape
    density = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), axis=0)
    density_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(density * density_prob)
    return weights, ids, aux


def _dispatch_compute_combine(x_flat, weights, ids, experts, m: MoEConfig, axis_model):
    """Core per-shard MoE. x_flat: (T, d); experts have local f shard."""
    T, d = x_flat.shape
    E, k = m.n_experts, m.top_k
    C = max(8, int(math.ceil(T * k / E * m.capacity_factor)))

    flat_ids = ids.reshape(T * k)
    flat_w = weights.reshape(T * k)
    order = jnp.argsort(flat_ids, stable=True)            # (T*k,) sorted by expert
    sorted_ids = flat_ids[order]
    counts = jnp.bincount(flat_ids, length=E)
    starts = jnp.cumsum(counts) - counts                  # exclusive per-expert start
    pos = jnp.arange(T * k) - starts[sorted_ids]          # position within expert
    valid = pos < C
    slot = jnp.where(valid, sorted_ids * C + pos, E * C)  # E*C = drop slot

    # slot -> source token row (T = zero row for unfilled slots)
    slot_src = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        (order // k).astype(jnp.int32), mode="drop")
    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, d), x_flat.dtype)], axis=0)
    xe = x_pad[slot_src[: E * C]].reshape(E, C, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, experts["gate"].astype(xe.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, experts["up"].astype(xe.dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, experts["down"].astype(h.dtype))
    if axis_model is not None:
        ye = jax.lax.psum(ye, axis_model)  # TP reduce over d_ff shards

    ye_pad = jnp.concatenate([ye.reshape(E * C, d), jnp.zeros((1, d), ye.dtype)], axis=0)
    token_slot = jnp.full((T * k,), E * C, jnp.int32).at[order].set(
        jnp.where(valid, slot, E * C).astype(jnp.int32))
    contrib = ye_pad[token_slot].reshape(T, k, d)
    y = jnp.sum(contrib * flat_w.reshape(T, k, 1).astype(contrib.dtype), axis=1)
    return y


@scoped("mlp")
def moe_ffn(p: dict, x: jnp.ndarray, cfg: ModelConfig, par: ParallelContext = None):
    """x: (B, S, d) -> (y (B, S, d), aux_loss scalar)."""
    m = cfg.moe
    B, S, d = x.shape
    par = par or ParallelContext()

    from repro.quant.qlinear import dequantize_model_params, is_quantized
    if is_quantized(p["experts"]["gate"]):
        p = dict(p, experts=dequantize_model_params(p["experts"]))

    def local_fn(x_loc, router_w, gate, up, down):
        T = x_loc.shape[0] * x_loc.shape[1]
        xf = x_loc.reshape(T, d)
        logits = xf @ router_w.astype(xf.dtype)
        weights, ids, aux = _route(logits, m)
        experts = {"gate": gate, "up": up, "down": down}
        axis_model = ("model" if (par.mesh is not None and par.tp
                                  and "model" in par.axes) else None)
        y = _dispatch_compute_combine(xf, weights, ids, experts, m, axis_model)
        if par.mesh is not None:
            aux = jax.lax.pmean(aux, tuple(par.axes))  # replicate for out_spec P()
        return y.reshape(x_loc.shape), aux

    if par.mesh is None:
        return local_fn(x, p["router"]["w"], p["experts"]["gate"],
                        p["experts"]["up"], p["experts"]["down"])

    # Small decode batches may not divide the data axis: fall back toward
    # replicated tokens (compute duplicated — trivial at batch 1 / seq 1).
    batch_axes = par.batch_axes_for(B)
    xs = P(batch_axes, None, None)
    ws = P(None, None)          # router replicated
    if par.tp:
        es_in = P(None, None, "model")   # gate/up: f sharded (TP)
        es_out = P(None, "model", None)  # down: f sharded
    else:
        es_in = es_out = P(None, None, None)  # fsdp-only: gathered per layer
    # checkpoint: the (E, C, d) dispatch/activation buffers are recomputed
    # in backward instead of saved — they dominate MoE training memory.
    fn = jax.shard_map(
        jax.checkpoint(local_fn),
        mesh=par.mesh,
        in_specs=(xs, ws, es_in, es_in, es_out),
        out_specs=(xs, P()),
        check_vma=False,
    )
    return fn(x, p["router"]["w"], p["experts"]["gate"], p["experts"]["up"],
              p["experts"]["down"])
