"""Token sampling, including distributed vocab-sharded sampling.

The paper keeps the lm_head on the CPU because full logits do not fit the
NPU's 32-bit address space and notes (§7.2.2) that at batch 16 this costs
>50% of step time.  The TPU-native fix implemented here: the lm_head stays
vocab-sharded on the ``model`` axis and sampling happens *per shard* (local
top-k / local gumbel-max), followed by one tiny psum-style merge — full
logits are never materialized or gathered.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import ParallelContext


@dataclass(frozen=True)
class SamplerConfig:
    temperature: float = 1.0
    top_k: int = 0          # 0 = no top-k
    top_p: float = 1.0      # 1 = no nucleus
    greedy: bool = False


def _mask_top_p(logits, p):
    # argsort is stable, so among tied logits lower token ids sort first
    order = jnp.argsort(-logits, axis=-1)
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep smallest prefix with cumulative prob >= p (always keep first);
    # mask by sorted *rank*, not by value: a value cutoff would keep every
    # token tied with the nucleus boundary and overshoot the target mass
    cutoff_idx = jnp.sum(cum < p, axis=-1, keepdims=True)
    ranks = jnp.argsort(order, axis=-1)
    return jnp.where(ranks <= cutoff_idx, logits, -jnp.inf)


def _sample_candidates(x, rng, k, p):
    """Gumbel-max over the k best of ``x`` (B, V), nucleus ``p`` taken over
    those k.  ``lax.top_k`` is the one full-width pass: it ranks ties by
    the lower id and keeps exactly k.  The noise is the full (B, V) draw
    ``jax.random.categorical`` makes, read at the candidates' ids, so a
    row whose k cover the vocabulary samples what categorical does."""
    vals, idx = jax.lax.top_k(x, min(k, x.shape[-1]))
    if p < 1.0:
        probs = jax.nn.softmax(vals, axis=-1)
        # the mass above each candidate; the first is always kept
        vals = jnp.where(jnp.cumsum(probs, axis=-1) - probs < p, vals,
                         -jnp.inf)
    g = jax.random.gumbel(rng, x.shape, x.dtype)
    j = jnp.argmax(vals + jnp.take_along_axis(g, idx, axis=-1), axis=-1)
    return jnp.take_along_axis(idx, j[..., None], axis=-1)[..., 0]


def candidates(sc: SamplerConfig) -> int:
    """Tokens per row that :func:`sample` ranks and samples among: ``top_k``
    on the candidate path, 0 on the full-vocabulary paths."""
    return 0 if sc.greedy else sc.top_k


def sample(logits: jnp.ndarray, rng, sc: SamplerConfig) -> jnp.ndarray:
    """logits: (B, V) f32 -> tokens (B,) int32."""
    if sc.greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    x = logits / jnp.maximum(sc.temperature, 1e-6)
    if sc.top_k:
        return _sample_candidates(x, rng, sc.top_k, sc.top_p)
    if sc.top_p < 1.0:
        x = _mask_top_p(x, sc.top_p)
    return jax.random.categorical(rng, x, axis=-1).astype(jnp.int32)


def logprobs_of(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Per-token log-probabilities (used by TTS scoring). (B,V),(B,)->(B,)."""
    lp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(lp, tokens[:, None], axis=-1)[:, 0]


# ---------------------------------------------------------------------------
# Vocab-sharded sampling (beyond-paper: removes the paper's lm_head wall)
# ---------------------------------------------------------------------------


def _merge_shard_winners(loc_max, loc_arg, axis):
    """Global argmax across shards with unsharded-``jnp.argmax`` tie
    semantics: among shards achieving the global max, the *lowest* global
    index wins (pmin over winner candidates; losers contribute INT32_MAX).
    A pmax merge would pick the highest index and diverge from the
    reference single-device decode on tied logits."""
    glob_max = jax.lax.pmax(loc_max, axis)
    winner = jnp.where(loc_max >= glob_max, loc_arg,
                       jnp.iinfo(jnp.int32).max)
    return jax.lax.pmin(winner, axis).astype(jnp.int32)


def _local_gumbel_max(logits_loc, rng, temperature, axis, vocab_per_shard):
    shard = jax.lax.axis_index(axis)
    # per-shard iid gumbel noise: fold the shard id into the key
    g = -jnp.log(-jnp.log(
        jax.random.uniform(jax.random.fold_in(rng, shard),
                           logits_loc.shape, minval=1e-20, maxval=1.0)))
    y = logits_loc / jnp.maximum(temperature, 1e-6) + g
    loc_max = jnp.max(y, axis=-1)
    loc_arg = jnp.argmax(y, axis=-1) + shard * vocab_per_shard
    return _merge_shard_winners(loc_max, loc_arg, axis)


def _local_greedy(logits_loc, axis, vocab_per_shard):
    shard = jax.lax.axis_index(axis)
    loc_max = jnp.max(logits_loc, axis=-1)
    loc_arg = jnp.argmax(logits_loc, axis=-1) + shard * vocab_per_shard
    return _merge_shard_winners(loc_max, loc_arg, axis)


def distributed_sample(logits: jnp.ndarray, rng, sc: SamplerConfig,
                       par: ParallelContext) -> jnp.ndarray:
    """Sample from (B, V) logits sharded over the ``model`` axis without
    gathering them.  Greedy = distributed argmax; stochastic = distributed
    Gumbel-max (exact categorical sample, temperature folded in)."""
    if par.mesh is None or "model" not in par.axes:
        return sample(logits, rng, sc)
    V = logits.shape[-1]
    n_model = par.mesh.shape["model"]
    if V % n_model:  # odd vocab (e.g. internvl2's 151655): gather + sample
        return sample(logits, rng, sc)
    vps = V // n_model

    def local_fn(lg, key):
        if sc.greedy:
            return _local_greedy(lg, "model", vps)
        return _local_gumbel_max(lg, key, sc.temperature, "model", vps)

    batch_axes = par.batch_axes_for(logits.shape[0])
    fn = jax.shard_map(
        local_fn, mesh=par.mesh,
        in_specs=(P(batch_axes, "model"), P()),
        out_specs=P(batch_axes),
        check_vma=False,
    )
    return fn(logits, rng)
