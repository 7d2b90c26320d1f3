"""Batched decode engine.

This is the system layer that realizes the paper's core observation: decode
is weight-bandwidth-bound, so the matrix units have idle rows that parallel
test-time-scaling samples can occupy for ~free.  The engine therefore
treats *batch* as the first-class resource:

* ``prefill`` runs the prompt once per unique prompt and yields the
  next-token logits at each sequence's true last position;
* ``fork`` replicates cache rows so N samples share one prompt's prefill
  (Best-of-N / beam-search fan-out without re-prefilling);
* ``reorder`` gathers the cache batch dim (beam-search survivor commit);
* ``generate`` runs a jit'd lax.scan over decode steps with done-masking.

The state carries ``pending_logits``: the logits the *next* token must be
sampled from. Each step samples, feeds the token through decode_step
(writing its KV at position cache_len), and replaces pending_logits — so no
KV row is ever written twice and the first generated token is sampled from
the prefill logits exactly.

On top of the engine sits :class:`ContinuousScheduler`, a slot-based
continuous-batching loop: one persistent ``GenState`` of ``n_slots`` rows
decodes every step; each step admits queued requests into free rows
(prefill → ``merge_rows`` scatter; TTS groups prefill once and ``fork``),
then releases any row that sampled a stop id or exhausted its token budget.
Requests enter and leave the batch independently mid-flight — the decode
batch stays full under mixed-length traffic, which is what makes parallel
test-time-scaling samples ride along for free.

The engine runs in one of two KV layouts:

* **dense** (default): every slot owns a ``(max_len, Hkv, D)`` cache row
  per layer, reserved up front.  ``fork`` physically replicates the
  prompt's KV rows N times and ``reorder`` copies whole rows — O(N·prompt)
  duplicated bytes for Best-of-N, plus a full ``max_len`` reservation per
  slot regardless of actual sequence length.
* **paged** (``paged=True``): KV lives in a shared, refcounted block pool
  (``repro.serving.kv_pool``) and each row holds a block *table*.
  ``fork`` becomes a refcount bump on the prompt's blocks (zero KV bytes
  copied — samples share the prefix until copy-on-write triggers on their
  first divergent write), ``reorder`` a table gather, and a slot only ever
  holds blocks for tokens it has actually produced.  ``prepare_decode``
  does the host-side block bookkeeping before each decode step and raises
  :class:`~repro.serving.kv_pool.OutOfBlocks` when the pool is exhausted,
  which the scheduler converts into preempting the youngest request.
  Paged states reference pool blocks by id, so they must be used linearly
  (step/merge/fork/release consume the state they are given); the dense
  path keeps full functional semantics.

On the paged layout the engine also supports the **cross-request prefix
cache** (``repro.serving.prefix_cache``): ``prefill(suffix_tokens, ...,
cached_table=, cached_lens=)`` is a *partial prefill* that runs the
transformer only over a prompt's uncached suffix while attending over the
cached prefix blocks (gathered from the pool through the row's table).
The row takes ownership of the caller's per-block lease on the cached
blocks (``PrefixCache.match`` retains them), a misaligned cached length
copy-on-writes the partially-used tail block before the suffix extends
it, and ``release_rows`` later drops exactly the row's references — the
tree's own pins keep cached prefixes alive across requests.  The
scheduler drives the full loop: longest-prefix-match at admission,
insertion of completed prompt prefixes back into the tree, and LRU
eviction of unreferenced cached blocks under pool pressure (via the
pool's ``pressure_hook``) *before* falling back to out-of-blocks
preemption.  ``SchedulerMetrics`` reports the hit rate and the prefill
tokens the cache saved.  The prefix gather is *bucketed*: only the table
columns covering the batch's longest cached prefix are gathered (block
granular), not the full ``max_len`` width.

``kv_quant`` ("q8" | "q4", paged only) swaps the pool for a
:class:`~repro.serving.kv_quant.QuantKVPool`: blocks store tile-quantized
codes plus per-(2, 16)-tile scales, quantization is fused into the
prefill/suffix/decode scatters (KV never lands in HBM at full precision)
and dequantization into every read path — the paged-attention gather, the
Pallas kernel's per-block VMEM dequant, and the partial-prefill prefix
gather.  Fork/CoW/prefix-cache semantics are unchanged (blocks move as
opaque code+scale payloads); the same ``n_blocks`` budget simply costs
2–4× fewer HBM bytes, or equivalently a fixed byte budget holds
proportionally more concurrent TTS streams.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParallelContext
from repro.models import api
from repro.serving.kv_pool import KVPool, OutOfBlocks, blocks_for
from repro.serving.prefix_cache import PrefixCache
from repro.serving.profiling import NULL_PROFILE_METRICS
from repro.serving.sampler import (SamplerConfig, candidates, logprobs_of,
                                   sample)
from repro.serving.telemetry import RequestLatency, Tracer, percentile, phase


@dataclass
class GenState:
    """Decoding state for a batch of sequences (a jax pytree).

    ``cache`` is layout-dependent: dense states carry the full KV arrays
    ({"k", "v"} of (L, B, S, Hkv, D), plus recurrent leaves for SSMs);
    paged states carry only the per-row indexing — {"table": (B, W) int32
    block ids, "n_blocks": (B,) int32 owned-block counts} — while the KV
    bytes live in the engine's shared :class:`~repro.serving.kv_pool.
    KVPool`.
    """

    cache: dict
    cache_len: jnp.ndarray       # (B,) int32 — prompt + generated so far
    pending_logits: jnp.ndarray  # (B, V) f32 — next token sampled from these
    done: jnp.ndarray            # (B,) bool
    logprob_sum: jnp.ndarray     # (B,) f32 cumulative sampled logprob
    n_gen: jnp.ndarray           # (B,) int32


jax.tree_util.register_dataclass(
    GenState,
    data_fields=["cache", "cache_len", "pending_logits", "done",
                 "logprob_sum", "n_gen"],
    meta_fields=[])


class DecodeEngine:
    def __init__(self, params, cfg: ModelConfig,
                 par: Optional[ParallelContext] = None, *, max_len: int = 512,
                 eos_id: int = 1, pad_id: int = 0, paged: bool = False,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 kv_quant: str = "none"):
        self.params = params
        self.cfg = cfg
        self.par = par
        self.max_len = max_len
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.model = api.get_model(cfg)
        self.paged = paged
        self.kv_quant = kv_quant
        self.pool: Optional[KVPool] = None
        # phase-span telemetry (repro.serving.telemetry.Tracer); installed
        # by ContinuousScheduler(tracer=...).  None = zero overhead: every
        # touchpoint is behind an `is not None` guard.
        self.tracer: Optional[Tracer] = None
        # roofline/canary profiler (repro.serving.profiling.
        # KernelProfiler); installed by ContinuousScheduler(profiler=...)
        # under the same `is not None` zero-overhead discipline.  The
        # canary jit is built lazily on the first canary step (traced
        # under the exact "xla" paged-attention impl, whatever the
        # production impl is).
        self.profiler = None
        self._canary_jit = None
        self.last_canary_logits = None
        if kv_quant != "none" and not paged:
            raise ValueError("kv_quant requires the paged KV layout "
                             "(DecodeEngine(paged=True))")
        if paged:
            if cfg.family != "transformer":
                raise ValueError(
                    f"paged KV cache supports the transformer family only "
                    f"(got {cfg.family!r})")
            if max_len % block_size:
                raise ValueError(
                    f"max_len ({max_len}) must be a multiple of "
                    f"block_size ({block_size})")
            if n_blocks is None:
                # scratch + eight full-length sequences' worth by default;
                # servers should size this to their slot count / traffic
                n_blocks = 1 + 8 * (max_len // block_size)
            if kv_quant != "none":
                from repro.serving.kv_quant import QuantKVPool

                self.pool = QuantKVPool(cfg, n_blocks, block_size,
                                        mode=kv_quant)
            else:
                self.pool = KVPool(cfg, n_blocks, block_size)
        self._prefill_jit = jax.jit(self._prefill_impl)
        self._prefill_paged_jit = jax.jit(self._prefill_paged_impl,
                                          donate_argnums=(4, 5))
        self._prefill_cached_jit = jax.jit(self._prefill_cached_impl,
                                           donate_argnums=(5, 6),
                                           static_argnames=("prefix_w",))
        self._gen_jit = jax.jit(self._generate_impl,
                                static_argnames=("n_steps", "sc", "stop_ids"))
        self._gen_paged_jit = jax.jit(
            self._gen_paged_impl, donate_argnums=(2, 3),
            static_argnames=("n_steps", "sc", "stop_ids"))
        self._step_jit = jax.jit(self._step_impl,
                                 static_argnames=("sc", "stop_ids"))
        self._step_paged_jit = jax.jit(self._step_paged_impl,
                                       donate_argnums=(2, 3),
                                       static_argnames=("sc", "stop_ids"))
        self._merge_jit = jax.jit(self._merge_impl)
        self._merge_donate_jit = jax.jit(self._merge_impl,
                                         donate_argnums=(0,))
        self._merge_paged_jit = jax.jit(self._merge_paged_impl)
        self._merge_paged_donate_jit = jax.jit(self._merge_paged_impl,
                                               donate_argnums=(0,))
        self._spec_verify_jit = jax.jit(
            self._spec_verify_impl, donate_argnums=(5, 6),
            static_argnames=("prefix_w", "stop_ids"))
        self._forced_jit = jax.jit(self._forced_step_impl)
        self._forced_paged_jit = jax.jit(self._forced_step_paged_impl,
                                         donate_argnums=(2, 3))

    @property
    def table_width(self) -> int:
        """Block-table slots per row (= max_len / block_size)."""
        return self.max_len // self.pool.block_size

    # -- prefill ------------------------------------------------------------
    def _prefill_impl(self, params, tokens, lengths, embeddings=None):
        logits, cache = self.model.prefill(
            params, tokens, self.cfg, self.par, max_len=self.max_len,
            lengths=lengths,
            **({"embeddings": embeddings} if embeddings is not None else {}))
        return logits, cache

    def _prefill_paged_impl(self, params, tokens, lengths, table, pool_k,
                            pool_v, embeddings=None):
        logits, cache = self.model.prefill(
            params, tokens, self.cfg, self.par, max_len=self.max_len,
            lengths=lengths,
            paged={"k": pool_k, "v": pool_v, "table": table},
            **({"embeddings": embeddings} if embeddings is not None else {}))
        return logits, cache["k"], cache["v"]

    def _prefill_cached_impl(self, params, tokens, lengths, cached_lens,
                             table, pool_k, pool_v, *, prefix_w: int):
        """Partial prefill: gather the rows' cached prefix KV through their
        (already fully planned) block tables, run the transformer over the
        suffix tokens only, and scatter the suffix KV in at the per-row
        offset.  Invalid gather slots (table padding, freshly allocated
        suffix blocks) are masked inside ``forward`` via ``cached_lens``.

        ``prefix_w`` (static) is the *bucketed* gather width: only the
        first ``ceil(max(cached_lens)/bs)`` table columns are gathered —
        block-granular, so short cached prefixes stop paying attention
        FLOPs over the full ``max_len`` table width.  Quantized pools
        gather code+scale leaves and dequantize the (L, B, P, Hkv, D)
        prefix view — in XLA under the "xla" paged-attention impl, through
        the vlut16 dequant kernel under the kernel impls
        (``repro.kernels.ops.lut_dequant_gather``, bit-identical).
        """
        prefix = self._gather_prefix(table, pool_k, pool_v, cached_lens,
                                     prefix_w=prefix_w)
        logits, cache = self.model.prefill(
            params, tokens, self.cfg, self.par, max_len=self.max_len,
            lengths=lengths,
            paged={"k": pool_k, "v": pool_v, "table": table},
            prefix=prefix)
        return logits, cache["k"], cache["v"]

    def _gather_prefix(self, table, pool_k, pool_v, lens, *, prefix_w: int):
        """Dequant-gather the first ``prefix_w`` table columns of every row
        into a dense (L, B, prefix_w*bs, Hkv, D) prefix view — the shared
        read path of the partial prefill and the speculative verify
        forward.  Invalid slots (rows shorter than the bucket) are masked
        downstream by ``lens`` inside ``forward``."""
        from repro.kernels import ops as kops
        from repro.models.layers import paged_attention_impl
        from repro.serving.kv_quant import dequantize_for_pool

        dequant = (dequantize_for_pool if paged_attention_impl() == "xla"
                   else kops.lut_dequant_gather)
        bs = self.pool.block_size
        ptab = jax.lax.slice_in_dim(table, 0, prefix_w, axis=1)

        def gather(pool):
            def leaf(a):
                g = a[:, ptab]  # (L, B, Wc, bs, *slab)
                return g.reshape(g.shape[0], g.shape[1], prefix_w * bs,
                                 *g.shape[4:])

            return dequant(jax.tree.map(leaf, pool))

        with jax.named_scope("attention"):  # attention's paged KV read
            return {"k": gather(pool_k), "v": gather(pool_v), "len": lens}

    def prefill(self, tokens: jnp.ndarray, lengths: Optional[jnp.ndarray] = None,
                embeddings=None, *, cached_table=None,
                cached_lens=None) -> GenState:
        """tokens: (B, S) right-padded prompts; lengths: (B,) true lengths.

        Partial prefill (paged engines only): ``cached_table`` (B, Wc)
        block ids covering each row's cached prompt prefix plus
        ``cached_lens`` (B,) cached lengths switch ``tokens``/``lengths``
        to describing the *uncached suffix* only.  The transformer runs
        over the suffix while attending over the cached blocks; each row
        must arrive holding one reference per cached block (the lease
        ``PrefixCache.match`` takes), which the resulting state owns and
        ``release_rows`` later drops.  A cached length that is not a
        block multiple has its partially-used tail block copy-on-written
        before the suffix extends it, so shared cache blocks are never
        written.  Every row needs at least one suffix token (the
        next-token logits come from the suffix's last position).

        B > 1 rows may carry *ragged* prefixes: per-row cached lengths
        (aligned or mid-block), per-row suffix lengths (right-padded to a
        common width via ``lengths``), and per-row tables.  Each row's
        positions are offset by its own cached length, the prefix gather
        covers ``ceil(max(cached_lens)/block_size)`` table columns
        (invalid slots masked per row), and tail CoWs across the batch
        commit in one device scatter — this is the device half of the
        scheduler's batched cache-aware admission.  One compile per
        distinct (batch, suffix width, gather width) triple; the
        scheduler buckets admissions by gather width so rows in one call
        pay no masked attention over columns none of them use.
        """
        B, S = tokens.shape
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        with phase(self.tracer, "prefill", batch=int(B),
                   cached=cached_table is not None):
            prof = self.profiler
            pt0 = prof.phase_begin("prefill") if prof is not None else 0.0
            if cached_table is not None:
                if not self.paged:
                    raise ValueError(
                        "cached-prefix prefill requires a paged engine "
                        "(DecodeEngine(paged=True))")
                if embeddings is not None:
                    raise NotImplementedError(
                        "cached-prefix prefill does not support "
                        "modality-stub embeddings")
                st = self._prefill_with_prefix(tokens, lengths, cached_table,
                                               cached_lens)
            elif self.paged:
                st = self._prefill_paged(tokens, lengths, embeddings)
            else:
                logits, cache = self._prefill_jit(self.params, tokens,
                                                  lengths, embeddings)
                st = GenState(
                    cache=cache,
                    cache_len=lengths.astype(jnp.int32),
                    pending_logits=logits.astype(jnp.float32),
                    done=jnp.zeros((B,), bool),
                    logprob_sum=jnp.zeros((B,), jnp.float32),
                    n_gen=jnp.zeros((B,), jnp.int32),
                )
            if prof is not None:
                # sampled: blocks on the new state's logits so the wall
                # spans the device work this prefill dispatched
                prof.phase_end("prefill", pt0, outputs=st.pending_logits)
        return st

    def _prefill_with_prefix(self, tokens, lengths, cached_table,
                             cached_lens) -> GenState:
        """Host-side planning for a cached-prefix partial prefill: build
        each row's full block table (cached blocks + tail CoW + fresh
        suffix blocks), then run the suffix-only device pass.

        The plan is *batched across rows*: every misaligned row's
        partially-used cached tail block is copy-on-written in ONE
        ``pool.cow`` call (one tree-mapped device scatter for the whole
        batch — quantized code+scale payloads move the same way) and all
        fresh suffix blocks come from one ``pool.alloc``, so a B-row
        admission costs O(1) device launches for block bookkeeping, not
        O(B).  The whole need (tail CoWs + fresh blocks) is reserved up
        front, so an :class:`OutOfBlocks` raise leaves pool and leases
        untouched."""
        B = tokens.shape[0]
        bs = self.pool.block_size
        lens_h = np.asarray(jax.device_get(lengths), np.int64)
        cach_h = np.asarray(cached_lens, np.int64).ravel()
        if cach_h.shape[0] != B:
            raise ValueError(f"cached_lens has {cach_h.shape[0]} rows for a "
                             f"batch of {B}")
        if (lens_h < 1).any():
            raise ValueError("cached-prefix prefill needs >= 1 suffix token "
                             "per row (the next-token logits come from the "
                             "suffix)")
        totals = cach_h + lens_h
        if (totals > self.max_len - 1).any():
            raise ValueError(
                f"cached + suffix length ({int(totals.max())}) overruns the "
                f"usable sequence length {self.max_len - 1}")
        ctab = np.asarray(cached_table, np.int64)
        n_full = cach_h // bs
        rem = cach_h % bs
        n_tot = np.array([blocks_for(t, bs) for t in totals])
        # tail CoW (one per misaligned row) + fresh suffix blocks
        n_new = n_tot - (n_full + (rem > 0))
        needed = int(n_new.sum() + (rem > 0).sum())
        if not self.pool.reserve(needed):
            raise OutOfBlocks(needed, self.pool.free_blocks)
        table = np.zeros((B, self.table_width), np.int32)
        for i in range(B):
            table[i, :n_full[i]] = ctab[i, :n_full[i]]
        # private copies of the partially-used cached tail blocks: each
        # row's lease on its original moves to the copy (cow drops one
        # source reference per block), and the suffix scatter may then
        # extend offsets [rem, bs) without touching shared KV.  One cow
        # call copies every misaligned row's tail in a single device
        # scatter.
        cow_rows = [i for i in range(B) if rem[i]]
        new_tails = self.pool.cow(
            [int(ctab[i, n_full[i]]) for i in cow_rows])
        for i, nt in zip(cow_rows, new_tails):
            table[i, n_full[i]] = nt
        fresh = self.pool.alloc(int(n_new.sum())) if n_new.any() else []
        off = 0
        for i in range(B):
            if n_new[i]:
                have = int(n_full[i] + (1 if rem[i] else 0))
                table[i, have:n_tot[i]] = fresh[off:off + int(n_new[i])]
                off += int(n_new[i])
        table_dev = jnp.asarray(table)
        # bucket the prefix gather to the blocks actually cached (batch
        # max): recompiles once per distinct width, saves the full
        # table-width gather + masked attention over max_len prefix slots
        prefix_w = max(1, int(-(-int(cach_h.max()) // bs)))
        logits, pk, pv = self._prefill_cached_jit(
            self.params, tokens, lengths, jnp.asarray(cach_h, jnp.int32),
            table_dev, self.pool.k, self.pool.v, prefix_w=prefix_w)
        self.pool.adopt(pk, pv)
        return GenState(
            cache={"table": table_dev,
                   "n_blocks": jnp.asarray(n_tot.astype(np.int32))},
            cache_len=jnp.asarray(totals.astype(np.int32)),
            pending_logits=logits.astype(jnp.float32),
            done=jnp.zeros((B,), bool),
            logprob_sum=jnp.zeros((B,), jnp.float32),
            n_gen=jnp.zeros((B,), jnp.int32),
        )

    def _prefill_paged(self, tokens, lengths, embeddings=None) -> GenState:
        """Allocate prompt blocks (host) and scatter prefill KV into them."""
        B = tokens.shape[0]
        bs = self.pool.block_size
        lens_h = np.asarray(jax.device_get(lengths))
        per_row = [blocks_for(l, bs) for l in lens_h]
        if not self.pool.reserve(sum(per_row)):
            raise OutOfBlocks(sum(per_row), self.pool.free_blocks)
        table = np.zeros((B, self.table_width), np.int32)
        n_blocks = np.zeros((B,), np.int32)
        for i, n in enumerate(per_row):
            table[i, :n] = self.pool.alloc(n)
            n_blocks[i] = n
        table_dev = jnp.asarray(table)
        logits, pk, pv = self._prefill_paged_jit(
            self.params, tokens, lengths, table_dev, self.pool.k,
            self.pool.v, embeddings)
        self.pool.adopt(pk, pv)
        return GenState(
            cache={"table": table_dev, "n_blocks": jnp.asarray(n_blocks)},
            cache_len=lengths.astype(jnp.int32),
            pending_logits=logits.astype(jnp.float32),
            done=jnp.zeros((B,), bool),
            logprob_sum=jnp.zeros((B,), jnp.float32),
            n_gen=jnp.zeros((B,), jnp.int32),
        )

    def empty_state(self, batch: int) -> GenState:
        """An all-free decoding state of ``batch`` rows (every row done).

        The continuous-batching scheduler keeps one of these alive for the
        server's lifetime and scatters admitted requests into its rows with
        :meth:`merge_rows`.  Done rows route their KV writes to the scratch
        slot, so idle rows cost one wasted lane of batched compute and no
        correctness hazards.  In paged mode an empty row holds zero blocks
        (its table is all scratch), so idle slots reserve no KV memory.
        """
        if self.paged:
            cache = {"table": jnp.zeros((batch, self.table_width), jnp.int32),
                     "n_blocks": jnp.zeros((batch,), jnp.int32)}
        else:
            cache = self.model.init_cache(self.cfg, batch, self.max_len)
        return GenState(
            cache=cache,
            cache_len=jnp.zeros((batch,), jnp.int32),
            pending_logits=jnp.zeros((batch, self.cfg.vocab_size),
                                     jnp.float32),
            done=jnp.ones((batch,), bool),
            logprob_sum=jnp.zeros((batch,), jnp.float32),
            n_gen=jnp.zeros((batch,), jnp.int32),
        )

    # -- row scatter (continuous-batching admission) -------------------------
    @staticmethod
    def _merge_impl(dst: GenState, src: GenState, rows) -> GenState:
        cache = jax.tree.map(
            lambda d, s: d.at[:, rows].set(s.astype(d.dtype)),
            dst.cache, src.cache)
        return dataclasses.replace(
            DecodeEngine._merge_vectors(dst, src, rows), cache=cache)

    @staticmethod
    def _merge_paged_impl(dst: GenState, src: GenState, rows) -> GenState:
        # paged cache leaves (table, n_blocks) carry batch on axis 0
        cache = jax.tree.map(lambda d, s: d.at[rows].set(s),
                             dst.cache, src.cache)
        return dataclasses.replace(
            DecodeEngine._merge_vectors(dst, src, rows), cache=cache)

    @staticmethod
    def _merge_vectors(dst: GenState, src: GenState, rows) -> GenState:
        return GenState(
            cache=None,
            cache_len=dst.cache_len.at[rows].set(src.cache_len),
            pending_logits=dst.pending_logits.at[rows].set(
                src.pending_logits),
            done=dst.done.at[rows].set(src.done),
            logprob_sum=dst.logprob_sum.at[rows].set(src.logprob_sum),
            n_gen=dst.n_gen.at[rows].set(src.n_gen),
        )

    def merge_rows(self, dst: GenState, src: GenState, rows: jnp.ndarray,
                   *, donate: bool = False) -> GenState:
        """Scatter ``src``'s batch rows into ``dst`` at indices ``rows``.

        ``rows`` is (B_src,) int32; dense cache leaves carry batch on
        axis 1 (axis 0 is the stacked layer dim), paged table leaves and
        per-sequence vectors on axis 0.  This is the admission primitive:
        prefill a new request into a small B_src state, then graft its
        cache/logits/length rows onto the live n_slots decode state without
        touching other rows.  Jitted so the per-leaf scatters fuse into one
        executable (recompiles once per distinct B_src).  ``donate=True``
        donates ``dst``'s buffers so the scatter happens in place — the
        scheduler hot path uses this since it immediately rebinds the
        state; callers that still need ``dst`` afterwards must keep the
        default.  Paged: the overwritten ``dst`` rows must already have
        been released (their blocks freed) — block ownership moves from
        ``src`` rows to ``dst`` rows without touching refcounts.
        """
        if self.paged:
            fn = (self._merge_paged_donate_jit if donate
                  else self._merge_paged_jit)
        else:
            fn = self._merge_donate_jit if donate else self._merge_jit
        return fn(dst, src, jnp.asarray(rows, jnp.int32))

    def release_rows(self, state: GenState, rows) -> GenState:
        """Mark ``rows`` done (slot release without a sampled stop token,
        e.g. a request hitting its max_new_tokens budget).  Paged: also
        frees the rows' blocks back to the pool and re-points their tables
        at the scratch block."""
        rows = np.asarray(rows, np.int64).ravel()
        if self.paged and rows.size:
            table, n_blocks = (np.array(a) for a in jax.device_get(
                (state.cache["table"], state.cache["n_blocks"])))
            for r in rows:
                self.pool.release(table[r, :n_blocks[r]])
                table[r] = 0
                n_blocks[r] = 0
            state = dataclasses.replace(
                state, cache={"table": jnp.asarray(table),
                              "n_blocks": jnp.asarray(n_blocks)})
        rows = jnp.asarray(rows, jnp.int32)
        return dataclasses.replace(state, done=state.done.at[rows].set(True))

    # -- fork / reorder (TTS batch fan-out) ----------------------------------
    _dense_fork_warned = False  # class-level: warn once per process

    def fork(self, state: GenState, n: int) -> GenState:
        """Replicate each sequence n times (prompt-shared Best-of-N).
        Row i maps to rows [i*n, (i+1)*n).

        Dense: physically copies each row's KV n times.  Paged: bumps the
        refcount of every owned block and repeats the table row — zero KV
        blocks are allocated or copied; the samples share the prompt's
        blocks until copy-on-write splits them at their first divergent
        write (see :meth:`prepare_decode`)."""
        if not self.paged and n > 1 and not DecodeEngine._dense_fork_warned:
            DecodeEngine._dense_fork_warned = True
            warnings.warn(
                "DecodeEngine.fork on the dense KV layout physically "
                "replicates each row's prompt KV n times (O(n*prompt) "
                "duplicated bytes); construct the engine with paged=True "
                "for zero-copy prefix sharing via the refcounted block "
                "pool", RuntimeWarning, stacklevel=2)

        def rep(x, axis):
            return jnp.repeat(x, n, axis=axis)

        if self.paged:
            table, n_blocks = jax.device_get(
                (state.cache["table"], state.cache["n_blocks"]))
            for i in range(table.shape[0]):
                if n > 1:
                    self.pool.retain(table[i, :n_blocks[i]], times=n - 1)
            cache = jax.tree.map(lambda x: rep(x, 0), state.cache)
        else:
            cache = jax.tree.map(lambda x: rep(x, 1), state.cache)
        return GenState(
            cache=cache,
            cache_len=rep(state.cache_len, 0),
            pending_logits=rep(state.pending_logits, 0),
            done=rep(state.done, 0),
            logprob_sum=rep(state.logprob_sum, 0),
            n_gen=rep(state.n_gen, 0),
        )

    def reorder(self, state: GenState, idx: jnp.ndarray) -> GenState:
        """Gather sequences by ``idx`` (beam-search survivor commit).

        Dense: copies the gathered cache rows.  Paged: gathers the block
        tables and fixes refcounts — rows dropped by ``idx`` release their
        blocks, rows duplicated k times gain k-1 references (their copies
        then diverge via copy-on-write)."""
        if self.paged:
            idx_h = np.asarray(jax.device_get(idx)).ravel()
            table, n_blocks = jax.device_get(
                (state.cache["table"], state.cache["n_blocks"]))
            counts = np.bincount(idx_h, minlength=table.shape[0])
            for r in range(table.shape[0]):
                owned = table[r, :n_blocks[r]]
                if counts[r] == 0:
                    self.pool.release(owned)
                elif counts[r] > 1:
                    self.pool.retain(owned, times=int(counts[r]) - 1)
            cache = jax.tree.map(lambda x: x[idx], state.cache)
        else:
            cache = jax.tree.map(lambda x: x[:, idx], state.cache)
        return GenState(
            cache=cache,
            cache_len=state.cache_len[idx],
            pending_logits=state.pending_logits[idx],
            done=state.done[idx],
            logprob_sum=state.logprob_sum[idx],
            n_gen=state.n_gen[idx],
        )

    # -- paged block bookkeeping ---------------------------------------------
    def prepare_decode(self, state: GenState, n_steps: int = 1,
                       clamp: bool = False) -> GenState:
        """Host-side paged bookkeeping before decoding ``n_steps`` tokens.

        For every live (not-done) row: allocate the blocks its next
        ``n_steps`` writes will land in, and copy-on-write any still-shared
        block at or past the write frontier (post-fork tail blocks).  The
        whole request is planned first and committed only if the free list
        covers it, so an :class:`OutOfBlocks` raise leaves the pool and the
        state untouched — the scheduler's preemption hook.  No-op in dense
        mode.

        ``clamp=True`` caps each row's plan at the usable sequence length
        instead of raising: a speculative verify plans ``k`` positions for
        every row, and a row near its budget simply has its over-length
        proposals routed to the scratch offset (never committed — the
        scheduler caps its proposal count to the remaining budget anyway).
        """
        if not self.paged:
            return state
        table, n_blocks, clen, done = jax.device_get(
            (state.cache["table"], state.cache["n_blocks"],
             state.cache_len, state.done))
        table = np.array(table)
        n_blocks = np.array(n_blocks)
        bs = self.pool.block_size
        plan_new: list[tuple] = []     # (row, slot)
        plan_cow: list[tuple] = []     # (row, slot, old_block)
        # planned CoWs drop a reference each, so the *last* planner of a
        # shared block sees an effective refcount of 1 and writes in place
        # (n-way fork costs n-1 copies, not n)
        pending_drops: dict[int, int] = {}
        for i in range(table.shape[0]):
            if done[i]:
                continue
            last = int(clen[i]) + n_steps - 1   # final position written
            if clamp:
                last = min(last, self.max_len - 2)
            if last > self.max_len - 2:
                raise ValueError(
                    f"row {i}: decoding {n_steps} steps from length "
                    f"{int(clen[i])} overruns the usable sequence length "
                    f"{self.max_len - 1} (last slot is KV scratch)")
            first_slot = int(clen[i]) // bs     # block of the first write
            for s in range(first_slot, int(n_blocks[i])):
                blk = int(table[i, s])
                if self.pool.refcount[blk] - pending_drops.get(blk, 0) > 1:
                    plan_cow.append((i, s, blk))
                    pending_drops[blk] = pending_drops.get(blk, 0) + 1
            for s in range(int(n_blocks[i]), last // bs + 1):
                plan_new.append((i, s))
        needed = len(plan_new) + len(plan_cow)
        if not needed:
            return state
        if not self.pool.reserve(needed):
            raise OutOfBlocks(needed, self.pool.free_blocks)
        new_ids = self.pool.cow([b for _, _, b in plan_cow])
        for (i, s, _), bid in zip(plan_cow, new_ids):
            table[i, s] = bid
        for (i, s), bid in zip(plan_new, self.pool.alloc(len(plan_new))):
            table[i, s] = bid
            n_blocks[i] = max(n_blocks[i], s + 1)
        return dataclasses.replace(
            state, cache={"table": jnp.asarray(table),
                          "n_blocks": jnp.asarray(n_blocks)})

    # -- decode -------------------------------------------------------------
    def _step_core(self, params, state: GenState, cache_in, rng,
                   sc: SamplerConfig, stop_ids: tuple, row_stops=None):
        stop_ids = tuple(stop_ids) or (self.eos_id,)
        with jax.named_scope("sampler"):
            tok = sample(state.pending_logits, rng, sc)
            lp = logprobs_of(state.pending_logits, tok)
        tok = jnp.where(state.done, self.pad_id, tok).astype(jnp.int32)
        new_done = state.done
        for s in stop_ids:
            new_done = new_done | (tok == s)
        if row_stops is not None:
            # per-row extra stop id (-1 = none): beam rows stop at their
            # step delimiter while chat rows in the same batch do not
            new_done = new_done | (tok == row_stops)
        new_len = jnp.where(state.done, state.cache_len, state.cache_len + 1)
        # Done rows must not clobber their last real KV slot: route their
        # (discarded) write to the reserved scratch slot max_len-1.  Usable
        # sequence length is therefore max_len - 1.  (The paged path maps
        # the same max_len-1 position through the block table — it lands in
        # the scratch block or an un-attended final offset.)
        model_len = jnp.where(state.done, self.max_len, new_len)
        logits, cache = self.model.decode_step(
            params, tok[:, None], cache_in, model_len, self.cfg, self.par)
        # Recurrent (non-positional) states have no scratch slot — restore
        # them for done rows.  These leaves are small (SSM/conv states).
        for key in ("conv", "ssm"):
            if key in cache:
                d = state.done.reshape((1, -1) + (1,) * (cache[key].ndim - 2))
                cache[key] = jnp.where(d, cache_in[key], cache[key])
        # Freeze pending logits on done rows so that resume() continues from
        # the logits that followed the stop token, not scratch-slot garbage.
        pending = jnp.where(state.done[:, None], state.pending_logits,
                            logits.astype(jnp.float32))
        new_state = GenState(
            cache=None,  # caller installs the layout-appropriate cache
            cache_len=new_len,
            pending_logits=pending,
            done=new_done,
            logprob_sum=state.logprob_sum + jnp.where(state.done, 0.0, lp),
            n_gen=state.n_gen + jnp.where(state.done, 0, 1),
        )
        return new_state, tok, cache

    def _step_impl(self, params, state: GenState, rng, row_stops=None, *,
                   sc: SamplerConfig, stop_ids: tuple = ()):
        st, tok, cache = self._step_core(params, state, state.cache, rng,
                                         sc, stop_ids, row_stops)
        return dataclasses.replace(st, cache=cache), tok

    def _step_paged_impl(self, params, state: GenState, pool_k, pool_v, rng,
                         row_stops=None, *, sc: SamplerConfig,
                         stop_ids: tuple = ()):
        cache_in = {"k": pool_k, "v": pool_v,
                    "table": state.cache["table"]}
        st, tok, cache = self._step_core(params, state, cache_in, rng,
                                         sc, stop_ids, row_stops)
        st = dataclasses.replace(st, cache=state.cache)
        return st, tok, cache["k"], cache["v"]

    def step(self, state: GenState, rng, sc: SamplerConfig = SamplerConfig(),
             stop_ids: tuple = (), row_stops=None, canary: bool = False):
        """One decode step. Returns (new_state, sampled tokens (B,)).

        ``row_stops`` (B,) int32 adds one *per-row* stop id on top of the
        shared ``stop_ids`` (-1 disables a row) — the scheduler uses it to
        stop beam-search rows at their step delimiter while plain chat
        rows in the same batch decode through it.

        Paged: runs :meth:`prepare_decode` first (may raise
        :class:`OutOfBlocks`), then scatters this step's KV into pool
        blocks in place.

        ``canary=True`` (paged only) additionally re-runs the step
        through the *exact* path — XLA paged attention, reference fp
        dequant, exact softmax — on the same post-plan state and
        pre-step pool (no donation), stashing the resulting logits in
        :attr:`last_canary_logits` for the scheduler's drift comparison.
        Under the default "xla" impl the exact path is the production
        path, so the comparison must be exact."""
        prof = self.profiler
        tr = self.tracer
        if self.paged:
            with phase(tr, "plan"):  # CoW/alloc host planning
                state = self.prepare_decode(state)
            if canary:
                self.last_canary_logits = self._canary_step(
                    state, rng, row_stops, sc, tuple(stop_ids))
            pt0 = prof.phase_begin("decode") if prof is not None else 0.0
            with phase(tr, "dispatch"):
                st, tok, pk, pv = self._step_paged_jit(
                    self.params, state, self.pool.k, self.pool.v, rng,
                    row_stops, sc=sc, stop_ids=tuple(stop_ids))
            if prof is not None:
                prof.phase_end("decode", pt0,
                               outputs=(tok, st.pending_logits))
            self.pool.adopt(pk, pv)
            return st, tok
        pt0 = prof.phase_begin("decode") if prof is not None else 0.0
        with phase(tr, "dispatch"):
            st, tok = self._step_jit(self.params, state, rng, row_stops,
                                     sc=sc, stop_ids=tuple(stop_ids))
        if prof is not None:
            prof.phase_end("decode", pt0, outputs=(tok, st.pending_logits))
        return st, tok

    def _canary_step(self, state: GenState, rng, row_stops, sc, stop_ids):
        """Exact-path replica of the paged decode step (no donation, no
        state commit): a dedicated jit of :meth:`_step_paged_impl` traced
        with the paged-attention impl forced to "xla" — table gather +
        reference ``dequantize_for_pool`` + exact f32 softmax — so its
        logits are the drift-free reference for whatever approximated
        path production runs.  The impl switch is trace-time-only state
        (``layers._PAGED_ATTN_IMPL`` is read when the jit traces), so it
        is set around every call and restored in ``finally``."""
        from repro.models import layers

        from repro.kernels import ops as _kops

        if self._canary_jit is None:
            impl = self._step_paged_impl

            # Distinct wrapper function, not ``jax.jit(impl)`` again: jax
            # caches the traced jaxpr per underlying callable, so jitting
            # the same bound method twice would let whichever jit runs
            # first (the canary, on step 0) satisfy the other's trace from
            # cache — and the production trace would never fire the op
            # hook inside the profiler's "decode" phase.
            def _canary_impl(params, state, pool_k, pool_v, rng,
                             row_stops=None, *, sc, stop_ids=()):
                return impl(params, state, pool_k, pool_v, rng, row_stops,
                            sc=sc, stop_ids=stop_ids)

            self._canary_jit = jax.jit(_canary_impl,
                                       static_argnames=("sc", "stop_ids"))
        prev = layers.set_paged_attention_impl("xla")
        # Canary work is verification overhead, not production compute —
        # mute the dispatch hook so its trace doesn't pollute attribution.
        prev_hook = _kops.set_op_hook(None)
        try:
            st, _tok, _pk, _pv = self._canary_jit(
                self.params, state, self.pool.k, self.pool.v, rng,
                row_stops, sc=sc, stop_ids=stop_ids)
        finally:
            _kops.set_op_hook(prev_hook)
            layers.set_paged_attention_impl(prev)
        return st.pending_logits

    def kv_roundtrip_error(self, max_blocks: int = 4):
        """Per-layer KV quantization round-trip error over a sample of
        live pool blocks: ``max |dequant(quant(dequant(pool))) -
        dequant(pool)|`` per layer, K and V leaves combined.  A stable
        quantizer round-trips its own output exactly (error 0.0); drift
        here means the stored codes sit on decision boundaries the
        re-quantization resolves differently — the online proxy for §5.1
        drift when no fp reference exists.  Returns None on fp pools."""
        from repro.serving.kv_quant import (dequantize_kv, kv_geometry,
                                            quantize_kv)

        pool = self.pool
        if pool is None or not isinstance(pool.k, dict):
            return None
        live = np.nonzero(pool.refcount > 0)[0][:max_blocks]
        if live.size == 0:
            return None
        per_layer = None
        for leaf in (pool.k, pool.v):
            sub = jax.tree.map(lambda a: a[:, live], leaf)
            mode, gr, gc, _ = kv_geometry(sub)
            x = dequantize_kv(sub)
            x2 = dequantize_kv(quantize_kv(x, mode=mode, gr=gr, gc=gc))
            err = jnp.max(jnp.abs(x2 - x),
                          axis=tuple(range(1, x.ndim)))  # (L,)
            per_layer = err if per_layer is None \
                else jnp.maximum(per_layer, err)
        return [float(e) for e in jax.device_get(per_layer)]

    def _generate_impl(self, params, state: GenState, rng, *, n_steps: int,
                       sc: SamplerConfig, stop_ids: tuple = ()):
        def body(st, key):
            st, tok = self._step_impl(params, st, key, sc=sc, stop_ids=stop_ids)
            return st, tok

        keys = jax.random.split(rng, n_steps)
        state, toks = jax.lax.scan(body, state, keys)
        return state, toks.T  # (B, n_steps)

    def _gen_paged_impl(self, params, state: GenState, pool_k, pool_v, rng,
                        *, n_steps: int, sc: SamplerConfig,
                        stop_ids: tuple = ()):
        def body(carry, key):
            st, pk, pv = carry
            st, tok, pk, pv = self._step_paged_impl(params, st, pk, pv, key,
                                                    sc=sc, stop_ids=stop_ids)
            return (st, pk, pv), tok

        keys = jax.random.split(rng, n_steps)
        (state, pk, pv), toks = jax.lax.scan(body, (state, pool_k, pool_v),
                                             keys)
        return state, toks.T, pk, pv

    def generate(self, state: GenState, n_steps: int, rng,
                 sc: SamplerConfig = SamplerConfig(), stop_ids: tuple = ()):
        """Decode up to n_steps tokens (stopping per-row at any id in
        ``stop_ids``, default EOS). Returns (final_state, (B, n_steps) tokens,
        pad_id after stop).

        Paged: blocks covering the whole n_steps horizon are allocated (and
        shared tails CoW'd) up front so the scan writes purely in place;
        rows that stop early keep their surplus blocks until released."""
        if self.paged:
            state = self.prepare_decode(state, n_steps)
            state, toks, pk, pv = self._gen_paged_jit(
                self.params, state, self.pool.k, self.pool.v, rng,
                n_steps=n_steps, sc=sc, stop_ids=tuple(stop_ids))
            self.pool.adopt(pk, pv)
            return state, toks
        return self._gen_jit(self.params, state, rng, n_steps=n_steps, sc=sc,
                             stop_ids=tuple(stop_ids))

    def resume(self, state: GenState) -> GenState:
        """Clear done flags (used by step-level beam search to continue
        beams after a step-delimiter stop)."""
        return GenState(
            cache=state.cache, cache_len=state.cache_len,
            pending_logits=state.pending_logits,
            done=jnp.zeros_like(state.done),
            logprob_sum=state.logprob_sum, n_gen=state.n_gen)

    def freeze_rows(self, state: GenState, rows) -> GenState:
        """Mark ``rows`` done *without* freeing paged blocks: the rows stop
        advancing (writes routed to scratch, pending logits frozen) but
        keep their KV.  The scheduler freezes beam rows that exhaust a
        reasoning step's token budget until the whole tree reaches its
        scoring boundary; :meth:`resume_rows` re-arms them."""
        rows = jnp.asarray(np.asarray(rows, np.int64).ravel(), jnp.int32)
        return dataclasses.replace(state, done=state.done.at[rows].set(True))

    def resume_rows(self, state: GenState, rows) -> GenState:
        """Clear done flags for ``rows`` only (the per-row counterpart of
        :meth:`resume` — other rows, e.g. idle scheduler slots, keep their
        done state)."""
        rows = jnp.asarray(np.asarray(rows, np.int64).ravel(), jnp.int32)
        return dataclasses.replace(state,
                                   done=state.done.at[rows].set(False))

    # -- speculative decoding (draft-then-verify) ----------------------------
    def _spec_verify_impl(self, params, state: GenState, xs, n_prop,
                          row_stops, pool_k, pool_v, *, prefix_w: int,
                          stop_ids: tuple):
        """Verify ``W`` proposed tokens per row in ONE target forward.

        ``xs`` (B, W): column 0 is the target's own pending-logits argmax
        (always correct under greedy), columns 1.. are draft proposals.
        The forward consumes all W tokens while attending over each row's
        committed prefix (gathered through its block table, exactly the
        partial-prefill read path) and returns logits at every position;
        position j's argmax is what greedy decoding *would* sample after
        ``xs[:, :j+1]`` — agreement with ``xs[:, j+1]`` extends the
        accepted prefix, the first disagreement cuts it.  Committed stop
        tokens are consumed (KV written, counted) exactly like
        ``_step_core``; everything past the acceptance point is masked
        out of lengths/logprobs and its already-scattered KV is reclaimed
        host-side by :meth:`trim_rows` (a block free, never a copy)."""
        from repro.models import transformer

        B, W = xs.shape
        bs = self.pool.block_size
        table = state.cache["table"]
        clen = state.cache_len
        prefix = self._gather_prefix(table, pool_k, pool_v, clen,
                                     prefix_w=prefix_w)
        logits, kvs, _ = self.model.forward(
            params, xs, self.cfg, self.par, return_kv=True, prefix=prefix)
        logits = logits.astype(jnp.float32)          # (B, W, V)
        # scatter all W proposal KVs at each row's write frontier; done
        # (frozen) rows route to the scratch clamp like _step_core
        start = jnp.where(state.done, self.max_len, clen)
        pk = transformer._scatter_suffix_blocks(pool_k, kvs[0], table, bs,
                                                start)
        pv = transformer._scatter_suffix_blocks(pool_v, kvs[1], table, bs,
                                                start)
        # greedy longest-agreeing-prefix acceptance: token 0 always
        # commits (it was sampled from the real pending logits), token
        # j >= 1 commits iff every earlier proposal agreed with the
        # target's argmax — and a committed stop truncates the run
        tgt = jnp.argmax(logits[:, :-1, :], axis=-1).astype(jnp.int32)
        agree = (xs[:, 1:] == tgt).astype(jnp.int32)
        jidx = jnp.arange(W, dtype=jnp.int32)[None, :]
        ok = jnp.concatenate(
            [jnp.ones((B, 1), jnp.int32), jnp.cumprod(agree, axis=1)],
            axis=1)
        ok = ok * (jidx < n_prop[:, None]).astype(jnp.int32)
        is_stop = jnp.zeros((B, W), bool)
        for s in stop_ids:
            is_stop = is_stop | (xs == s)
        if row_stops is not None:
            is_stop = is_stop | (xs == row_stops[:, None])
        stop_commit = ok * is_stop.astype(jnp.int32)
        before = jnp.cumsum(stop_commit, axis=1) - stop_commit
        commit = ok * (before == 0).astype(jnp.int32)
        commit = commit * (~state.done).astype(jnp.int32)[:, None]
        a = jnp.sum(commit, axis=1).astype(jnp.int32)      # accepted count
        new_done = state.done | jnp.any(commit.astype(bool) & is_stop,
                                        axis=1)
        # next pending logits = the target's distribution after the last
        # committed token (frozen for done rows, like _step_core)
        idx = jnp.clip(a - 1, 0, W - 1)
        q_next = jnp.take_along_axis(logits, idx[:, None, None],
                                     axis=1)[:, 0]
        pending = jnp.where(state.done[:, None], state.pending_logits,
                            q_next)
        # per-token logprobs under the distribution each was sampled
        # from: column 0 under the old pending logits, column j under
        # the verify logits at j-1 — committed columns only
        dists = jnp.concatenate(
            [state.pending_logits[:, None, :], logits[:, :-1, :]], axis=1)
        lps = jax.vmap(logprobs_of, in_axes=(1, 1), out_axes=1)(dists, xs)
        new_state = GenState(
            cache=state.cache,
            cache_len=clen + a,
            pending_logits=pending,
            done=new_done,
            logprob_sum=state.logprob_sum
            + jnp.sum(lps * commit.astype(jnp.float32), axis=1),
            n_gen=state.n_gen + a,
        )
        return new_state, commit, pk, pv

    def spec_verify(self, state: GenState, xs, n_prop, row_stops=None,
                    stop_ids: tuple = ()):
        """Speculative verify step: commit the longest greedy-agreeing
        prefix of ``xs`` (B, W) per row in one batched target forward.

        ``xs[:, 0]`` must be the argmax of ``state.pending_logits`` (the
        token a plain greedy step would emit — so a round always commits
        at least one token per live row) and ``n_prop`` (B,) the number
        of valid columns per row; padding beyond it is ignored.  Returns
        ``(new_state, commit)`` with ``commit`` a (B, W) host 0/1 prefix
        mask — row i committed ``xs[i, :commit[i].sum()]``.  Blocks for
        the full W-token horizon are planned up front (may raise
        :class:`OutOfBlocks` — state and pool untouched, the scheduler's
        preemption hook) and the rejected suffix's blocks are reclaimed
        by :meth:`trim_rows`.  Paged only."""
        if not self.paged:
            raise ValueError("spec_verify requires the paged KV layout "
                             "(DecodeEngine(paged=True))")
        W = int(xs.shape[1])
        stop_ids = tuple(stop_ids) or (self.eos_id,)
        with phase(self.tracer, "plan"):
            state = self.prepare_decode(state, W, clamp=True)
        bs = self.pool.block_size
        clen_h, done_h = (np.asarray(a) for a in jax.device_get(
            (state.cache_len, state.done)))
        live = ~done_h
        # bucket the prefix gather like the partial prefill: block
        # granular, so a recompile costs one new shape per block of
        # context growth, not one per round
        top = int(clen_h[live].max()) if live.any() else 1
        prefix_w = max(1, -(-top // bs))
        prof = self.profiler
        with phase(self.tracer, "spec_verify", batch=int(xs.shape[0]),
                   width=W):
            pt0 = prof.phase_begin("spec_verify") if prof is not None else 0.0
            st, commit, pk, pv = self._spec_verify_jit(
                self.params, state, jnp.asarray(xs, jnp.int32),
                jnp.asarray(n_prop, jnp.int32), row_stops, self.pool.k,
                self.pool.v, prefix_w=prefix_w, stop_ids=stop_ids)
            if prof is not None:
                prof.phase_end("spec_verify", pt0,
                               outputs=(commit, st.pending_logits))
            self.pool.adopt(pk, pv)
            commit_h = np.asarray(jax.device_get(commit))
        return st, commit_h

    def trim_rows(self, state: GenState, rows) -> GenState:
        """Free the planned-but-unused tail blocks of ``rows`` after a
        speculative round: blocks past ``blocks_for(cache_len)`` were
        allocated (or copy-on-written — either way private, refcount 1)
        for proposals the verify rejected, so releasing them *is* the
        cost of rejection — a free-list append, zero KV bytes moved.
        Callers pass only rows that were live at verify time (frozen
        beam lanes keep their surplus blocks like any frozen row).
        No-op in dense mode."""
        if not self.paged:
            return state
        rows = np.asarray(rows, np.int64).ravel()
        if not rows.size:
            return state
        bs = self.pool.block_size
        table, n_blocks, clen = (np.array(a) for a in jax.device_get(
            (state.cache["table"], state.cache["n_blocks"],
             state.cache_len)))
        changed = False
        for r in rows:
            keep = blocks_for(int(clen[r]), bs)
            if n_blocks[r] > keep:
                self.pool.release(table[r, keep:n_blocks[r]])
                table[r, keep:n_blocks[r]] = 0
                n_blocks[r] = keep
                changed = True
        if not changed:
            return state
        return dataclasses.replace(
            state, cache={"table": jnp.asarray(table),
                          "n_blocks": jnp.asarray(n_blocks)})

    def spec_snapshot(self, state: GenState, rows) -> GenState:
        """Self-drafting draft lane: a second state aliasing ``rows``'
        blocks via a refcount bump — the draft lane IS a paged fork.  The
        draft's first divergent write copy-on-writes its frontier block
        (``prepare_decode`` sees refcount > 1), so the target's KV is
        never touched, and ``release_rows`` on the snapshot undoes the
        bump: rejection frees blocks, never copies KV.  Rows not in
        ``rows`` come back done with empty tables (idle draft lanes)."""
        if not self.paged:
            raise ValueError("spec_snapshot requires the paged KV layout "
                             "(DecodeEngine(paged=True))")
        rows = [int(r) for r in np.asarray(rows, np.int64).ravel()]
        table, n_blocks = (np.array(a) for a in jax.device_get(
            (state.cache["table"], state.cache["n_blocks"])))
        mask = np.zeros(table.shape[0], bool)
        mask[rows] = True
        for r in rows:
            self.pool.retain(table[r, :n_blocks[r]])
        table[~mask] = 0
        n_blocks[~mask] = 0
        return GenState(
            cache={"table": jnp.asarray(table),
                   "n_blocks": jnp.asarray(n_blocks)},
            cache_len=state.cache_len,
            pending_logits=state.pending_logits,
            done=state.done | jnp.asarray(~mask),
            logprob_sum=state.logprob_sum,
            n_gen=state.n_gen)

    def _forced_step_impl(self, params, state: GenState, tok):
        tok = jnp.where(state.done, self.pad_id, tok).astype(jnp.int32)
        new_len = jnp.where(state.done, state.cache_len,
                            state.cache_len + 1)
        model_len = jnp.where(state.done, self.max_len, new_len)
        logits, cache = self.model.decode_step(
            params, tok[:, None], state.cache, model_len, self.cfg,
            self.par)
        for key in ("conv", "ssm"):
            if key in cache:
                d = state.done.reshape((1, -1)
                                       + (1,) * (cache[key].ndim - 2))
                cache[key] = jnp.where(d, state.cache[key], cache[key])
        pending = jnp.where(state.done[:, None], state.pending_logits,
                            logits.astype(jnp.float32))
        return dataclasses.replace(state, cache=cache, cache_len=new_len,
                                   pending_logits=pending)

    def _forced_step_paged_impl(self, params, state: GenState, pool_k,
                                pool_v, tok):
        st = self._forced_step_impl(
            params, dataclasses.replace(state, cache={
                "k": pool_k, "v": pool_v, "table": state.cache["table"]}),
            tok)
        return (dataclasses.replace(st, cache=state.cache), st.cache["k"],
                st.cache["v"])

    def forced_step(self, state: GenState, tok) -> GenState:
        """Feed a *given* token per row (no sampling): the scheduler's
        draft-model engine consumes the target's already-committed token
        before proposing its continuation, and teacher-forced numerics
        checks compare the logits that follow.  Logprob/n_gen bookkeeping
        is untouched — draft-side counts never reach scheduler metrics.
        Paged: plans blocks first (:meth:`prepare_decode`) and writes the
        token's KV into the pool in place."""
        tok = jnp.asarray(tok, jnp.int32)
        if not self.paged:
            return self._forced_jit(self.params, state, tok)
        state = self.prepare_decode(state)
        st, pk, pv = self._forced_paged_jit(self.params, state, self.pool.k,
                                            self.pool.v, tok)
        self.pool.adopt(pk, pv)
        return st


# ---------------------------------------------------------------------------
# Continuous batching scheduler (slot-based)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BeamSpec:
    """Step-level tree search as a scheduler request class (paper §2.1).

    A request carrying a ``BeamSpec`` occupies ``width * expand`` slots
    ("lanes") and decodes like any other row of the continuous batch.  A
    lane stops at ``step_stop_id`` (the reasoning-step delimiter) or after
    ``step_tokens`` tokens; once every lane has stopped the tree hits a
    *scoring boundary*: ``score`` ranks all ``width * expand`` candidate
    prefixes in ONE batched call, the top ``width`` survive, and one
    ``DecodeEngine.reorder`` commits the prune + re-expansion — on a paged
    pool, losing lanes' blocks free (refcount to zero) and each survivor's
    blocks gain ``expand - 1`` references (zero KV bytes copied).  After
    ``max_steps`` boundaries (or ``finished`` returning True on the
    survivors) ``final_score`` picks the answer and the request completes
    with ``width`` samples.

    The callbacks keep the scheduler tokenizer-agnostic; the controller
    builds them (decode token lists -> texts -> PRM):

    * ``score(token_lists, logprob_sum, n_gen) -> (n,) scores`` — batched
      candidate scoring at each boundary (required);
    * ``final_score`` — final-beam selection (defaults to ``score``);
    * ``finished(token_lists) -> bool`` — early-exit check on the
      survivors (e.g. every beam contains a final answer).
    """

    width: int                   # surviving beams per boundary
    expand: int                  # candidates per surviving beam
    step_tokens: int = 16        # token budget per reasoning step
    max_steps: int = 8           # scoring boundaries before final selection
    step_stop_id: int = -1       # step delimiter token id (e.g. '.')
    score: Optional[Callable] = None
    final_score: Optional[Callable] = None
    finished: Optional[Callable] = None

    @property
    def fan(self) -> int:
        """Slots (lanes) the request occupies while decoding."""
        return self.width * self.expand


@dataclass(frozen=True)
class SpecConfig:
    """Speculative decoding mode for the continuous scheduler.

    Each scheduler step becomes a draft-then-verify *round*: a cheap
    drafter proposes up to ``k`` tokens per eligible row and ONE batched
    target forward verifies all of them, committing the longest prefix
    that agrees with what plain greedy decoding would have produced — so
    speculative greedy output is bit-identical to the direct path, the
    only thing that changes is tokens per step.  Exactly one draft source
    must be chosen:

    * ``self_draft=True`` — the target drafts for itself on a forked
      (refcount-bumped) snapshot of its own paged state: zero extra
      params, and the draft always agrees, so every round commits all
      ``k`` tokens.  This is the machinery-exercising / upper-bound mode.
    * ``draft_model="<arch>"`` — a small model from the configs registry
      (smoke config, vocab aligned to the target) runs k-1 cheap dense
      decode steps per round; acceptance then depends on how often the
      draft's greedy argmax matches the target's.

    Speculation applies under greedy sampling on a paged engine only;
    beam lanes and ``Request(no_spec=True)`` rows ride along in the same
    verify at one token per round (plain-step-equivalent)."""

    k: int = 4                   # max tokens committed per row per round
    draft_model: str = ""        # configs-registry arch of the drafter
    self_draft: bool = False     # target drafts on a forked snapshot

    def __post_init__(self):
        if self.k < 2:
            raise ValueError(f"SpecConfig.k must be >= 2 (k={self.k} "
                             f"proposes nothing beyond the plain step)")
        if bool(self.draft_model) == bool(self.self_draft):
            raise ValueError("SpecConfig needs exactly one draft source: "
                             "draft_model=<arch> or self_draft=True")


@dataclass
class Request:
    req_id: int
    prompt: jnp.ndarray          # (S,) int32
    max_new_tokens: int = 64
    n_samples: int = 1           # >1: TTS fan-out sharing one prefill (fork)
    search: Optional[BeamSpec] = None  # beam-search tree request class
    no_spec: bool = False        # opt out of speculative decoding rounds


@dataclass
class CompletedSample:
    """One finished slot occupancy (one sample of one request)."""

    req_id: int
    sample_idx: int
    tokens: list                 # generated ids, stop token excluded
    logprob_sum: float           # cumulative sampled logprob (TTS scoring)
    n_gen: int                   # tokens sampled incl. any stop token — the
                                 # denominator matching logprob_sum
    finish_reason: str           # "stop" | "length"
    admitted_step: int           # scheduler step the slot was filled
    first_decode_step: int       # first step this sample decoded in batch
    finished_step: int           # step the slot was released


@dataclass
class _Slot:
    """Host-side bookkeeping for one occupied decode row."""

    req: Request
    sample_idx: int
    admitted_step: int
    tokens: list = field(default_factory=list)
    first_decode_step: int = -1


@dataclass
class _BeamRun:
    """Host-side bookkeeping for one in-flight beam-search request.

    ``rows`` are the ``fan`` slot indices the tree occupies (fixed for the
    request's lifetime — boundary reorders move KV *between* these rows,
    never out of them).  Lane ``j`` accumulates its candidate prefix in
    ``tokens[j]`` (step-delimiter stops included, like the direct path's
    decode of the generate output); ``step_gen``/``stopped`` track each
    lane's progress toward the current scoring boundary."""

    req: Request
    spec: BeamSpec
    rows: list
    tokens: list                 # per-lane generated ids since admission
    step_gen: list               # per-lane tokens sampled this beam step
    stopped: list                # per-lane: reached delimiter/budget
    beam_step: int = 0           # boundaries completed


@dataclass
class StepRecord:
    step: int
    occupancy: int               # rows decoding this step
    admitted: int                # requests admitted this step
    prefill_tokens: int          # prompt tokens prefilled this step
    wall_s: float = 0.0          # host wall time of this step_once call
    # tokens committed this step; None = one per occupied row (plain
    # decode).  Speculative rounds commit several per row, so occupancy
    # alone would under-count throughput.
    decode_tokens: Optional[int] = None


class SchedulerMetrics:
    """Step-level metrics of the continuous batching loop."""

    def __init__(self, n_slots: int):
        self.n_slots = n_slots
        self.records: list[StepRecord] = []
        self.completed_requests = 0
        self.completed_samples = 0
        self.preemptions = 0
        self.wall_s = 0.0
        # cross-request prefix cache (zero unless a cache is attached):
        # one lookup per admitted request; a hit means some prefix of the
        # prompt was served from cached blocks, and prefill_tokens_saved
        # counts the prompt tokens whose prefill compute was skipped
        self.cache_lookups = 0
        self.cache_hits = 0
        self.prefill_tokens_saved = 0
        # paged KV accounting in *bytes* (dtype-aware: a quantized pool's
        # blocks are smaller, so block counts alone would overstate its
        # footprint); updated by the scheduler each step, 0 when dense
        self.peak_kv_bytes = 0
        self.kv_quant = "none"
        # admission batching: one entry per engine.prefill call made at
        # admission, holding the number of requests that call admitted.
        # prefill_calls_per_request < 1 is the batched-admission win the
        # serving benchmark asserts (it was pinned at 1 for cache-aware
        # admission before batched partial prefill).
        self.admission_batch_sizes: list[int] = []
        # beam-search (tree) workload counters: a boundary is one
        # prune+expand commit; expansions/prunes count lanes forked /
        # released there (fan - width each); prm_batches counts batched
        # score-callback calls and prm_candidates the candidates they
        # covered — candidates_per_batch > 1 is the batched-scoring win
        # (the pre-scheduler path scored per-candidate at batch 1)
        self.beam_boundaries = 0
        self.beam_expansions = 0
        self.beam_prunes = 0
        self.prm_batches = 0
        self.prm_candidates = 0
        # speculative decoding: one "round" is one draft+verify cycle.
        # draft_tokens counts proposals beyond the mandatory first token
        # per eligible row, accepted those the verify committed beyond
        # it; committed/row_steps counts every committed token over every
        # (row, round) pair, so accepted_tokens_per_step > 1 iff
        # speculation beat one-token-per-step decoding.
        self.spec_rounds = 0
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_committed_tokens = 0
        self.spec_row_steps = 0
        # per-request latency records (telemetry.RequestLatency), appended
        # by the scheduler at request completion when a Tracer is attached
        # — the histogram behind the summary's ttft/itl/queue_wait
        # percentiles.  Always empty without a tracer (the keys then
        # report 0.0); step_time_* comes from StepRecord.wall_s and needs
        # no tracer.
        self.latencies: list[RequestLatency] = []
        # roofline/canary profiler (profiling.KernelProfiler) bound by
        # ContinuousScheduler(profiler=...); summary() merges its
        # kernel_time_share / roofline_efficiency / canary drift keys
        # (all 0.0 when no profiler is attached, so the key set is
        # stable either way)
        self.profiler = None

    def record(self, rec: StepRecord):
        self.records.append(rec)

    def record_prefill(self, batch_size: int):
        """Account one admission prefill call covering ``batch_size``
        requests (a TTS group counts as one request: one prefill, forked)."""
        self.admission_batch_sizes.append(batch_size)

    @property
    def prefill_calls(self) -> int:
        return len(self.admission_batch_sizes)

    def summary(self) -> dict:
        steps = len(self.records)
        decode = sum(r.occupancy if r.decode_tokens is None
                     else r.decode_tokens for r in self.records)
        occ_rows = sum(r.occupancy for r in self.records)
        prefill = sum(r.prefill_tokens for r in self.records)
        occ = (occ_rows / (steps * self.n_slots)) if steps else 0.0
        admitted = sum(r.admitted for r in self.records)
        sizes = self.admission_batch_sizes
        # tail latency (seconds).  Every key below must survive an
        # admitted == 0 drain: `percentile` returns 0.0 on empty input and
        # the list comprehensions are empty-safe, so a scheduler that
        # never admitted anything still yields the full key set.
        lat = self.latencies
        ttfts = [l.ttft for l in lat]
        waits = [l.queue_wait for l in lat]
        gaps = [g for l in lat for g in l.gaps]
        step_ts = [r.wall_s for r in self.records]
        return {
            "admitted_requests": admitted,
            "prefill_calls": self.prefill_calls,
            "prefill_calls_per_request": (self.prefill_calls / admitted
                                          if admitted else 0.0),
            "admission_batch_max": max(sizes, default=0),
            "admission_batch_avg": (sum(sizes) / len(sizes)
                                    if sizes else 0.0),
            "steps": steps,
            "n_slots": self.n_slots,
            "avg_slot_occupancy": occ,
            "decode_tokens": decode,
            "prefill_tokens": prefill,
            "completed_requests": self.completed_requests,
            "completed_samples": self.completed_samples,
            "preemptions": self.preemptions,
            "wall_s": self.wall_s,
            "requests_per_s": (self.completed_requests / self.wall_s
                               if self.wall_s > 0 else 0.0),
            "decode_tok_per_s": (decode / self.wall_s
                                 if self.wall_s > 0 else 0.0),
            "prefix_cache_lookups": self.cache_lookups,
            "prefix_cache_hits": self.cache_hits,
            "prefix_cache_hit_rate": (self.cache_hits / self.cache_lookups
                                      if self.cache_lookups else 0.0),
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "peak_kv_bytes": self.peak_kv_bytes,
            "kv_quant": self.kv_quant,
            "beam_boundaries": self.beam_boundaries,
            "beam_expansions": self.beam_expansions,
            "beam_prunes": self.beam_prunes,
            "prm_batches": self.prm_batches,
            "prm_candidates": self.prm_candidates,
            "prm_candidates_per_batch": (self.prm_candidates
                                         / self.prm_batches
                                         if self.prm_batches else 0.0),
            "spec_rounds": self.spec_rounds,
            "draft_tokens": self.spec_draft_tokens,
            "spec_acceptance_rate": (self.spec_accepted_tokens
                                     / self.spec_draft_tokens
                                     if self.spec_draft_tokens else 0.0),
            "accepted_tokens_per_step": (self.spec_committed_tokens
                                         / self.spec_row_steps
                                         if self.spec_row_steps else 0.0),
            "latency_requests": len(lat),
            "ttft_p50": percentile(ttfts, 50),
            "ttft_p90": percentile(ttfts, 90),
            "ttft_p99": percentile(ttfts, 99),
            "itl_p50": percentile(gaps, 50),
            "itl_p99": percentile(gaps, 99),
            "queue_wait_p50": percentile(waits, 50),
            "queue_wait_p99": percentile(waits, 99),
            "preempt_delay_s": sum(l.preempt_delay for l in lat),
            "step_time_p50": percentile(step_ts, 50),
            "step_time_p99": percentile(step_ts, 99),
            **(self.profiler.summary_metrics()
               if self.profiler is not None
               else dict(NULL_PROFILE_METRICS)),
        }


class ContinuousScheduler:
    """Slot-based continuous batching on top of :class:`DecodeEngine`.

    The scheduler owns one persistent ``GenState`` of ``n_slots`` rows that
    decodes **every step**; requests flow through slots independently:

    1. **Admit** — while free slots remain, the queue head is prefilled
       (one prefill per request, batch 1) and its cache/logits/length rows
       are scattered into the live state with ``DecodeEngine.merge_rows``.
       A TTS request (``n_samples > 1``) does *one* prefill and ``fork``\\ s
       the prefilled row into ``n_samples`` slots, so Best-of-N rides along
       with exactly one prompt pass.
    2. **Decode** — one batched ``DecodeEngine.step`` over all rows.  Free
       rows are ``done`` and cost an idle lane, never a correctness hazard.
    3. **Release** — a row that samples a stop id, or reaches its request's
       ``max_new_tokens``, releases its slot *immediately*; the next step's
       admission refills it while other rows keep decoding.  Nothing ever
       waits for a whole batch to drain.

    Late-arriving work therefore starts decoding as soon as any earlier
    request finishes (true continuous admission); per-step occupancy,
    prefill/decode token counts and requests/s are recorded in
    ``self.metrics``.  ``step_once`` exposes the admit→decode→release cycle
    so callers can interleave ``submit`` with a running drain.

    With a paged engine the scheduler also budgets KV *blocks*: admission
    only proceeds while the pool can cover the head request's prompt
    blocks, and when a decode step cannot get the blocks it needs
    (:class:`OutOfBlocks`), the **youngest** live request is preempted —
    its slots released, its blocks freed, the request requeued at the
    queue head to rerun from scratch — and the step retried.  Preemptions
    are counted in ``self.metrics.preemptions``; under greedy sampling a
    preempted request's final tokens are unchanged (it simply re-prefills
    later).

    With a :class:`~repro.serving.prefix_cache.PrefixCache` attached
    (paged engines only), admission becomes **cache-aware**: each request
    does a longest-prefix-match against the radix tree, leases the
    matched blocks, and prefills only the uncached suffix (the engine's
    partial-prefill path); block budgeting counts only the *new* blocks a
    request needs.  Right after its prefill the request's full prompt
    blocks are inserted into the tree — so the very next admission (even
    in the same step) can hit, and a preempted request re-prefills almost
    for free — and completed rows re-touch their prefix on release.
    Because the cache registers itself as the pool's pressure hook, block
    shortages first evict LRU unreferenced cached leaves and only then
    fall back to preemption.  Hit rate and prefill-tokens-saved land in
    ``self.metrics``.

    Cache-aware admission is **batched**: a run of consecutive plain
    requests at the queue head is matched/leased together, bucketed by
    cached-block-column width (``ceil(cached_len / block_size)``, the
    PR-4 gather bucketing — padded suffix shapes are uniform at
    ``prompt_len`` already), and each bucket runs through ONE batched
    partial prefill + merge, recovering the one-prefill-per-step shape
    discipline the uncached path has.  A candidate whose prompt shares a
    longer full-block prefix with an *earlier request in the same run*
    than the tree currently holds is deferred to the next collection
    round (same step, after that request's insert), so a cold shared
    header still costs exactly one full prefill and every follower
    admits as a hit — identical hits, leases and prefill-token counts to
    one-at-a-time admission, and bit-identical greedy outputs.
    ``max_admission_batch=1`` restores the sequential behavior (the
    parity baseline); ``SchedulerMetrics.admission_batch_sizes`` records
    the per-call request counts, driving the benchmark's
    ``prefill_calls_per_request < 1`` assertion.

    **Tree search** is a first-class request class: a request carrying
    ``search=``:class:`BeamSpec` admits through the same (cache-aware)
    path — one prefill, ``fork`` into ``width * expand`` lanes — and its
    lanes decode inside the shared batch alongside chat/BoN traffic,
    stopping per-row at the spec's step delimiter via ``row_stops``.
    When every lane has stopped, the tree hits a scoring boundary: the
    spec's ``score`` callback ranks all candidates in one batched call
    (PRM forwards batch with the tree's fan instead of the pre-scheduler
    per-candidate B=1 loop) and one ``engine.reorder`` commits the
    prune+expansion (block frees + refcount bumps on the paged pool).
    Finished trees emit ``width`` samples plus a ``beam_results`` entry
    and free every lane's blocks; ``OutOfBlocks`` preemption treats a
    tree like any group (all lanes released, the search restarts on
    re-admission).  Boundary/expansion/prune and PRM batching counters
    land in ``SchedulerMetrics``.

    **Speculative decoding** (``spec=``:class:`SpecConfig`, paged engines
    under greedy sampling): each decode step becomes a draft-then-verify
    round — a drafter (the target itself on a refcount-bumped snapshot,
    or a small dense registry model) proposes up to ``spec.k`` tokens per
    eligible row, one batched ``engine.spec_verify`` forward checks all
    of them, and the longest greedy-agreeing prefix commits, so outputs
    stay bit-identical to plain greedy decoding.  Draft lanes are pure
    fork/CoW bookkeeping and a rejected suffix is a block *free* (never a
    KV copy, reclaimed by ``engine.trim_rows``).  Beam lanes and
    ``Request(no_spec=True)`` rows ride the verify at one token per
    round; canary steps and non-greedy samplers fall back to the plain
    step.  ``OutOfBlocks`` anywhere in the round aborts it cleanly (the
    snapshot's references are dropped first) and retries after
    preemption.  Round/acceptance counters land in ``SchedulerMetrics``
    (``spec_acceptance_rate``, ``accepted_tokens_per_step``), a
    ``spec_verify`` span and ``spec_accepted_tokens`` gauge in the
    tracer, and the verify forward is attributed as its own profiler
    phase.
    """

    def __init__(self, engine: DecodeEngine, n_slots: int = 8,
                 prompt_len: int = 32, stop_ids: tuple = (),
                 prefix_cache: Optional[PrefixCache] = None,
                 max_admission_batch: Optional[int] = None,
                 tracer: Optional[Tracer] = None,
                 profiler=None,
                 spec: Optional[SpecConfig] = None):
        self.engine = engine
        # request-lifecycle telemetry (None = default: zero overhead, no
        # events, bit-identical scheduling).  The scheduler owns its
        # engine's tracer slot — constructing a scheduler (re)binds it, so
        # engine-level prefill/plan spans land in the same trace.  The
        # tracer's injectable clock also drives the per-step wall_s
        # measurement, keeping latency tests deterministic.
        self._tracer: Optional[Tracer] = None
        self.tracer = tracer
        # roofline/canary profiler (profiling.KernelProfiler), same
        # ownership discipline as the tracer: constructing a scheduler
        # (re)binds the engine's profiler slot and installs the kernel
        # dispatch hook.  None = zero overhead, bit-identical outputs.
        # The step-wall clock prefers the tracer's, then the profiler's
        # (both injectable), so profiled runs are clock-deterministic.
        self._clock = (tracer.now if tracer is not None
                       else profiler.now if profiler is not None
                       else time.perf_counter)
        self.profiler = profiler
        engine.profiler = profiler
        if profiler is not None:
            profiler.install()
        self._preempted: set = set()   # req_ids awaiting re-admission
        self._ft_emitted: set = set()  # req_ids whose first_token fired
        self.paged = engine.paged
        self.n_slots = n_slots
        self.prompt_len = prompt_len
        self.stop_ids = tuple(stop_ids) or (engine.eos_id,)
        if max_admission_batch is not None and max_admission_batch < 1:
            raise ValueError("max_admission_batch must be >= 1 or None")
        # cap on requests sharing one admission prefill call (None = the
        # free-slot count); 1 recovers strict one-at-a-time admission
        self.max_admission_batch = max_admission_batch
        if prefix_cache is not None:
            if not engine.paged:
                raise ValueError("prefix_cache requires a paged engine "
                                 "(DecodeEngine(paged=True))")
            if prefix_cache.pool is not engine.pool:
                raise ValueError("prefix_cache is bound to a different "
                                 "KVPool than the engine's")
        self.cache = prefix_cache
        self.spec = spec
        # draft-model mode: one persistent dense engine whose KV shadows
        # the target's committed context (prompts prefilled at admission,
        # cache_len resynced each round, proposals rolled back to the
        # verify's acceptance point).  Untrained smoke params by default —
        # callers wanting a *useful* drafter swap self._draft.params.
        self._draft: Optional[DecodeEngine] = None
        self._draft_state: Optional[GenState] = None
        if spec is not None:
            if not engine.paged:
                raise ValueError(
                    "speculative decoding requires a paged engine "
                    "(DecodeEngine(paged=True)): draft lanes and rejected "
                    "suffixes are refcount operations on the block pool")
            if spec.draft_model:
                from repro.configs.registry import get_config

                dcfg = get_config(spec.draft_model, smoke=True)
                if dcfg.vocab_size != engine.cfg.vocab_size:
                    dcfg = dcfg.with_(vocab_size=engine.cfg.vocab_size)
                dparams = api.get_model(dcfg).init_params(
                    jax.random.key(0), dcfg)
                self._draft = DecodeEngine(
                    dparams, dcfg, max_len=engine.max_len,
                    eos_id=engine.eos_id, pad_id=engine.pad_id)
                self._draft_state = self._draft.empty_state(n_slots)
        self.queue: deque[Request] = deque()
        self.slots: list[Optional[_Slot]] = [None] * n_slots
        self.state: Optional[GenState] = None   # built on first admission
        self.step_count = 0
        self.n_prefills = 0
        self.completed: dict[int, list[CompletedSample]] = {}
        self._n_samples: dict[int, int] = {}
        self._beams: dict[int, _BeamRun] = {}   # req_id -> in-flight tree
        self.beam_results: dict[int, dict] = {}  # req_id -> final selection
        self.metrics = SchedulerMetrics(n_slots)
        self.metrics.profiler = profiler
        if self.paged:
            # bytes, not blocks-equivalent: quantized pools have smaller
            # blocks, and this is the number a byte-budgeted operator sizes
            self._block_bytes = engine.pool.block_bytes()
            self.metrics.kv_quant = engine.pool.mode

    @property
    def tracer(self) -> Optional[Tracer]:
        return self._tracer

    @tracer.setter
    def tracer(self, tracer: Optional[Tracer]) -> None:
        """Bind ``tracer`` to this scheduler and its engine.  A bound
        tracer records garbage collections; the tracer it replaces, here
        or in the engine, stops."""
        for old in (self._tracer, self.engine.tracer):
            if old is not None and old is not tracer:
                old.unwatch_gc()
        if tracer is not None:
            tracer.watch_gc()
        self._tracer = self.engine.tracer = tracer

    # -- submission ----------------------------------------------------------
    def submit(self, req: Request):
        if req.req_id in self._n_samples:
            raise ValueError(
                f"request id {req.req_id} already submitted to this "
                f"scheduler (results are keyed by req_id)")
        if req.search is not None:
            spec = req.search
            if req.n_samples != 1:
                raise ValueError(
                    f"request {req.req_id}: search and n_samples > 1 are "
                    f"mutually exclusive (the tree owns its fan-out)")
            if min(spec.width, spec.expand, spec.step_tokens,
                   spec.max_steps) < 1:
                raise ValueError(
                    f"request {req.req_id}: BeamSpec width/expand/"
                    f"step_tokens/max_steps must all be >= 1")
            if spec.score is None:
                raise ValueError(
                    f"request {req.req_id}: BeamSpec.score is required "
                    f"(batched candidate scoring callback)")
            if spec.step_stop_id < 0:
                raise ValueError(
                    f"request {req.req_id}: BeamSpec.step_stop_id must be "
                    f"a valid token id (the reasoning-step delimiter)")
            if spec.fan > self.n_slots:
                raise ValueError(
                    f"request {req.req_id}: beam fan-out width*expand="
                    f"{spec.fan} exceeds n_slots={self.n_slots}")
        elif req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.req_id}: max_new_tokens must be >= 1, got "
                f"{req.max_new_tokens}")
        if req.n_samples > self.n_slots:
            raise ValueError(
                f"request {req.req_id}: n_samples={req.n_samples} exceeds "
                f"n_slots={self.n_slots}")
        if req.prompt.shape[0] > self.prompt_len:
            raise ValueError(
                f"request {req.req_id}: prompt length {req.prompt.shape[0]} "
                f"exceeds prompt_len={self.prompt_len}")
        # usable sequence length is max_len - 1 (the engine reserves the
        # last slot as the done-row KV scratch position)
        budget = int(req.prompt.shape[0]) + self._max_new(req)
        if budget > self.engine.max_len - 1:
            raise ValueError(
                f"request {req.req_id}: prompt ({req.prompt.shape[0]}) + "
                f"worst-case new tokens ({self._max_new(req)}) = {budget} "
                f"exceeds engine max_len - 1 = {self.engine.max_len - 1}")
        if self.paged:
            worst = self._worst_case_blocks(req)
            if worst > self.engine.pool.capacity:
                raise ValueError(
                    f"request {req.req_id}: worst-case KV footprint "
                    f"({worst} blocks) exceeds pool capacity "
                    f"({self.engine.pool.capacity} blocks) — the request "
                    f"could never run even alone")
        self._n_samples[req.req_id] = (req.search.width if req.search
                                       else max(1, req.n_samples))
        self.queue.append(req)
        if self.tracer is not None:
            self.tracer.event("enqueue", req.req_id, step=self.step_count)

    @staticmethod
    def _fan(req: Request) -> int:
        """Slots the request occupies: beam fan-out, TTS samples, or 1."""
        return req.search.fan if req.search is not None \
            else max(1, req.n_samples)

    @staticmethod
    def _max_new(req: Request) -> int:
        """Worst-case tokens one of the request's rows can generate."""
        if req.search is not None:
            return req.search.max_steps * req.search.step_tokens
        return req.max_new_tokens

    def _worst_case_blocks(self, req: Request) -> int:
        """Blocks the request needs when running alone at full divergence:
        shared full prompt blocks + per-sample tail-CoW and growth."""
        bs = self.engine.pool.block_size
        plen = int(req.prompt.shape[0])
        n = self._fan(req)
        shared = plen // bs  # full prompt blocks stay shared
        per_sample = blocks_for(plen + self._max_new(req), bs) - shared
        return shared + n * per_sample

    def _pad(self, prompt):
        S = self.prompt_len
        out = jnp.full((S,), self.engine.pad_id, jnp.int32)
        return out.at[: prompt.shape[0]].set(prompt), prompt.shape[0]

    # -- admission -----------------------------------------------------------
    def _merge(self, st: GenState, rows: list):
        if self.state is None:
            self.state = self.engine.empty_state(self.n_slots)
        self.state = self.engine.merge_rows(self.state, st,
                                            jnp.array(rows, jnp.int32),
                                            donate=True)

    def _count_prefill(self, batch_size: int):
        """Account one admission prefill call (``n_prefills`` is the
        lifetime scalar, metrics keep the per-call batch sizes)."""
        self.n_prefills += 1
        self.metrics.record_prefill(batch_size)

    def _batch_cap(self, free: list) -> int:
        """Requests one admission prefill may carry this round."""
        if self.max_admission_batch is None:
            return len(free)
        return min(len(free), self.max_admission_batch)

    def _trace_admit(self, req: Request, rows: list, cached_tokens: int = 0):
        """Emit the request's admit/readmit event (readmit when it was
        previously preempted) carrying its slot rows and, on the
        cache-aware path, the lease width it admitted with."""
        tr = self.tracer
        if tr is None:
            return
        kind = "readmit" if req.req_id in self._preempted else "admit"
        self._preempted.discard(req.req_id)
        tr.event(kind, req.req_id, step=self.step_count,
                 rows=[int(r) for r in rows],
                 cache_hit=bool(cached_tokens),
                 lease_tokens=int(cached_tokens))

    def _admit_plain(self, reqs: list, free: list) -> int:
        """One batched prefill + one merge for a run of plain requests
        (prompts share the fixed prompt_len padding)."""
        padded = [self._pad(r.prompt) for r in reqs]
        st = self.engine.prefill(
            jnp.stack([t for t, _ in padded]),
            jnp.array([ln for _, ln in padded], jnp.int32))
        self._count_prefill(len(reqs))
        rows = [free.pop(0) for _ in reqs]
        self._merge(st, rows)
        for req, r in zip(reqs, rows):
            self.slots[r] = _Slot(req=req, sample_idx=0,
                                  admitted_step=self.step_count)
            self._trace_admit(req, [r])
        return sum(ln for _, ln in padded)

    def _admit_group(self, req: Request, free: list) -> int:
        """TTS group or beam tree: one batch-1 prefill forked into
        ``_fan(req)`` slots (samples, or beam lanes sharing the prompt's
        blocks until their first divergent write)."""
        n = self._fan(req)
        toks, length = self._pad(req.prompt)
        st = self.engine.prefill(toks[None], jnp.array([length], jnp.int32))
        self._count_prefill(1)
        if n > 1:
            st = self.engine.fork(st, n)
        rows = [free.pop(0) for _ in range(n)]
        self._merge(st, rows)
        for j, r in enumerate(rows):
            self.slots[r] = _Slot(req=req, sample_idx=j,
                                  admitted_step=self.step_count)
        self._trace_admit(req, rows)
        if req.search is not None:
            self._start_beam(req, rows)
        return int(length)

    def _start_beam(self, req: Request, rows: list) -> None:
        n = len(rows)
        self._beams[req.req_id] = _BeamRun(
            req=req, spec=req.search, rows=list(rows),
            tokens=[[] for _ in range(n)], step_gen=[0] * n,
            stopped=[False] * n)

    def _prompt_blocks(self, req: Request) -> int:
        return blocks_for(int(req.prompt.shape[0]),
                          self.engine.pool.block_size)

    def _insert_prompt(self, toks: list, table_row) -> None:
        """Record a prompt's full blocks in the prefix cache (the single
        insert contract shared by admission and release)."""
        n_ins = len(toks) // self.engine.pool.block_size
        if n_ins:
            self.cache.insert(toks, np.asarray(table_row)[:n_ins])

    def _host_prompt(self, req: Request) -> list:
        return [int(t) for t in np.asarray(jax.device_get(req.prompt)).ravel()]

    def _admit_cached_group(self, req: Request, free: list) -> int:
        """Cache-aware admission of one TTS group or beam tree:
        longest-prefix-match, lease, one partial prefill of the uncached
        suffix, insert the full prompt's blocks back into the tree, fork
        into ``_fan(req)`` slots.  Returns the suffix tokens prefilled,
        or -1 when the pool
        cannot cover the group's *new* blocks even after cache eviction —
        the head then waits (FIFO), holding no lease."""
        toks = self._host_prompt(req)
        plen = len(toks)
        bs = self.engine.pool.block_size
        # cap the match at plen - 1: at least one suffix token must be
        # recomputed to produce the row's next-token logits
        blocks, clen = self.cache.match(toks[:plen - 1])
        need = blocks_for(plen, bs) - clen // bs  # tail CoW + fresh blocks
        if not self.engine.pool.reserve(need):
            if blocks:
                self.engine.pool.release(blocks)  # abandon the lease
            return -1
        # scheduler-level hit accounting covers *admitted* requests only
        # (an abandoned attempt re-matches next step; the cache's own
        # stats() still count every raw lookup)
        self.metrics.cache_lookups += 1
        suffix = toks[clen:]
        padded, _ = self._pad(jnp.asarray(suffix, jnp.int32))
        if clen:
            ctab = np.zeros((1, self.engine.table_width), np.int32)
            ctab[0, :len(blocks)] = blocks
            st = self.engine.prefill(padded[None],
                                     jnp.array([len(suffix)], jnp.int32),
                                     cached_table=ctab,
                                     cached_lens=np.array([clen], np.int64))
        else:
            # miss: the plain paged prefill skips the (masked) full-width
            # prefix gather the partial path would pay for nothing
            st = self.engine.prefill(padded[None],
                                     jnp.array([len(suffix)], jnp.int32))
        self._count_prefill(1)
        if clen:
            self.metrics.cache_hits += 1
            self.metrics.prefill_tokens_saved += clen
        self._insert_prompt(toks, np.asarray(jax.device_get(
            st.cache["table"]))[0])
        n = self._fan(req)
        if n > 1:
            st = self.engine.fork(st, n)
        rows = [free.pop(0) for _ in range(n)]
        self._merge(st, rows)
        for j, r in enumerate(rows):
            self.slots[r] = _Slot(req=req, sample_idx=j,
                                  admitted_step=self.step_count)
        self._trace_admit(req, rows, cached_tokens=clen)
        if req.search is not None:
            self._start_beam(req, rows)
        return len(suffix)

    def _collect_cached_run(self, free: list) -> list:
        """Pop a run of consecutive plain requests off the queue head for
        one batched cache-aware admission round, taking each request's
        lease as it is collected.  Entries are ``{"req", "toks",
        "blocks", "clen"}``.

        Stops at: a TTS group (admitted separately), the batch cap, a
        request the pool cannot cover even after cache eviction (FIFO —
        it stays at the head holding no lease), or a *deferral*: a
        candidate that would match a longer prefix after an earlier
        same-run request's insert than the tree holds now (probed
        lease-free; see ``PrefixCache.potential_match`` — deferral
        preserves one-at-a-time admission's hits, leases and token
        counts exactly, duplicate prompts included: they defer once,
        then batch as partial-tail hits).  Deferred
        candidates admit next round — same step, after this run's
        inserts — with exactly the sequential path's match, so batching
        never shortens a lease or turns a hit into a miss.  Block
        reservations are cumulative across the run: every collected
        lease's new-block need is counted before the next candidate
        reserves."""
        bs = self.engine.pool.block_size
        cap = self._batch_cap(free)
        entries: list[dict] = []
        pending = 0  # new blocks already promised to earlier entries
        while (self.queue and self.queue[0].n_samples <= 1
               and self.queue[0].search is None
               and len(entries) < cap):
            req = self.queue[0]
            toks = self._host_prompt(req)
            plen = len(toks)
            if entries:
                probe = self.cache.probe(toks[:plen - 1])
                if any(self.cache.potential_match(toks[:plen - 1],
                                                  e["toks"]) > probe
                       for e in entries):
                    break  # defer: a same-run insert will serve it better
            blocks, clen = self.cache.match(toks[:plen - 1])
            need = blocks_for(plen, bs) - clen // bs
            if not self.engine.pool.reserve(pending + need):
                if blocks:
                    self.engine.pool.release(blocks)  # abandon the lease
                break  # FIFO: the head waits for blocks
            pending += need
            self.queue.popleft()
            entries.append({"req": req, "toks": toks, "blocks": blocks,
                            "clen": clen})
        return entries

    def _admit_cached_rows(self, entries: list, free: list) -> int:
        """Admit one collected run: bucket the entries by cached-block
        column width (``ceil(clen / block_size)`` — the partial prefill's
        static gather width, so one bucket is one compile shape) and run
        ONE batched prefill per bucket: misses (width 0) through the
        plain paged prefill, hits through the batched partial prefill
        with ragged per-row cached lengths.  All admitted prompts then
        land in the tree via one ``insert_batch``.  Returns the suffix
        tokens prefilled."""
        bs = self.engine.pool.block_size
        buckets: dict[int, list[dict]] = {}
        for e in entries:
            buckets.setdefault(-(-e["clen"] // bs), []).append(e)
        suffix_tokens = 0
        for wc in sorted(buckets):
            group = buckets[wc]
            B = len(group)
            suffixes = [e["toks"][e["clen"]:] for e in group]
            toks = jnp.stack([self._pad(jnp.asarray(s, jnp.int32))[0]
                              for s in suffixes])
            lens = jnp.array([len(s) for s in suffixes], jnp.int32)
            if wc:
                ctab = np.zeros((B, self.engine.table_width), np.int32)
                for i, e in enumerate(group):
                    ctab[i, :len(e["blocks"])] = e["blocks"]
                st = self.engine.prefill(
                    toks, lens, cached_table=ctab,
                    cached_lens=np.array([e["clen"] for e in group],
                                         np.int64))
            else:
                st = self.engine.prefill(toks, lens)
            self._count_prefill(B)
            table = np.asarray(jax.device_get(st.cache["table"]))
            self.cache.insert_batch(
                (e["toks"], table[i, :len(e["toks"]) // bs])
                for i, e in enumerate(group))
            rows = [free.pop(0) for _ in range(B)]
            self._merge(st, rows)
            for e, r in zip(group, rows):
                self.slots[r] = _Slot(req=e["req"], sample_idx=0,
                                      admitted_step=self.step_count)
                self._trace_admit(e["req"], [r], cached_tokens=e["clen"])
                self.metrics.cache_lookups += 1
                if e["clen"]:
                    self.metrics.cache_hits += 1
                    self.metrics.prefill_tokens_saved += e["clen"]
            suffix_tokens += sum(len(s) for s in suffixes)
        return suffix_tokens

    def _admit(self) -> tuple:
        """Fill free slots from the queue (FIFO). Consecutive plain
        requests admitted in the same step share one batched prefill; a
        TTS group prefills once and forks. Returns (requests admitted,
        prompt tokens prefilled).

        Paged: admission additionally stops (FIFO, no skipping) when the
        pool cannot cover the head request's prompt blocks — decode-time
        growth is handled by preemption, not reservation.  With a prefix
        cache attached, runs of plain requests admit through the batched
        cache-aware partial-prefill path (one prefill per cached-width
        bucket; see :meth:`_collect_cached_run`), TTS groups one at a
        time."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        admitted = prefill_tokens = 0
        if self.cache is not None:
            while self.queue and free:
                if self._fan(self.queue[0]) > len(free):
                    break  # FIFO: the group waits for enough free slots
                if (self.queue[0].n_samples > 1
                        or self.queue[0].search is not None):
                    got = self._admit_cached_group(self.queue[0], free)
                    if got < 0:
                        break  # FIFO: the head waits for blocks
                    self.queue.popleft()
                    admitted += 1
                    prefill_tokens += got
                    continue
                entries = self._collect_cached_run(free)
                if not entries:
                    break  # FIFO: the head waits for blocks
                prefill_tokens += self._admit_cached_rows(entries, free)
                admitted += len(entries)
            return admitted, prefill_tokens
        blk_budget = self.engine.pool.free_blocks if self.paged else None
        while self.queue and free:
            if self._fan(self.queue[0]) > len(free):
                break  # FIFO: the group waits for enough free slots
            if self.paged and self._prompt_blocks(self.queue[0]) > blk_budget:
                break  # FIFO: the head waits for blocks to free up
            if self.queue[0].n_samples > 1 or self.queue[0].search is not None:
                req = self.queue.popleft()
                if self.paged:
                    blk_budget -= self._prompt_blocks(req)
                prefill_tokens += self._admit_group(req, free)
                admitted += 1
                continue
            plain = []
            while (self.queue and self.queue[0].n_samples <= 1
                   and self.queue[0].search is None
                   and len(plain) < self._batch_cap(free)):
                if self.paged:
                    need = self._prompt_blocks(self.queue[0])
                    if need > blk_budget:
                        break
                    blk_budget -= need
                plain.append(self.queue.popleft())
            if not plain:
                break
            prefill_tokens += self._admit_plain(plain, free)
            admitted += len(plain)
        return admitted, prefill_tokens

    # -- release -------------------------------------------------------------
    def _release(self, row: int, reason: str, logprob_sum: float,
                 n_gen: int):
        slot = self.slots[row]
        sample = CompletedSample(
            req_id=slot.req.req_id, sample_idx=slot.sample_idx,
            tokens=slot.tokens, logprob_sum=logprob_sum, n_gen=n_gen,
            finish_reason=reason, admitted_step=slot.admitted_step,
            first_decode_step=slot.first_decode_step,
            finished_step=self.step_count)
        done = self.completed.setdefault(slot.req.req_id, [])
        done.append(sample)
        self.metrics.completed_samples += 1
        tr = self.tracer
        if tr is not None:
            tr.event("release", slot.req.req_id, step=self.step_count,
                     rows=[int(row)], reason=reason)
        if len(done) == max(1, slot.req.n_samples):
            self.metrics.completed_requests += 1
            if tr is not None:
                # the request is complete: derive its latency record now,
                # after its final release event (e2e closes on it)
                self.metrics.latencies.append(
                    tr.request_latency(slot.req.req_id))
        self.slots[row] = None

    # -- preemption (paged out-of-blocks) ------------------------------------
    def _preempt_youngest(self):
        """Free the youngest live request's slots and blocks and requeue it
        at the queue head (it reruns from scratch).  Raises when only one
        live request remains — preempting it could never unblock decoding,
        the pool is simply too small for the workload."""
        by_req: dict[int, list[int]] = {}
        for i, s in enumerate(self.slots):
            if s is not None:
                by_req.setdefault(s.req.req_id, []).append(i)
        if len(by_req) <= 1:
            raise RuntimeError(
                "KV pool exhausted with a single live request — pool too "
                "small to make progress (raise n_blocks)")
        victim = max(by_req, key=lambda rid: (
            self.slots[by_req[rid][0]].admitted_step, rid))
        rows = by_req[victim]
        req = self.slots[rows[0]].req
        self.state = self.engine.release_rows(self.state, rows)
        for r in rows:
            self.slots[r] = None
        # discard any already-finished samples of the victim; the rerun
        # regenerates every sample (deterministic under greedy sampling).
        # A beam victim drops its whole in-flight tree the same way: its
        # lanes' blocks just freed above, and re-admission restarts the
        # search from the prompt.
        self._beams.pop(victim, None)
        dropped = self.completed.pop(victim, [])
        self.metrics.completed_samples -= len(dropped)
        self.queue.appendleft(req)
        self.metrics.preemptions += 1
        if self.tracer is not None:
            self.tracer.event("preempt", victim, step=self.step_count,
                              rows=[int(r) for r in rows])
            self._preempted.add(victim)
            # the rerun decodes its first token afresh: re-arm the event
            self._ft_emitted.discard(victim)

    # -- beam-search (tree) workload -----------------------------------------
    def _row_stops(self):
        """Per-row extra stop ids for the decode step: each in-flight
        tree's lanes stop at its step delimiter; every other row gets -1
        (no extra stop).  None when no tree is in flight (keeps the
        row_stops-free jit trace for pure chat/BoN traffic)."""
        if not self._beams:
            return None
        stops = np.full((self.n_slots,), -1, np.int32)
        for run in self._beams.values():
            stops[run.rows] = run.spec.step_stop_id
        return jnp.asarray(stops)

    def _beam_track(self, toks_h, done_h) -> tuple:
        """Advance every in-flight tree's host bookkeeping after a decode
        step.  Returns (rows to freeze, runs at their scoring boundary):
        a lane that exhausts its step token budget without sampling the
        delimiter is *frozen* (done on device, blocks kept) so it stops
        advancing while sibling lanes finish their step."""
        tr = self.tracer
        to_freeze: list = []
        boundaries: list = []
        for run in self._beams.values():
            advanced = False
            for j, r in enumerate(run.rows):
                if run.stopped[j]:
                    continue
                advanced = True
                run.tokens[j].append(int(toks_h[r]))
                run.step_gen[j] += 1
                if bool(done_h[r]):      # sampled '.'/eos this step
                    run.stopped[j] = True
                elif run.step_gen[j] >= run.spec.step_tokens:
                    run.stopped[j] = True
                    to_freeze.append(r)
            if advanced and tr is not None:
                tr.event("token", run.req.req_id, step=self.step_count)
            if all(run.stopped):
                boundaries.append(run)
        return to_freeze, boundaries

    def _beam_boundary(self, run: _BeamRun):
        """Scoring boundary: one batched score call over all fan
        candidates, then either final selection or a prune+expand commit.

        The commit is ONE ``engine.reorder`` whose index is identity
        outside the tree's rows and maps lane j to survivor ``keep[j //
        expand]`` inside them — on the paged pool the reorder's refcount
        fixup *is* the tree update: losing lanes' blocks drop to refcount
        zero and free (prune), each survivor's blocks gain ``expand - 1``
        references (expansion, zero KV bytes copied) and diverge later
        via copy-on-write."""
        spec, rows = run.spec, run.rows
        tr = self.tracer
        lp, ng = (np.asarray(a) for a in jax.device_get(
            (self.state.logprob_sum, self.state.n_gen)))
        with phase(tr, "prm", self.step_count, candidates=len(rows)):
            scores = np.asarray(
                spec.score([list(t) for t in run.tokens], lp[rows], ng[rows]),
                np.float64).ravel()
        self.metrics.prm_batches += 1
        self.metrics.prm_candidates += len(rows)
        # stable sort: ties keep the lowest lane index, matching the
        # direct path's jnp.argsort over -scores
        keep = np.argsort(-scores, kind="stable")[:spec.width]
        run.beam_step += 1
        self.metrics.beam_boundaries += 1
        if tr is not None:
            tr.event("beam_boundary", run.req.req_id, step=self.step_count,
                     boundary=run.beam_step)
        survivors = [list(run.tokens[int(k)]) for k in keep]
        if run.beam_step >= spec.max_steps or (
                spec.finished is not None and spec.finished(survivors)):
            self._finish_beam(run, keep, survivors, lp, ng)
            return
        idx = np.arange(self.n_slots, dtype=np.int32)
        for j in range(len(rows)):
            idx[rows[j]] = rows[int(keep[j // spec.expand])]
        self.state = self.engine.reorder(self.state, jnp.asarray(idx))
        self.metrics.beam_expansions += len(rows) - spec.width
        self.metrics.beam_prunes += len(rows) - spec.width
        run.tokens = [list(survivors[j // spec.expand])
                      for j in range(len(rows))]
        run.step_gen = [0] * len(rows)
        run.stopped = [False] * len(rows)
        self.state = self.engine.resume_rows(self.state, rows)
        if tr is not None:
            tr.event("resume", run.req.req_id, step=self.step_count,
                     rows=[int(r) for r in rows])

    def _finish_beam(self, run: _BeamRun, keep, survivors, lp, ng):
        """Final selection: score the ``width`` survivors, record the
        choice in ``beam_results``, emit one ``CompletedSample`` per
        survivor and release every lane's blocks."""
        spec, rows, req = run.spec, run.rows, run.req
        tr = self.tracer
        final = spec.final_score or spec.score
        krows = [rows[int(k)] for k in keep]
        with phase(tr, "prm", self.step_count, candidates=len(survivors)):
            final_scores = np.asarray(
                final(survivors, lp[krows], ng[krows]), np.float64).ravel()
        self.metrics.prm_batches += 1
        self.metrics.prm_candidates += len(survivors)
        if self.cache is not None:
            # the tree's full prompt blocks sit below every lane's write
            # frontier (never CoW'd) — reusable by later requests
            table = np.asarray(jax.device_get(self.state.cache["table"]))
            self._insert_prompt(self._host_prompt(req), table[rows[0]])
        first = self.slots[rows[0]]
        self.state = self.engine.release_rows(self.state, rows)
        done_list = self.completed.setdefault(req.req_id, [])
        for j, k in enumerate(keep):
            r = rows[int(k)]
            done_list.append(CompletedSample(
                req_id=req.req_id, sample_idx=j, tokens=list(survivors[j]),
                logprob_sum=float(lp[r]), n_gen=int(ng[r]),
                finish_reason="beam", admitted_step=first.admitted_step,
                first_decode_step=first.first_decode_step,
                finished_step=self.step_count))
        self.beam_results[req.req_id] = {
            "scores": [float(s) for s in final_scores],
            "chosen": int(np.argmax(final_scores)),
            "beam_steps": run.beam_step,
        }
        self.metrics.completed_samples += len(survivors)
        self.metrics.completed_requests += 1
        if tr is not None:
            tr.event("release", req.req_id, step=self.step_count,
                     rows=[int(r) for r in rows], reason="beam")
            self.metrics.latencies.append(tr.request_latency(req.req_id))
        for r in rows:
            self.slots[r] = None
        del self._beams[req.req_id]

    # -- the admit -> decode -> release cycle --------------------------------
    def _record_canary(self, live: list) -> None:
        """Drift comparison for a canary step: the production step's new
        logits vs the engine's exact-path logits, over the live rows.
        Frozen/done rows carry identically-frozen pending logits in both
        paths, so every live row is comparable.  Under the default "xla"
        paged-attention impl the two jits compile the same HLO and the
        comparison must be exact (flip rate 0 — the CI row asserts it);
        under kernel/kernel_lut impls this measures the fused kernels'
        LUT-softmax/dequant drift online."""
        prof = self.profiler
        exact = self.engine.last_canary_logits
        self.engine.last_canary_logits = None
        ex, pr = jax.device_get((exact, self.state.pending_logits))
        rows = np.asarray(live, np.int64)
        ex = np.asarray(ex)[rows]
        pr = np.asarray(pr)[rows]
        max_err = float(np.max(np.abs(ex - pr))) if rows.size else 0.0
        flips = (int(np.sum(np.argmax(ex, -1) != np.argmax(pr, -1)))
                 if rows.size else 0)
        prof.record_canary(
            max_logit_err=max_err, flips=flips, rows=int(rows.size),
            kv_err_per_layer=self.engine.kv_roundtrip_error())
        if self.tracer is not None:
            self.tracer.gauge("canary_max_logit_err", max_err)
            self.tracer.gauge("canary_flips", flips)

    # -- speculative rounds --------------------------------------------------
    def _spec_eligible(self, slot: _Slot) -> bool:
        """Rows speculation may commit > 1 token for: plain chat/BoN rows
        that did not opt out.  Beam lanes stay one-token-per-round (their
        freeze/boundary bookkeeping is stepwise)."""
        return slot.req.search is None and not slot.req.no_spec

    def _sync_draft_admissions(self, live: list) -> None:
        """Prefill newly admitted rows' prompts into the persistent dense
        draft engine (draft-model mode) so its KV shadows the target's
        committed context from the prompt on.  Rows admitted for beam or
        opted-out requests are skipped — the drafter never proposes for
        them."""
        rows = [i for i in live
                if self.slots[i].first_decode_step < 0
                and self._spec_eligible(self.slots[i])]
        if not rows:
            return
        padded = [self._pad(self.slots[i].req.prompt) for i in rows]
        st = self._draft.prefill(
            jnp.stack([t for t, _ in padded]),
            jnp.array([ln for _, ln in padded], jnp.int32))
        self._draft_state = self._draft.merge_rows(
            self._draft_state, st, jnp.array(rows, jnp.int32), donate=True)

    def _draft_proposals_self(self, xs, n_prop, eligible, W, rng, sc):
        """Self-drafting: run W-1 plain greedy steps on a refcount-bumped
        snapshot of the target state (the draft lane is a fork; its
        divergent writes CoW, its release frees — target KV untouched).
        Fills ``xs[:, 1:]`` in place; returns False when the snapshot ran
        out of blocks mid-draft (round falls back to a plain step)."""
        eng = self.engine
        snap = eng.spec_snapshot(self.state, eligible)
        try:
            dts = []
            for m in range(1, W):
                frz = [i for i in eligible if int(n_prop[i]) == m]
                if frz:
                    snap = eng.freeze_rows(snap, frz)
                snap, dt = eng.step(snap, rng, sc, stop_ids=self.stop_ids)
                dts.append(dt)
            # each row's last proposal comes from its (possibly frozen)
            # pending logits — the distribution after its final sampled
            # draft token
            final = jnp.argmax(snap.pending_logits, axis=-1)
            dts_h, final_h = (np.asarray(a) for a in jax.device_get(
                (jnp.stack(dts), final)))
        except OutOfBlocks:
            # mid-draft exhaustion: drop the snapshot's references and
            # let the caller fall back to a plain step (which has its own
            # preemption path) — nothing leaks, target state untouched
            eng.release_rows(snap, eligible)
            return False
        eng.release_rows(snap, eligible)
        for i in eligible:
            npi = int(n_prop[i])
            for c in range(1, npi - 1):
                xs[i, c] = int(dts_h[c][i])  # step c+1 sampled column c
            xs[i, npi - 1] = int(final_h[i])
        return True

    def _draft_proposals_model(self, xs, n_prop, eligible, W, t0, clen_h,
                               rng, sc):
        """Draft-model proposals: resync the dense drafter's lengths to
        the target's committed context, force-feed the round's first
        token, then run W-1 cheap greedy steps — every proposal column is
        *written* to draft KV so a fully-accepted round leaves no hole.
        Returns the advanced draft state (rolled back to the acceptance
        point by the caller only after the verify succeeds)."""
        de = self._draft
        dn = np.ones(self.n_slots, bool)
        dn[eligible] = False
        ds = dataclasses.replace(
            self._draft_state,
            cache_len=jnp.asarray(clen_h.astype(np.int32)),
            done=jnp.asarray(dn))
        ds = de.forced_step(ds, t0)
        dts = []
        for m in range(1, W):
            frz = [i for i in eligible if int(n_prop[i]) == m]
            if frz:
                ds = de.freeze_rows(ds, frz)
            ds, dt = de.step(ds, rng, sc, stop_ids=self.stop_ids)
            dts.append(dt)
        dts_h = np.asarray(jax.device_get(jnp.stack(dts)))
        for i in eligible:
            for c in range(1, int(n_prop[i])):
                xs[i, c] = int(dts_h[c - 1][i])  # step c sampled column c
        return ds

    def _spec_step(self, rng, sc: SamplerConfig):
        """One draft-then-verify round over the live batch.  Returns
        ``(xs, a)`` — proposals and per-row accepted counts — or None to
        fall back to a plain step (no row can use > 1 proposal, or the
        self-draft ran out of blocks).  An :class:`OutOfBlocks` from the
        verify plan propagates to ``step_once``'s preempt-retry loop; the
        whole round reruns after preemption, and any draft snapshot was
        already released, so an aborted round leaks nothing."""
        eng = self.engine
        live = [i for i, s in enumerate(self.slots) if s is not None]
        done_h, clen_h = (np.asarray(a) for a in jax.device_get(
            (self.state.done, self.state.cache_len)))
        n_prop = np.zeros(self.n_slots, np.int32)
        for i in live:
            if done_h[i]:
                continue  # frozen beam lane: rides along, commits nothing
            slot = self.slots[i]
            if not self._spec_eligible(slot):
                n_prop[i] = 1  # plain-step-equivalent lane in the verify
            else:
                rem = slot.req.max_new_tokens - len(slot.tokens)
                n_prop[i] = max(1, min(self.spec.k, rem))
        W = int(n_prop.max(initial=0))
        if W < 2:
            return None
        eligible = [i for i in live if n_prop[i] > 1]
        # column 0: the token a plain greedy step would commit right now
        t0 = np.asarray(jax.device_get(
            jnp.argmax(self.state.pending_logits, axis=-1))).astype(
                np.int32)
        xs = np.full((self.n_slots, W), eng.pad_id, np.int32)
        for i in live:
            if n_prop[i]:
                xs[i, 0] = t0[i]
        ds = None
        if self.spec.self_draft:
            if not self._draft_proposals_self(xs, n_prop, eligible, W,
                                              rng, sc):
                return None
        else:
            ds = self._draft_proposals_model(
                xs, n_prop, eligible, W, jnp.asarray(t0), clen_h, rng, sc)
        self.state, commit_h = eng.spec_verify(
            self.state, xs, n_prop, row_stops=self._row_stops(),
            stop_ids=self.stop_ids)
        a = commit_h.sum(axis=1).astype(np.int64)
        # reclaim the rejected suffixes' blocks (rows live at verify time
        # only — frozen lanes keep their blocks like any frozen row)
        self.state = eng.trim_rows(
            self.state, [i for i in live if not done_h[i]])
        if ds is not None:
            # roll the drafter back to the acceptance point: lengths to
            # the target's new lengths, all rows idle until the next
            # round resyncs.  Only committed once the verify succeeded —
            # a verify OutOfBlocks keeps the pre-round _draft_state.
            self._draft_state = dataclasses.replace(
                ds,
                cache_len=jnp.asarray((clen_h + a).astype(np.int32)),
                done=jnp.ones((self.n_slots,), bool))
        m = self.metrics
        m.spec_rounds += 1
        for i in eligible:
            m.spec_draft_tokens += int(n_prop[i]) - 1
            m.spec_accepted_tokens += int(a[i]) - 1
            m.spec_committed_tokens += int(a[i])
            m.spec_row_steps += 1
        if self.tracer is not None:
            self.tracer.gauge("spec_accepted_tokens",
                              int(a[np.asarray(eligible, np.int64)].sum())
                              if eligible else 0)
        return xs, a

    def step_once(self, rng, sc: SamplerConfig = SamplerConfig()) -> bool:
        """One scheduler step. Returns False when idle (nothing admitted,
        nothing decoding).

        Wall time is measured *here* (not in :meth:`run`), so callers
        driving ``step_once`` directly — controller loops, tests — get
        real ``wall_s``/throughput numbers: each step's host time lands
        in ``StepRecord.wall_s`` and accumulates into
        ``metrics.wall_s``.

        With a tracer the step records the span tree of
        :mod:`repro.serving.telemetry`: ``step`` ⊃ {``admit``, ``decode``
        ⊃ {``plan``, ``dispatch``, ``sync``}, ``post``}; an idle step
        records its ``admit`` alone."""
        tr = self.tracer
        prof = self.profiler
        t_wall = self._clock()
        s = self.step_count
        with phase(tr, "step", s) as step_phase:
            if prof is not None:
                prof.begin_step()
            with phase(tr, "admit", s) as ph:
                admitted, prefill_tokens = self._admit()
                ph.set(admitted=admitted, prefill_tokens=prefill_tokens)
            live = [i for i, slot in enumerate(self.slots) if slot is not None]
            if not live:
                step_phase.cancel()
                return False
            if self._draft is not None:
                self._sync_draft_admissions(live)
            for i in live:
                if self.slots[i].first_decode_step < 0:
                    self.slots[i].first_decode_step = s
            canary = (prof is not None and self.paged and prof.want_canary())
            # speculative rounds need greedy sampling (acceptance compares
            # argmaxes) and skip canary steps, whose exact-path replica is
            # defined over the single-token decode step
            spec_round = (self.spec is not None and sc.greedy and self.paged
                          and not canary)
            spec_out = None
            # the decode span ends after the sync: the host-visible latency
            # of this decode step
            with phase(tr, "decode", s) as dec:
                while True:
                    try:
                        if spec_round:
                            spec_out = self._spec_step(rng, sc)
                        if spec_out is None:
                            self.state, toks = self.engine.step(
                                self.state, rng, sc, stop_ids=self.stop_ids,
                                row_stops=self._row_stops(), canary=canary)
                        break
                    except OutOfBlocks:
                        # atomic: the failed prepare touched neither pool
                        # nor state
                        self._preempt_youngest()
                        live = [i for i, slot in enumerate(self.slots)
                                if slot is not None]
                        dec.restart()
                if canary and self.engine.last_canary_logits is not None:
                    self._record_canary(live)
                with phase(tr, "sync", s):
                    if spec_out is not None:
                        xs_h, a_h = spec_out
                        # beam tracking sees the stepwise token
                        toks_h = xs_h[:, 0]
                        done_h, lp_h, ng_h = jax.device_get(
                            (self.state.done, self.state.logprob_sum,
                             self.state.n_gen))
                    else:
                        xs_h = a_h = None
                        toks_h, done_h, lp_h, ng_h = jax.device_get(
                            (toks, self.state.done, self.state.logprob_sum,
                             self.state.n_gen))
                dec.set(batch=len(live))
            with phase(tr, "post", s):
                self._post_step(live, toks_h, done_h, lp_h, ng_h, xs_h, a_h,
                                sc)
                wall = self._clock() - t_wall
                if prof is not None:
                    prof.end_step(wall)
                self.metrics.wall_s += wall
                self.metrics.record(StepRecord(
                    step=s, occupancy=len(live), admitted=admitted,
                    prefill_tokens=prefill_tokens, wall_s=wall,
                    decode_tokens=(int(a_h[np.asarray(live, np.int64)].sum())
                                   if a_h is not None else None)))
            step_phase.set(occupancy=len(live))
        self.step_count += 1
        return True

    def _post_step(self, live, toks_h, done_h, lp_h, ng_h, xs_h, a_h, sc):
        """A step's host bookkeeping once its tokens are on the host: the
        token events, each row's tokens, releases and prefix-cache
        re-inserts, beam boundaries and the gauges."""
        tr = self.tracer
        s = self.step_count
        if tr is not None:
            seen: set = set()
            for i in live:
                rid = self.slots[i].req.req_id
                if (self.slots[i].first_decode_step == s
                        and rid not in self._ft_emitted):
                    self._ft_emitted.add(rid)
                    tr.event("first_token", rid, step=s)
                # every live non-beam row sampled a token this step (stop
                # tokens included); beam lanes are tracked in _beam_track
                if self.slots[i].req.search is None and rid not in seen:
                    seen.add(rid)
                    tr.event("token", rid, step=s)
        released = []
        over_budget = []
        released_reqs: list[tuple] = []
        for i in live:
            slot = self.slots[i]
            if slot.req.search is not None:
                continue  # beam lanes: tracked per-tree below
            # the tokens this row committed this step: the accepted
            # prefix of its proposals on a speculative round, else the
            # one sampled token
            run = ([int(t) for t in xs_h[i, :int(a_h[i])]]
                   if a_h is not None else [int(toks_h[i])])
            if bool(done_h[i]):          # committed a stop id this step
                slot.tokens.extend(run[:-1])  # stop token excluded
                released_reqs.append((i, slot.req))
                self._release(i, "stop", float(lp_h[i]), int(ng_h[i]))
                released.append(i)
                continue
            slot.tokens.extend(run)
            if len(slot.tokens) >= slot.req.max_new_tokens:
                over_budget.append(i)
                released.append(i)
                released_reqs.append((i, slot.req))
                self._release(i, "length", float(lp_h[i]), int(ng_h[i]))
        if self.paged and released:
            if self.cache is not None:
                # re-insert completed prompt prefixes before the rows'
                # blocks go back to the pool: normally an idempotent LRU
                # touch (admission already inserted), but it restores
                # entries that pool pressure evicted mid-flight — the
                # blocks still hold valid prompt KV (full prompt blocks
                # sit below the write frontier and are never CoW'd)
                table = np.asarray(jax.device_get(self.state.cache["table"]))
                seen = set()
                for r, req in released_reqs:
                    if req.req_id in seen:  # one insert per group, not row
                        continue
                    seen.add(req.req_id)
                    toks = [int(t) for t in
                            np.asarray(jax.device_get(req.prompt)).ravel()]
                    self._insert_prompt(toks, table[r])
            # return every released row's blocks to the pool (stop rows
            # included — done alone doesn't free paged memory)
            self.state = self.engine.release_rows(self.state, released)
        elif over_budget:
            # freeze the rows so they stop growing until a new occupant
            # overwrites them at admission
            self.state = self.engine.release_rows(self.state, over_budget)
        if self._beams:
            to_freeze, boundaries = self._beam_track(toks_h, done_h)
            if to_freeze:
                self.state = self.engine.freeze_rows(self.state, to_freeze)
                if tr is not None:
                    by_req: dict = {}
                    for r in to_freeze:
                        by_req.setdefault(self.slots[r].req.req_id,
                                          []).append(int(r))
                    for rid, rs in by_req.items():
                        tr.event("freeze", rid, step=s, rows=rs)
            for run in boundaries:
                self._beam_boundary(run)
        if self.paged:
            # pool.peak_in_use also sees intra-step highs (CoW before
            # release), so this is the true byte high-water mark
            self.metrics.peak_kv_bytes = max(
                self.metrics.peak_kv_bytes,
                self.engine.pool.peak_in_use * self._block_bytes)
        if tr is not None:
            tr.gauge("occupancy", len(live))
            # the sampler's path: k candidates a row, or 0 for the whole
            # vocabulary
            tr.gauge("sampler_candidates", candidates(sc))
            if self.paged:
                tr.gauge("free_blocks", self.engine.pool.free_blocks)
                # the bytes the pool's live blocks hold, beside occupancy
                # on the Perfetto timeline
                tr.gauge("kv_bytes_in_use",
                         self.engine.pool.blocks_in_use * self._block_bytes)
                if self.cache is not None:
                    tr.gauge("cache_pinned_blocks",
                             self.cache.n_cached_blocks)

    # -- drain ---------------------------------------------------------------
    def run(self, rng, sc: SamplerConfig = SamplerConfig(),
            max_steps: int = 4096):
        """Drain the queue.  Returns ``{req_id: tokens}`` for plain requests
        and ``{req_id: [tokens] * n_samples}`` for TTS requests (sample
        order).  Rich per-sample records stay in ``self.completed``.

        Raises ``RuntimeError`` if ``max_steps`` elapses with work still
        queued or decoding (finished requests remain in ``self.completed``
        and the drain can be resumed with another ``run`` call)."""
        steps = 0
        while steps < max_steps:
            rng, key = jax.random.split(rng)
            # step_once accumulates per-step wall time into metrics.wall_s
            if not self.step_once(key, sc):
                break
            steps += 1
        live = sum(1 for s in self.slots if s is not None)
        if self.queue or live:
            raise RuntimeError(
                f"scheduler truncated at max_steps={max_steps}: "
                f"{len(self.queue)} queued + {live} decoding requests "
                f"unfinished ({len(self.completed)} request ids completed; "
                f"re-run to continue)")
        results = {}
        for req_id, samples in self.completed.items():
            ordered = sorted(samples, key=lambda s: s.sample_idx)
            if self._n_samples.get(req_id, 1) == 1:
                results[req_id] = ordered[0].tokens
            else:
                results[req_id] = [s.tokens for s in ordered]
        return results
