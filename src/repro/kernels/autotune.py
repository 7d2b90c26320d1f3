"""Block-size autotuner for the Pallas kernel wrappers.

The wrappers in ``repro.kernels.ops`` used to hard-code block-size targets
(128/256) and pick the largest dividing block under them.  This module
keeps that shape discipline but chooses among *valid candidates* with an
analytic roofline model (the same v5e constants ``benchmarks/roofline.py``
reports against), and lets ``benchmarks/kernel_ablation.py`` overwrite the
analytic choice with a *measured* one: its autotune section times the
candidate set through the real kernels and records the winner in a cached
per-shape table (``runs/autotune.json`` by default, override with
``REPRO_AUTOTUNE_CACHE``), tagged with the ``device_kind`` it was timed
on.  Lookup order per shape key:

1. in-process memo;
2. measured entry in the cache file, if it was recorded on the running
   device kind (a CPU-timed winner never steers a TPU run);
3. analytic roofline score over the candidate set.

Candidates are the block sizes Mosaic accepts: along each axis a divisor
of the dim that is a multiple of the axis' alignment (8 sublanes, 128
lanes, or what a derived scales block needs), or the whole dim.

``REPRO_AUTOTUNE=0`` opts out entirely and restores the largest
candidate per axis (still Mosaic-legal).

Scoring is deterministic: ``max(flops/peak, bytes/bw)`` plus a per-grid-
step launch overhead, with a hard penalty for blocks whose VMEM footprint
exceeds the budget.  For the small shapes the repo's tests use, the
largest valid blocks win — i.e. the analytic tuner reproduces the legacy
choices exactly and only diverges where a measured entry says otherwise.
"""
from __future__ import annotations

import json
import os
from typing import Callable, Sequence

# TPU v5e — single-sourced here; benchmarks/roofline.py imports these.
PEAK_FLOPS = 197e12   # bf16 MXU FLOP/s
HBM_BW = 819e9        # HBM bytes/s
LINK_BW = 50e9        # ICI bytes/s per link

GRID_STEP_OVERHEAD_S = 2e-6     # per-grid-step issue/DMA-setup cost
VMEM_BUDGET_BYTES = 16 * 2**20  # working-set budget per kernel instance

_MEMO: dict = {}
_FILE_CACHE: dict | None = None


def enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "1") != "0"


def cache_path() -> str:
    return os.environ.get("REPRO_AUTOTUNE_CACHE", "runs/autotune.json")


def reset() -> None:
    """Drop the in-process memo and the loaded cache file (tests)."""
    global _FILE_CACHE
    _MEMO.clear()
    _FILE_CACHE = None


# ---------------------------------------------------------------------------
# Valid block enumeration (the divisibility contracts live here)
# ---------------------------------------------------------------------------


def block_candidates(n: int, target: int, align: int = 1,
                     max_candidates: int = 4) -> list[int]:
    """Block sizes along an axis of size ``n`` that Mosaic accepts:
    divisors of ``n`` that are multiples of ``align`` and <= ``target``,
    largest first, at most ``max_candidates``; ``[n]`` when there are
    none (a block spanning the whole dim is always legal)."""
    out = [b for b in range(min(n, target), 0, -1)
           if n % b == 0 and b % align == 0]
    return out[:max_candidates] or [n]


# ---------------------------------------------------------------------------
# Cache file (measured entries recorded by benchmarks/kernel_ablation.py)
# ---------------------------------------------------------------------------


def device_kind() -> str:
    """The running device's kind, the key measured entries are valid for."""
    import jax

    return jax.devices()[0].device_kind


def _load_cache() -> dict:
    global _FILE_CACHE
    if _FILE_CACHE is None:
        path = cache_path()
        try:
            with open(path) as f:
                _FILE_CACHE = json.load(f).get("entries", {})
        except (OSError, ValueError):
            _FILE_CACHE = {}
    return _FILE_CACHE


def record(key: str, blocks: Sequence[int], us: float) -> None:
    """Record a measured block choice for ``key``, timed on the running
    device kind, in the cache file (and the in-process view, so
    subsequent picks use it immediately)."""
    entries = dict(_load_cache())
    entries[key] = {"blocks": [int(b) for b in blocks], "us": float(us),
                    "source": "measured", "device_kind": device_kind()}
    path = cache_path()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"version": 1, "entries": entries}, f, indent=2,
                  sort_keys=True)
        f.write("\n")
    global _FILE_CACHE
    _FILE_CACHE = entries
    _MEMO.pop((key, True), None)


# ---------------------------------------------------------------------------
# Choice machinery
# ---------------------------------------------------------------------------


def roofline_bound_s(flops: float, hbm_bytes: float) -> float:
    """Analytic lower bound on wall seconds: compute- or bandwidth-bound,
    whichever is worse.  The profiler divides this by measured wall time
    to get achieved-vs-peak efficiency."""
    return max(flops / PEAK_FLOPS, hbm_bytes / HBM_BW)


def _roofline_score(flops: float, hbm_bytes: float, grid_steps: int,
                    vmem_bytes: float) -> float:
    t = roofline_bound_s(flops, hbm_bytes)
    t += grid_steps * GRID_STEP_OVERHEAD_S
    if vmem_bytes > VMEM_BUDGET_BYTES:
        t *= 1e3  # does not fit: effectively reject
    return t


def choose(key: str, axes: Sequence[tuple[int, int, int]],
           score_fn: Callable[[Sequence[int]], float]) -> tuple[int, ...]:
    """Pick one block size per ``(n, target, align)`` axis.

    With autotuning off this is the largest :func:`block_candidates`
    entry per axis.  Otherwise a measured cache entry for ``key``
    recorded on this device kind wins; failing that, the lowest
    ``score_fn`` over the cartesian candidate set (ties to the largest
    blocks)."""
    memo_key = (key, enabled())
    if memo_key in _MEMO:
        return _MEMO[memo_key]
    if not enabled():
        blocks = tuple(block_candidates(*a)[0] for a in axes)
    else:
        ent = _load_cache().get(key)
        if (ent and len(ent.get("blocks", ())) == len(axes)
                and ent.get("device_kind") == device_kind()):
            blocks = tuple(int(b) for b in ent["blocks"])
        else:
            import itertools

            cands = [block_candidates(*a) for a in axes]
            blocks = min(itertools.product(*cands),
                         key=lambda bl: (score_fn(bl),
                                         tuple(-b for b in bl)))
    _MEMO[memo_key] = blocks
    return blocks


# ---------------------------------------------------------------------------
# Per-kernel shape keys, constraints and cost models
# ---------------------------------------------------------------------------
#
# Each ``*_cost`` function returns ``(flops, hbm_bytes)`` for one kernel
# invocation.  Called without block sizes it gives the *ideal single-pass*
# traffic — the roofline lower bound ``serving/profiling.KernelProfiler``
# attributes measured wall time against; with block sizes it gives the
# *streamed* traffic (operands re-read once per block of the other
# operand) that the score closures below rank candidates by.  Keeping
# both behind one function is what "single-sourced cost models" means:
# the tuner and the profiler can never disagree about what a kernel
# should cost.


def gemm_cost(M: int, K: int, N: int, *, bm: int | None = None,
              bn: int | None = None) -> tuple[float, float]:
    """LUT-dequant GEMM: x (M,K) f32 @ 4-bit codes (K,N) -> (M,N) f32."""
    m_rep = 1 if bm is None else M // bm
    n_rep = 1 if bn is None else N // bn
    # x streams once per N-block, codes once per M-block, out once
    hbm = (M * K * 4) * n_rep + (K * N // 2) * m_rep + M * N * 4
    return 2.0 * M * N * K, float(hbm)


def attn_cost(BH: int, Sq: int, Skv: int, D: int, *,
              bq: int | None = None) -> tuple[float, float]:
    """LUT-softmax flash attention over (BH, Sq|Skv, D) fp16 operands."""
    q_rep = 1 if bq is None else Sq // bq
    hbm = BH * (Sq * D * 2 + 2 * Skv * D * 2 * q_rep + Sq * D * 2)
    return 4.0 * BH * Sq * Skv * D, float(hbm)


def paged_attn_cost(B: int, Hq: int, W: int, bs: int, D: int, *,
                    slab_bytes: float) -> tuple[float, float]:
    """Paged decode attention: q (B,1,Hq,D) against W blocks of bs
    tokens per row.  ``slab_bytes`` is one token's (Hkv, D) K-slab in
    pool storage (codes+scales for quantized pools), so the bound is
    layout-aware: a q8 pool moves ~4x fewer KV bytes than fp32."""
    skv = W * bs
    hbm = B * (Hq * D * 2 + 2 * skv * slab_bytes + Hq * D * 4)
    return 4.0 * B * Hq * skv * D, float(hbm)


def quantize_cost(K: int, N: int) -> tuple[float, float]:
    """Tile quantization of a (K, N) f32 weight to 4-bit codes."""
    return 4.0 * K * N, float(K * N * 4 + K * N // 2)


def dequant_kv_cost(R: int, H: int, D: int,
                    mode: str) -> tuple[float, float]:
    """vlut16 KV-slab dequant: R token slabs of (H, D) codes -> f32."""
    slab_in = H * (D // 2 if mode == "q4" else D) + H * D // 8
    slab_out = H * D * 4
    return 2.0 * R * H * D, float(R * (slab_in + slab_out))


def gemm_key(M: int, K: int, N: int, scheme: str, group_size: int) -> str:
    return f"gemm:{M}x{K}x{N}:{scheme}:g{group_size}"


def gemm_axes(M: int, K: int, N: int, *, scheme: str,
              group_size: int = 32) -> list[tuple[int, int, int]]:
    """``(n, target, align)`` for ``lut_dequant_gemm``'s (bm, bn, bk).

    x and out blocks need bm % 8 and bk % 128; the packed codes block
    (bk, bn/2) needs bn % 256.  Tile-scheme scales reach the kernel as
    column panels whose blocks are whole along lanes; common-scheme
    scales blocks are (bk/g, bn), so bk % (8·g).  Any axis without such
    a divisor takes its whole dim."""
    bk_align = 128 if scheme == "tile" else 8 * group_size
    return [(M, 128, 8), (N, 256, 256), (K, max(128, bk_align), bk_align)]


def gemm_blocks(M: int, K: int, N: int, *, scheme: str,
                group_size: int = 32) -> tuple[int, int, int]:
    """(bm, bn, bk) for ``lut_dequant_gemm``: the Mosaic-legal candidates
    of :func:`gemm_axes`, ranked by the roofline score."""
    axes = gemm_axes(M, K, N, scheme=scheme, group_size=group_size)

    def score(bl):
        bm, bn, bk = bl
        steps = (M // bm) * (N // bn) * (K // bk)
        vmem = (bm * bk + 2 * bk * bn + 2 * bm * bn) * 4
        flops, hbm = gemm_cost(M, K, N, bm=bm, bn=bn)
        return _roofline_score(flops, hbm, steps, vmem)

    return choose(gemm_key(M, K, N, scheme, group_size), axes, score)


def attn_key(BH: int, Sq: int, Skv: int, D: int, bq_target: int = 128,
             bkv_target: int = 128) -> str:
    return f"attn:{BH}x{Sq}x{Skv}x{D}:t{bq_target}x{bkv_target}"


def attn_blocks(BH: int, Sq: int, Skv: int, D: int, *, bq_target: int = 128,
                bkv_target: int = 128) -> tuple[int, int]:
    """(bq, bkv) for ``lut_softmax_attention``."""
    axes = [(Sq, bq_target, 1), (Skv, bkv_target, 1)]

    def score(bl):
        bq, bkv = bl
        steps = BH * (Sq // bq) * (Skv // bkv)
        vmem = (bq * D + 2 * bkv * D) * 2 + bq * D * 4 + bq * bkv * 4
        flops, hbm = attn_cost(BH, Sq, Skv, D, bq=bq)
        return _roofline_score(flops, hbm, steps, vmem)

    return choose(attn_key(BH, Sq, Skv, D, bq_target, bkv_target), axes,
                  score)


def quantize_key(K: int, N: int) -> str:
    return f"quantize:{K}x{N}"


def quantize_blocks(K: int, N: int) -> tuple[int, int]:
    """(bk, bn) for ``tile_quantize``."""
    axes = [(K, 128, 1), (N, 256, 1)]

    def score(bl):
        bk, bn = bl
        steps = (K // bk) * (N // bn)
        vmem = bk * bn * 6
        flops, hbm = quantize_cost(K, N)
        return _roofline_score(flops, hbm, steps, vmem)

    return choose(quantize_key(K, N), axes, score)


def dequant_key(R: int, H: int, D: int, mode: str) -> str:
    return f"dequant_kv:{R}x{H}x{D}:{mode}"


def dequant_rows(R: int, H: int, D: int, mode: str) -> int:
    """Row-block size for ``lut_dequant_kv`` (token-slab dequant): a
    multiple of 8 sublanes, or all of R."""
    axes = [(R, 256, 8)]
    slab_in = H * (D // 2 if mode == "q4" else D) + H * D // 8
    slab_out = H * D * 4

    def score(bl):
        (br,) = bl
        steps = R // br
        flops, hbm = dequant_kv_cost(R, H, D, mode)
        return _roofline_score(flops, hbm, steps,
                               br * (slab_in + slab_out))

    (br,) = choose(dequant_key(R, H, D, mode), axes, score)
    return br
