"""Figures 14 & 15 + Tables 2 & 5: kernel-level ablations.

Fig. 14 — softmax exp implementations: LUT vs fp16 polynomial vs exact f32,
accuracy vs f64 + CPU wall time of the interpret-mode kernel (relative
ordering; absolute speed is TPU territory).

Fig. 15 — dequant-GEMM layouts: (a) conventional column-group layout with
the runtime scatter the paper describes (emulated with a gather), (b) tile
layout (unit-stride), (c) + coalesced packing (the Pallas kernel path),
(d) the no-dequantization upper bound (fp16 weights straight to matmul).

Table 2 — the matrix-vs-vector unit gap, analytic for TPU v5e (MXU 197
TFLOP/s bf16 vs VPU ~4 TFLOP/s) + measured CPU proxy.

Table 5 — LUT-fp16 attention vs f32 attention output error, plus the
fused LUT-softmax quantized paged-decode kernel vs its exact-f32 mode
(time + error against the f32 oracle).

The ``autotune.*`` rows time the dequant-GEMM block-size candidate set at
the Fig. 15 shape and record the measured winner in the autotune cache
(``repro.kernels.autotune``), which subsequent wrapper calls pick up.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.kernels import ops, ref
from repro.kernels.lut_softmax_attention import build_exp_lut
from repro.quant import tile_quant as TQ

KEY = jax.random.key(0)


def fig14_softmax():
    lut = build_exp_lut()
    x = -jnp.abs(jax.random.normal(KEY, (64, 16384))).astype(jnp.float16)
    exact64 = np.exp(np.asarray(x, np.float64))

    from repro.kernels.lut_softmax_attention import _lut_exp, _poly_exp

    lut_fn = jax.jit(lambda v: _lut_exp(lut, v))
    poly_fn = jax.jit(_poly_exp)
    exact_fn = jax.jit(lambda v: jnp.exp(v.astype(jnp.float32)))
    for name, fn in [("lut", lut_fn), ("poly_f16", poly_fn),
                     ("exact_f32", exact_fn)]:
        t = time_fn(fn, x)
        err = float(np.abs(np.asarray(fn(x), np.float64) - exact64).max())
        emit(f"fig14.exp.{name}", t, f"max_err_vs_f64={err:.2e}")

    # full attention softmax path latency at (reduced) paper shapes (q x kv);
    # interpret mode executes the kernel body in python — relative ordering
    # only, absolute numbers are TPU territory.
    for (q, kv) in [(1, 1024), (16, 2048)]:
        qv = jax.random.normal(KEY, (2, max(q, 8), 4, 64)) * 0.5
        kvv = jax.random.normal(KEY, (2, kv, 4, 64)) * 0.5
        B, Sq, H, D = qv.shape
        kt = kvv.transpose(0, 2, 1, 3).reshape(B * H, kv, D)
        o32 = ref.attention_f32_ref(
            qv.transpose(0, 2, 1, 3).reshape(B * H, Sq, D), kt, kt,
            causal=False).reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
        for mode in ("lut", "exact"):
            t = time_fn(lambda a, b, c: ops.flash_attention(
                a, b, c, causal=False, exp_mode=mode), qv, kvv, kvv,
                iters=2, warmup=1)
            o = ops.flash_attention(qv, kvv, kvv, causal=False,
                                    exp_mode=mode).astype(jnp.float32)
            err = float(jnp.abs(o - o32).max())
            emit(f"fig14.attn_q{q}_kv{kv}.{mode}", t,
                 f"max_err_vs_f32={err:.2e}")


def fig15_dequant_gemm():
    M, K, N = 16, 1024, 1024
    w = jax.random.normal(KEY, (K, N)) * 0.05
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (M, K))
    qw_common = TQ.quantize(w, scheme="common")
    qw_tile = TQ.quantize(w, scheme="tile")

    # (a) baseline: conventional layout + runtime scatter (emulated: dequant
    # in group order then permute elements into matmul order with a gather)
    perm = jax.random.permutation(KEY, K * N).reshape(K, N)  # worst-case scatter

    def baseline(xv):
        wd = TQ.dequantize(qw_common, dtype=xv.dtype)
        wd = wd.reshape(-1)[perm.reshape(-1)].reshape(K, N)  # scatter cost
        return xv @ wd

    # (b) tile layout: unit-stride dequant then matmul
    def hmx_layout(xv):
        return xv @ TQ.dequantize(qw_tile, dtype=xv.dtype)

    # (c) ours: Pallas kernel, dequant fused in the MXU tile loop.  The
    # plan hoists the wrapper's scheme inference and block-size choice out
    # of the timed region, so this bar times the jitted kernel the same
    # way (a)/(b)/(d) time their jitted closures — previously the unjitted
    # wrapper re-ran that python work on every timed call, overstating the
    # fused bar's cost.
    fused = ops.plan_lut_dequant_matmul(qw_tile, m=M)

    # (d) upper bound: no dequantization
    w16 = w.astype(jnp.bfloat16)

    def no_dequant(xv):
        return xv @ w16.astype(xv.dtype)

    t_base = time_fn(jax.jit(baseline), x, iters=3)
    t_hmx = time_fn(jax.jit(hmx_layout), x, iters=3)
    t_fused = time_fn(fused, x, iters=3)
    t_ub = time_fn(jax.jit(no_dequant), x, iters=3)

    # accuracy of each bar against its own f32 unfused reference product
    # (the scatter baseline computes a deliberately permuted weight, so
    # its reference permutes the same way — the metric checks the *path*,
    # timing emulation included, not the permutation).  The interesting
    # bar is (c): the fused Pallas kernel must reproduce the unfused
    # f32 dequant-then-matmul; (d) shows the bf16 weight-cast error.
    ref_scatter = x @ TQ.dequantize(qw_common, dtype=jnp.float32) \
        .reshape(-1)[perm.reshape(-1)].reshape(K, N)
    ref_tile = x @ TQ.dequantize(qw_tile, dtype=jnp.float32)
    err_base = float(jnp.abs(jax.jit(baseline)(x) - ref_scatter).max())
    err_hmx = float(jnp.abs(jax.jit(hmx_layout)(x) - ref_tile).max())
    err_fused = float(jnp.abs(fused(x) - ref_tile).max())
    err_ub = float(jnp.abs(jax.jit(no_dequant)(x) - x @ w).max())

    emit("fig15.baseline_scatter", t_base,
         f"speedup=1.0 max_err_vs_f32={err_base:.2e} "
         "(conventional group layout + runtime permute)")
    emit("fig15.hmx_tile_layout", t_hmx,
         f"speedup={t_base / t_hmx:.2f} max_err_vs_f32={err_hmx:.2e} "
         "(tile layout: unit-stride dequant, no permute)")
    emit("fig15.ours_fused_kernel", t_fused,
         f"speedup={t_base / t_fused:.2f} max_err_vs_f32={err_fused:.2e} "
         "(interpret-mode python timing; "
         "on TPU the fused kernel also removes the HBM round-trip of the "
         "dequantized weights)")
    emit("fig15.no_dequant_bound", t_ub,
         f"speedup={t_base / t_ub:.2f} max_err_vs_f32={err_ub:.2e}")
    # the perf-relevant byte counts (HBM traffic per call, analytic)
    int4_bytes = K * N // 2 + (K // 2) * (N // 16) * 2
    bf16_bytes = K * N * 2
    emit("fig15.bytes_int4_weights", 0, f"{int4_bytes}")
    emit("fig15.bytes_bf16_weights", 0,
         f"{bf16_bytes} ({bf16_bytes / int4_bytes:.2f}x more HBM traffic)")


def autotune_gemm():
    """Measure the dequant-GEMM block-size candidates at the Fig. 15 shape
    and record the winner in the autotune cache (``runs/autotune.json``) —
    subsequent ``lut_dequant_matmul`` calls at this shape pick it up.
    Interpret-mode timings only order candidates by python-loop trip
    count, but the record/lookup plumbing is identical on TPU."""
    from repro.kernels import autotune as AT
    from repro.kernels import lut_dequant_gemm as G

    M, K, N = 16, 1024, 1024
    g = 32
    w = jax.random.normal(KEY, (K, N)) * 0.05
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (M, K))
    qw = TQ.quantize(w, scheme="tile")
    # the Mosaic-legal candidates, with room along K to choose from
    (m_ax, n_ax, (_, k_target, k_align)) = AT.gemm_axes(M, K, N,
                                                        scheme="tile",
                                                        group_size=g)
    bm = AT.block_candidates(*m_ax)[0]
    best, best_us = None, float("inf")
    for bn in AT.block_candidates(*n_ax, max_candidates=2):
        for bk in AT.block_candidates(K, 4 * k_target, k_align,
                                      max_candidates=2):
            fn = lambda xv: G.lut_dequant_gemm(
                xv, qw["codes"], qw["scales"], qw["codebook"], scheme="tile",
                group_size=g, bm=bm, bn=bn, bk=bk)
            t = time_fn(fn, x, iters=2, warmup=1)
            emit(f"autotune.gemm_bm{bm}_bn{bn}_bk{bk}", t, "")
            if t < best_us:
                best, best_us = (bm, bn, bk), t
    AT.record(AT.gemm_key(M, K, N, "tile", g), best, best_us)
    emit("autotune.gemm_best", best_us,
         f"blocks={best} recorded_in={AT.cache_path()}")


def paged_lut_attention():
    """Fused LUT-softmax quantized paged decode vs the exact-f32 mode:
    wall time of both paths plus the LUT path's error against the f32
    oracle (the Table-5 envelope applied to the paged decode kernel)."""
    import numpy as np

    from repro.serving import kv_quant as KQ

    B, W, bs, Hkv, G, D = 2, 4, 4, 2, 2, 32
    nb = 1 + B * W
    rng = np.random.default_rng(5)
    kp = jnp.asarray(rng.normal(size=(nb, bs, Hkv, D)), jnp.float32) * 0.5
    vp = jnp.asarray(rng.normal(size=(nb, bs, Hkv, D)), jnp.float32) * 0.5
    q = jnp.asarray(rng.normal(size=(B, 1, Hkv * G, D)), jnp.float32) * 0.5
    avail = list(range(1, nb))
    table = np.zeros((B, W), np.int32)
    for b in range(B):
        for j in range(W):
            table[b, j] = avail.pop(rng.integers(len(avail)))
    table = jnp.asarray(table)
    lens = jnp.asarray([W * bs, 2 * bs + 3], jnp.int32)
    kq = KQ.quantize_kv(kp, mode="q8", gr=2, gc=16)
    vq = KQ.quantize_kv(vp, mode="q8", gr=2, gc=16)

    qg = q.reshape(B, Hkv, G, D)
    o32 = ref.quant_paged_decode_attention_ref(qg, kq, vq, table, lens)
    for mode in ("exact", "lut"):
        fn = lambda a: ops.paged_flash_decode(a, kq, vq, table, lens,
                                              exp_mode=mode)
        t = time_fn(fn, q, iters=3, warmup=1)
        o = fn(q).reshape(B, Hkv, G, D).astype(jnp.float32)
        err = float(jnp.abs(o - o32).max())
        emit(f"tbl5.quant_paged_decode.{mode}", t,
             f"max_err_vs_f32={err:.2e}")


def tbl2_unit_gap():
    # analytic v5e: MXU 197 TFLOP/s bf16; VPU ≈ 8 lanes*128*2ops*0.94GHz/core…
    emit("tbl2.v5e_mxu_tflops", 0, "197")
    emit("tbl2.v5e_vpu_tflops_est", 0, "~4 (≈50x gap; Hexagon's was ~365x)")
    # CPU proxy: matmul vs elementwise throughput on this host
    a = jax.random.normal(KEY, (1024, 1024))
    mm = jax.jit(lambda v: v @ v)
    ew = jax.jit(lambda v: jax.nn.silu(v) * v + 1.0)
    t_mm = time_fn(mm, a, iters=3)
    t_ew = time_fn(ew, a, iters=3)
    gf_mm = 2 * 1024 ** 3 / (t_mm * 1e-6) / 1e9
    gf_ew = 3 * 1024 ** 2 / (t_ew * 1e-6) / 1e9
    emit("tbl2.cpu_matmul_gflops", t_mm, f"{gf_mm:.1f}")
    emit("tbl2.cpu_elementwise_gflops", t_ew, f"{gf_ew:.1f}")


def tbl5_attention_accuracy():
    B, S, H, D = 2, 256, 4, 64
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, H, D)) * 0.5
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, H, D)) * 0.5
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, H, D)) * 0.5
    o_lut = ops.flash_attention(q, k, v, causal=True, exp_mode="lut")
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    o32 = ref.attention_f32_ref(qt, k.transpose(0, 2, 1, 3).reshape(B * H, S, D),
                                v.transpose(0, 2, 1, 3).reshape(B * H, S, D),
                                causal=True)
    o32 = o32.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    err = float(jnp.abs(o_lut.astype(jnp.float32) - o32).max())
    rel = float(jnp.sqrt(jnp.mean((o_lut.astype(jnp.float32) - o32) ** 2)) /
                jnp.sqrt(jnp.mean(o32 ** 2)))
    emit("tbl5.lut16_vs_f32_attention", 0, f"max_err={err:.2e} relRMS={rel:.2e}")


def run():
    fig14_softmax()
    fig15_dequant_gemm()
    autotune_gemm()
    tbl2_unit_gap()
    tbl5_attention_accuracy()
    paged_lut_attention()


if __name__ == "__main__":
    run()
