#!/usr/bin/env python3
"""Bring-up smoke run: serve qwen2.5-1.5b at full width on one TPU chip.

    python chip_smoke.py

Drives the serving path the way ``python -m repro.launch.serve`` does —
``DecodeEngine(paged=True)`` -> ``PrefixCache`` -> ``controller.sweep``
through the continuous-batching scheduler — at the published widths of
qwen2.5-1.5b (28 layers, d_model 1536, 12/2 heads of 128, d_ff 8960,
vocab 151936) with random weights from seed 0, so generated text is
gibberish.  It serves 4 tasks x Best-of-4, 32 new tokens each, behind a
shared 2-shot header so the prefix cache hits, in four phases over one
parameter init:

  a. fp paged KV pool, Pallas paged attention;
  b. Q8 KV pool: the quantized paged-attention kernel, and the prefix
     gather dequantized by ``lut_dequant_kv``;
  c. Q4 KV pool, the same kernels over packed codes;
  d. tile-Q4 weights (``--quantize``) through the LUT-dequant GEMM.

Each phase also runs a teacher-forced pass (header prefill, cached-suffix
prefill, 8 forced decode tokens) through the kernel path and through the
XLA path on the same chip, and fails unless their logits agree within
``REL_TOL``.  Every engine must drain its KV pool to the prefix cache's
pins.  Any failure raises.  The last line of output is one JSON object
naming the device; without a TPU the script exits 1 and prints none.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# The served workload: 4 tasks x Best-of-4, 32 new tokens, 2-shot header.
SERVE_ARGV = ["--arch", "qwen2.5-1.5b", "--continuous", "--paged",
              "--prefix-cache", "--fewshot", "2", "--tasks", "4",
              "--budget", "4", "--max-tokens", "32"]

# (name, extra serve flags, reference path, kernel path); a path is
# (paged-attention impl, LUT-dequant GEMM on)
PHASES = [
    ("a_fp_kv", [], ("xla", False), ("kernel", False)),
    ("b_q8_kv", ["--kv-quant", "q8"], ("xla", False), ("kernel", False)),
    ("c_q4_kv", ["--kv-quant", "q4"], ("xla", False), ("kernel", False)),
    ("d_q4_weights", ["--quantize"], ("xla", False), ("xla", True)),
]

FORCED_TOKENS = 8

# Kernel and XLA paths compute in bf16 with f32 accumulation, in different
# orders: the kernels keep softmax probabilities and dequantized values in
# f32 where XLA rounds them to bf16 (8 significant bits, a relative step
# of 2^-8).  Those roundings pass through 28 residual layers, so the
# teacher-forced logits may differ by a few percent of their range; a
# wrong block, head, scale or codebook entry moves them by the whole range.
REL_TOL = 0.05


@contextlib.contextmanager
def serving_path(impl: str, pallas_gemm: bool):
    """Select the paged-attention impl and the quantized-GEMM backend.
    Engines trace their programs lazily, so build and run them inside."""
    from repro.models import layers
    from repro.quant import qlinear

    prev_impl = layers.set_paged_attention_impl(impl)
    prev_gemm = qlinear.use_pallas_kernels(pallas_gemm)
    try:
        yield
    finally:
        layers.set_paged_attention_impl(prev_impl)
        qlinear.use_pallas_kernels(prev_gemm)


def _pinned(prefix_cache) -> int:
    return prefix_cache.stats()["cached_blocks"] if prefix_cache else 0


def teacher_forced_logits(engine, prompts, forced):
    """Logits after a cached-prefix prefill of ``prompts`` and after each
    of the ``forced`` (B, T) tokens: (T + 1, B, V) f32 on the host.

    The prompts' common prefix is prefilled once; every row then
    prefills its suffix against those blocks as a cached prefix (the
    partial-prefill path: prefix gather, tail copy-on-write) and decodes
    the forced tokens through paged attention.  All blocks are released
    again."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    hdr = os.path.commonprefix(prompts)
    n, B, bs = len(hdr), len(prompts), engine.pool.block_size
    st = engine.prefill(jnp.asarray([hdr], jnp.int32))
    blocks = np.asarray(st.cache["table"])[0, :-(-n // bs)]
    engine.pool.retain(blocks.tolist(), times=B)   # one lease per row
    engine.release_rows(st, [0])
    sfx = [p[n:] for p in prompts]
    width = max(map(len, sfx))
    toks = np.zeros((B, width), np.int32)
    for i, s in enumerate(sfx):
        toks[i, :len(s)] = s
    st = engine.prefill(jnp.asarray(toks),
                        jnp.asarray([len(s) for s in sfx], jnp.int32),
                        cached_table=np.tile(blocks, (B, 1)),
                        cached_lens=np.full((B,), n))
    out = [st.pending_logits]
    for t in range(forced.shape[1]):
        st = engine.forced_step(st, forced[:, t])
        out.append(st.pending_logits)
    engine.release_rows(st, list(range(B)))
    return np.stack(jax.device_get(out))


def run_phase(name, argv, params, ref, test):
    """Serve ``argv``'s workload on the kernel path, then compare its
    teacher-forced logits with the XLA path's; returns diagnostics."""
    import jax
    import numpy as np

    from repro.data import tasks as T
    from repro.launch import serve

    args = serve.parse_args(argv)
    cfg, tok, _ = serve.load_model(args)
    params = serve.quantize_params(args, params)
    prompts = [tok.encode(t.prompt) for t in
               T.shared_prefix_dataset(123, args.tasks,
                                       n_shots=args.fewshot)]
    forced = np.asarray(jax.random.randint(
        jax.random.key(1), (len(prompts), FORCED_TOKENS), 0, tok.vocab_size))
    logits = {}
    for path in (test, ref):
        with serving_path(*path):
            engine, cache = serve.build_engine(args, cfg, params, tok)
            if path == test:
                rows = serve.run_sweep(args, engine, tok, cache)
            logits[path] = teacher_forced_logits(engine, prompts, forced)
            if engine.pool.blocks_in_use != _pinned(cache):
                raise RuntimeError(f"{name}: teacher forcing leaked "
                                   f"{engine.pool.blocks_in_use} blocks")
            del engine, cache
    got, want = logits[test], logits[ref]
    if got.shape != (FORCED_TOKENS + 1, len(prompts), cfg.vocab_size):
        raise RuntimeError(f"{name}: logits shape {got.shape}")
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise RuntimeError(f"{name}: non-finite logits")
    delta = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    if not delta <= REL_TOL * scale:
        raise RuntimeError(f"{name}: kernel path differs from the XLA path "
                           f"by {delta} > {REL_TOL} x {scale}")
    s = rows[0]["serving"]
    if not s["prefix_cache"]["hit_rate"] > 0:
        raise RuntimeError(f"{name}: the prefix cache never hit")
    return {"decode_tokens": rows[0]["decode_tokens"],
            "prefill_tokens": s["prefill_tokens"],
            "prefix_hit_rate": s["prefix_cache"]["hit_rate"],
            "max_abs_dlogit": delta, "logit_scale": scale}


def run(base_argv=SERVE_ARGV, phases=PHASES) -> dict:
    """Every phase over one parameter init; returns per-phase
    diagnostics.  Raises on any failure."""
    import jax

    from repro.launch import serve

    args = serve.parse_args(base_argv)
    cfg, _, model = serve.load_model(args)
    t0 = time.perf_counter()
    params = serve.init_params(args, cfg, model)
    jax.block_until_ready(params)
    print(f"[smoke] {cfg.name}: {cfg.param_count() / 1e9:.3f}B params "
          f"initialised in {time.perf_counter() - t0:.1f}s", flush=True)
    report = {}
    for name, flags, ref, test in phases:
        t0 = time.perf_counter()
        d = run_phase(name, base_argv + flags, params, ref, test)
        d["wall_s_with_compiles"] = time.perf_counter() - t0
        report[name] = d
        print(f"[smoke] {name}: " + " ".join(f"{k}={v}" for k, v in
                                             d.items()), flush=True)
    return report


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
    run()
    stats = dev.memory_stats() or {}
    print(f"[smoke] peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
