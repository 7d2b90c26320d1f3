"""Serving entrypoint: batched decode with test-time scaling.

CPU-scale (real execution, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-1.5b --smoke \
      --method best_of_n --budget 8 --tasks 10 [--quantize] [--ckpt runs/ckpt]

On a TPU the same command without ``--smoke`` serves the published widths;
``chip_smoke.py`` at the repo root drives this module's functions that way.
The production path is the same engine under the production mesh
(launch/dryrun.py proves the serve_step lowers for every arch × shape).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.core import reward as R
from repro.core.controller import TTSSpec, sweep
from repro.data import tasks as T
from repro.data.tokenizer import ByteTokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.serving.engine import DecodeEngine


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--method", default="best_of_n",
                    choices=["best_of_n", "self_consistency", "beam_search"])
    ap.add_argument("--budget", type=int, default=8)
    ap.add_argument("--tasks", type=int, default=10)
    ap.add_argument("--max-tokens", type=int, default=48)
    ap.add_argument("--quantize", action="store_true",
                    help="apply tile-group W4A16 quantization (paper §5.1)")
    ap.add_argument("--ckpt", default="", help="restore trained params")
    ap.add_argument("--continuous", action="store_true",
                    help="serve best_of_n through the slot-based "
                         "continuous-batching scheduler")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode slots for --continuous")
    ap.add_argument("--paged", action="store_true",
                    help="back the decode slots with the paged KV block "
                         "pool (copy-on-write prompt sharing) instead of "
                         "dense per-slot max_len caches")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV block size in tokens for --paged")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="pool size in blocks for --paged (0 = auto: one "
                         "dense-equivalent reservation per slot)")
    ap.add_argument("--kv-quant", default="none",
                    choices=["none", "q8", "q4"],
                    help="store --paged pool blocks tile-quantized (Q8 "
                         "int8 / Q4 packed codes + per-(2,16)-tile "
                         "scales) with dequant fused into the paged "
                         "attention gather — ~4x / ~7x fewer KV bytes "
                         "than fp32 at matched block count")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="keep completed prompt prefixes pinned in the "
                         "paged KV pool (radix tree, LRU-evicted under "
                         "pressure) so requests sharing a system prompt / "
                         "few-shot header skip re-prefilling it; requires "
                         "--paged --continuous")
    ap.add_argument("--cache-capacity", type=int, default=0,
                    help="max pool blocks the prefix cache may pin "
                         "(0 = bounded only by pool pressure)")
    ap.add_argument("--fewshot", type=int, default=0,
                    help="prepend a shared header of N worked examples to "
                         "every task prompt (the cross-request common "
                         "prefix the cache exploits)")
    ap.add_argument("--beam-width", type=int, default=0,
                    help="surviving beams for --method beam_search "
                         "(0 = budget // 2)")
    ap.add_argument("--beam-expand", type=int, default=2,
                    help="candidates per surviving beam per step")
    ap.add_argument("--beam-steps", type=int, default=8,
                    help="reasoning-step scoring boundaries before final "
                         "selection")
    ap.add_argument("--step-tokens", type=int, default=16,
                    help="token budget per reasoning step")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft-then-verify rounds "
                         "committing up to K tokens per slot per step "
                         "(greedy acceptance — outputs stay bit-identical "
                         "to plain decoding); 0 disables; requires "
                         "--continuous --paged; defaults to self-drafting "
                         "unless --draft-model is given")
    ap.add_argument("--draft-model", default="",
                    help="configs-registry arch of the small draft model "
                         "proposing tokens for --spec-k (smoke config, "
                         "vocab aligned to the target)")
    ap.add_argument("--self-draft", action="store_true",
                    help="draft with the target model itself on a forked "
                         "(copy-on-write) snapshot of its paged state — "
                         "no extra params, 100%% acceptance; the "
                         "machinery-exercising mode for --spec-k")
    ap.add_argument("--trace", default="",
                    help="write a Chrome-trace-event JSON of the request "
                         "lifecycle (slots as tracks, scheduler/engine "
                         "phase spans as nested slices, pool gauges as "
                         "counters) to this path — open it at "
                         "https://ui.perfetto.dev; requires --continuous")
    ap.add_argument("--profile", default="",
                    help="write a roofline-attributed kernel-profile JSON "
                         "report (per-kernel analytic FLOPs/HBM bytes vs "
                         "sampled measured wall time, prefill/decode cost "
                         "breakdown, canary drift gauges) to this path — "
                         "validate with `python -m repro.serving.profiling "
                         "PATH`; requires --continuous")
    ap.add_argument("--canary-rate", type=float, default=0.25,
                    help="fraction of decode steps the profiler re-runs "
                         "through the exact path (XLA paged attention, fp "
                         "dequant, exact softmax) to measure max logit "
                         "error / argmax flip rate / KV round-trip drift "
                         "online (0 disables; only active with --profile)")
    ap.add_argument("--metrics", action="store_true",
                    help="print every serving row's full "
                         "SchedulerMetrics.summary() dict (all latency "
                         "percentiles included) instead of the one-line "
                         "summaries; requires --continuous")
    ap.add_argument("--dry", action="store_true",
                    help="CI smoke: shrink tasks/budget/steps so the run "
                         "finishes in seconds while still exercising the "
                         "full serving path")
    args = ap.parse_args(argv)
    if args.dry:
        args.tasks = min(args.tasks, 2)
        args.budget = min(args.budget, 4)
        args.max_tokens = min(args.max_tokens, 12)
        args.beam_steps = min(args.beam_steps, 2)
        args.step_tokens = min(args.step_tokens, 8)
    return args


def load_model(args):
    """(cfg, tokenizer, model) for ``--arch`` (``--smoke``: reduced)."""
    cfg = get_config(args.arch, smoke=args.smoke)
    tok = ByteTokenizer()
    if cfg.vocab_size < tok.vocab_size:
        cfg = cfg.with_(vocab_size=tok.vocab_size)
    return cfg, tok, api.get_model(cfg)


def init_params(args, cfg, model):
    """``--ckpt`` restored, or random weights from seed 0."""
    if args.ckpt:
        from repro.checkpoint.checkpointer import Checkpointer

        ckpt = Checkpointer(args.ckpt)  # params-only checkpoint dir
        params, _ = ckpt.restore(model.abstract_params(cfg))
        return params
    # one compiled program: op by op, a 1.5B init took about a minute on
    # one TPU v5e chip
    return jax.jit(model.init_params, static_argnums=1)(jax.random.key(0),
                                                        cfg)


def quantize_params(args, params):
    """Apply ``--quantize`` (identity without it)."""
    if not args.quantize:
        return params
    from repro.quant.qlinear import quantize_model_params

    params = quantize_model_params(params)
    print("[serve] weights quantized: tile-group Q4_0 + Q8_0 down-proj")
    return params


def build_engine(args, cfg, params, tok):
    """The engine ``args`` describe, and its prefix cache (or None)."""
    max_len = 256
    kv_kwargs = {}
    if args.kv_quant != "none" and not args.paged:
        raise SystemExit("--kv-quant requires --paged (the quantized pool "
                         "is a block-pool storage layout)")
    if args.paged:
        if max_len % args.block_size:
            raise SystemExit(f"--block-size must divide max_len={max_len}")
        # auto-size for the widest of the slot pool, the TTS fan-out and
        # the beam fan-out: the direct (non-continuous) path forks
        # `budget` (or width*expand) rows at once and has no preemption
        # to fall back on, and sweep() itself grows the scheduler to
        # max(slots, fan) slots
        fan = ((args.beam_width or max(1, args.budget // 2))
               * args.beam_expand if args.method == "beam_search" else 0)
        rows = max(args.slots, args.budget, fan)
        n_blocks = args.kv_blocks or (
            1 + rows * (max_len // args.block_size))
        kv_kwargs = dict(paged=True, block_size=args.block_size,
                         n_blocks=n_blocks, kv_quant=args.kv_quant)
    engine = DecodeEngine(params, cfg, max_len=max_len, eos_id=tok.eos_id,
                          pad_id=tok.pad_id, **kv_kwargs)
    prefix_cache = None
    if args.prefix_cache:
        if not (args.paged and args.continuous):
            raise SystemExit("--prefix-cache requires --paged --continuous "
                             "(the cache lives in the paged block pool and "
                             "is driven by the scheduler)")
        from repro.serving.prefix_cache import PrefixCache

        prefix_cache = PrefixCache(
            engine.pool, capacity_blocks=args.cache_capacity or None)
    return engine, prefix_cache


def run_sweep(args, engine, tok, prefix_cache=None, *, tracer=None,
              profiler=None, spec_decode=None) -> list:
    """Serve ``args``' task set through ``controller.sweep``; on a paged
    engine, fail unless the drained pool holds only the prefix cache's
    pins."""
    if args.fewshot:
        tasks = T.shared_prefix_dataset(123, args.tasks,
                                        n_shots=args.fewshot)
    else:
        tasks = T.gen_dataset(123, args.tasks)
    scorer = R.OracleVerifier()
    spec = TTSSpec(method=args.method, budget=args.budget,
                   max_tokens=args.max_tokens, beam_width=args.beam_width,
                   beam_expand=args.beam_expand, beam_steps=args.beam_steps,
                   step_tokens=args.step_tokens)
    sc = None
    if spec_decode is not None:
        from repro.serving.sampler import SamplerConfig

        sc = SamplerConfig(greedy=True)
    rows = sweep(engine, tok, tasks, [spec], jax.random.key(0), scorer,
                 continuous=args.continuous, n_slots=args.slots,
                 prefix_cache=prefix_cache, tracer=tracer,
                 profiler=profiler, spec_decode=spec_decode, sc=sc)
    if args.paged:
        # leak check: after a full drain the pool holds only the prefix
        # cache's pins — beam trees included (the pre-scheduler beam path
        # used to leak every task's blocks here)
        pinned = (prefix_cache.stats()["cached_blocks"]
                  if prefix_cache is not None else 0)
        in_use = engine.pool.blocks_in_use
        if in_use != pinned:
            raise SystemExit(
                f"[serve] KV pool leak: {in_use} blocks still in use after "
                f"drain (expected {pinned} cache-pinned)")
        print(f"[serve] kv pool clean: {in_use} blocks in use after drain "
              f"({pinned} cache-pinned)")
    return rows


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    cfg, tok, model = load_model(args)
    params = quantize_params(args, init_params(args, cfg, model))
    if args.continuous and args.method == "self_consistency":
        print(f"[serve] WARNING: --continuous routes best_of_n and "
              f"beam_search through the slot scheduler; {args.method} "
              f"uses the direct path")
    engine, prefix_cache = build_engine(args, cfg, params, tok)
    tracer = None
    if args.trace or args.metrics:
        if not args.continuous:
            raise SystemExit("--trace/--metrics require --continuous (the "
                             "tracer records the scheduler's request "
                             "lifecycle)")
        from repro.serving.telemetry import Tracer

        tracer = Tracer()
    profiler = None
    if args.profile:
        if not args.continuous:
            raise SystemExit("--profile requires --continuous (the "
                             "profiler samples the scheduler's decode "
                             "steps)")
        from repro.serving.profiling import KernelProfiler

        profiler = KernelProfiler(canary_rate=args.canary_rate)
    spec_decode = None
    if args.spec_k or args.draft_model or args.self_draft:
        if not args.spec_k:
            raise SystemExit("--draft-model/--self-draft need --spec-k K "
                             "(the proposal budget per round)")
        if not (args.paged and args.continuous):
            raise SystemExit("--spec-k requires --paged --continuous "
                             "(draft lanes and rejected suffixes are "
                             "refcount operations on the block pool)")
        from repro.serving.engine import SpecConfig

        spec_decode = SpecConfig(
            k=args.spec_k, draft_model=args.draft_model,
            self_draft=args.self_draft or not args.draft_model)
        # acceptance compares greedy argmaxes, so speculative serving
        # decodes greedily (that is also what makes it bit-identical to
        # the plain path)
        print(f"[serve] speculative decoding: k={args.spec_k} "
              f"{'draft=' + args.draft_model if args.draft_model else 'self-draft'}"
              f" (greedy sampling forced)")
    rows = run_sweep(args, engine, tok, prefix_cache, tracer=tracer,
                     profiler=profiler, spec_decode=spec_decode)
    if args.trace:
        tracer.write_chrome_trace(args.trace)
        print(f"[serve] trace: {len(tracer.events)} events / "
              f"{len(tracer.spans)} spans -> {args.trace} "
              f"(load in https://ui.perfetto.dev)")
    if profiler is not None:
        profiler.uninstall()
        profiler.write_report(args.profile)
        ps = profiler.summary_metrics()
        print(f"[serve] profile: {len(profiler.report()['kernels'])} "
              f"kernels, kernel_time_share={ps['kernel_time_share']:.3f} "
              f"eff_p50={ps['roofline_efficiency_p50']:.3g} "
              f"canary_samples={ps['canary_samples']} "
              f"flip_rate={ps['canary_argmax_flip_rate']:.3g} "
              f"max_logit_err={ps['canary_max_logit_err']:.3g} "
              f"-> {args.profile}")
        for w in profiler.warnings:
            print(f"[serve] profile WARNING: {w}")
    for r in rows:
        print(f"[serve] {r['method']} budget={r['budget']} "
              f"accuracy={r['accuracy']:.3f} "
              f"decode_tokens={r['decode_tokens']}")
        if "serving" in r:
            s = r["serving"]
            if args.metrics:
                for k in sorted(s):
                    print(f"[serve]   {k}={s[k]}")
            print(f"[serve] continuous: slots={s['n_slots']} "
                  f"occupancy={s['avg_slot_occupancy']:.2f} "
                  f"requests_per_s={s['requests_per_s']:.2f} "
                  f"prefill_tokens={s['prefill_tokens']} "
                  f"decode_tokens={s['decode_tokens']} "
                  f"prefill_calls={s['prefill_calls']} "
                  f"calls_per_request={s['prefill_calls_per_request']:.2f} "
                  f"admission_batch_max={s['admission_batch_max']} "
                  f"preemptions={s['preemptions']}")
            # tail latency: ttft/itl/queue_wait come from the tracer's
            # per-request records (0 without --trace/--metrics);
            # step_time needs no tracer (StepRecord.wall_s)
            print(f"[serve] latency: "
                  f"ttft_p50={s['ttft_p50'] * 1e3:.1f}ms "
                  f"ttft_p99={s['ttft_p99'] * 1e3:.1f}ms "
                  f"itl_p50={s['itl_p50'] * 1e3:.1f}ms "
                  f"itl_p99={s['itl_p99'] * 1e3:.1f}ms "
                  f"queue_wait_p99={s['queue_wait_p99'] * 1e3:.1f}ms "
                  f"step_time_p50={s['step_time_p50'] * 1e3:.1f}ms "
                  f"step_time_p99={s['step_time_p99'] * 1e3:.1f}ms")
            if s.get("spec_rounds"):
                print(f"[serve] speculative: rounds={s['spec_rounds']} "
                      f"draft_tokens={s['draft_tokens']} "
                      f"acceptance_rate={s['spec_acceptance_rate']:.2f} "
                      f"accepted_tokens_per_step="
                      f"{s['accepted_tokens_per_step']:.2f}")
            if s.get("beam_boundaries"):
                print(f"[serve] beam: boundaries={s['beam_boundaries']} "
                      f"expansions={s['beam_expansions']} "
                      f"prunes={s['beam_prunes']} "
                      f"prm_batches={s['prm_batches']} "
                      f"prm_candidates_per_batch="
                      f"{s['prm_candidates_per_batch']:.1f}")
            if "prefix_cache" in s:
                pc = s["prefix_cache"]
                print(f"[serve] prefix cache: hit_rate={pc['hit_rate']:.2f} "
                      f"tokens_matched={pc['tokens_matched']} "
                      f"prefill_tokens_saved={s['prefill_tokens_saved']} "
                      f"cached_blocks={pc['cached_blocks']} "
                      f"evictions={pc['evictions']}")
            if "kv" in s:
                kv = s["kv"]
                print(f"[serve] paged kv: block_size={kv['block_size']} "
                      f"kv_quant={kv['kv_quant']} "
                      f"peak_blocks={kv['peak_blocks_in_use']} "
                      f"cow_copies={kv['cow_copies']} "
                      f"peak_bytes={kv['peak_bytes_in_use']} "
                      f"dense_bytes={kv['dense_bytes']} "
                      f"hbm_saved_rightsized={kv['hbm_saved_bytes']}")


if __name__ == "__main__":
    main()
