"""Run one benchmark cell: set up, warm up, measure a window, check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration and traffic mix in ``bench/configs`` and
``bench/traffic``, its model family (weights, reference and counts) in
``bench/families/<family>.py``, its loop in ``bench/loops/<kind>.py`` and
each metric in ``bench/metrics/<metric>.py``.  The system under test is
the serving stack in ``src/repro``: ``ContinuousScheduler.submit``/
``step_once`` over a paged ``DecodeEngine`` with a ``PrefixCache``, sized
by the cell.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Optional

import numpy as np

from bench import check as checks
from bench import cost
from bench import generator as gen_mod
from bench import trace_scopes
from bench import weights as weights_mod

ROOT = Path(__file__).resolve().parents[1]
TRACE_SECONDS = 4.0     # the traced run profiles the last seconds of its window
MAX_STEPS = 1 << 16     # step keys drawn per run
WARM_NEW_TOKENS = 2


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# -- finding the parts by name ---------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The cell, its configuration and mix files, its model family, and
    the metrics it reports with ``--trace 0`` and ``--trace 1``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = load_mix(cell["traffic"], root)

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {"cell": cell, "config": config, "mix": mix,
            "family": load_family(config.get("family", "dense"), root),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def load_mix(name: str, root: Path = ROOT) -> dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json").read_text())


def load_loop(kind: str):
    return importlib.import_module(f"bench.loops.{kind}")


@functools.cache
def load_family(name: str, root: Path = ROOT) -> ModuleType:
    """``bench/families/<name>.py`` (one module per name and root)."""
    path = root / "bench" / "families" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_family_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- what a run leaves for the readers -------------------------------------

@dataclass
class RunRecord:
    """What the readers in ``bench/metrics`` read.  Times are seconds on
    the tracer's clock; steps are scheduler step indices."""

    m: dict                        # model sizes
    n_slots: int
    setup_s: float
    t0: float                      # window start
    t1: float                      # window end (after its last step)
    steps: range                   # steps inside the window
    step_spans: dict               # step -> (t0, t1) of the tracer's "step"
    decode_spans: dict             # step -> (t0, t1) of the tracer's "decode"
    occupancy: dict                # step -> rows that decoded
    ctx: dict                      # step -> context length of each live row
    kv_tokens: dict                # step -> distinct KV tokens of those rows
    prefills: dict                 # step -> [(suffix, cached)] admitted
    token_times: dict              # req_id -> times of its token events
    due: dict                      # req_id -> when the client meant to send it
    peaks: dict
    family: ModuleType             # the model's (``bench/families``)
    events: list = field(default_factory=list)   # the tracer's events
    traced: Optional[range] = None  # steps inside the traced window
    trace: Optional[dict] = None    # trace_scopes.reduce output
    extra: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)    # the tracer's spans
    gauges: list = field(default_factory=list)   # its gauges, with times

    @functools.cached_property
    def counters(self) -> dict:
        """step -> {gauge name: value} (:func:`step_counters`)."""
        return step_counters(self.step_spans, self.gauges)


def step_counters(step_spans: dict, gauges) -> dict:
    """``{step: {gauge name: value}}`` of the gauges recorded inside each
    step's ``step`` span (the last value of a name, where it has many)."""
    spans = sorted((a, b, s) for s, (a, b) in step_spans.items())
    starts = [a for a, _, _ in spans]
    out: dict = {}
    for g in gauges:
        i = bisect.bisect_right(starts, g.t) - 1
        if i >= 0 and g.t <= spans[i][1]:
            out.setdefault(spans[i][2], {})[g.name] = g.value
    return out


def record_from(sched, tracer, *, m, n_slots, setup_s, t0, t1, steps, due,
                prompt_len_of, peaks, family, shared_prefix=0, traced=None,
                trace=None) -> RunRecord:
    step_spans, decode_spans = {}, {}
    for sp in tracer.spans:
        if sp.name == "step":
            step_spans[sp.step] = (sp.t0, sp.t1)
        elif sp.name == "decode":
            decode_spans[sp.step] = (sp.t0, sp.t1)
    occupancy = {r.step: r.occupancy for r in sched.metrics.records}
    ctx: dict = {}
    live: dict = {}
    prefills: dict = {}
    token_times: dict = {}
    token_index: dict = {}
    fan: dict = {}
    for ev in tracer.events:
        if ev.kind in ("admit", "readmit"):
            plen = prompt_len_of[ev.req_id]
            cached = int(ev.args.get("lease_tokens", 0))
            prefills.setdefault(ev.step, []).append((plen - cached, cached))
            token_index[ev.req_id] = 0
            fan[ev.req_id] = len(ev.args.get("rows", [0]))
        elif ev.kind == "token":
            k = token_index[ev.req_id]
            token_index[ev.req_id] = k + 1
            plen = prompt_len_of[ev.req_id]
            ctx.setdefault(ev.step, []).extend([plen + k + 1] * fan[ev.req_id])
            live.setdefault(ev.step, []).append((plen, fan[ev.req_id], k + 1))
            token_times.setdefault(ev.req_id, []).append(ev.t)
    kv_tokens = {s: cost.distinct_kv_tokens(reqs, shared_prefix)
                 for s, reqs in live.items()}
    return RunRecord(m=m, n_slots=n_slots, setup_s=setup_s, t0=t0, t1=t1,
                     steps=steps, step_spans=step_spans,
                     decode_spans=decode_spans, occupancy=occupancy, ctx=ctx,
                     kv_tokens=kv_tokens,
                     prefills=prefills, token_times=token_times, due=due,
                     peaks=peaks, events=tracer.events, traced=traced,
                     trace=trace, spans=list(tracer.spans),
                     gauges=list(tracer.gauges), family=family)


# -- the system under test -------------------------------------------------

def model_config(config: dict):
    """The registry's configuration with this file's sizes over it; every
    size the file states must hold in what is served.  A nested group
    (``"moe": {...}``) is applied field by field onto the registry's
    dataclass of that name."""
    from repro.configs.registry import get_config

    base = get_config(config["arch"])
    model = config["model"]
    kw = {}
    for k, v in model.items():
        if isinstance(v, dict):
            group = getattr(base, k)
            if not dataclasses.is_dataclass(group):
                raise ValueError(f"{config['name']}: the registry's "
                                 f"{config['arch']} has no {k} group to "
                                 f"apply {v} onto")
            v = dataclasses.replace(group, **v)
        kw[k] = v
    cfg = base.with_(**kw, param_dtype=config["weight_dtype"])
    for k, v in model.items():
        pairs = ([(f"{k}.{f}", getattr(getattr(cfg, k), f), want)
                  for f, want in v.items()] if isinstance(v, dict)
                 else [(k, getattr(cfg, k), v)])
        for name, served, want in pairs:
            if served != want:
                raise ValueError(f"{config['name']}: served {name}={served}"
                                 f" differs from the file's {want}")
    return cfg


def sampler_config(config: dict):
    from repro.serving.sampler import SamplerConfig

    s = config["sampler"]
    return SamplerConfig(temperature=s["temperature"], top_k=s["top_k"],
                         top_p=s["top_p"], greedy=bool(s.get("greedy", False)))


def new_engine(config: dict, mix: dict, params=None):
    """The paged engine the cell sizes: its compiled programs are kept
    for as long as it lives."""
    from repro.serving.engine import DecodeEngine

    return DecodeEngine(params, model_config(config), max_len=mix["max_len"],
                        eos_id=-1, pad_id=0, paged=True,
                        block_size=config["block_size"],
                        n_blocks=config["n_blocks"],
                        kv_quant=config["kv_quant"])


def build(config: dict, mix: dict, params, engine=None):
    """Engine, prefix cache, tracer and scheduler as the cell sizes them.
    An ``engine`` built before for the same cell is given the new weights
    and an empty pool, and keeps its compiled programs."""
    from repro.serving.engine import ContinuousScheduler
    from repro.serving.kv_pool import KVPool
    from repro.serving.prefix_cache import PrefixCache
    from repro.serving.telemetry import Tracer

    if engine is None:
        engine = new_engine(config, mix, params)
    else:
        engine.params = params
        engine.pool = None      # the old pool goes before the new one comes
        engine.pool = KVPool(engine.cfg, config["n_blocks"],
                             config["block_size"])
    cache = PrefixCache(engine.pool) if config["prefix_cache"] else None
    tracer = Tracer()
    sched = ContinuousScheduler(engine, n_slots=config["slots"],
                                prompt_len=mix["prompt_len"],
                                prefix_cache=cache, tracer=tracer)
    return sched, tracer


def request(item):
    from repro.serving.engine import Request

    return Request(req_id=item.req_id, prompt=item.prompt,
                   max_new_tokens=item.max_new_tokens,
                   n_samples=item.n_samples)


class Driver:
    """Steps the scheduler with the run's per-step keys, under the
    harness's profiler annotations."""

    def __init__(self, sched, sc, keys):
        import jax

        self.sched, self.sc, self.keys = sched, sc, keys
        self.annotate = jax.profiler.TraceAnnotation

    def step(self) -> bool:
        s = self.sched.step_count
        if s >= len(self.keys):
            raise RuntimeError(f"run passed {len(self.keys)} steps")
        with self.annotate("bench.step_once"):
            return self.sched.step_once(self.keys[s], self.sc)

    def drain(self):
        while self.sched.queue or any(s is not None for s in self.sched.slots):
            self.step()


def warm_plan(mix: dict, gen, n_groups: int, block_size: int) -> list:
    """Groups of problem lengths to submit together in warm-up, so that
    every program shape the window can use is compiled: the miss prefill,
    each distinct suffix length, and k requests admitted (copy-on-write of
    7k tail blocks) and released (8k rows) in one step, k = 1..n_groups."""
    lengths = sorted(set(int(p) for p in gen.problems))
    misaligned = [p for p in lengths
                  if (mix["header_tokens"] + p) % block_size] or lengths
    plan = [[lengths[0]]]
    i = 0
    for k in range(1, n_groups + 1):
        plan.append([misaligned[(i + j) % len(misaligned)] for j in range(k)])
        i += k
    seen = {p for g in plan for p in g}
    rest = [p for p in lengths if p not in seen]
    plan += [rest[j:j + n_groups] for j in range(0, len(rest), n_groups)]
    return plan


def warm_up(driver: Driver, mix: dict, gen, n_groups: int, block_size: int,
            due: dict, prompt_len_of: dict, items: dict, clock):
    for group in warm_plan(mix, gen, n_groups, block_size):
        for p in group:
            item = gen.make(p, WARM_NEW_TOKENS)
            items[item.req_id] = item
            prompt_len_of[item.req_id] = len(item.prompt)
            due[item.req_id] = clock()
            driver.sched.submit(request(item))
        driver.drain()


class CompileCounter:
    """Counts traces and backend compiles while ``on``.  JAX's listeners
    are process-wide, so a process makes one (:func:`compile_counter`)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_a, **_k):
        if self.on and name in self.EVENTS:
            self.n += 1


@functools.cache
def compile_counter() -> CompileCounter:
    return CompileCounter()


def enable_compile_cache():
    """The program's persistent cache (``.jax_cache/`` in the checkout, or
    ``JAX_COMPILATION_CACHE_DIR``), for every program however small, so
    that only a checkout's first run compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def memory_peak() -> Optional[int]:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT, parts: Optional[dict] = None,
             peaks: Optional[dict] = None, engine=None,
             control: bool = False) -> tuple[dict, dict, RunRecord]:
    """One run of one cell.  Returns the result line's object, the
    check's full verdict (with ``control``, the fp8 control's readings
    too) and what the readers read.  ``parts`` (from :func:`find_cell`)
    and ``peaks`` may be given by a caller that has them, such as a test
    on the CPU; ``engine`` by one that runs many seeds of a cell in one
    process."""
    import jax

    parts = parts or find_cell(load_benchmark(root), name, root)
    config, mix, family = parts["config"], parts["mix"], parts["family"]
    gen_mod.check_mix(mix)
    dev = jax.devices()[0]
    peaks = peaks or cost.load_peaks(dev.device_kind)
    m = config["model"]
    counter = compile_counter()
    counter.n = 0

    params = weights_mod.from_layout(family.layout(m), seed,
                                     config["weight_dtype"])
    sched, tracer = build(config, mix, params, engine)
    sc = sampler_config(config)
    keys = np.random.default_rng([int(seed), 7]).integers(
        0, 2 ** 32, size=(MAX_STEPS, 2), dtype=np.uint32)
    driver = Driver(sched, sc, keys)
    n_groups = config["slots"] // mix["n_samples"]
    due: dict = {}
    prompt_len_of: dict = {}
    items: dict = {}

    t_warm = time.perf_counter()
    warm_up(driver, mix,
            gen_mod.Generator(mix, seed, gen_mod.WARMUP, m["vocab_size"],
                              first_id=1 << 30),
            n_groups, config["block_size"], due, prompt_len_of, items,
            tracer.now)
    warm_s = time.perf_counter() - t_warm

    # the window: the loop fills every slot, then measures
    loop = load_loop(mix["loop"]).Loop(
        mix, gen_mod.Generator(mix, seed, gen_mod.WINDOW, m["vocab_size"]),
        config["slots"])

    def submit(item, due_t):
        items[item.req_id] = item
        prompt_len_of[item.req_id] = len(item.prompt)
        due[item.req_id] = due_t
        sched.submit(request(item))

    with jax.profiler.TraceAnnotation("bench.submit"):
        loop.start(sched, submit, tracer.now())
    while True:
        driver.step()
        loop.after_step(sched, submit, tracer.now())
        if sched.metrics.records[-1].occupancy == config["slots"]:
            break

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    counter.on = True
    t0 = tracer.now()
    setup_s = time.perf_counter() - t_start
    s0 = sched.step_count
    traced_from = None
    annotation = None
    while tracer.now() - t0 < seconds:
        if trace and traced_from is None and \
                tracer.now() - t0 >= seconds - TRACE_SECONDS:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            annotation = jax.profiler.TraceAnnotation("bench.traced_window")
            annotation.__enter__()
            traced_from = sched.step_count
        driver.step()
        with jax.profiler.TraceAnnotation("bench.submit"):
            loop.after_step(sched, submit, tracer.now())
    t1 = tracer.now()
    s1 = sched.step_count
    counter.on = False
    reduced = None
    if trace:
        annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        t_read = time.perf_counter()
        try:
            reduced = trace_scopes.reduce(
                trace_scopes.read(trace_scopes.find_trace(trace_dir)))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        read_s = time.perf_counter() - t_read
    mem_peak = memory_peak()

    rec = record_from(sched, tracer, m=m, n_slots=config["slots"],
                      setup_s=setup_s, t0=t0, t1=t1, steps=range(s0, s1),
                      due=due, prompt_len_of=prompt_len_of, peaks=peaks,
                      shared_prefix=mix["header_tokens"],
                      traced=range(traced_from, s1) if trace else None,
                      trace=reduced, family=family)
    window_gauges = [g for g in tracer.gauges if t0 <= g.t <= t1]
    rec.extra = {"compiles_in_window": counter.n, "warm_s": warm_s,
                 "preemptions": sched.metrics.preemptions,
                 "memory_peak_bytes": mem_peak,
                 "n_blocks": config["n_blocks"],
                 "trace_read_s": read_s if trace else None,
                 "blocks_free_min": min((g.value for g in window_gauges
                                         if g.name == "free_blocks"),
                                        default=None),
                 "cache_pinned_max": max((g.value for g in window_gauges
                                          if g.name == "cache_pinned_blocks"),
                                         default=None)}
    wanted = parts["per_layer"] if trace else parts["end_to_end"]
    metrics = {}
    for spec in wanted:
        value = load_reader(spec["name"], root)(rec)
        if value is not None:
            metrics[spec["name"]] = {"value": float(value), "unit": spec["unit"]}

    # what the window finished, then the program's state freed before the
    # reference runs
    finished = checks.finished_in_window(sched, tracer, items, t0, t1)
    picked = checks.pick(finished, sched, tracer, items, seed)
    n_failed = checks.failed(sched, items, finished)
    sched.engine.pool = sched.engine.params = None
    del sched, driver, loop
    gc.collect()
    t_check = time.perf_counter()
    verdict = checks.run_check(params, config, mix, picked, keys, n_failed,
                               family, control=control)
    rec.extra["check_s"] = time.perf_counter() - t_check
    result = {
        "correct": verdict["correct"],
        "attempted": len(finished),
        "failed": n_failed,
        "metrics": metrics,
        "device": {**device_info(), "memory_peak_bytes": mem_peak},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    log("run", json.dumps({**rec.extra, "steps_in_window": s1 - s0,
                           "window_s": t1 - t0, **{
                               k: v for k, v in verdict.items()
                               if k not in ("numbers", "correct")},
                           **({"programs": reduced["programs"],
                               "idle_by_span": reduced["idle_by_span"]}
                              if reduced else {})}))
    result["check"] = verdict["numbers"]
    return result, verdict, rec
