"""Telemetry tests: deterministic latency derivation under an injected
clock, scheduler event-stream integration (admit/preempt/readmit/release
ordering, beam boundary/freeze/resume), null-tracer parity (zero overhead
when disabled), Chrome-trace export validity, and the summary() latency
keys' robustness on empty drains."""
import gc
import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.engine import (BeamSpec, ContinuousScheduler, DecodeEngine,
                                  Request)
from repro.serving import telemetry
from repro.serving.sampler import SamplerConfig
from repro.serving.telemetry import (Tracer, main, percentile,
                                     validate_chrome_trace)

NO_STOP = (9999,)
GREEDY = SamplerConfig(greedy=True)

# every latency-bearing key summary() must always carry (0.0-safe)
LATENCY_KEYS = ("latency_requests", "ttft_p50", "ttft_p90", "ttft_p99",
                "itl_p50", "itl_p99", "queue_wait_p50", "queue_wait_p99",
                "preempt_delay_s", "step_time_p50", "step_time_p99")


@pytest.fixture(scope="module")
def engine(trained_tiny, tiny_cfg, tok):
    return DecodeEngine(trained_tiny, tiny_cfg, max_len=128,
                        eos_id=tok.eos_id, pad_id=tok.pad_id)


def _req(tok, rid, text, max_new, n_samples=1):
    return Request(req_id=rid, prompt=jnp.asarray(tok.encode(text)),
                   max_new_tokens=max_new, n_samples=n_samples)


def _counting_clock(tick_s=1e-3):
    c = itertools.count()
    return lambda: next(c) * tick_s


class ManualClock:
    """Set ``.t`` before each tracer call to script exact timestamps."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# Tracer unit tests (no scheduler)
# ---------------------------------------------------------------------------


def test_percentile_empty_is_zero():
    assert percentile([], 99) == 0.0
    assert percentile([1.0, 2.0, 3.0], 50) == 2.0


def test_hand_computed_latency_on_scripted_schedule():
    """Three scripted requests; every derived interval is hand-checked."""
    c = ManualClock()
    tr = Tracer(clock=c)  # epoch at t=0

    def at(t, kind, rid, **args):
        c.t = t
        tr.event(kind, rid, **args)

    # req 0: clean life, three tokens
    at(1.0, "enqueue", 0)
    at(2.0, "admit", 0, rows=[0])
    at(3.0, "first_token", 0)
    at(3.0, "token", 0)
    at(5.0, "token", 0)
    at(8.0, "token", 0)
    at(9.0, "release", 0, rows=[0])
    # req 1: preempted mid-flight, token gap spans the requeue wait
    at(1.5, "enqueue", 1)
    at(2.0, "admit", 1, rows=[1])
    at(3.0, "first_token", 1)
    at(3.0, "token", 1)
    at(6.0, "preempt", 1, rows=[1])
    at(7.0, "readmit", 1, rows=[1])
    at(9.0, "token", 1)
    at(10.0, "release", 1, rows=[1])
    # req 2: enqueued, never admitted
    at(4.0, "enqueue", 2)

    r0 = tr.request_latency(0)
    assert r0.queue_wait == 1.0 and r0.ttft == 2.0
    assert r0.gaps == (2.0, 3.0) and r0.itl_mean == 2.5
    assert r0.preempt_delay == 0.0 and r0.e2e == 8.0

    r1 = tr.request_latency(1)
    assert r1.queue_wait == 0.5 and r1.ttft == 1.5
    assert r1.gaps == (6.0,)        # 3.0 -> 9.0 includes the requeue wait
    assert r1.preempt_delay == 1.0  # preempt@6 -> readmit@7
    assert r1.e2e == 8.5

    r2 = tr.request_latency(2)
    assert r2.queue_wait == r2.ttft == r2.e2e == 0.0 and r2.gaps == ()

    with pytest.raises(ValueError, match="no events"):
        tr.request_latency(99)

    trace = tr.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    # the two admitted requests appear as slot-occupancy slices
    slices = [e for e in trace["traceEvents"]
              if e["ph"] == "X" and e["name"].startswith("req")]
    assert {e["name"] for e in slices} == {"req0", "req1"}


def test_validator_negative_cases():
    assert validate_chrome_trace([]) != []          # not an object
    assert validate_chrome_trace({}) != []          # no traceEvents
    assert "empty" in validate_chrome_trace({"traceEvents": []})[0]

    def one(ev):
        return validate_chrome_trace({"traceEvents": [ev]})

    base = {"name": "x", "ph": "i", "s": "t", "ts": 1.0, "pid": 1, "tid": 0}
    assert one({k: v for k, v in base.items() if k != "pid"})  # missing key
    assert "unknown phase" in one({**base, "ph": "Z"})[0]
    assert "bad ts" in one({**base, "ts": -1.0})[0]
    assert "without non-negative dur" in one({**base, "ph": "X"})[0]
    assert "counter without" in one(
        {**base, "ph": "C", "args": {"note": "nan"}})[0]
    # non-monotone timeline
    bad = validate_chrome_trace({"traceEvents": [
        {**base, "ts": 5.0}, {**base, "ts": 1.0}]})
    assert any("not monotone" in b for b in bad)
    # partially-overlapping spans on one track are unbalanced
    bad = validate_chrome_trace({"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "pid": 1, "tid": 0},
        {"name": "b", "ph": "X", "ts": 5.0, "dur": 10.0, "pid": 1, "tid": 0},
    ]})
    assert any("partially overlaps" in b for b in bad)
    # nested and disjoint spans are fine
    ok = validate_chrome_trace({"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "pid": 1, "tid": 0},
        {"name": "b", "ph": "X", "ts": 2.0, "dur": 3.0, "pid": 1, "tid": 0},
        {"name": "c", "ph": "X", "ts": 6.0, "dur": 4.0, "pid": 1, "tid": 0},
        {"name": "d", "ph": "X", "ts": 20.0, "dur": 1.0, "pid": 1, "tid": 0},
    ]})
    assert ok == []


def test_write_and_cli_validate(tmp_path, capsys):
    c = ManualClock()
    tr = Tracer(clock=c)
    c.t = 1.0
    tr.event("enqueue", 0)
    c.t = 2.0
    tr.event("admit", 0, rows=[0])
    c.t = 3.0
    tr.event("release", 0, rows=[0])
    tr.gauge("occupancy", 1)
    path = str(tmp_path / "trace.json")
    tr.write_chrome_trace(path)
    assert validate_chrome_trace(json.load(open(path))) == []
    assert main([path]) == 0
    assert "OK" in capsys.readouterr().out

    bad_path = str(tmp_path / "bad.json")
    json.dump({"traceEvents": [{"name": "x", "ph": "X", "ts": -4.0,
                                "pid": 1, "tid": 0}]}, open(bad_path, "w"))
    assert main([bad_path]) == 1
    assert main([str(tmp_path / "missing.json")]) == 1
    assert main([]) == 2


# ---------------------------------------------------------------------------
# Scheduler integration
# ---------------------------------------------------------------------------

_REQS = [("Q:2+7=?A:", 7), ("Q:1+1=?A:", 2), ("Q:9+9=?A:", 5),
         ("Q:4+5=?A:", 3)]


def _run(engine, tok, tracer, sc=GREEDY):
    sched = ContinuousScheduler(engine, n_slots=2, prompt_len=16,
                                stop_ids=NO_STOP, tracer=tracer)
    for i, (text, max_new) in enumerate(_REQS):
        sched.submit(_req(tok, i, text, max_new))
    res = sched.run(jax.random.key(0), sc)
    return res, sched


def test_null_tracer_parity_and_golden_summary_keys(engine, tok):
    """tracer=None (the default) must change nothing: bit-identical
    outputs vs a traced run, and summary() still carries every latency
    key (0.0 where only the tracer could fill it in)."""
    res_off, sched_off = _run(engine, tok, None)
    res_on, sched_on = _run(engine, tok, Tracer())
    assert res_off == res_on
    s = sched_off.metrics.summary()
    for k in LATENCY_KEYS:
        assert k in s, f"summary() lost key {k}"
    assert s["latency_requests"] == 0
    assert s["ttft_p50"] == s["itl_p99"] == s["queue_wait_p99"] == 0.0
    # step_time_* comes from StepRecord.wall_s — no tracer needed
    assert s["step_time_p99"] >= s["step_time_p50"] > 0.0
    assert sched_on.metrics.summary()["latency_requests"] == len(_REQS)


def test_summary_safe_on_empty_drain(engine):
    """admitted == 0: every dividing key must come back 0.0, not raise."""
    sched = ContinuousScheduler(engine, n_slots=2, prompt_len=16,
                                stop_ids=NO_STOP)
    assert sched.run(jax.random.key(0), GREEDY) == {}
    s = sched.metrics.summary()
    for k in LATENCY_KEYS:
        assert s[k] == 0, f"{k} != 0 on an empty drain"


def test_traced_run_deterministic_under_injected_clock(engine, tok):
    """Two identical runs under identical fake clocks produce identical
    event streams, spans and latency records — exact equality, no
    wall-clock in sight."""
    runs = []
    for _ in range(2):
        tr = Tracer(clock=_counting_clock())
        _run(engine, tok, tr)
        runs.append(tr)
    a, b = runs
    key = lambda e: (e.kind, e.t, e.req_id, e.step, sorted(e.args.items()))
    assert [key(e) for e in a.events] == [key(e) for e in b.events]
    assert ([(s.name, s.t0, s.t1, s.step) for s in a.spans]
            == [(s.name, s.t0, s.t1, s.step) for s in b.spans])
    assert ([a.request_latency(i) for i in range(len(_REQS))]
            == [b.request_latency(i) for i in range(len(_REQS))])
    assert a.to_chrome_trace() == b.to_chrome_trace()


@pytest.mark.parametrize("sc,want", [
    (SamplerConfig(temperature=0.7, top_k=5, top_p=0.8), 5),
    (SamplerConfig(temperature=0.7, top_p=0.8), 0),
    (GREEDY, 0),
])
def test_sampler_candidates_gauge_per_step(engine, tok, sc, want):
    """Every decode step records the sampler's path: k candidates a row
    with a top-k, 0 for the full-vocabulary and greedy paths."""
    tr = Tracer(clock=_counting_clock())
    _, sched = _run(engine, tok, tr, sc)
    got = [g.value for g in tr.gauges if g.name == "sampler_candidates"]
    assert len(got) == sched.metrics.summary()["steps"]
    assert set(got) == {want}
    assert validate_chrome_trace(tr.to_chrome_trace()) == []


def test_lifecycle_event_ordering(engine, tok):
    tr = Tracer(clock=_counting_clock())
    _, sched = _run(engine, tok, tr)
    for rid in range(len(_REQS)):
        evs = tr.request_events(rid)
        kinds = [e.kind for e in evs]
        assert kinds[0] == "enqueue" and kinds[-1] == "release"
        assert kinds.index("admit") < kinds.index("first_token")
        assert kinds.index("first_token") <= kinds.index("token")
        ts = [e.t for e in evs]
        assert ts == sorted(ts), f"req {rid}: event times not monotone"
        lat = tr.request_latency(rid)
        assert lat.e2e >= lat.ttft >= lat.queue_wait >= 0
        # max_new tokens -> max_new - 1 inter-token gaps
        assert len(lat.gaps) == _REQS[rid][1] - 1
    # every step span contains its admit/decode spans (the final drain
    # step can record an admit span and bail before its step span when
    # nothing was live — that admit is legitimately top-level)
    steps = {s.step: s for s in tr.spans if s.name == "step"}
    for sp in tr.spans:
        if sp.name in ("admit", "decode") and sp.step in steps:
            outer = steps[sp.step]
            assert outer.t0 <= sp.t0 and sp.t1 <= outer.t1
    assert any(sp.step in steps for sp in tr.spans
               if sp.name in ("admit", "decode"))
    assert validate_chrome_trace(tr.to_chrome_trace()) == []


def test_preemption_events_and_delay(trained_tiny, tiny_cfg, tok):
    """A starved paged pool: preempt/readmit land in the event stream in
    order, first_token re-arms for the rerun, and the derived
    preempt_delay is positive."""
    eng = DecodeEngine(trained_tiny, tiny_cfg, max_len=64,
                       eos_id=tok.eos_id, pad_id=tok.pad_id, paged=True,
                       block_size=8, n_blocks=8)
    tr = Tracer(clock=_counting_clock())
    sched = ContinuousScheduler(eng, n_slots=3, prompt_len=16,
                                stop_ids=NO_STOP, tracer=tr)
    reqs = [("Q:2+7=?A:", 12), ("Q:1+1=?A:", 6), ("Q:9+9=?A:", 10),
            ("Q:4+5=?A:", 8)]
    for i, (text, max_new) in enumerate(reqs):
        sched.submit(_req(tok, i, text, max_new))
    res = sched.run(jax.random.key(0), GREEDY)
    assert set(res) == set(range(len(reqs)))
    assert sched.metrics.preemptions > 0
    preempted = [rid for rid in range(len(reqs))
                 if any(e.kind == "preempt" for e in tr.request_events(rid))]
    assert preempted, "pool starvation produced no preempt events"
    for rid in preempted:
        kinds = [e.kind for e in tr.request_events(rid)]
        i_pre = kinds.index("preempt")
        assert "readmit" in kinds[i_pre:], "no readmit after preempt"
        # the rerun decodes its first token afresh
        assert kinds.count("first_token") == 1 + kinds[:i_pre].count(
            "first_token")
        assert kinds[-1] == "release"
        lat = tr.request_latency(rid)
        assert lat.preempt_delay > 0
    s = sched.metrics.summary()
    assert s["preempt_delay_s"] > 0
    assert s["latency_requests"] == len(reqs)
    # free_blocks gauge tracked the pool on every step
    free = [g for g in tr.gauges if g.name == "free_blocks"]
    assert len(free) == sched.metrics.summary()["steps"]
    assert validate_chrome_trace(tr.to_chrome_trace()) == []


def test_beam_request_trace(trained_tiny, tiny_cfg, tok):
    """A beam (tree) request's trace carries freeze / beam_boundary /
    resume events and closes with a reason='beam' release."""
    eng = DecodeEngine(trained_tiny, tiny_cfg, max_len=64,
                       eos_id=tok.eos_id, pad_id=tok.pad_id, paged=True,
                       block_size=8, n_blocks=33)
    tr = Tracer(clock=_counting_clock())
    sched = ContinuousScheduler(eng, n_slots=4, prompt_len=16,
                                stop_ids=NO_STOP, tracer=tr)
    # delimiter '4' on this prompt: some lanes emit it mid-step (they
    # freeze and wait), others run to the step budget — both paths to a
    # boundary appear in the trace
    stop = int(tok.encode("4", bos=False)[0])
    spec = BeamSpec(width=2, expand=2, step_tokens=4, max_steps=2,
                    step_stop_id=stop,
                    score=lambda tl, lp, ng: np.asarray(lp)
                    / np.maximum(np.asarray(ng), 1))
    sched.submit(Request(req_id=0,
                         prompt=jnp.asarray(tok.encode("Q:12+34=?A:")),
                         search=spec))
    res = sched.run(jax.random.key(0), GREEDY)
    assert 0 in res
    kinds = [e.kind for e in tr.request_events(0)]
    for kind in ("freeze", "beam_boundary", "resume"):
        assert kind in kinds, f"beam trace missing {kind}"
    assert kinds.count("beam_boundary") == spec.max_steps
    rel = [e for e in tr.request_events(0) if e.kind == "release"]
    assert len(rel) == 1 and rel[0].args["reason"] == "beam"
    # boundaries happen between freezes and resumes, in time order
    t = {k: next(e.t for e in tr.request_events(0) if e.kind == k)
         for k in ("freeze", "beam_boundary", "resume")}
    assert t["freeze"] <= t["beam_boundary"] <= t["resume"]
    assert tr.request_latency(0).e2e > 0
    assert any(s.name == "prm" for s in tr.spans)
    assert validate_chrome_trace(tr.to_chrome_trace()) == []


def test_step_once_wall_time_is_per_step(engine, tok):
    """Satellite: wall_s is measured inside step_once (covers submit-
    while-stepping drains), every record carries its own share, and the
    total is their sum."""
    _, sched = _run(engine, tok, None)
    recs = sched.metrics.records
    assert recs and all(r.wall_s > 0 for r in recs)
    assert sched.metrics.wall_s == pytest.approx(
        sum(r.wall_s for r in recs))


# ---------------------------------------------------------------------------
# Phases on the profiler's clock
# ---------------------------------------------------------------------------


class _Notes:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs what opens."""

    def __init__(self):
        self.opened = []

    def __call__(self, name):
        log = self.opened

        class _Note:
            def __enter__(self):
                log.append(name)

            def __exit__(self, *exc):
                return False

        return _Note()


def test_phase_records_nested_spans_under_annotations(monkeypatch):
    notes = _Notes()
    monkeypatch.setattr(telemetry, "TraceAnnotation", notes)
    c = ManualClock()
    tr = Tracer(clock=c)
    c.t = 1.0
    with tr.phase("step", 0) as step:
        c.t = 2.0
        with tr.phase("sync", 0, rows=2):
            c.t = 3.0
        with tr.phase("post", 0) as post:
            post.set(released=1)
            c.t = 4.0
        step.set(occupancy=2)
        c.t = 5.0
    assert [(s.name, s.t0, s.t1, s.step, s.args) for s in tr.spans] == [
        ("sync", 2.0, 3.0, 0, {"rows": 2}),
        ("post", 3.0, 4.0, 0, {"released": 1}),
        ("step", 1.0, 5.0, 0, {"occupancy": 2})]
    assert notes.opened == ["repro.step", "repro.sync", "repro.post"]
    # an exception or a cancel records no span; a restart moves the start
    with pytest.raises(RuntimeError):
        with tr.phase("admit", 1):
            raise RuntimeError("boom")
    with tr.phase("admit", 1) as ph:
        ph.cancel()
    with tr.phase("decode", 1) as ph:
        c.t = 6.0
        ph.restart()
        c.t = 7.0
    assert [(s.name, s.t0, s.t1) for s in tr.spans[3:]] == [
        ("decode", 6.0, 7.0)]
    assert validate_chrome_trace(tr.to_chrome_trace()) == []
    assert telemetry.phase(None, "step", 0) is telemetry.NO_PHASE


def _paged_run(trained_tiny, tiny_cfg, tok, tracer):
    eng = DecodeEngine(trained_tiny, tiny_cfg, max_len=64,
                       eos_id=tok.eos_id, pad_id=tok.pad_id, paged=True,
                       block_size=8, n_blocks=40)
    sched = ContinuousScheduler(eng, n_slots=3, prompt_len=16,
                                stop_ids=NO_STOP, tracer=tracer)
    for i, (text, max_new) in enumerate(_REQS):
        sched.submit(_req(tok, i, text, max_new, n_samples=1 + i % 2))
    return sched.run(jax.random.key(0), SamplerConfig(temperature=0.8,
                                                      top_p=0.9))


def test_step_span_holds_its_phases(trained_tiny, tiny_cfg, tok):
    """step ⊃ {admit, decode ⊃ {plan, dispatch, sync}, post}, in that
    order, on every step."""
    tr = Tracer(clock=_counting_clock())
    _paged_run(trained_tiny, tiny_cfg, tok, tr)
    by = {}
    for sp in tr.spans:
        by.setdefault(sp.name, []).append(sp)
    steps = {sp.step: sp for sp in by["step"]}
    assert len(steps) == len(by["step"]) > 3

    def inside(inner, outer):
        return outer.t0 <= inner.t0 <= inner.t1 <= outer.t1

    for name in ("admit", "decode", "sync", "post"):
        of_step = {sp.step: sp for sp in by[name] if sp.step in steps}
        assert set(of_step) == set(steps), name
        for s, sp in of_step.items():
            assert inside(sp, steps[s]), (name, s)
    for s, step in steps.items():
        dec = next(sp for sp in by["decode"] if sp.step == s)
        sync = next(sp for sp in by["sync"] if sp.step == s)
        post = next(sp for sp in by["post"] if sp.step == s)
        admit = next(sp for sp in by["admit"] if sp.step == s)
        assert inside(sync, dec) and admit.t1 <= dec.t0 <= dec.t1 <= post.t0
        for name in ("plan", "dispatch"):
            assert sum(inside(sp, dec) for sp in by[name]) == 1, (name, s)
    for sp in by["prefill"]:
        assert any(inside(sp, a) for a in by["admit"])
    assert validate_chrome_trace(tr.to_chrome_trace()) == []


def test_null_tracer_opens_no_phase(trained_tiny, tiny_cfg, tok, monkeypatch):
    """tracer=None: the same tokens as a traced run, and no span, no
    annotation and no gc hook is made."""
    notes = _Notes()
    monkeypatch.setattr(telemetry, "TraceAnnotation", notes)
    hooks = list(gc.callbacks)
    off = _paged_run(trained_tiny, tiny_cfg, tok, None)
    assert notes.opened == [] and gc.callbacks == hooks
    tr = Tracer(clock=_counting_clock())
    on = _paged_run(trained_tiny, tiny_cfg, tok, tr)
    assert on == off
    # every phase opened its annotation; the drain's last, idle step
    # cancelled its step span
    assert tr.spans and len(notes.opened) == len(tr.spans) + 1


def test_gc_spans_while_the_tracer_is_bound(engine):
    tr = Tracer()
    sched = ContinuousScheduler(engine, n_slots=2, prompt_len=16,
                                stop_ids=NO_STOP, tracer=tr)
    try:
        gc.collect()
        spans = [sp for sp in tr.spans if sp.name == "gc"]
        assert spans and spans[-1].args["generation"] == 2
        assert spans[-1].args["collected"] >= 0
        assert spans[-1].t1 >= spans[-1].t0
        assert validate_chrome_trace(tr.to_chrome_trace()) == []
        # a new scheduler takes the engine's tracer slot: the old tracer
        # stops recording collections
        ContinuousScheduler(engine, n_slots=2, prompt_len=16,
                            stop_ids=NO_STOP)
        n = len(tr.spans)
        gc.collect()
        assert len(tr.spans) == n
        assert tr._on_gc not in gc.callbacks
        # an injected clock records no collections
        tr2 = Tracer(clock=_counting_clock())
        sched = ContinuousScheduler(engine, n_slots=2, prompt_len=16,
                                    stop_ids=NO_STOP, tracer=tr2)
        gc.collect()
        assert not [sp for sp in tr2.spans if sp.name == "gc"]
    finally:
        sched.tracer = None
    assert tr._on_gc not in gc.callbacks
