"""The program's names in a profiler trace: op metadata read from the
``.xplane.pb``, self time per scope, idle time per host phase, and the
readers that turn them into per-layer metrics.

``data/fixture.xplane.pb`` is ``record_fixture.py``'s (no scopes);
``data/scope_fixture.xplane.pb`` was recorded on one TPU v5e chip by
``record_scope_fixture.py``: five runs of a four-layer ``scan`` whose body
holds the scopes ``alpha`` and ``beta``, each in ``repro.step`` over
``repro.dispatch``, ``repro.sync`` and a 20 ms ``repro.post`` sleep.
"""
import time
from types import SimpleNamespace

import pytest

from bench import trace_reduce, trace_run
from bench import trace_scopes as ts
from bench.tests.conftest import DATA, TINY_PEAKS, tiny_parts
from repro.serving.telemetry import Span

MS = 1_000_000  # ns
FIXTURE = DATA / "fixture.xplane.pb"
SCOPED = DATA / "scope_fixture.xplane.pb"


def chip(scoped, modules=()):
    """A device plane of ``(start, end, program, scope)`` ops, none of
    them scoped by dataflow."""
    return {"name": "/device:TPU:0",
            "ops": [(a, b, "op") for a, b, _, _ in scoped],
            "modules": list(modules),
            "scoped_ops": [(*op, False) for op in scoped]}


def test_metadata_names_the_fixtures_ops():
    meta = ts.op_metadata(FIXTURE)
    ids = {pid for pid, _ in meta}
    assert len(ids) == 1
    pid = ids.pop()
    assert meta[(pid, "convolution_tanh_fusion")] == \
        "jit(fixture_step)/dot_general"
    assert (pid, "copy-start") not in meta      # a copy XLA added
    # the id is the one in the module's name
    raw = ts.read(FIXTURE)
    names = {n for c in raw["chips"] for _, _, n in c["modules"]}
    assert names == {"jit_fixture_step"}


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(f)/while/body/closed_call/attention/kv_write/scatter",
     "attention"),
    ("jit(f)/sampler/jit(cumsum)/f/reduce_window_sum", "sampler"),
    ("jit(f)/lm_head/dot_general", "lm_head"),
    ("jit(f)/jit(_where)/select_n", ts.UNSCOPED),
    ("jit(f)/dot_general", ts.UNSCOPED),
    (None, ts.UNSCOPED),
])
def test_scope_is_the_first_named_component(tf_op, scope):
    assert ts.scope_of(tf_op) == scope


def test_self_time_of_a_loop_and_its_body_is_their_union():
    """A ``while`` over [0, 10] ms holds two ops; a third op follows.
    The loop keeps only the instants no body op covers."""
    ops = [(0, 10 * MS, "while"), (1 * MS, 4 * MS, "a"),
           (5 * MS, 9 * MS, "b"), (12 * MS, 13 * MS, "c")]
    t = ts.self_times(ops)
    assert t == {"while": 3 * MS, "a": 3 * MS, "b": 4 * MS, "c": 1 * MS}
    busy = trace_reduce.union((a, b) for a, b, _ in ops)
    assert sum(t.values()) == sum(b - a for a, b in busy)
    # an op that outlives its enclosing one keeps its own tail
    assert ts.self_times([(0, 4, "x"), (2, 6, "y")]) == {"x": 2, "y": 4}


def test_scopes_per_program_sum_to_its_busy_time():
    scoped = [(0, 10 * MS, "jit_step", ts.UNSCOPED),
              (1 * MS, 4 * MS, "jit_step", "attention"),
              (5 * MS, 9 * MS, "jit_step", "mlp"),
              (12 * MS, 13 * MS, "jit_other", "lm_head")]
    mods = [(0, 11 * MS, "jit_step"), (12 * MS, 13 * MS, "jit_other")]
    r = ts.reduce({"chips": [chip(scoped, mods)],
                   "spans": [("bench.traced_window", 0, 20 * MS)]})
    assert r["scopes"]["jit_step"] == pytest.approx(
        {"unscoped": 0.003, "attention": 0.003, "mlp": 0.004})
    assert r["program_busy"] == pytest.approx({"jit_step": 0.010,
                                               "jit_other": 0.001})
    assert r["scope_calls"]["jit_step"] == {"unscoped": 1, "attention": 1,
                                            "mlp": 1}


def test_idle_is_shared_by_the_phases_a_gap_crosses():
    """A gap from one step's ``post`` into the next step's ``admit`` and
    ``plan`` goes to each phase for its part, not whole to a span around
    them; the gap is named by the phase innermost for most of it, where
    the span overlapping it most is the next ``bench.step_once``."""
    scoped = [(0, 2 * MS, "jit_step", "attention"),
              (8 * MS, 10 * MS, "jit_step", "attention")]
    mods = [(0, 2 * MS, "jit_step"), (8 * MS, 10 * MS, "jit_step")]
    spans = [("bench.traced_window", 0, 10 * MS),
             ("bench.step_once", 0, 4 * MS), ("repro.step", 0, 4 * MS),
             ("repro.sync", 0, 2 * MS), ("repro.post", 2 * MS, 4 * MS),
             ("bench.step_once", 5 * MS, 10 * MS),
             ("repro.step", 5.2 * MS, 10 * MS),
             ("repro.admit", 5.2 * MS, 6.5 * MS),
             ("repro.plan", 6.5 * MS, 8 * MS)]
    r = ts.reduce({"chips": [chip(scoped, mods)], "spans": spans})
    assert r["idle_by_phase"] == pytest.approx(
        {"repro.post": 0.002, "none": 0.001, "bench.step_once": 0.0002,
         "repro.admit": 0.0013, "repro.plan": 0.0015})
    assert r["idle_gaps"] == [["repro.post", pytest.approx(0.006)]]
    assert r["idle_by_span"] == pytest.approx({"repro.post": 0.006})
    old = trace_reduce.reduce({"chips": [chip(scoped, mods)],
                               "spans": spans})
    assert old["idle_gaps"][0][0] == "bench.step_once"


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _instr(iid, name, opcode, operands=(), op_name="", called=()):
    b = _field(1, name) + _field(2, opcode) + _field(35, iid)
    if op_name:
        b += _field(7, _field(2, op_name))
    if operands:
        b += _field(36, b"".join(_varint(o) for o in operands))
    if called:
        b += _field(38, b"".join(_varint(c) for c in called))
    return b


def test_unscoped_ops_take_the_scope_their_result_flows_into():
    """A hand-made HLO module (``HloProto`` wire format): what the
    compiler added and a loop's plumbing take a scope by dataflow; the
    program's own unscoped work and what only feeds control flow keep
    none."""
    entry = [
        _instr(1, "p", "parameter"),
        _instr(2, "s", "argmax", [1], "jit(f)/sampler/argmax"),
        _instr(3, "sel", "select", [2], "jit(f)/select_n"),
        _instr(4, "emb", "gather", [1, 3], "jit(f)/gather"),
        _instr(5, "cvt", "convert", [4]),
        _instr(6, "dot", "fusion", [5], "jit(f)/attention/dot_general",
               [30]),
        _instr(7, "cp", "copy", [6]),
        _instr(8, "cvt2", "convert", [1]),
        _instr(9, "fus", "fusion", [8], called=[20]),
        _instr(10, "w", "while", [9]),
        _instr(11, "cp2", "copy", [1]),
        _instr(12, "w2", "while", [11]),
        _instr(13, "ys", "dynamic-update-slice", [7],
               "jit(f)/while/body/dynamic_update_slice"),
    ]
    comps = [_field(5, 10) + b"".join(_field(2, i) for i in entry),
             _field(5, 20) + _field(2, _instr(21, "m", "multiply", [],
                                              "jit(f)/mlp/mul")),
             _field(5, 30) + _field(2, _instr(31, "d", "dot", [],
                                              "jit(f)/attention/dot"))]
    proto = _field(1, b"".join(_field(3, c) for c in comps))
    flow = ts.flow_scopes(memoryview(proto), (0, len(proto)))
    assert flow == {"cvt": "attention", "cp": "attention",
                    "cvt2": "mlp", "ys": "attention"}


def test_existing_keys_are_unchanged_on_the_fixture():
    old = trace_reduce.reduce(trace_reduce.read(FIXTURE))
    new = ts.reduce(ts.read(FIXTURE))
    assert {k: new[k] for k in old} == old
    assert set(new) - set(old) == {"scopes", "scopes_by_flow", "scope_calls",
                                   "program_busy", "idle_by_phase",
                                   "top_op_scopes"}


def test_recorded_scoped_fixture():
    r = ts.reduce(ts.read(SCOPED))
    step = [p for p in r["scopes"] if "scoped_step" in p]
    assert len(step) == 1
    scopes = r["scopes"][step[0]]
    assert scopes["alpha"] > 0 and scopes["beta"] > 0    # a matmul each
    # the scopes and the rest sum to the program's busy time, and that
    # is the union of its operations' intervals
    assert sum(scopes.values()) == pytest.approx(
        r["program_busy"][step[0]], rel=0.01)
    assert r["program_busy"][step[0]] <= r["programs"][step[0]] + 1e-9
    # the 20 ms sleeps are the program's post phase, by name
    assert r["idle_gaps"][0][0] == "repro.post"
    assert r["idle_by_phase"]["repro.post"] >= 0.08
    assert r["idle_by_span"].get("repro.post", 0) >= 0.08
    # the accepted reduction reads the same trace as before: the
    # program's annotations are not its spans
    old = trace_reduce.reduce(trace_reduce.read(SCOPED))
    assert {k: r[k] for k in ("window_s", "busy_s", "programs",
                              "top_ops")} == {k: old[k] for k in (
        "window_s", "busy_s", "programs", "top_ops")}
    assert old["idle_gaps"][0][0] == "bench.step_once"


def _record(trace=None, spans=(), steps=range(0), **kw):
    return SimpleNamespace(trace=trace, spans=list(spans), steps=steps, **kw)


def test_readers_of_the_scoped_trace_and_the_host_spans():
    from bench.metrics import attention_ms, mlp_ms, sampler_ms, step_host_ms

    trace = {"scopes": {"jit__step_paged_impl": {"attention": 0.06,
                                                 "sampler": 0.3},
                        "jit__forced_step_paged_impl": {"attention": 1.0},
                        "jit__prefill_cached_impl": {"attention": 1.0}},
             "program_calls": {"jit__step_paged_impl": 3,
                               "jit__forced_step_paged_impl": 1,
                               "jit__prefill_cached_impl": 2}}
    assert attention_ms.read(_record(trace)) == pytest.approx(20.0)
    assert sampler_ms.read(_record(trace)) == pytest.approx(100.0)
    # a reduction without scopes, or a decode program without the scope:
    # the readers read nothing
    assert attention_ms.read(_record({"program_calls": {}})) is None
    assert attention_ms.read(_record(None)) is None
    assert mlp_ms.read(_record(trace)) is None
    spans = [Span("step", 0.0, 0.200, 4), Span("sync", 0.010, 0.190, 4),
             Span("step", 0.200, 0.400, 5), Span("sync", 0.205, 0.395, 5),
             Span("step", 0.400, 0.600, 6)]
    assert step_host_ms.read(_record(spans=spans, steps=range(4, 7))) == \
        pytest.approx(15.0)
    assert step_host_ms.read(_record(spans=(), steps=range(4, 7))) is None


def test_trace_run_names_the_host_phases_on_the_cpu():
    """The tiny cell's untraced run through ``trace_run``: every step's
    phases, garbage collections and the gap between steps."""
    result, phases = trace_run.run(
        "tiny", 2 ** 31 + 77, 1.0, False, tiny_parts(),
        t_start=time.perf_counter(), peaks=TINY_PEAKS)
    assert result["correct"]
    # the cell's own metrics, nothing added at run time
    assert set(result["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    for name in trace_run.PHASES:
        assert phases[f"{name}_ms"] >= 0
    assert phases["step_ms"] >= phases["decode_ms"] >= phases["sync_ms"] > 0
    assert phases["step_ms"] >= phases["admit_ms"] + phases["decode_ms"] \
        + phases["post_ms"]
    assert phases["gc_s"] >= phases["gc_max_s"] >= 0
    assert phases["step_gap_max_s"] > 0 and "step_gap_span" in phases


def test_new_readers_read_the_recorded_scoped_fixture():
    """The fixture's ``scoped_step`` stands for the decode program, its
    ``alpha`` and ``beta`` scopes for attention and the MLP: each reader
    reads them from the harness's reduction of the recorded trace."""
    from bench.metrics import (attention_ms, attention_roofline, mlp_ms,
                               mlp_roofline)

    r = ts.reduce(ts.read(SCOPED))
    step = next(p for p in r["scopes"] if "scoped_step" in p)
    names = {"alpha": "attention", "beta": "mlp"}
    runs = r["program_calls"][step]
    trace = {**r, "scopes": {"jit__step_paged_impl": {
        names.get(k, k): v for k, v in r["scopes"][step].items()}},
        "program_calls": {"jit__step_paged_impl": runs}}
    alpha, beta = r["scopes"][step]["alpha"], r["scopes"][step]["beta"]
    assert runs >= 4 and alpha > 0 and beta > 0
    # one traced step a run, whose scopes each need 1 ms at the roofline:
    # attention bound by compute, the MLP by bandwidth
    need = {"attention": (197e9, 1.0), "mlp": (1.0, 8.19e8)}
    rec = _record(trace, traced=range(runs), ctx={s: [8] for s in range(runs)},
                  kv_tokens={}, counters={}, m={},
                  family=SimpleNamespace(
                      scope_cost=lambda m, scope, *a: need.get(scope)),
                  peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert attention_ms.read(rec) == pytest.approx(1e3 * alpha / runs)
    assert mlp_ms.read(rec) == pytest.approx(1e3 * beta / runs)
    assert attention_roofline.read(rec) == pytest.approx(
        100 * runs * 1e-3 / alpha)
    assert mlp_roofline.read(rec) == pytest.approx(100 * runs * 1e-3 / beta)
    # a scope the family does not count reads nothing, not 0
    need.clear()
    assert attention_roofline.read(rec) is None
