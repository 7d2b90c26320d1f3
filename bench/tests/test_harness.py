"""The harness: traffic from the seed, windowed metrics, parts found by
name, and the command's refusal of a machine without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import generator as gen
from bench import harness
from bench.tests.conftest import DATA, ROOT, TINY_PEAKS
from repro.serving.telemetry import Gauge, Span

BIG_SEED = 2 ** 31 + 123456789


def mix(name="bon8-2shot"):
    return harness.load_mix(name)


def test_traffic_is_reproducible_from_the_seed():
    a = gen.Generator(mix(), BIG_SEED, gen.WINDOW, 151936)
    b = gen.Generator(mix(), BIG_SEED, gen.WINDOW, 151936)
    for _ in range(70):
        x, y = a.next(), b.next()
        assert x.req_id == y.req_id and x.max_new_tokens == y.max_new_tokens
        assert (x.prompt == y.prompt).all()


def test_every_seed_serves_the_same_sizes_in_the_same_order():
    a = gen.Generator(mix(), 1, gen.WINDOW, 151936)
    b = gen.Generator(mix(), BIG_SEED, gen.WINDOW, 151936)
    assert list(a.problems) == list(b.problems)
    assert list(a.outputs) == list(b.outputs)
    assert sorted(a.outputs) != list(a.outputs)
    assert not (a.header == b.header).all()
    assert not (a.next().prompt == b.next().prompt).all()
    w = gen.Generator(mix(), BIG_SEED, gen.WARMUP, 151936)
    assert (w.header == b.header).all()          # one header per seed
    assert list(w.problems) != list(b.problems)  # its own stream


def test_length_sets_keep_to_the_mix():
    m = mix()
    p = gen.length_set(m["problem_tokens"], m["sizes"])
    o = gen.length_set(m["output_tokens"], m["sizes"])
    assert p.min() >= 32 and p.max() <= 160 and (p % 8 == 0).all()
    assert o.min() >= 64 and o.max() <= 640
    assert np.median(p) == pytest.approx(64, abs=8)
    assert np.median(o) == pytest.approx(256, rel=0.05)
    for name in ("bon8-2shot", "bon8-8shot"):
        gen.check_mix(mix(name))


def test_warm_up_plan_covers_every_shape():
    m = mix()
    g = gen.Generator(m, 5, gen.WARMUP, 151936)
    plan = harness.warm_plan(m, g, 8, 16)
    sizes = [len(group) for group in plan]
    assert set(range(1, 9)) <= set(sizes)
    assert {p for group in plan for p in group} == set(int(x) for x in g.problems)


def record(**kw):
    base = dict(m={}, n_slots=4, setup_s=1.0, t0=10.0, t1=12.0,
                steps=range(5, 9), step_spans={}, decode_spans={},
                occupancy={4: 4, 5: 4, 6: 3, 7: 4, 8: 2, 9: 4}, ctx={},
                kv_tokens={},
                prefills={}, token_times={}, due={}, peaks={},
                family=harness.load_family("dense"))
    base.update(kw)
    return harness.RunRecord(**base)


def test_rate_is_all_tokens_of_the_window_over_all_its_time():
    read = harness.load_reader("output_tokens_per_s")
    # steps 5..8 decoded 4 + 3 + 4 + 2 rows over 2 s; steps 4 and 9 are out
    assert read(record()) == pytest.approx(13 / 2.0)


def test_tail_is_over_every_gap_whose_later_token_is_in_the_window():
    read = harness.load_reader("itl_p95_ms")
    times = {1: [9.0, 10.5] + [10.5 + 0.01 * i for i in range(1, 20)],
             2: [10.0 + 0.1 * i for i in range(21)] + [13.0]}
    gaps = []
    for t in times.values():
        for a, b in zip(t, t[1:]):
            if 10.0 <= b <= 12.0:
                gaps.append(b - a)
    assert read(record(token_times=times)) == pytest.approx(
        1e3 * np.percentile(gaps, 95))
    assert read(record()) is None


def test_layer_readers():
    rec = record(prefills={5: [(64, 512)], 7: [(100, 512), (80, 0)], 9: [(1, 1)]},
                 decode_spans={5: (10.0, 10.02), 6: (10.1, 10.14)})
    assert harness.load_reader("slot_occupancy")(rec) == pytest.approx(
        100 * 13 / 16)
    assert harness.load_reader("prefix_saved_share")(rec) == pytest.approx(
        100 * 1024 / (576 + 612 + 80))
    assert harness.load_reader("decode_step_ms")(rec) == pytest.approx(30.0)
    # no trace: the device readers find nothing to read
    for name in ("decode_roofline", "step_mfu", "device_idle_share"):
        assert harness.load_reader(name)(rec) is None


def test_record_counts_each_steps_rows_and_their_distinct_kv():
    # two Best-of-4 requests behind a 32-token header: prompts of 40 and
    # 48 tokens, admitted at steps 1 and 2, one token per step each
    ev = lambda kind, rid, step, **args: SimpleNamespace(
        kind=kind, req_id=rid, step=step, t=float(step), args=args)
    events = [ev("admit", 1, 1, rows=[0, 1, 2, 3], lease_tokens=32),
              ev("token", 1, 1), ev("admit", 2, 2, rows=[4, 5, 6, 7],
                                    lease_tokens=32),
              ev("token", 1, 2), ev("token", 2, 2),
              ev("token", 1, 3), ev("token", 2, 3)]
    sched = SimpleNamespace(metrics=SimpleNamespace(records=[]))
    spans = [Span("step", s - 0.5, s + 0.4, s) for s in (1, 2, 3)]
    gauges = [Gauge("occupancy", 1.3, 4), Gauge("occupancy", 2.3, 8),
              Gauge("free_blocks", 2.35, 90), Gauge("occupancy", 2.45, 7),
              Gauge("occupancy", 4.0, 8)]
    rec = harness.record_from(
        sched, SimpleNamespace(spans=spans, events=events, gauges=gauges),
        m={}, n_slots=8, setup_s=0.0, t0=0.0, t1=4.0, steps=range(1, 4),
        due={}, prompt_len_of={1: 40, 2: 48}, peaks={},
        family=harness.load_family("dense"), shared_prefix=32)
    assert rec.ctx[3] == [43] * 4 + [50] * 4
    # the header once, each prompt's own 8 and 16 tokens once, and 3 and 2
    # tokens of each of the 4 samples
    assert rec.kv_tokens[3] == 32 + (8 + 4 * 3) + (16 + 4 * 2)
    assert rec.kv_tokens[1] == 40 + 4
    assert rec.prefills == {1: [(8, 32)], 2: [(16, 32)]}
    # every span and gauge, and each step's gauges by name: a gauge
    # between two steps' spans belongs to neither
    assert rec.spans == spans and rec.gauges == gauges
    assert rec.counters == {1: {"occupancy": 4},
                            2: {"occupancy": 8, "free_blocks": 90}}


@pytest.fixture
def copy_root(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return tmp_path


def tree_bytes(root):
    return {p.relative_to(root): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_traffic_and_metric_are_found_by_name(copy_root):
    """A configuration with a model family of its own, its traffic, a
    metric and a cell come in as new files and entries alone: a whole run
    of the cell, its weights and check, and the roofline readers reach
    the new family."""
    root = copy_root
    before = tree_bytes(root)
    cfg = json.loads((DATA / "tiny.json").read_text())
    cfg["family"] = "counted"
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    shutil.copy(DATA / "counted_family.py", root / "bench/families/counted.py")
    shutil.copy(DATA / "tiny-mix.json", root / "bench/traffic/tiny-mix.json")
    (root / "bench/metrics/steps_in_window.py").write_text(
        "def read(run):\n    return len(run.steps)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny.cell", "config": "tiny",
                               "traffic": "tiny-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": "output_tokens_per_s",
                               "workloads": ["tiny.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    parts = harness.find_cell(harness.load_benchmark(root), "tiny.cell", root)
    assert parts["config"]["name"] == "tiny"
    assert parts["mix"]["header_tokens"] == 32
    assert [m["name"] for m in parts["per_layer"]] == ["steps_in_window"]
    assert {m["name"] for m in parts["end_to_end"]} == {
        "output_tokens_per_s", "itl_p95_ms", "setup_s"}
    assert harness.load_reader("steps_in_window", root)(record()) == 4
    assert harness.load_loop(parts["mix"]["loop"]).Loop

    family = parts["family"]
    assert family is harness.load_family("counted", root)
    result, _, rec = harness.run_cell(
        "tiny.cell", 2 ** 31 + 99, 1.0, False, t_start=time.perf_counter(),
        root=root, parts=parts, peaks=TINY_PEAKS)
    assert result["correct"], result["check"]
    assert rec.family is family
    assert family.CALLS["layout"] == 1       # the weights
    assert family.CALLS["hidden"] > 0        # the check's reference
    assert family.CALLS["head"] == 1
    # the device readers count the step's work through it
    rec.trace = {"programs": {"jit__step_paged_impl": 1.0},
                 "scopes": {"jit__step_paged_impl": {"attention": 0.5}},
                 "program_calls": {"jit__step_paged_impl": 2}}
    rec.traced = rec.steps
    for name in ("decode_roofline", "step_mfu", "attention_roofline"):
        assert harness.load_reader(name, root)(rec) > 0
    assert family.CALLS["decode_cost"] > 0 and family.CALLS["scope_cost"] > 0
    changed = {p for p, b in tree_bytes(root).items() if before.get(p) != b}
    assert {p for p in changed if p in before} == {Path("BENCHMARK.json")}


def test_the_cells_list_their_metrics():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        parts = harness.find_cell(bench, cell["name"])
        assert {m["name"] for m in parts["end_to_end"]} >= {"setup_s"}
        assert parts["per_layer"]
        for m in parts["per_layer"] + parts["end_to_end"]:
            assert (ROOT / "bench/metrics" / f"{m['name']}.py").exists()


def test_command_refuses_a_machine_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen1.5b-bon8-2shot", "--seed", str(BIG_SEED),
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 2
    assert r.stdout == ""
    assert "TPU" in r.stderr


def test_loop_keeps_slots_and_backlog_full():
    from bench.loops import closed

    g = gen.Generator(mix(), 1, gen.WINDOW, 1000)
    loop = closed.Loop(mix(), g, 64)
    sched = SimpleNamespace(metrics=SimpleNamespace(completed_requests=7))
    sent = []
    loop.start(sched, lambda item, due: sent.append((item, due)), 1.0)
    assert len(sent) == 10 and sent[-1][1] == 1.0
    sched.metrics.completed_requests = 9
    loop.after_step(sched, lambda item, due: sent.append((item, due)), 2.0)
    assert len(sent) == 12 and sent[-1][1] == 2.0


def test_model_config_applies_a_nested_group_onto_the_registrys():
    from repro.configs.base import MoEConfig

    config = {"name": "olmoe", "arch": "olmoe-1b-7b", "weight_dtype": "bfloat16",
              "model": {"n_layers": 4, "d_model": 2048,
                        "moe": {"n_experts": 64, "top_k": 8}}}
    cfg = harness.model_config(config)
    assert isinstance(cfg.moe, MoEConfig)
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.expert_d_ff) == \
        (64, 8, 1024)                   # the registry's width is kept
    assert cfg.n_layers == 4 and cfg.param_dtype == "bfloat16"
    hash(cfg)                           # jit takes it as a static argument
    with pytest.raises(TypeError):      # a size the group does not hold
        harness.model_config({**config, "model": {"moe": {"n_expert": 64}}})
    with pytest.raises(ValueError, match="no moe group"):
        harness.model_config({**config, "arch": "qwen2.5-1.5b"})
