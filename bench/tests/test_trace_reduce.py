"""The reduction from a profiler trace to device metrics.

``data/fixture.xplane.pb`` was recorded on one TPU v5e chip by
``record_fixture.py``: five runs of a jitted ``fixture_step`` inside
``bench.step_once`` spans, each followed by a 20 ms ``bench.client_wait``
sleep, all in a ``bench.traced_window``.
"""
import pytest

from bench import trace_reduce as tr
from bench.tests.conftest import DATA

MS = 1_000_000  # ns


def chip(ops, modules=()):
    return {"name": "/device:TPU:0", "ops": list(ops), "modules": list(modules)}


def test_busy_is_a_union_of_overlapping_ops():
    raw = {"chips": [chip([(0, 4 * MS, "a"), (2 * MS, 6 * MS, "b"),
                           (8 * MS, 9 * MS, "c")])],
           "spans": [("bench.traced_window", 0, 10 * MS)]}
    r = tr.reduce(raw)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.007)        # [0,6] + [8,9]
    assert r["idle_share"] == pytest.approx(0.3)


def test_busy_is_averaged_over_chips_and_clipped_to_the_window():
    raw = {"chips": [chip([(0, 10 * MS, "a")]),
                     {**chip([(-5 * MS, 2 * MS, "a")]), "name": "/device:TPU:1"}],
           "spans": [("bench.traced_window", 0, 10 * MS)]}
    r = tr.reduce(raw)
    assert r["busy_s"] == pytest.approx((0.010 + 0.002) / 2)
    assert r["n_chips"] == 2


def test_device_time_per_program_and_ops_named_by_program():
    mods = [(0, 3 * MS, "jit_step"), (5 * MS, 6 * MS, "jit_merge"),
            (7 * MS, 9 * MS, "jit_step")]
    ops = [(0, 3 * MS, "fusion.1"), (5 * MS, 6 * MS, "copy.2"),
           (7 * MS, 9 * MS, "fusion.1")]
    r = tr.reduce({"chips": [chip(ops, mods)],
                   "spans": [("bench.traced_window", 0, 10 * MS)]})
    assert r["programs"] == pytest.approx({"jit_step": 0.005, "jit_merge": 0.001})
    assert r["program_calls"] == {"jit_step": 2, "jit_merge": 1}
    assert r["top_ops"][0] == ["jit_step/fusion.1", pytest.approx(0.005)]


def test_gaps_go_to_the_span_that_covers_them_most():
    ops = [(0, 2 * MS, "a"), (2.5 * MS, 3 * MS, "a"), (8 * MS, 9 * MS, "b")]
    mods = [(0, 3 * MS, "jit_step"), (8 * MS, 9 * MS, "jit_step")]
    spans = [("bench.traced_window", 0, 10 * MS),
             ("bench.step_once", 0, 4 * MS),
             ("bench.submit", 4 * MS, 5 * MS),
             ("bench.client_wait", 5 * MS, 8 * MS),
             ("bench.step_once", 8 * MS, 10 * MS)]
    r = tr.reduce({"chips": [chip(ops, mods)], "spans": spans})
    by = r["idle_by_span"]
    assert by["in_program"] == pytest.approx(0.0005)   # [2, 2.5] inside jit_step
    assert by["bench.client_wait"] == pytest.approx(0.005)  # [3, 8]: wait 3 of 5
    assert by["bench.step_once"] == pytest.approx(0.001)    # [9, 10]
    assert r["idle_gaps"][0] == ["bench.client_wait", pytest.approx(0.005)]


def test_a_trace_without_a_device_is_refused():
    with pytest.raises(ValueError):
        tr.reduce({"chips": [], "spans": []})


def test_recorded_fixture():
    raw = tr.read(DATA / "fixture.xplane.pb")
    r = tr.reduce(raw)
    assert r["n_chips"] == 1
    step = [k for k in r["programs"] if "fixture_step" in k]
    # the device's clock in this trace reads about 1 ms behind the host's:
    # the first of the five runs is stamped before the host dispatched it,
    # and before the window opened, so four count
    assert step == ["jit_fixture_step"] and r["program_calls"][step[0]] == 4
    names = {name.split("/", 1)[1] for name, _ in r["top_ops"]}
    assert {"fusion", "convolution_tanh_fusion"} <= names
    # five 20 ms sleeps leave the chip idle for at least 100 ms of the window
    assert r["window_s"] >= 0.1 and r["busy_s"] > 0
    assert r["idle_share"] > 0.5
    assert r["busy_s"] <= r["programs"][step[0]] + 1e-9
    assert r["idle_by_span"].get("bench.client_wait", 0) >= 0.09
    assert r["idle_gaps"][0][0] == "bench.client_wait"
    assert r["idle_gaps"][0][1] == pytest.approx(0.02, rel=0.5)
