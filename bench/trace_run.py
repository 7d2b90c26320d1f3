#!/usr/bin/env python3
"""Run one benchmark cell as ``bench/run.py`` does, and log besides what
the result line leaves out: what the scheduler's host phases took and
each program's device time by named scope.

    python3 bench/trace_run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--out <file.json>]

In every run it writes a ``phases`` object on standard error (and in
``--out``): each host phase's mean ms per step, garbage collection in the
window (``gc_s``, ``gc_max_s``), the longest gap between consecutive
steps with the span covering most of it, and with ``--trace 1`` each
program's ms per run by scope, the scopes' sum against the union of the
program's operations, the idle seconds by host phase, and the host phases
of the traced steps beside those of the steps before the trace.

Without a TPU, or with fewer chips than the cell asks for, it exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

PHASES = ("step", "admit", "prefill", "decode", "plan", "dispatch", "sync",
          "post", "gc")
# a step's child phases, where the host keeps the device waiting
CHILDREN = ("repro.admit", "repro.prefill", "repro.plan", "repro.dispatch",
            "repro.sync", "repro.post", "repro.prm", "repro.gc")


def host_phases(spans, steps, t0: float, t1: float) -> dict:
    """Mean ms per step of each phase over ``steps`` (``prefill`` and
    ``gc`` summed within a step), garbage collection in ``[t0, t1]`` and
    the longest gap between consecutive ``step`` spans there, named by
    the span covering most of it."""
    per = {name: 0.0 for name in PHASES}
    for sp in spans:
        if sp.name in per and sp.step in steps:
            per[sp.name] += sp.t1 - sp.t0
    n = max(len(steps), 1)
    out = {f"{name}_ms": 1e3 * v / n for name, v in per.items()}
    gcs = [sp for sp in spans if sp.name == "gc" and t0 <= sp.t0 <= t1]
    out["gc_s"] = sum(sp.t1 - sp.t0 for sp in gcs)
    out["gc_max_s"] = max((sp.t1 - sp.t0 for sp in gcs), default=0.0)
    out["gc_n"] = len(gcs)
    starts = sorted((sp.t0, sp.t1) for sp in spans
                    if sp.name == "step" and t0 <= sp.t0 <= t1)
    gap, between = 0.0, None
    for (_, a), (b, _) in zip(starts, starts[1:]):
        if b - a > gap:
            gap, between = b - a, (a, b)
    out["step_gap_max_s"] = gap
    if between:
        a, b = between
        cover = {}
        for sp in spans:
            ov = min(sp.t1, b) - max(sp.t0, a)
            if ov > 0:
                cover[sp.name] = cover.get(sp.name, 0.0) + ov
        out["step_gap_span"] = max(cover, key=cover.get) if cover else "none"
    return out


def device_phases(trace: dict) -> dict:
    """Per program, ms per run by scope; the scopes' sum against the
    union of the program's operations; idle by phase, and the share of
    the idle time inside ``bench.step_once`` that a child phase holds."""
    calls = trace["program_calls"]
    scopes = {p: {s: 1e3 * t / max(calls.get(p, 1), 1) for s, t in v.items()}
              for p, v in trace["scopes"].items()}
    check = {p: {"scopes_s": sum(v.values()),
                 "union_s": trace["program_busy"].get(p, 0.0),
                 "unscoped_share": v.get("unscoped", 0.0)
                 / max(sum(v.values()), 1e-12)}
             for p, v in trace["scopes"].items()}
    idle = trace["idle_by_phase"]
    in_step = sum(v for k, v in idle.items()
                  if k.startswith("repro.") or k == "bench.step_once")
    by_flow = {p: {s: 1e3 * t / max(calls.get(p, 1), 1)
                   for s, t in v.items()}
               for p, v in trace["scopes_by_flow"].items()}
    return {"scope_ms_per_run": scopes, "scope_by_flow_ms_per_run": by_flow,
            "scope_check": check,
            "idle_by_phase": idle,
            "idle_in_step_on_child": (sum(idle.get(k, 0.0) for k in CHILDREN)
                                      / in_step if in_step else None),
            "idle_gaps": trace["idle_gaps"],
            "top_ops": [[name, t, trace["top_op_scopes"].get(name)]
                        for name, t in trace["top_ops"]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    from bench import harness

    parts = harness.find_cell(harness.load_benchmark(), args.workload)
    import jax

    devices = jax.devices()
    chips = parts["cell"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    result, phases = run(args.workload, args.seed, args.seconds,
                         bool(args.trace), parts)
    harness.log("phases", json.dumps(phases))
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps({"result": result, "phases": phases},
                                       indent=1))
    print(json.dumps(result), flush=True)
    return 0


def run(name, seed, seconds, trace, parts, *, t_start=T_START, **kw):
    """``harness.run_cell``; returns the result line's object and the
    ``phases`` object."""
    from bench import harness

    result, _, rec = harness.run_cell(name, seed, seconds, trace,
                                      t_start=t_start, parts=parts, **kw)
    phases = host_phases(rec.spans, set(rec.steps), rec.t0, rec.t1)
    if rec.trace is not None:
        phases.update(device_phases(rec.trace))
        # what the profiler costs a step: the traced steps against the
        # window's steps before the trace started
        before = set(rec.steps) - set(rec.traced)
        for key, steps in (("traced", set(rec.traced)), ("untraced", before)):
            phases.update({f"{key}_{k}": v for k, v in host_phases(
                rec.spans, steps, rec.t0, rec.t1).items()
                if k in ("step_ms", "sync_ms", "plan_ms", "dispatch_ms",
                         "post_ms", "admit_ms")})
    return result, phases


if __name__ == "__main__":
    sys.exit(main())
