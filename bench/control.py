#!/usr/bin/env python3
"""Readings that set the check's limit: the program and its fp8 control
on many seeds of one cell, in one process on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [--first <seed>] [--also <seed> ...]

Each seed is a whole run of the cell at its own load (weights, warm-up,
window, check), served by one engine whose programs compile once.  The
check then also runs the fp8 control over the same requests.  One JSON
line per seed, then the summary: the lower reading (the program's widest
gap over the seeds), the upper one (the control's smallest), and their
ratio.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=4_000_000_001)
    ap.add_argument("--also", type=int, nargs="*", default=[],
                    help="further seeds, run after the --seeds series")
    args = ap.parse_args(argv)

    import jax

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    parts = harness.find_cell(harness.load_benchmark(), args.workload)
    engine = harness.new_engine(parts["config"], parts["mix"])
    served, ctrl = [], []
    seeds = [args.first + 7919 * i for i in range(args.seeds)] + args.also
    for seed in seeds:
        result, verdict, _ = harness.run_cell(
            args.workload, seed, args.seconds, False,
            t_start=time.perf_counter(), parts=parts, engine=engine,
            control=True)
        served.append(verdict["numbers"]["max_gap_logit"]["value"])
        ctrl.append(verdict["control_max_gap_logit"])
        print(json.dumps({"seed": seed, "served_max_gap": served[-1],
                          "control_max_gap": ctrl[-1],
                          **{k: verdict[k] for k in (
                              "tokens_compared", "tokens_differing",
                              "control_tokens_differing", "control_correct")},
                          "correct": result["correct"],
                          "failed": result["failed"],
                          "attempted": result["attempted"],
                          "metrics": {k: v["value"] for k, v in
                                      result["metrics"].items()}}),
              flush=True)
    print(json.dumps({"workload": args.workload, "lower": max(served),
                      "upper": min(ctrl), "ratio": min(ctrl) / max(max(served), 1e-12)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
