#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's model with weights from the seed, warms up every program
shape its traffic uses, measures for ``--seconds`` and checks what the
window served against the plain reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics from a profiler trace of the window's last seconds),
``device``, with ``--trace 1`` ``breakdown``, and last ``check``: each
number compared beside its limit, which also close standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
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
    result, _, _ = harness.run_cell(args.workload, args.seed, args.seconds,
                                    bool(args.trace), t_start=T_START,
                                    parts=parts)
    for name, n in result["check"].items():
        print(f"check {name} = {n['value']!r}  limit {n['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
