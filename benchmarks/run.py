"""Benchmark harness: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).

  python -m benchmarks.run            # everything
  python -m benchmarks.run --only fig14,fig15

Snapshot mode (the recorded perf trajectory):

  python -m benchmarks.run --record --areas kernels,serving
  python -m benchmarks.run --check  --areas kernels,serving

``--record`` runs each area and (re)writes its ``BENCH_<area>.json``
snapshot; ``--check`` asserts the fresh rows against the committed
snapshot's envelope (see ``benchmarks.common.check_snapshot``) and exits
non-zero on violations.  Combined ``--check --record`` (what CI runs)
checks first, then refreshes the snapshot only for areas that passed, so
a regressed run cannot overwrite the evidence against it.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import common


def snapshot_areas() -> dict:
    """Area name -> callable emitting that area's snapshot rows.

    ``kernels`` is the full kernel-ablation sweep (pure kernel work,
    stable shapes); ``serving`` is the dry serving sweep — small enough
    for CI, still exercising the paged / prefix-cache / kv-quant engines
    end to end with their built-in assertions.
    """
    from benchmarks import kernel_ablation, serving_scaling

    return {"kernels": kernel_ablation.run,
            "serving": serving_scaling.dry_rows}


def run_snapshots(areas, record: bool, check: bool) -> int:
    import json
    import os

    table = snapshot_areas()
    unknown = [a for a in areas if a not in table]
    if unknown:
        print(f"unknown areas {unknown}; have {sorted(table)}",
              file=sys.stderr)
        return 2
    failures = []
    for area in areas:
        mark = len(common.ROWS)
        table[area]()
        rows = common.ROWS[mark:]
        path = common.snapshot_path(area)
        ok = True
        if check:
            if os.path.exists(path):
                old = json.load(open(path))
                bad = common.check_snapshot(area, rows, old)
                for msg in bad:
                    print(f"ENVELOPE VIOLATION: {msg}", file=sys.stderr)
                ok = not bad
                failures.extend(bad)
            else:
                print(f"{path} not found; treating this run as the "
                      f"baseline", file=sys.stderr)
        if record and ok:
            print(f"recorded {common.write_snapshot(area, rows)}")
    return 1 if failures else 0


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: quant,kernels,serving,roofline")
    ap.add_argument("--record", action="store_true",
                    help="write BENCH_<area>.json snapshots for --areas")
    ap.add_argument("--check", action="store_true",
                    help="assert fresh rows against the committed "
                         "BENCH_<area>.json envelopes for --areas")
    ap.add_argument("--areas", default="kernels,serving",
                    help="comma list of snapshot areas (default "
                         "kernels,serving)")
    args = ap.parse_args()

    if args.record or args.check:
        print("name,us_per_call,derived")
        areas = [a for a in args.areas.split(",") if a]
        sys.exit(run_snapshots(areas, record=args.record, check=args.check))

    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    sections = []
    if only is None or {"quant", "tbl1", "tbl4"} & only:
        from benchmarks import quant_accuracy
        sections.append(("quant_accuracy", quant_accuracy.run))
    if only is None or {"kernels", "fig14", "fig15", "tbl2", "tbl5"} & only:
        from benchmarks import kernel_ablation
        sections.append(("kernel_ablation", kernel_ablation.run))
    if only is None or {"serving", "fig8", "fig10", "fig11", "fig17"} & only:
        from benchmarks import serving_scaling
        sections.append(("serving_scaling", serving_scaling.run))
    if only is None or "roofline" in only:
        from benchmarks import roofline
        sections.append(("roofline", roofline.run))

    failed = []
    for name, fn in sections:
        try:
            fn()
        except Exception:  # noqa
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED sections: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
