import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape) cell on the
production mesh and extract the roofline terms from the compiled artifact.

Run:
  PYTHONPATH=src python -m repro.launch.dryrun --all                # 16x16
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod    # 2x16x16
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k

Outputs one JSON per cell under runs/dryrun/ with:
  per-device HLO FLOPs / bytes (cost_analysis), per-device argument/output/
  temp bytes (memory_analysis — proves it fits), and collective bytes by
  primitive parsed from the compiled HLO (feeds EXPERIMENTS.md §Roofline).
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax

from repro.configs.base import ALL_SHAPES, SHAPES_BY_NAME
from repro.configs.registry import ASSIGNED_ARCHS, cells, get_config
from repro.distributed.sharding import ParallelContext
from repro.launch.mesh import make_production_mesh

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_COLL_RE = re.compile(
    r"\b(all-gather(?:-start)?|all-reduce(?:-start)?|reduce-scatter"
    r"|all-to-all|collective-permute(?:-start)?)\b")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "s4": 0.5, "u4": 0.5,
}


def _result_bytes(line: str) -> float:
    """Sum byte sizes of the result shapes on an HLO op line (= per-device
    payload moved by the collective)."""
    lhs = line.split(" = ", 1)
    if len(lhs) != 2:
        return 0.0
    # result type is just before the '=': "  name = bf16[1,2,3]{...} op(...)"
    total = 0.0
    rhs = lhs[1]
    opname = rhs.split("(", 1)[0]
    for dt, dims in _SHAPE_RE.findall(opname):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Collective op counts + per-device bytes by primitive."""
    out = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or " = " not in line:
            continue
        kind = m.group(1).replace("-start", "")
        b = _result_bytes(line)
        d = out.setdefault(kind, {"count": 0, "bytes": 0.0})
        d["count"] += 1
        d["bytes"] += b
    return out


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, par: ParallelContext,
             out_dir: str = "runs/dryrun", mesh_tag: str = "",
             quantized: bool = False) -> dict:
    from repro.configs.inputs import build_cell

    shape = SHAPES_BY_NAME[shape_name]
    t0 = time.time()
    cell = build_cell(arch, shape, par, quantized=quantized)
    lowered = jax.jit(cell.fn, donate_argnums=cell.static.get("donate", ())).lower(*cell.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text())

    rec = {
        "arch": arch,
        "shape": shape_name,
        "quantized": quantized,
        "mesh": mesh_tag,
        "n_devices": par.mesh.size if par.mesh is not None else 1,
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "per_device": {
            "flops": ca.get("flops", 0.0),
            "bytes_accessed": ca.get("bytes accessed", 0.0),
            "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
            "output_bytes": getattr(ma, "output_size_in_bytes", 0),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
        },
        "collectives": colls,
        "param_count": get_config(arch).param_count(),
        "param_count_active": get_config(arch).param_count(active_only=True),
    }
    os.makedirs(out_dir, exist_ok=True)
    qtag = "__q4" if quantized else ""
    fname = f"{arch.replace('.', '_')}__{shape_name}__{mesh_tag}{qtag}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--continue-on-error", action="store_true")
    ap.add_argument("--quantized", action="store_true",
                    help="serve cells with tile-Q4 weights (paper deployment)")
    ap.add_argument("--layout", default="tp", choices=["tp", "fsdp"],
                    help="fsdp: no tensor parallelism (model axis = 2nd "
                         "FSDP axis) — §Perf H2 layout for small models")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    mesh_tag = "2x16x16" if args.multi_pod else "16x16"
    par = ParallelContext(mesh=mesh, tp=(args.layout == "tp"))
    if args.layout != "tp":
        mesh_tag += "_fsdp"
    print(f"[dryrun] mesh {mesh_tag}: {mesh.size} devices, axes "
          f"{mesh.axis_names}", flush=True)

    todo = []
    if args.all:
        for arch, shape, runnable, reason in cells():
            if runnable:
                todo.append((arch, shape.name))
            else:
                print(f"[dryrun] SKIP {arch}:{shape.name} — {reason}",
                      flush=True)
    else:
        assert args.arch and args.shape
        todo = [(args.arch, args.shape)]

    failures = []
    for arch, shape_name in todo:
        print(f"[dryrun] {arch}:{shape_name} ({mesh_tag}) ...",
              end=" ", flush=True)
        try:
            rec = run_cell(arch, shape_name, par, out_dir=args.out,
                           mesh_tag=mesh_tag, quantized=args.quantized)
            pd = rec["per_device"]
            print(f"ok lower={rec['lower_s']}s compile={rec['compile_s']}s "
                  f"flops/dev={pd['flops']:.3e} "
                  f"args/dev={pd['argument_bytes']/2**20:.0f}MiB "
                  f"temp/dev={pd['temp_bytes']/2**20:.0f}MiB", flush=True)
        except Exception as e:  # noqa
            print(f"FAIL: {type(e).__name__}: {e}", flush=True)
            failures.append((arch, shape_name, traceback.format_exc()))
            if not args.continue_on_error:
                traceback.print_exc()
                sys.exit(1)
    if failures:
        print(f"[dryrun] {len(failures)} failures:")
        for a, s, tb in failures:
            print(f"  {a}:{s}\n{tb}")
        sys.exit(1)
    print(f"[dryrun] all {len(todo)} cells compiled OK on {mesh_tag}")


if __name__ == "__main__":
    main()
