import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# arbitrary, but fixed: the tiny cell reads these peaks on the CPU
TINY_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_parts(**config_overrides):
    from bench import harness

    config = json.loads((DATA / "tiny.json").read_text())
    config.update(config_overrides)
    mix = json.loads((DATA / "tiny-mix.json").read_text())
    bench = harness.load_benchmark()
    return {"cell": {"name": "tiny", "chips": 1}, "config": config, "mix": mix,
            "family": harness.load_family(config.get("family", "dense")),
            "end_to_end": bench["end_to_end"], "per_layer": bench["per_layer"]}


def run_tiny(seed: int, seconds: float = 1.5, parts=None, control=False):
    """One whole run of the tiny cell on the CPU (the harness's look for a
    chip is the command's, which this skips)."""
    from bench import harness

    result, verdict, _ = harness.run_cell(
        "tiny", seed, seconds, False, t_start=time.perf_counter(),
        parts=parts or tiny_parts(), peaks=TINY_PEAKS, control=control)
    return result, verdict


@pytest.fixture
def tiny():
    return tiny_parts()
