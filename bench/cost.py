"""The peaks of a chip, the roofline bound of a piece of work and of a
named scope's traced time, and the distinct KV tokens one decode step
needs.

What a model's work needs in operations and bytes depends on its
architecture and is its family's to count (``bench/families``); the
names below that count it are the dense family's, for callers that
serve a dense model.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench.families.dense import (  # noqa: F401
    BYTES, _weights_read, decode_cost, kv_bytes_per_token,
    layer_matmul_params, prefill_cost, weight_bytes, weight_params)


def load_peaks(device_kind: str, path: Path | None = None) -> dict:
    """Peaks of one chip; a device not in the table is an error."""
    path = path or Path(__file__).with_name("peaks.json")
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def distinct_kv_tokens(requests, shared_prefix: int) -> int:
    """KV tokens one decode step needs to read or write once each.

    ``requests`` holds ``(prompt_len, rows, generated)`` per live request:
    its rows share the prompt, and each row has ``generated`` tokens of its
    own, the new one included.  Every prompt starts with the same
    ``shared_prefix`` tokens, which are counted once for the step."""
    requests = list(requests)
    if not requests:
        return 0
    return shared_prefix + sum(plen - shared_prefix + rows * gen
                               for plen, rows, gen in requests)


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Roofline bound of one piece of work and which side bounds it."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def scope_roofline(run, scope: str, seconds: float):
    """Percent of ``seconds``, a named scope's device time in the traced
    decode steps, that the scope's needed work takes at the roofline: the
    least time of each step's need (the run's family's ``scope_cost``)
    summed.  None where the family does not count the scope, or no step
    or time is there to compare."""
    steps = [s for s in (run.traced or ()) if run.ctx.get(s)]
    if not seconds or not steps:
        return None
    need = [run.family.scope_cost(run.m, scope, run.ctx[s],
                                  run.kv_tokens.get(s),
                                  run.counters.get(s, {})) for s in steps]
    if any(n is None for n in need):
        return None
    return 100.0 * sum(least_time(f, b, run.peaks)[0]
                       for f, b in need) / seconds
