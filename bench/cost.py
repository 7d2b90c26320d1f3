"""Operations and bytes that the served work *needs*, from shapes.

Counted from a configuration's sizes and each row's valid context length,
never from how the program lays the work out: a decode step reads every
weight once, each distinct live KV token once and writes one new token's KV
per row; masked table columns, padding and idle rows cost nothing here.
KV that rows share (a Best-of-N request's prompt, a header every request
starts with) is one set of blocks and is counted once per step.  Matrix
multiplications count 2 operations per multiply-add.  Weights and KV are
bf16 (2 bytes).
"""
from __future__ import annotations

import json
from pathlib import Path

BYTES = 2  # bf16 weights and KV


def load_peaks(device_kind: str, path: Path | None = None) -> dict:
    """Peaks of one chip; a device not in the table is an error."""
    path = path or Path(__file__).with_name("peaks.json")
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def layer_matmul_params(m: dict) -> int:
    d, f = m["d_model"], m["d_ff"]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def weight_params(m: dict) -> int:
    """Every parameter the model holds (biases and norm scales included)."""
    d, L, V = m["d_model"], m["n_layers"], m["vocab_size"]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    bias = (hq + 2 * hkv) * hd if m.get("qkv_bias") else 0
    per_layer = layer_matmul_params(m) + bias + 2 * d
    tables = V * d * (1 if m.get("tie_embeddings") else 2)
    return L * per_layer + tables + d


def weight_bytes(m: dict) -> int:
    return weight_params(m) * BYTES


def kv_bytes_per_token(m: dict) -> int:
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * BYTES


def _weights_read(m: dict, rows: int) -> int:
    """Weight bytes one forward over ``rows`` tokens reads: every matrix
    once; of an untied embedding table only the rows it looks up (a tied
    table is read whole by the head)."""
    if m.get("tie_embeddings"):
        return weight_bytes(m)
    return weight_bytes(m) + (rows - m["vocab_size"]) * m["d_model"] * BYTES


def _attn_flops(m: dict, ctx: int) -> int:
    """One query position attending over ``ctx`` keys: QK^T and PV."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * ctx


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


def decode_cost(m: dict, ctx_lens, kv_tokens=None) -> tuple[int, int]:
    """(flops, bytes) of one decode step over the live rows, where
    ``ctx_lens`` holds each row's context length including the new token
    and ``kv_tokens`` the distinct KV tokens those contexts hold
    (:func:`distinct_kv_tokens`; by default none are shared).  Attention
    operations are per row, whatever the rows share."""
    ctx_lens = [int(c) for c in ctx_lens]
    if not ctx_lens:
        return 0, 0
    d, V = m["d_model"], m["vocab_size"]
    per_row = 2 * (m["n_layers"] * layer_matmul_params(m) + d * V)
    flops = len(ctx_lens) * per_row + sum(_attn_flops(m, c) for c in ctx_lens)
    if kv_tokens is None:
        kv_tokens = sum(ctx_lens)
    kv = int(kv_tokens) * kv_bytes_per_token(m)   # read once + new written
    return flops, _weights_read(m, len(ctx_lens)) + kv


def prefill_cost(m: dict, suffix: int, cached: int) -> tuple[int, int]:
    """(flops, bytes) of prefilling ``suffix`` new prompt tokens after
    ``cached`` tokens already in the pool; logits at the last position
    only."""
    if suffix <= 0:
        return 0, 0
    d, V = m["d_model"], m["vocab_size"]
    flops = (2 * suffix * m["n_layers"] * layer_matmul_params(m) + 2 * d * V
             + sum(_attn_flops(m, cached + j + 1) for j in range(suffix)))
    kv = (cached + suffix) * kv_bytes_per_token(m)
    return flops, _weights_read(m, suffix) + kv


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Roofline bound of one piece of work and which side bounds it."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
