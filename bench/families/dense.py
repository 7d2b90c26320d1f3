"""The dense decoder-only transformer: attention and a SwiGLU feed-forward
in every layer (the interface is in ``bench/families/__init__.py``).

The reference is ``bench/reference/model.py``.  Counts are of the work the
served tokens *need*, never of how the program lays it out: a decode step
reads every weight once, each distinct live KV token once and writes one
new token's KV per row; masked table columns, padding and idle rows cost
nothing here.  KV that rows share (a Best-of-N request's prompt, a header
every request starts with) is one set of blocks and is counted once per
step.  Matrix multiplications count 2 operations per multiply-add.
Weights and KV are bf16 (2 bytes).
"""
from __future__ import annotations

from bench.reference.model import head, hidden  # noqa: F401

BYTES = 2          # bf16 weights and KV
BIAS_STD = 0.5     # qkv biases: large enough that a dropped bias shows
NORM_STD = 0.1     # norm scales around 1
EMBED_STD = 0.02


def layout(m: dict) -> dict:
    """Leaves are ``(shape, mean, std)``: ``embedding``, stacked
    ``layers``, ``final_norm``, ``lm_head`` when untied."""
    d, L, f, V = m["d_model"], m["n_layers"], m["d_ff"], m["vocab_size"]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]

    def lin(i, o, bias=False):
        leaf = {"w": ((L, i, o), 0.0, i ** -0.5)}
        if bias:
            leaf["b"] = ((L, o), 0.0, BIAS_STD)
        return leaf

    def norm(*lead):
        return {"scale": ((*lead, d), 1.0, NORM_STD)}

    qkv_bias = bool(m.get("qkv_bias", False))
    tree = {
        "embedding": {"table": ((V, d), 0.0, EMBED_STD)},
        "layers": {
            "attn_norm": norm(L),
            "attn": {"wq": lin(d, hq * hd, qkv_bias),
                     "wk": lin(d, hkv * hd, qkv_bias),
                     "wv": lin(d, hkv * hd, qkv_bias),
                     "wo": lin(hq * hd, d)},
            "ffn_norm": norm(L),
            "ffn": {"gate": lin(d, f), "up": lin(d, f), "down": lin(f, d)},
        },
        "final_norm": norm(),
    }
    if not m.get("tie_embeddings", False):
        tree["lm_head"] = {"table": ((V, d), 0.0, EMBED_STD)}
    return tree


def attn_matmul_params(m: dict) -> int:
    d, hq, hkv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d


def mlp_matmul_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def layer_matmul_params(m: dict) -> int:
    return attn_matmul_params(m) + mlp_matmul_params(m)


def qkv_bias_params(m: dict) -> int:
    if not m.get("qkv_bias"):
        return 0
    return (m["n_heads"] + 2 * m["n_kv_heads"]) * m["head_dim"]


def weight_params(m: dict) -> int:
    """Every parameter the model holds (biases and norm scales included)."""
    d, L, V = m["d_model"], m["n_layers"], m["vocab_size"]
    per_layer = layer_matmul_params(m) + qkv_bias_params(m) + 2 * d
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


def decode_cost(m: dict, ctx_lens, kv_tokens=None, counters=None
                ) -> tuple[int, int]:
    """(flops, bytes) of one decode step over the live rows, where
    ``ctx_lens`` holds each row's context length including the new token
    and ``kv_tokens`` the distinct KV tokens those contexts hold
    (``cost.distinct_kv_tokens``; by default none are shared).  Attention
    operations are per row, whatever the rows share.  A dense step reads
    the same weights whatever its counters say."""
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


def prefill_cost(m: dict, suffix: int, cached: int, counters=None
                 ) -> tuple[int, int]:
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


def scope_cost(m: dict, scope: str, ctx_lens, kv_tokens=None, counters=None):
    """(flops, bytes) of one decode step's work in the program's named
    ``scope``, or None for a scope not counted here:

    * ``attention``: the q/k/v/o matrices and qkv biases of every layer,
      each distinct live KV token read once and the new ones written
      (as :func:`decode_cost` counts them), the projections and QK^T, PV
      of every row;
    * ``mlp``: the gate, up and down matrices of every layer, and their
      products over every row.

    The norms lie outside both scopes, and so are left out."""
    ctx_lens = [int(c) for c in ctx_lens]
    if not ctx_lens:
        return None
    L, rows = m["n_layers"], len(ctx_lens)
    if scope == "attention":
        weights = L * (attn_matmul_params(m) + qkv_bias_params(m))
        if kv_tokens is None:
            kv_tokens = sum(ctx_lens)
        flops = (2 * rows * L * attn_matmul_params(m)
                 + sum(_attn_flops(m, c) for c in ctx_lens))
        return flops, (weights * BYTES
                       + int(kv_tokens) * kv_bytes_per_token(m))
    if scope == "mlp":
        weights = L * mlp_matmul_params(m)
        return 2 * rows * weights, weights * BYTES
    return None
