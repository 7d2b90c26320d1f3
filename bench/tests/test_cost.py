"""Needed operations and bytes, checked against hand-worked numbers."""
import json

import pytest

from bench import cost
from bench.tests.conftest import ROOT


def model(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())["model"]


def test_kv_bytes_per_token():
    # 2 (K, V) x layers x KV heads x head_dim x 2 bytes
    assert cost.kv_bytes_per_token(model("qwen2.5-1.5b")) == 2 * 28 * 2 * 128 * 2 == 28 * 1024
    assert cost.kv_bytes_per_token(model("stablelm-3b")) == 2 * 32 * 32 * 80 * 2 == 320 * 1024


def test_weight_bytes():
    # qwen: 151936 x 1536 tied table + 28 x (attention 1536 x (1536 + 2 x 256)
    # + 1536 x 1536 out + qkv bias 2048 + SwiGLU 3 x 1536 x 8960 + 2 norms)
    # + final norm
    q = 151936 * 1536 + 28 * (1536 * 2048 + 1536 * 1536 + 2048
                              + 3 * 1536 * 8960 + 2 * 1536) + 1536
    assert cost.weight_params(model("qwen2.5-1.5b")) == q
    assert cost.weight_bytes(model("qwen2.5-1.5b")) / 1e9 == pytest.approx(3.087, abs=5e-4)
    s = 2 * 50304 * 2560 + 32 * (4 * 2560 * 2560 + 3 * 2560 * 6912
                                 + 2 * 2560) + 2560
    assert cost.weight_params(model("stablelm-3b")) == s
    assert cost.weight_bytes(model("stablelm-3b")) / 1e9 == pytest.approx(5.59, abs=1e-3)


def test_weight_layout_matches_cost():
    from bench import weights

    for name in ("qwen2.5-1.5b", "stablelm-3b"):
        assert weights.n_params(model(name)) == cost.weight_params(model(name))


def test_decode_cost_counts_valid_context_only():
    m = model("qwen2.5-1.5b")
    f1, b1 = cost.decode_cost(m, [100])
    f2, b2 = cost.decode_cost(m, [100, 300])
    # the second row adds its matmuls, its 300-token attention and its KV
    per_row = 2 * (28 * cost.layer_matmul_params(m) + 1536 * 151936)
    assert f2 - f1 == per_row + 4 * 28 * 12 * 128 * 300
    assert b2 - b1 == 300 * 28 * 1024
    assert b1 == cost.weight_bytes(m) + 100 * 28 * 1024
    assert cost.decode_cost(m, []) == (0, 0)


def test_best_of_8_counts_shared_kv_once():
    # stablelm-3b, two Best-of-8 requests behind one 1536-token header:
    # problems of 64 and 96 tokens, 200 and 40 tokens generated per sample
    m = model("stablelm-3b")
    reqs = [(1536 + 64, 8, 200), (1536 + 96, 8, 40)]
    tokens = cost.distinct_kv_tokens(reqs, 1536)
    # the header once, each problem once, each sample's own tokens
    assert tokens == 1536 + 64 + 96 + 8 * 200 + 8 * 40 == 3616
    ctx = [1536 + 64 + 200] * 8 + [1536 + 96 + 40] * 8
    f, b = cost.decode_cost(m, ctx, tokens)
    f_rows, b_rows = cost.decode_cost(m, ctx)
    assert f == f_rows          # every row still attends over its context
    # 3616 tokens x 320 KiB = 1.18 GB, not the 27,776 x 320 KiB = 9.1 GB
    # that reading every row's context would take
    assert b_rows - b == (sum(ctx) - 3616) * 320 * 1024
    assert (b - cost._weights_read(m, 16)) / 1e9 == pytest.approx(1.185, abs=1e-3)
    assert cost.distinct_kv_tokens([], 1536) == 0


def test_prefill_cost_attends_over_the_cached_prefix():
    m = model("stablelm-3b")
    f, b = cost.prefill_cost(m, 2, 10)
    matmul = 2 * 2 * 32 * cost.layer_matmul_params(m) + 2 * 2560 * 50304
    assert f == matmul + 4 * 32 * 32 * 80 * (11 + 12)
    # the untied embedding table is read for its 2 looked-up rows only
    assert b == (cost.weight_bytes(m) - (50304 - 2) * 2560 * 2
                 + 12 * 320 * 1024)


def test_untied_decode_reads_embedding_rows_not_the_table():
    m = model("stablelm-3b")
    _, b = cost.decode_cost(m, [1700] * 3)
    assert b == (cost.weight_bytes(m) - 50304 * 2560 * 2 + 3 * 2560 * 2
                 + 3 * 1700 * 320 * 1024)


def test_least_time_names_its_bound():
    peaks = cost.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    assert cost.least_time(197e12, 1, peaks) == (1.0, "compute")
    assert cost.least_time(1, 819e9, peaks) == (1.0, "memory")
    with pytest.raises(KeyError):
        cost.load_peaks("cpu")


@pytest.mark.parametrize("name, mlp_gb", [("qwen2.5-1.5b", 2.312),
                                          ("stablelm-3b", 3.397)])
def test_dense_scopes_count_their_own_matrices(name, mlp_gb):
    from bench import harness

    fam = harness.load_family("dense")
    m = model(name)
    ctx = [700, 900, 1100]
    kv = cost.distinct_kv_tokens([(600, 3, 200)], 512)
    fa, ba = fam.scope_cost(m, "attention", ctx, kv, {})
    fm, bm = fam.scope_cost(m, "mlp", ctx, kv, {})
    assert bm / 1e9 == pytest.approx(mlp_gb, abs=5e-4)
    assert fm == 2 * 3 * bm // 2
    # attention and mlp, the norms and the tables make the whole step
    d, L, V = m["d_model"], m["n_layers"], m["vocab_size"]
    f, b = fam.decode_cost(m, ctx, kv, {})
    norms = (2 * L * d + d) * 2
    tables = (V * d + (0 if m["tie_embeddings"] else 3 * d)) * 2
    assert ba + bm + norms + tables == b
    assert fa + fm + 3 * 2 * d * V == f
    assert fam.scope_cost(m, "sampler", ctx, kv, {}) is None
    assert fam.scope_cost(m, "mlp", [], 0, {}) is None
    # the dense entry points are the family's
    assert cost.decode_cost(m, ctx, kv) == (f, b)
    assert cost.weight_bytes(m) == fam.weight_bytes(m)
    assert cost.kv_bytes_per_token(m) == fam.kv_bytes_per_token(m)
    assert cost.prefill_cost(m, 5, 7) == fam.prefill_cost(m, 5, 7, {})
