"""Per-Pallas-kernel shape/dtype sweeps vs the pure-jnp oracles (ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.lut_softmax_attention import build_exp_lut
from repro.kernels.tile_quantize import tile_quantize
from repro.quant import tile_quant as TQ

KEY = jax.random.key(0)


@pytest.mark.parametrize("scheme", ["tile", "common"])
@pytest.mark.parametrize("codebook", ["q4_0", "nf4", "fp4", "iq4_nl"])
@pytest.mark.parametrize("mkn", [(4, 64, 128), (8, 256, 512), (16, 128, 96),
                                 (128, 512, 256)])
def test_lut_dequant_gemm_vs_oracle(scheme, codebook, mkn):
    M, K, N = mkn
    w = jax.random.normal(jax.random.fold_in(KEY, hash((scheme, codebook, M)) %
                                             2**31), (K, N)) * 0.1
    x = jax.random.normal(KEY, (M, K), jnp.float32)
    qw = TQ.quantize(w, scheme=scheme, codebook=codebook)
    y_kernel = ops.lut_dequant_matmul(x, qw)
    y_ref = ref.dequant_matmul_ref(x, qw["codes"], qw["scales"], qw["codebook"])
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lut_dequant_gemm_dtypes(dtype):
    w = jax.random.normal(KEY, (128, 256)) * 0.1
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (8, 128)).astype(dtype)
    qw = TQ.quantize(w, scheme="tile")
    y = ops.lut_dequant_matmul(x, qw)
    assert y.dtype == dtype
    y_ref = ref.dequant_matmul_ref(x.astype(jnp.float32), qw["codes"],
                                   qw["scales"], qw["codebook"])
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("shape", [(2, 128, 128, 4, 2, 64),
                                   (1, 256, 256, 8, 8, 32),
                                   (2, 128, 384, 4, 1, 64)])
@pytest.mark.parametrize("exp_mode", ["lut", "exact"])
def test_lut_attention_vs_oracle(shape, exp_mode):
    B, Sq, Skv, Hq, Hkv, D = shape
    causal = Sq == Skv
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Sq, Hq, D)) * 0.5
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (B, Skv, Hkv, D)) * 0.5
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (B, Skv, Hkv, D)) * 0.5
    o = ops.flash_attention(q, k, v, causal=causal, exp_mode=exp_mode)
    # oracle runs the same fp16 blocked recurrence
    G = Hq // Hkv
    qt = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D).astype(jnp.float16)
    kt = jnp.repeat(k.transpose(0, 2, 1, 3), G, 1).reshape(B * Hq, Skv, D).astype(jnp.float16)
    vt = jnp.repeat(v.transpose(0, 2, 1, 3), G, 1).reshape(B * Hq, Skv, D).astype(jnp.float16)
    o_ref = ref.lut_flash_attention_ref(qt, kt, vt, causal=causal,
                                        exp_mode=exp_mode)
    o_ref = o_ref.reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=2e-3)


def test_lut_attention_accuracy_vs_f32():
    """Paper Table 5: LUT-fp16 attention ≈ f32 attention."""
    B, S, H, D = 2, 256, 4, 64
    q = jax.random.normal(jax.random.fold_in(KEY, 5), (B, S, H, D)) * 0.5
    k = jax.random.normal(jax.random.fold_in(KEY, 6), (B, S, H, D)) * 0.5
    v = jax.random.normal(jax.random.fold_in(KEY, 7), (B, S, H, D)) * 0.5
    o = ops.flash_attention(q, k, v, causal=True, exp_mode="lut")
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    o32 = ref.attention_f32_ref(qt, kt, vt, causal=True)
    o32 = o32.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    err = float(jnp.abs(o.astype(jnp.float32) - o32).max())
    assert err < 2e-2, err


def test_exp_lut_table_exactness():
    """LUT[i] must equal exp of the fp16 decoded from (0x8000 | i)."""
    lut = build_exp_lut()
    idx = jnp.array([0, 1, 1000, 20000, 0x7BFF], jnp.uint32)
    bits = (idx | 0x8000).astype(jnp.uint16)
    x = jax.lax.bitcast_convert_type(bits, jnp.float16).astype(jnp.float32)
    want = jnp.exp(x).astype(jnp.float16)
    got = lut[0, idx]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-3)
    # inf/nan patterns hold 0
    assert float(lut[0, 0x7C00]) == 0.0


@pytest.mark.parametrize("shape", [(2, 14, 4, 2, 4, 6, 32),
                                   (1, 8, 8, 1, 1, 4, 64),
                                   (3, 16, 16, 4, 4, 3, 16)])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (6, 0.0), (0, 30.0)])
def test_paged_attention_kernel_vs_oracle(shape, window, softcap):
    """The block-table-walking Pallas kernel must match the materialized
    gather + masked-softmax oracle for ragged lengths, GQA grouping,
    sliding windows and softcapping alike."""
    B, nb, bs, Hkv, G, W, D = shape
    rng = np.random.default_rng(B * 100 + bs)
    q = jax.random.normal(jax.random.fold_in(KEY, 10), (B, Hkv, G, D)) * 0.5
    k_pool = jax.random.normal(jax.random.fold_in(KEY, 11),
                               (nb, bs, Hkv, D)) * 0.5
    v_pool = jax.random.normal(jax.random.fold_in(KEY, 12),
                               (nb, bs, Hkv, D)) * 0.5
    # ragged rows: each picks distinct non-scratch blocks, padding -> 0;
    # row 0 is pinned completely full (len == W*bs) so the last position
    # of a fully-occupied table is covered, not just interior lengths
    lens = rng.integers(1, W * bs + 1, size=B).astype(np.int32)
    lens[0] = W * bs
    table = np.zeros((B, W), np.int32)
    avail = list(range(1, nb))
    for b in range(B):
        n = -(-int(lens[b]) // bs)
        table[b, :n] = [avail.pop(rng.integers(len(avail))) for _ in range(n)]
    table, lens = jnp.asarray(table), jnp.asarray(lens)
    o = ops.paged_flash_decode(q.reshape(B, 1, Hkv * G, D), k_pool, v_pool,
                               table, lens, window=window, softcap=softcap)
    o_ref = ref.paged_decode_attention_ref(q, k_pool, v_pool, table, lens,
                                           window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(o.reshape(B, Hkv, G, D)),
                               np.asarray(o_ref), atol=2e-5)


def test_paged_attention_matches_xla_layers_path():
    """Kernel == the model's XLA gather fallback (identical semantics on
    the exact arrays the decode path produces)."""
    from repro.models.layers import paged_decode_attention

    B, nb, bs, Hkv, G, W, D = 2, 10, 4, 2, 3, 5, 16
    q = jax.random.normal(jax.random.fold_in(KEY, 20), (B, 1, Hkv * G, D))
    k_pool = jax.random.normal(jax.random.fold_in(KEY, 21), (nb, bs, Hkv, D))
    v_pool = jax.random.normal(jax.random.fold_in(KEY, 22), (nb, bs, Hkv, D))
    table = jnp.array([[1, 2, 3, 0, 0], [4, 5, 6, 7, 8]], jnp.int32)
    lens = jnp.array([9, 18], jnp.int32)
    o_kernel = ops.paged_flash_decode(q, k_pool, v_pool, table, lens)
    o_xla = paged_decode_attention(q, k_pool, v_pool, table=table,
                                   cache_len=lens)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_xla),
                               atol=2e-5)


@pytest.mark.parametrize("mode", ["q8", "q4"])
@pytest.mark.parametrize("shape", [(2, 14, 4, 2, 4, 6, 32),
                                   (1, 8, 8, 1, 1, 4, 64),
                                   (3, 16, 16, 4, 4, 3, 16)])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (6, 30.0)])
def test_quant_paged_attention_kernel_vs_oracle(mode, shape, window,
                                                softcap):
    """The fused-dequant table walk must match dequantize-the-whole-pool
    + the fp paged oracle — Q8 scale-multiply and packed-Q4 codebook
    lookups alike, incl. the gr=1 odd-head-count scale geometry."""
    from repro.serving import kv_quant as KQ

    B, nb, bs, Hkv, G, W, D = shape
    rng = np.random.default_rng(B * 100 + bs)
    q = jax.random.normal(jax.random.fold_in(KEY, 30), (B, Hkv, G, D)) * 0.5
    gr, gc = KQ.kv_tile_geometry(Hkv, D)
    pools = []
    for i in (31, 32):
        fp = jax.random.normal(jax.random.fold_in(KEY, i),
                               (nb, bs, Hkv, D)) * 0.5
        pools.append(KQ.quantize_kv(fp, mode=mode, gr=gr, gc=gc))
    k_pool, v_pool = pools
    lens = rng.integers(1, W * bs + 1, size=B).astype(np.int32)
    lens[0] = W * bs
    table = np.zeros((B, W), np.int32)
    avail = list(range(1, nb))
    for b in range(B):
        n = -(-int(lens[b]) // bs)
        table[b, :n] = [avail.pop(rng.integers(len(avail))) for _ in range(n)]
    table, lens = jnp.asarray(table), jnp.asarray(lens)
    o = ops.paged_flash_decode(q.reshape(B, 1, Hkv * G, D), k_pool, v_pool,
                               table, lens, window=window, softcap=softcap)
    o_ref = ref.quant_paged_decode_attention_ref(
        q, k_pool, v_pool, table, lens, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(o.reshape(B, Hkv, G, D)),
                               np.asarray(o_ref), atol=2e-5)


def test_quant_paged_attention_matches_xla_layers_path():
    """Fused-dequant kernel == the model's XLA gather-then-dequant
    fallback on the exact leaf dicts the quantized decode path carries."""
    from repro.models.layers import paged_decode_attention
    from repro.serving import kv_quant as KQ

    B, nb, bs, Hkv, G, W, D = 2, 10, 4, 2, 3, 5, 16
    q = jax.random.normal(jax.random.fold_in(KEY, 40), (B, 1, Hkv * G, D))
    k_pool = KQ.quantize_kv(
        jax.random.normal(jax.random.fold_in(KEY, 41), (nb, bs, Hkv, D)),
        mode="q8", gr=2, gc=16)
    v_pool = KQ.quantize_kv(
        jax.random.normal(jax.random.fold_in(KEY, 42), (nb, bs, Hkv, D)),
        mode="q8", gr=2, gc=16)
    table = jnp.array([[1, 2, 3, 0, 0], [4, 5, 6, 7, 8]], jnp.int32)
    lens = jnp.array([9, 18], jnp.int32)
    o_kernel = ops.paged_flash_decode(q, k_pool, v_pool, table, lens)
    o_xla = paged_decode_attention(q, k_pool, v_pool, table=table,
                                   cache_len=lens)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_xla),
                               atol=2e-5)


@pytest.mark.parametrize("kn", [(128, 256), (256, 512), (512, 1024)])
def test_tile_quantize_kernel_vs_oracle(kn):
    K, N = kn
    w = jax.random.normal(KEY, (K, N)) * 0.2
    ck, sk = tile_quantize(w)
    cr, sr = ref.tile_quantize_ref(w)
    assert (np.asarray(ck) == np.asarray(cr)).mean() > 0.999  # rounding ties
    np.testing.assert_allclose(np.asarray(sk, np.float32),
                               np.asarray(sr, np.float32), rtol=1e-3)


# ---------------------------------------------------------------------------
# LUT-fused paged decode (exp_mode='lut')
# ---------------------------------------------------------------------------


def _paged_case(shape, seed_base, pool_kind):
    """Build a ragged paged-decode case; pools fp or tile-quantized."""
    from repro.serving import kv_quant as KQ

    B, nb, bs, Hkv, G, W, D = shape
    rng = np.random.default_rng(B * 100 + bs)
    q = jax.random.normal(jax.random.fold_in(KEY, seed_base),
                          (B, Hkv, G, D)) * 0.5
    pools = []
    for i in (seed_base + 1, seed_base + 2):
        fp = jax.random.normal(jax.random.fold_in(KEY, i),
                               (nb, bs, Hkv, D)) * 0.5
        if pool_kind == "fp":
            pools.append(fp)
        else:
            gr, gc = KQ.kv_tile_geometry(Hkv, D)
            pools.append(KQ.quantize_kv(fp, mode=pool_kind, gr=gr, gc=gc))
    lens = rng.integers(1, W * bs + 1, size=B).astype(np.int32)
    lens[0] = W * bs
    table = np.zeros((B, W), np.int32)
    avail = list(range(1, nb))
    for b in range(B):
        n = -(-int(lens[b]) // bs)
        table[b, :n] = [avail.pop(rng.integers(len(avail))) for _ in range(n)]
    return q, pools[0], pools[1], jnp.asarray(table), jnp.asarray(lens)


@pytest.mark.parametrize("pool_kind", ["fp", "q8", "q4"])
@pytest.mark.parametrize("shape", [(2, 14, 4, 2, 4, 6, 32),
                                   (1, 8, 8, 1, 1, 4, 64)])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (6, 30.0)])
def test_lut_paged_attention_kernel_vs_oracle(pool_kind, shape, window,
                                              softcap):
    """exp_mode='lut': the fused fp16 LUT-softmax table walk must match
    the blocked fp16 LUT oracle over fp, Q8 and packed-Q4 pools."""
    B, nb, bs, Hkv, G, W, D = shape
    q, k_pool, v_pool, table, lens = _paged_case(shape, 50, pool_kind)
    o = ops.paged_flash_decode(q.reshape(B, 1, Hkv * G, D), k_pool, v_pool,
                               table, lens, window=window, softcap=softcap,
                               exp_mode="lut")
    fn = (ref.lut_paged_decode_attention_ref if pool_kind == "fp"
          else ref.quant_lut_paged_decode_attention_ref)
    o_ref = fn(q, k_pool, v_pool, table, lens, window=window,
               softcap=softcap)
    np.testing.assert_allclose(np.asarray(o.reshape(B, Hkv, G, D)),
                               np.asarray(o_ref), atol=2e-3)


@pytest.mark.parametrize("pool_kind", ["fp", "q8"])
def test_lut_paged_attention_accuracy_vs_f32(pool_kind):
    """Table-5 envelope on the paged decode path: the fused LUT-fp16
    recurrence stays within ~2e-2 of the exact-f32 oracle."""
    shape = (2, 14, 4, 2, 4, 6, 32)
    B, nb, bs, Hkv, G, W, D = shape
    q, k_pool, v_pool, table, lens = _paged_case(shape, 60, pool_kind)
    o = ops.paged_flash_decode(q.reshape(B, 1, Hkv * G, D), k_pool, v_pool,
                               table, lens, exp_mode="lut")
    fn = (ref.paged_decode_attention_ref if pool_kind == "fp"
          else ref.quant_paged_decode_attention_ref)
    o32 = fn(q, k_pool, v_pool, table, lens)
    err = float(jnp.abs(o.reshape(B, Hkv, G, D).astype(jnp.float32)
                        - o32).max())
    assert err < 2e-2, err


@pytest.mark.parametrize("pool_kind", ["fp", "q8", "q4"])
@pytest.mark.parametrize("exp_mode", ["exact", "lut"])
def test_paged_attention_zero_length_row(pool_kind, exp_mode):
    """A slot with lengths[b] == 0 (empty/just-freed row in a live batch)
    must contribute exactly 0 — before the all-masked guard, every block's
    p was exp(s - m) with m == s (all-masked), i.e. 1, so the kernel
    silently averaged garbage pool contents into the output."""
    shape = (3, 14, 4, 2, 2, 4, 32)
    B, nb, bs, Hkv, G, W, D = shape
    q, k_pool, v_pool, table, lens = _paged_case(shape, 70, pool_kind)
    lens = lens.at[1].set(0)
    o = ops.paged_flash_decode(q.reshape(B, 1, Hkv * G, D), k_pool, v_pool,
                               table, lens, exp_mode=exp_mode)
    o = o.reshape(B, Hkv, G, D)
    assert float(jnp.abs(o[1]).max()) == 0.0
    # live rows are untouched by the guard
    if exp_mode == "exact":
        fn = (ref.paged_decode_attention_ref if pool_kind == "fp"
              else ref.quant_paged_decode_attention_ref)
        atol = 2e-5
    else:
        fn = (ref.lut_paged_decode_attention_ref if pool_kind == "fp"
              else ref.quant_lut_paged_decode_attention_ref)
        atol = 2e-3
    o_ref = fn(q, k_pool, v_pool, table, lens)
    for b in (0, 2):
        np.testing.assert_allclose(np.asarray(o[b]), np.asarray(o_ref[b]),
                                   atol=atol)


# ---------------------------------------------------------------------------
# vlut16 gather dequant + plan wrapper
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["q8", "q4"])
def test_lut_dequant_gather_bitwise(mode):
    """The kernel twin of dequantize_kv must be bit-identical on the
    (L, B, P, Hkv, D) prefix-gather views the engine produces."""
    from repro.serving import kv_quant as KQ

    x = jax.random.normal(jax.random.fold_in(KEY, 80), (3, 2, 8, 2, 32))
    qd = KQ.quantize_kv(x, mode=mode, gr=2, gc=16)
    a = KQ.dequantize_kv(qd)
    b = ops.lut_dequant_gather(qd)
    assert a.dtype == b.dtype and a.shape == b.shape
    assert bool(jnp.all(a == b))
    # identity on fp views
    assert ops.lut_dequant_gather(x) is x


def test_f16_bits_decode_exactly_in_kernel_form():
    """fp16 scales reach Mosaic kernels as bf16-typed bits; the in-kernel
    decode must reproduce every fp16 value (normals, subnormals, zeros,
    infinities) bit for bit, and NaN patterns as NaN."""
    from repro.kernels.common import as_bf16_bits, f16_bits_to_f32

    bits = jnp.arange(2**16, dtype=jnp.uint32).astype(jnp.uint16)
    x16 = jax.lax.bitcast_convert_type(bits, jnp.float16)
    got = np.asarray(f16_bits_to_f32(as_bf16_bits(x16)))
    want = np.asarray(x16.astype(jnp.float32))
    nan = np.isnan(want)
    assert (np.isnan(got) == nan).all()
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


def test_plan_lut_dequant_matmul_hoists_python_work(monkeypatch):
    """plan() must match the one-shot wrapper bitwise and resolve scheme
    inference once, not per call."""
    w = jax.random.normal(KEY, (128, 256)) * 0.1
    x = jax.random.normal(jax.random.fold_in(KEY, 81), (8, 128))
    qw = TQ.quantize(w, scheme="tile")
    y0 = ops.lut_dequant_matmul(x, qw)

    calls = []
    orig = TQ.infer_scheme
    monkeypatch.setattr(ops.TQ, "infer_scheme",
                        lambda *a, **k: calls.append(1) or orig(*a, **k))
    run = ops.plan_lut_dequant_matmul(qw, m=8)
    for _ in range(3):
        y1 = run(x)
    assert len(calls) == 1
    assert bool(jnp.all(y0 == y1))


# ---------------------------------------------------------------------------
# Block-size contracts: ValueErrors instead of silent truncation/asserts
# ---------------------------------------------------------------------------


def test_block_candidates_obey_mosaic_rule():
    """Candidates are divisors that are multiples of the alignment, largest
    first; an axis with none takes its whole dim (always legal), never a
    block that would truncate the BlockSpec."""
    from repro.kernels import autotune as AT

    assert AT.block_candidates(1024, 256, 128) == [256, 128]
    assert AT.block_candidates(8960, 256, 256) == [256]
    assert AT.block_candidates(96, 256, 256) == [96]      # whole dim
    assert AT.block_candidates(24, 256, 16) == [24]       # whole dim
    assert AT.block_candidates(7, 4) == [1]


def test_lut_attention_rejects_indivisible_blocks():
    q = jnp.zeros((1, 12, 64), jnp.float16)
    lut = build_exp_lut()
    from repro.kernels.lut_softmax_attention import lut_softmax_attention

    with pytest.raises(ValueError, match=r"Sq=12 with bq=8"):
        lut_softmax_attention(q, q, q, lut, bq=8, bkv=4)


def test_lut_dequant_gemm_rejects_bad_shapes():
    from repro.kernels.lut_dequant_gemm import lut_dequant_gemm

    w = jax.random.normal(KEY, (96, 64)) * 0.1
    qw = TQ.quantize(w, scheme="tile")
    x = jnp.zeros((4, 100), jnp.float32)
    with pytest.raises(ValueError, match="96 rows but x has K=100"):
        lut_dequant_gemm(x, qw["codes"], qw["scales"], qw["codebook"])
    x = jnp.zeros((4, 96), jnp.float32)
    with pytest.raises(ValueError, match="must divide the GEMM shape"):
        lut_dequant_gemm(x, qw["codes"], qw["scales"], qw["codebook"],
                         bk=36)


# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------


def test_autotune_defaults_match_legacy_choices(tmp_path, monkeypatch):
    """With no measured cache, the analytic roofline reproduces the
    fixed-target picks wherever Mosaic's block rule allows them (the
    common scheme's (bk/32, bn) scales block needs bk % 256) —
    autotuning must not churn kernel behavior.  (Point the cache at an
    empty path: a benchmark run in this checkout may have recorded
    measured winners in runs/autotune.json, and those legitimately
    override the analytic choice this test pins.)"""
    from repro.kernels import autotune as AT

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "empty.json"))
    AT.reset()
    assert AT.gemm_blocks(16, 1024, 1024, scheme="tile") == (16, 256, 128)
    assert AT.gemm_blocks(8, 256, 512, scheme="common") == (8, 256, 256)
    # qwen2.5-1.5b's FFN up-projection: Mosaic-legal tiles, not full width
    assert AT.gemm_blocks(8, 1536, 8960, scheme="tile") == (8, 256, 128)
    assert AT.attn_blocks(8, 256, 256, 64) == (128, 128)
    assert AT.quantize_blocks(512, 1024) == (128, 256)
    assert AT.dequant_rows(48, 2, 32, "q8") == 48
    AT.reset()  # drop memo entries computed under the empty cache


def test_autotune_cache_roundtrip(tmp_path, monkeypatch):
    """A measured entry recorded by the benchmark overrides the analytic
    choice; REPRO_AUTOTUNE=0 restores the legacy path."""
    from repro.kernels import autotune as AT

    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    AT.reset()
    key = AT.gemm_key(16, 1024, 1024, "tile", 32)
    AT.record(key, (16, 64, 32), 12.5)
    assert AT.gemm_blocks(16, 1024, 1024, scheme="tile") == (16, 64, 32)
    # survives a fresh load
    AT.reset()
    assert AT.gemm_blocks(16, 1024, 1024, scheme="tile") == (16, 64, 32)
    # kill switch: measured entry ignored, legacy picks
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    AT.reset()
    assert AT.gemm_blocks(16, 1024, 1024, scheme="tile") == (16, 256, 128)
    monkeypatch.delenv("REPRO_AUTOTUNE")
    AT.reset()


def test_autotune_ignores_entries_from_other_devices(tmp_path, monkeypatch):
    """A measured winner applies only on the device kind it was timed on:
    a CPU-timed entry must not steer a TPU run, nor the reverse."""
    import json

    from repro.kernels import autotune as AT

    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    AT.reset()
    key = AT.gemm_key(16, 1024, 1024, "tile", 32)
    AT.record(key, (16, 512, 256), 3.0)
    entry = json.loads(cache.read_text())["entries"][key]
    assert entry["device_kind"] == AT.device_kind()
    entry["device_kind"] = "not " + entry["device_kind"]  # another device
    cache.write_text(json.dumps({"version": 1, "entries": {key: entry}}))
    AT.reset()
    assert AT.gemm_blocks(16, 1024, 1024, scheme="tile") == (16, 256, 128)
    AT.reset()
