"""Compile-only checks: the serving kernels lower and compile for TPU v5e.

Each test compiles one Pallas kernel at qwen2.5-1.5b's widths (2 KV heads
of 6 query heads each, head_dim 128, d_model 1536, d_ff 8960) for one chip
of a described ``v5e:2x2`` topology — no chip is attached, nothing runs.
This catches what interpret mode cannot: Mosaic's block-shape rule, the
vector types it refuses, and VMEM overruns.  The topology is described
inside a fixture, never at import, because only one process at a time may
load the TPU library; the persistent compilation cache is off around these
compiles (an executable compiled for a described chip cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.kernels import autotune as AT
from repro.kernels import lut_dequant_gemm as GEMM
from repro.kernels import lut_softmax_attention as LSA
from repro.kernels import paged_attention as PA
from repro.kernels import tile_quantize as TQK
from repro.serving.kv_quant import kv_tile_geometry

CFG = get_config("qwen2.5-1.5b")
HKV = CFG.n_kv_heads
G = CFG.n_heads // CFG.n_kv_heads
D = CFG.resolved_head_dim()
GR, GC = kv_tile_geometry(HKV, D)
BATCH, BLOCK, WIDTH = 16, 16, 16            # slots, block size, table width
N_BLOCKS = 1 + BATCH * WIDTH


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs;
    return the optimized HLO text."""
    args = [jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s[0], s[1], sharding=sharding), a,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], type))
        for a in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _quant_leaf(mode):
    dc = D // 2 if mode == "q4" else D
    codes = jnp.uint8 if mode == "q4" else jnp.int8
    return {"codes": ((N_BLOCKS, BLOCK, HKV, dc), codes),
            "scales": ((N_BLOCKS, BLOCK, HKV // GR, D // GC), jnp.float16)}


QUERY = ((BATCH, HKV, G, D), jnp.bfloat16)
TABLE = ((BATCH, WIDTH), jnp.int32)
LENGTHS = ((BATCH,), jnp.int32)


def test_paged_attention_compiles(one_chip):
    pool = ((N_BLOCKS, BLOCK, HKV, D), jnp.bfloat16)
    hlo = _compile(lambda q, k, v, t, n: PA.paged_attention(
        q, k, v, t, n, interpret=False), one_chip, QUERY, pool, pool, TABLE,
        LENGTHS)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("mode", ["q8", "q4"])
def test_quant_paged_attention_compiles(one_chip, mode):
    leaf = _quant_leaf(mode)
    hlo = _compile(lambda q, k, v, t, n: PA.quant_paged_attention(
        q, k, v, t, n, interpret=False), one_chip, QUERY, leaf, leaf, TABLE,
        LENGTHS)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("mode", ["q8", "q4"])
def test_lut_dequant_kv_compiles(one_chip, mode):
    # the partial-prefill prefix view: 28 layers x 4 rows x 64 cached slots
    rows = CFG.n_layers * 4 * 64
    leaf = _quant_leaf(mode)
    codes = ((rows, *leaf["codes"][0][-2:]), leaf["codes"][1])
    scales = ((rows, *leaf["scales"][0][-2:]), jnp.float16)
    br = AT.dequant_rows(rows, HKV, D, mode)
    hlo = _compile(lambda c, s, cb: GEMM.lut_dequant_kv(
        c, s, cb, mode=mode, gr=GR, gc=GC, br=br, interpret=False),
        one_chip, codes, scales, ((16,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m,k,n", [
    (16, CFG.d_model, CFG.d_ff),                            # decode, FFN up
    (16, CFG.d_model, HKV * D),                             # decode, K proj
    (512, CFG.n_heads * D, CFG.d_model),                    # prefill, O proj
])
def test_lut_dequant_gemm_compiles(one_chip, m, k, n):
    bm, bn, bk = AT.gemm_blocks(m, k, n, scheme="tile")
    hlo = _compile(lambda x, c, s, cb: GEMM.lut_dequant_gemm(
        x, c, s, cb, scheme="tile", bm=bm, bn=bn, bk=bk, interpret=False),
        one_chip, ((m, k), jnp.bfloat16), ((k, n // 2), jnp.uint8),
        ((k // 2, n // 16), jnp.float16), ((16,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_fp16_kernels_refuse_to_compile():
    """The fp16 / LUT-softmax kernels and tile_quantize raise a clear error
    when compiled instead of running some other path."""
    q16 = jnp.zeros((2, 16, D), jnp.float16)
    lut = LSA.build_exp_lut()
    with pytest.raises(NotImplementedError, match="fp16"):
        LSA.lut_softmax_attention(q16, q16, q16, lut, interpret=False)
    q = jnp.zeros((1, HKV, G, D), jnp.float32)
    pool = jnp.zeros((2, BLOCK, HKV, D), jnp.float32)
    table, lens = jnp.ones((1, 1), jnp.int32), jnp.ones((1,), jnp.int32)
    with pytest.raises(NotImplementedError, match="exp table"):
        PA.paged_attention(q, pool, pool, table, lens, lut, exp_mode="lut",
                           interpret=False)
    with pytest.raises(NotImplementedError, match="tile_quantize"):
        TQK.tile_quantize(jnp.zeros((128, 256)), interpret=False)
