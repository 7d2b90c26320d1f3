"""The plain reference against the program's prefill and paged decode, at
smoke sizes on the CPU.

Two smoke models with the shapes of the two configurations: qwen2.5-1.5b's
(grouped-query attention, qkv biases, tied head) and stablelm-3b's
(one KV head per query head, untied head, a head_dim off a power of two).
In float32 the program and the reference compute the same equations in
different orders, so they agree to float32 rounding: 1e-4 of the logits'
spread bounds that at these sizes.  In bf16 every activation is rounded
to 8 significant bits; across 2 layers the logits then move by about a
percent of their spread, so 4% holds them while a wrong rotary
convention, head grouping, bias or norm scale moves them by the whole
spread.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import weights
from bench.reference import model as ref

SMOKE = {
    "qwen-like": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                  "head_dim": 16, "d_ff": 96, "vocab_size": 300,
                  "qkv_bias": True, "tie_embeddings": True,
                  "rope_theta": 1000000.0, "norm_eps": 1e-06},
    "stablelm-like": {"n_layers": 2, "d_model": 80, "n_heads": 4,
                      "n_kv_heads": 4, "head_dim": 20, "d_ff": 112,
                      "vocab_size": 300, "qkv_bias": False,
                      "tie_embeddings": False, "rope_theta": 10000.0,
                      "norm_eps": 1e-05},
}
TOL = {"float32": 1e-4, "bfloat16": 0.04}


def engine(m, dtype, params):
    from repro.configs.base import ModelConfig
    from repro.serving.engine import DecodeEngine

    cfg = ModelConfig(name="smoke", family="transformer", dtype=dtype,
                      param_dtype=dtype, remat="none", **m)
    return DecodeEngine(params, cfg, max_len=64, paged=True, block_size=16,
                        n_blocks=16, eos_id=-1)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(SMOKE))
def test_prefill_and_paged_decode_match_reference(name, dtype):
    m = SMOKE[name]
    params = weights.make(m, seed=11, dtype=dtype)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, m["vocab_size"], 21).astype(np.int32)
    forced = rng.integers(0, m["vocab_size"], 6).astype(np.int32)
    seq = np.concatenate([prompt, forced])

    want = np.asarray(ref.hidden(params, seq, m) @ ref.head(params).T)
    spread = want.max() - want.min()

    eng = engine(m, dtype, params)
    st = eng.prefill(jnp.asarray(prompt)[None], jnp.array([len(prompt)]))
    got = [np.asarray(st.pending_logits[0])]
    for t in forced:
        st = eng.forced_step(st, jnp.array([t]))
        got.append(np.asarray(st.pending_logits[0]))
    got = np.stack(got)
    err = np.abs(got - want[len(prompt) - 1:]).max()
    assert err <= TOL[dtype] * spread, (err, spread)


def test_fp8_control_rounds_every_matrix():
    m = SMOKE["qwen-like"]
    params = weights.make(m, seed=5, dtype="bfloat16")
    seq = np.arange(12, dtype=np.int32)
    a = np.asarray(ref.hidden(params, seq, m, "f32"))
    b = np.asarray(ref.hidden(params, seq, m, "fp8"))
    rel = np.abs(a - b).max() / np.abs(a).max()
    assert 1e-3 < rel < 0.3
    h32, h8 = np.asarray(ref.head(params)), np.asarray(ref.head(params, "fp8"))
    assert 0 < np.abs(h32 - h8).max() < 0.1 * np.abs(h32).max()


def test_gumbel_replay_is_the_samplers_draw():
    """The check redraws the sampler's noise: categorical(key, x) is the
    argmax of x plus gumbel(key, x.shape)."""
    key = np.array([12345, 678], np.uint32)
    x = jax.random.normal(jax.random.key(1), (8, 300))
    want = jax.random.categorical(key, x, axis=-1)
    got = jnp.argmax(x + jax.random.gumbel(key, x.shape, jnp.float32), axis=-1)
    assert (np.asarray(want) == np.asarray(got)).all()


TINY_SEED = 2 ** 31 + 12345
# recorded from the code before the model families existed: the weights
# and the reference's logits of data/tiny.json at TINY_SEED are the same
TINY_WEIGHTS_SHA256 = \
    "6630da323fe3bcd48a6eb3146eecd46a05c87be41e2d539ada09036766fc7ca3"
TINY_LOGITS = {  # [position, token] -> logit, then the sum of |logits|
    "f32": ([0.15592080354690552, -0.1912640929222107, -0.2754184305667877,
             -0.001193277072161436], 1411.7208251953125),
    "fp8": ([0.15916940569877625, -0.18320320546627045, -0.2602327764034271,
             -0.010415240190923214], 1406.80615234375),
}


def tiny_model():
    import json

    from bench.tests.conftest import DATA

    return json.loads((DATA / "tiny.json").read_text())["model"]


@pytest.mark.parametrize("entry", ["family", "dense_entry_point"])
def test_dense_family_weights_are_the_same_from_the_same_seed(entry):
    """The harness's path (the family's layout) and ``weights.make``."""
    import hashlib

    from bench import harness

    m = tiny_model()
    if entry == "family":
        params = weights.from_layout(harness.load_family("dense").layout(m),
                                     TINY_SEED, "bfloat16")
    else:
        params = weights.make(m, TINY_SEED, "bfloat16")
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == TINY_WEIGHTS_SHA256


@pytest.mark.parametrize("precision", ["f32", "fp8"])
def test_dense_family_reference_logits_are_unchanged(precision):
    from bench import harness

    fam = harness.load_family("dense")
    m = tiny_model()
    params = weights.from_layout(fam.layout(m), TINY_SEED, "bfloat16")
    seq = (np.arange(24) * 37 % m["vocab_size"]).astype(np.int32)
    logits = np.asarray(fam.hidden(params, seq, m, precision)
                        @ fam.head(params, precision).T)
    values, total = TINY_LOGITS[precision]
    got = logits[[0, 5, 11, 23], [3, 100, 250, 511]]
    np.testing.assert_allclose(got, values, rtol=1e-6, atol=1e-7)
    assert float(np.abs(logits).sum()) == pytest.approx(total, rel=1e-6)
