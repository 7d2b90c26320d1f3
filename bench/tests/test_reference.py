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
