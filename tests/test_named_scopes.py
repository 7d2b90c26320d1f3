"""The serving programs name their layers: every compiled op of the paged
decode step and the cached-prefix prefill carries the ``jax.named_scope``
of the layer it belongs to in its HLO ``op_name``, which is what a
profiler trace reports as the op's ``tf_op``."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import api
from repro.serving.engine import DecodeEngine
from repro.serving.sampler import SamplerConfig

SC = SamplerConfig(temperature=0.7, top_p=0.8, top_k=20)


@pytest.fixture(scope="module")
def paged_engine():
    cfg = ModelConfig(name="tiny", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=192, vocab_size=300,
                      dtype="float32", param_dtype="float32", remat="none")
    params = api.get_model(cfg).init_params(jax.random.key(0), cfg)
    return DecodeEngine(params, cfg, max_len=64, paged=True, block_size=8,
                        n_blocks=32)


def _scopes(hlo: str, program: str) -> set:
    """The first name-stack component after the program and its loops."""
    out = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo):
        parts = name.split("/")
        if parts[0] != f"jit({program})":
            continue
        rest = [p for p in parts[1:-1]
                if p not in ("while", "body", "cond", "closed_call")]
        if rest:
            out.add(rest[0])
    return out


def test_paged_decode_step_names_its_layers(paged_engine):
    eng = paged_engine
    st = eng.prefill(jnp.ones((2, 8), jnp.int32), jnp.array([8, 5], jnp.int32))
    st = eng.prepare_decode(st)
    hlo = eng._step_paged_jit.lower(
        eng.params, st, eng.pool.k, eng.pool.v, jax.random.key(1), None,
        sc=SC, stop_ids=(1,)).compile().as_text()
    assert {"attention", "mlp", "lm_head", "sampler"} <= _scopes(
        hlo, "_step_paged_impl")
    # the KV write of the step is attention's too
    assert re.search(r'op_name="[^"]*/attention/kv_write/', hlo)


def test_cached_prefill_names_its_layers(paged_engine):
    eng = paged_engine
    table = jnp.asarray(np.arange(1, 1 + 2 * eng.table_width,
                                  dtype=np.int32).reshape(2, -1) % 32)
    hlo = eng._prefill_cached_jit.lower(
        eng.params, jnp.ones((2, 4), jnp.int32), jnp.array([4, 3], jnp.int32),
        jnp.array([8, 5], jnp.int32), table, eng.pool.k, eng.pool.v,
        prefix_w=1).compile().as_text()
    scopes = _scopes(hlo, "_prefill_cached_impl")
    assert {"attention", "mlp", "lm_head", "kv_write"} <= scopes
    assert "sampler" not in scopes     # prefill returns logits, unsampled


def test_scopes_leave_the_step_bit_identical(paged_engine, monkeypatch):
    """The scopes are metadata: the decode step traced with every
    ``jax.named_scope`` made a no-op computes the same bits."""
    eng = paged_engine
    st = eng.prepare_decode(eng.prefill(jnp.ones((2, 8), jnp.int32),
                                        jnp.array([8, 5], jnp.int32)))
    args = (eng.params, st, eng.pool.k, eng.pool.v, jax.random.key(3), None)
    named = jax.jit(lambda *a: eng._step_paged_impl(*a, sc=SC,
                                                    stop_ids=(1,)))(*args)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    unnamed_step = jax.jit(lambda *a: eng._step_paged_impl(*a, sc=SC,
                                                           stop_ids=(1,)))
    hlo = unnamed_step.lower(*args).compile().as_text()
    assert not re.search(r'op_name="[^"]*/(attention|mlp|sampler)/', hlo)
    unnamed = unnamed_step(*args)
    for a, b in zip(jax.tree.leaves(named), jax.tree.leaves(unnamed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
