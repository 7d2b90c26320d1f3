"""Property-style parity suite locking paged == dense.

Seeded loops (same style as test_quant_properties) drive the dense and the
paged engine through identical prefill/decode/fork/reorder histories and
assert the logits and greedy token streams match: the block pool, block
tables, scatter writes, copy-on-write splits and table gathers must be
*invisible* to the model's numerics.  Masked positions differ physically
(dense zeros vs pool garbage) but are NEG_INF'd out before softmax, so the
paths agree to float tolerance.

The full batch × seq-len × block-size grid is marked ``slow``; a reduced
grid keeps fast CI honest.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.engine import DecodeEngine
from repro.serving.sampler import SamplerConfig

NO_STOP = (9999,)
GREEDY = SamplerConfig(greedy=True)
ATOL = 1e-4


@pytest.fixture(scope="module")
def dense_engine(trained_tiny, tiny_cfg, tok):
    return DecodeEngine(trained_tiny, tiny_cfg, max_len=64,
                        eos_id=tok.eos_id, pad_id=tok.pad_id)


def make_paged(trained_tiny, tiny_cfg, tok, block_size, n_blocks=128):
    return DecodeEngine(trained_tiny, tiny_cfg, max_len=64,
                        eos_id=tok.eos_id, pad_id=tok.pad_id, paged=True,
                        block_size=block_size, n_blocks=n_blocks)


def _draw_prompts(seed, batch, max_prompt=20, vocab=300):
    """Right-padded random token prompts with ragged true lengths."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, max_prompt + 1, size=batch)
    toks = np.zeros((batch, max_prompt), np.int32)
    for i, l in enumerate(lens):
        toks[i, :l] = rng.integers(3, vocab, size=l)
    return jnp.asarray(toks), jnp.asarray(lens.astype(np.int32))


def _assert_run_parity(dense, paged, toks, lens, n_steps, seed):
    sd = dense.prefill(toks, lens)
    sp = paged.prefill(toks, lens)
    np.testing.assert_allclose(np.asarray(sd.pending_logits),
                               np.asarray(sp.pending_logits), atol=ATOL)
    sd, out_d = dense.generate(sd, n_steps, jax.random.key(seed), GREEDY,
                               stop_ids=NO_STOP)
    sp, out_p = paged.generate(sp, n_steps, jax.random.key(seed), GREEDY,
                               stop_ids=NO_STOP)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))
    np.testing.assert_allclose(np.asarray(sd.pending_logits),
                               np.asarray(sp.pending_logits), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(sd.cache_len),
                                  np.asarray(sp.cache_len))
    return sp


def test_prefill_and_decode_parity_small_grid(dense_engine, trained_tiny,
                                              tiny_cfg, tok):
    """Fast subset: every block size, one ragged batch each."""
    for seed, (batch, block_size) in enumerate([(1, 8), (3, 16), (2, 4)]):
        paged = make_paged(trained_tiny, tiny_cfg, tok, block_size)
        toks, lens = _draw_prompts(seed, batch)
        sp = _assert_run_parity(dense_engine, paged, toks, lens,
                                n_steps=10, seed=seed)
        paged.release_rows(sp, list(range(batch)))
        assert paged.pool.blocks_in_use == 0


@pytest.mark.slow
def test_prefill_and_decode_parity_full_grid(dense_engine, trained_tiny,
                                             tiny_cfg, tok):
    """Full batch × seq-len × block-size sweep, incl. decode runs that
    cross several block boundaries."""
    seed = 0
    for block_size in (4, 8, 16, 32):
        paged = make_paged(trained_tiny, tiny_cfg, tok, block_size,
                           n_blocks=256)
        for batch in (1, 2, 5):
            for max_prompt in (5, 13, 24):
                seed += 1
                toks, lens = _draw_prompts(seed, batch,
                                           max_prompt=max_prompt)
                # cross >= 2 block boundaries where the length budget
                # (prompt + steps <= max_len - 1) allows it
                n_steps = min(2 * block_size + 3, 63 - max_prompt)
                sp = _assert_run_parity(dense_engine, paged, toks, lens,
                                        n_steps=n_steps, seed=seed)
                paged.release_rows(sp, list(range(batch)))
                assert paged.pool.blocks_in_use == 0


def test_fork_then_diverge_parity(dense_engine, trained_tiny, tiny_cfg,
                                  tok):
    """Best-of-N shape: one prefill, fork, stochastic divergence.  The
    paged fork shares prompt blocks (CoW on first write); streams must
    match the dense fork's replicated-rows streams token for token."""
    for seed, (n, block_size) in enumerate([(2, 8), (4, 8), (3, 16)]):
        paged = make_paged(trained_tiny, tiny_cfg, tok, block_size)
        toks, lens = _draw_prompts(100 + seed, 1, max_prompt=14)
        sd = dense_engine.fork(dense_engine.prefill(toks, lens), n)
        sp = paged.fork(paged.prefill(toks, lens), n)
        sc = SamplerConfig(temperature=0.8)
        sd, out_d = dense_engine.generate(sd, 12, jax.random.key(seed), sc,
                                          stop_ids=NO_STOP)
        sp, out_p = paged.generate(sp, 12, jax.random.key(seed), sc,
                                   stop_ids=NO_STOP)
        np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))
        np.testing.assert_allclose(np.asarray(sd.pending_logits),
                                   np.asarray(sp.pending_logits), atol=ATOL)
        # samples really diverged (otherwise CoW was never exercised)
        assert len({tuple(r) for r in np.asarray(out_p).tolist()}) > 1
        assert paged.pool.cow_copies > 0
        paged.release_rows(sp, list(range(n)))
        assert paged.pool.blocks_in_use == 0


def test_reorder_after_fork_parity(dense_engine, trained_tiny, tiny_cfg,
                                   tok):
    """The beam-search shape from test_engine_tts: fork maps row i to rows
    [i*n, (i+1)*n); a reorder picking swapped copies must keep decoding
    identically on both layouts."""
    paged = make_paged(trained_tiny, tiny_cfg, tok, block_size=8)
    ids, lens = tok.encode_batch(["Q:1+1=?A:", "Q:2+2=?A:"], 24)
    toks, lens = jnp.asarray(ids), jnp.asarray(lens)
    sd = dense_engine.fork(dense_engine.prefill(toks, lens), 2)
    sp = paged.fork(paged.prefill(toks, lens), 2)
    idx = jnp.array([3, 0])
    pd = dense_engine.reorder(sd, idx)
    pp = paged.reorder(sp, idx)
    np.testing.assert_allclose(np.asarray(pd.pending_logits),
                               np.asarray(pp.pending_logits), atol=ATOL)
    _, out_d = dense_engine.generate(pd, 8, jax.random.key(0), GREEDY,
                                     stop_ids=NO_STOP)
    sp2, out_p = paged.generate(pp, 8, jax.random.key(0), GREEDY,
                                stop_ids=NO_STOP)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))
    paged.release_rows(sp2, [0, 1])
    assert paged.pool.blocks_in_use == 0


def test_merge_rows_parity_into_live_state(dense_engine, trained_tiny,
                                           tiny_cfg, tok):
    """Admission primitive: grafting a prefilled request into a live paged
    state behaves exactly like the dense scatter."""
    paged = make_paged(trained_tiny, tiny_cfg, tok, block_size=8)
    base_ids, base_lens = tok.encode_batch(["Q:1+2=?A:", "Q:3+4=?A:",
                                            "Q:5+6=?A:"], 24)
    new_ids, new_lens = tok.encode_batch(["Q:7+8=?A:"], 24)
    outs = {}
    for name, eng in (("dense", dense_engine), ("paged", paged)):
        base = eng.prefill(jnp.asarray(base_ids), jnp.asarray(base_lens))
        new = eng.prefill(jnp.asarray(new_ids), jnp.asarray(new_lens))
        # paged contract: a merged-over row must be released first (its
        # blocks go back to the pool); mirrored on dense for symmetry
        base = eng.release_rows(base, [1])
        merged = eng.merge_rows(base, new, jnp.array([1]))
        st, out = eng.generate(merged, 6, jax.random.key(0), GREEDY,
                               stop_ids=NO_STOP)
        outs[name] = (np.asarray(out), np.asarray(st.pending_logits))
        if eng.paged:
            eng.release_rows(st, [0, 1, 2])
            assert eng.pool.blocks_in_use == 0
    np.testing.assert_array_equal(outs["dense"][0], outs["paged"][0])
    np.testing.assert_allclose(outs["dense"][1], outs["paged"][1],
                               atol=ATOL)


def test_stop_ids_and_done_freezing_parity(dense_engine, trained_tiny,
                                           tiny_cfg, tok):
    """Stop masking, scratch-slot routing and pending-logit freezing all
    behave identically on the paged path (done rows write into the scratch
    block instead of the dense scratch slot)."""
    paged = make_paged(trained_tiny, tiny_cfg, tok, block_size=8)
    ids, lens = tok.encode_batch(["Q:2+3=?A:", "Q:8+1=?A:"], 24)
    toks, lens = jnp.asarray(ids), jnp.asarray(lens)
    dot = tok.encode(".", bos=False)[0]
    stops = (dense_engine.eos_id, dot)
    sd, out_d = dense_engine.generate(dense_engine.prefill(toks, lens), 16,
                                      jax.random.key(0), GREEDY,
                                      stop_ids=stops)
    sp, out_p = paged.generate(paged.prefill(toks, lens), 16,
                               jax.random.key(0), GREEDY, stop_ids=stops)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))
    np.testing.assert_array_equal(np.asarray(sd.done), np.asarray(sp.done))
    np.testing.assert_allclose(np.asarray(sd.pending_logits),
                               np.asarray(sp.pending_logits), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(sd.cache_len),
                                  np.asarray(sp.cache_len))


def test_forced_step_parity(dense_engine, trained_tiny, tiny_cfg, tok):
    """Teacher forcing on the paged layout: feeding given tokens writes
    their KV into pool blocks and yields the dense engine's logits; the
    rows' blocks all return to the pool on release."""
    paged = make_paged(trained_tiny, tiny_cfg, tok, block_size=4,
                       n_blocks=64)
    toks, lens = _draw_prompts(7, batch=3)
    forced = np.random.default_rng(7).integers(3, 300, size=(3, 6))
    sd, sp = dense_engine.prefill(toks, lens), paged.prefill(toks, lens)
    for t in range(forced.shape[1]):
        sd = dense_engine.forced_step(sd, forced[:, t])
        sp = paged.forced_step(sp, forced[:, t])
        np.testing.assert_allclose(np.asarray(sd.pending_logits),
                                   np.asarray(sp.pending_logits), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(sp.cache_len),
                                  np.asarray(lens) + forced.shape[1])
    paged.release_rows(sp, [0, 1, 2])
    assert paged.pool.blocks_in_use == 0
