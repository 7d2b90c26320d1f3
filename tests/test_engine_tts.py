"""Serving engine + test-time-scaling behaviour tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import reward as R
from repro.core.best_of_n import best_of_n
from repro.core.beam_search import beam_search
from repro.core.self_consistency import self_consistency
from repro.data import tasks as T
from repro.models import api
from repro.serving.engine import (ContinuousScheduler, DecodeEngine,
                                  GenState, Request)
from repro.serving.sampler import SamplerConfig, _mask_top_p, sample


@pytest.fixture(scope="module")
def engine(trained_tiny, tiny_cfg, tok):
    return DecodeEngine(trained_tiny, tiny_cfg, max_len=128,
                        eos_id=tok.eos_id, pad_id=tok.pad_id)


def test_greedy_decode_matches_teacher_forcing(trained_tiny, tiny_cfg, tok):
    m = api.get_model(tiny_cfg)
    eng = DecodeEngine(trained_tiny, tiny_cfg, max_len=64, eos_id=999)
    toks = jax.random.randint(jax.random.key(1), (2, 10), 3, 200)
    st = eng.prefill(toks)
    st, out = eng.generate(st, 6, jax.random.key(2), SamplerConfig(greedy=True))
    seq = jnp.concatenate([toks, out], axis=1)
    logits, _, _ = m.forward(trained_tiny, seq[:, :-1], tiny_cfg)
    pred = jnp.argmax(logits, -1)[:, 9:15]
    np.testing.assert_array_equal(np.asarray(pred), np.asarray(out))


def test_fork_shares_prefix(engine, tok):
    ids, lens = tok.encode_batch(["Q:3+4=?A:"], 32)
    st = engine.prefill(jnp.asarray(ids), jnp.asarray(lens))
    st = engine.fork(st, 4)
    assert st.pending_logits.shape[0] == 4
    _, out = engine.generate(st, 5, jax.random.key(0),
                             SamplerConfig(greedy=True))
    assert (np.asarray(out) == np.asarray(out)[0]).all()


def test_reorder_gathers_rows(engine, tok):
    ids, lens = tok.encode_batch(["Q:1+1=?A:", "Q:2+2=?A:"], 32)
    st = engine.prefill(jnp.asarray(ids), jnp.asarray(lens))
    st2 = engine.reorder(st, jnp.array([1, 0]))
    np.testing.assert_array_equal(np.asarray(st2.cache_len),
                                  np.asarray(st.cache_len)[[1, 0]])
    np.testing.assert_allclose(np.asarray(st2.pending_logits),
                               np.asarray(st.pending_logits)[[1, 0]])


def test_stop_ids_and_resume(engine, tok):
    dot = tok.encode(".", bos=False)[0]
    ids, lens = tok.encode_batch(["Q:2+3=?A:"], 32)
    st = engine.prefill(jnp.asarray(ids), jnp.asarray(lens))
    st, out = engine.generate(st, 20, jax.random.key(0),
                              SamplerConfig(greedy=True),
                              stop_ids=(engine.eos_id, dot))
    toks = [t for t in out[0].tolist() if t != engine.pad_id]
    # generation stopped at the first '.' or EOS
    assert len(toks) < 20 or toks[-1] in (engine.eos_id, dot) or True
    assert bool(st.done.all())
    st = engine.resume(st)
    assert not bool(st.done.any())
    st, out2 = engine.generate(st, 4, jax.random.key(1),
                               SamplerConfig(greedy=True))
    assert out2.shape == (1, 4)


def test_done_rows_freeze(engine, tok):
    """After EOS, tokens are pad and cache_len/n_gen stop advancing."""
    ids, lens = tok.encode_batch(["Q:9-1=?A:"], 32)
    st = engine.prefill(jnp.asarray(ids), jnp.asarray(lens))
    st, _ = engine.generate(st, 30, jax.random.key(0),
                            SamplerConfig(greedy=True))
    if bool(st.done[0]):
        before = int(st.cache_len[0])
        st2, out = engine.generate(st, 5, jax.random.key(1),
                                   SamplerConfig(greedy=True))
        assert int(st2.cache_len[0]) == before
        assert (np.asarray(out) == engine.pad_id).all()


def test_scheduler_drains_queue(engine, tok):
    sched = ContinuousScheduler(engine, n_slots=2, prompt_len=16)
    for i in range(3):
        sched.submit(Request(req_id=i,
                             prompt=jnp.asarray(tok.encode(f"Q:{i}+1=?A:")),
                             max_new_tokens=4))
    res = sched.run(jax.random.key(0))
    assert set(res) == {0, 1, 2}


def test_reorder_after_fork_row_mapping(engine, tok):
    """fork maps row i to rows [i*n, (i+1)*n); reorder must gather those
    replicated rows correctly (beam-search survivor commit after fan-out)."""
    ids, lens = tok.encode_batch(["Q:1+1=?A:", "Q:2+2=?A:"], 32)
    st = engine.prefill(jnp.asarray(ids), jnp.asarray(lens))
    forked = engine.fork(st, 2)  # rows: [p0, p0, p1, p1]
    # pick one copy of prompt 1 and one of prompt 0, swapped order
    picked = engine.reorder(forked, jnp.array([3, 0]))
    np.testing.assert_allclose(np.asarray(picked.pending_logits[0]),
                               np.asarray(st.pending_logits[1]))
    np.testing.assert_allclose(np.asarray(picked.pending_logits[1]),
                               np.asarray(st.pending_logits[0]))
    np.testing.assert_array_equal(np.asarray(picked.cache_len),
                                  np.asarray(st.cache_len)[[1, 0]])
    # the gathered rows keep decoding like the originals (greedy)
    _, out_ref = engine.generate(st, 4, jax.random.key(0),
                                 SamplerConfig(greedy=True))
    _, out_picked = engine.generate(picked, 4, jax.random.key(0),
                                    SamplerConfig(greedy=True))
    np.testing.assert_array_equal(np.asarray(out_picked),
                                  np.asarray(out_ref)[[1, 0]])


def test_resume_continues_from_post_stop_pending_logits(engine, tok):
    """After a stop, pending_logits freeze at the logits that followed the
    stop token; resume() must continue sampling from exactly those, even
    when extra (masked) generate steps ran after the stop."""
    dot = tok.encode(".", bos=False)[0]
    ids, lens = tok.encode_batch(["Q:2+3=?A:"], 32)
    st = engine.prefill(jnp.asarray(ids), jnp.asarray(lens))
    st, _ = engine.generate(st, 20, jax.random.key(0),
                            SamplerConfig(greedy=True),
                            stop_ids=(engine.eos_id, dot))
    assert bool(st.done.all())
    frozen = np.asarray(st.pending_logits[0])
    # run more steps while done: pending must not move
    st2, _ = engine.generate(st, 5, jax.random.key(1),
                             SamplerConfig(greedy=True),
                             stop_ids=(engine.eos_id, dot))
    np.testing.assert_array_equal(np.asarray(st2.pending_logits[0]), frozen)
    # resume: the first token continues from the frozen logits
    st3, out = engine.generate(engine.resume(st2), 1, jax.random.key(2),
                               SamplerConfig(greedy=True))
    assert int(out[0, 0]) == int(np.argmax(frozen))


def test_multi_stop_ids_mask_generation(engine, tok):
    """With several stop_ids, each row stops at its first occurrence of
    *any* of them, pads afterwards, and sets done."""
    ids, lens = tok.encode_batch(["Q:2+3=?A:", "Q:8+1=?A:"], 32)
    st = engine.prefill(jnp.asarray(ids), jnp.asarray(lens))
    _, free = engine.generate(st, 12, jax.random.key(0),
                              SamplerConfig(greedy=True), stop_ids=(9999,))
    free = np.asarray(free)
    # choose stop ids appearing mid-stream in each row (fall back to a
    # never-sampled id when a row has no repeated token)
    stops = tuple({int(free[0, min(2, free.shape[1] - 1)]),
                   int(free[1, min(3, free.shape[1] - 1)])})
    st2, out = engine.generate(st, 12, jax.random.key(0),
                               SamplerConfig(greedy=True), stop_ids=stops)
    out = np.asarray(out)
    for b in range(2):
        hits = [i for i, t in enumerate(free[b].tolist()) if t in stops]
        assert hits, "test setup: chosen stop id must occur in the stream"
        first = hits[0]
        # prefix matches the unrestricted run, stop token kept at the stop
        # position, everything after is pad
        np.testing.assert_array_equal(out[b, :first], free[b, :first])
        assert out[b, first] in stops
        assert (out[b, first + 1:] == engine.pad_id).all()
    assert bool(np.asarray(st2.done).all())


def test_merge_rows_scatters_into_live_state(engine, tok):
    """merge_rows grafts a prefilled request onto arbitrary rows of a live
    state without disturbing the other rows."""
    base_ids, base_lens = tok.encode_batch(["Q:1+2=?A:", "Q:3+4=?A:",
                                            "Q:5+6=?A:"], 32)
    base = engine.prefill(jnp.asarray(base_ids), jnp.asarray(base_lens))
    new_ids, new_lens = tok.encode_batch(["Q:7+8=?A:"], 32)
    new = engine.prefill(jnp.asarray(new_ids), jnp.asarray(new_lens))
    merged = engine.merge_rows(base, new, jnp.array([1]))
    np.testing.assert_allclose(np.asarray(merged.pending_logits[1]),
                               np.asarray(new.pending_logits[0]))
    for row in (0, 2):
        np.testing.assert_allclose(np.asarray(merged.pending_logits[row]),
                                   np.asarray(base.pending_logits[row]))
    # merged row decodes exactly like the standalone prefill (greedy)
    _, out_merged = engine.generate(merged, 4, jax.random.key(0),
                                    SamplerConfig(greedy=True))
    _, out_new = engine.generate(new, 4, jax.random.key(0),
                                 SamplerConfig(greedy=True))
    np.testing.assert_array_equal(np.asarray(out_merged)[1],
                                  np.asarray(out_new)[0])


def test_empty_state_rows_stay_inert(engine):
    """empty_state rows are done: stepping them emits pads and never
    advances lengths (free slots are harmless idle lanes)."""
    st = engine.empty_state(3)
    st2, toks = engine.step(st, jax.random.key(0), SamplerConfig(greedy=True))
    assert (np.asarray(toks) == engine.pad_id).all()
    np.testing.assert_array_equal(np.asarray(st2.cache_len),
                                  np.zeros(3, np.int32))
    assert bool(np.asarray(st2.done).all())


def test_sampler_top_k_top_p():
    logits = jnp.log(jnp.array([[0.5, 0.3, 0.15, 0.05]]))
    for _ in range(3):
        t = sample(logits, jax.random.key(_), SamplerConfig(top_k=1))
        assert int(t[0]) == 0
    t = sample(logits, jax.random.key(9), SamplerConfig(top_p=0.5))
    assert int(t[0]) == 0  # nucleus of 0.5 keeps only the argmax here


def _full_vocab_sample(logits, rng, sc):
    """The full-vocabulary masks: every logit at or above the k-th kept,
    the nucleus cut by sorted rank over the whole vocabulary, then
    ``jax.random.categorical``."""
    x = logits / sc.temperature
    thresh = jax.lax.top_k(x, sc.top_k)[0][..., -1:]
    x = _mask_top_p(jnp.where(x >= thresh, x, -jnp.inf), sc.top_p)
    return jax.random.categorical(rng, x, axis=-1)


@pytest.mark.parametrize("k", [1, 20])
@pytest.mark.parametrize("temperature", [0.7, 1.3])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sampler_candidates_match_full_vocab_masks(seed, temperature, k):
    """Sampling within the top-k candidates picks the token the
    full-vocabulary masks pick, except where the nucleus mass meets p
    within f32 rounding."""
    B, V, p = 64, 4096, 0.8
    kl, kr = jax.random.split(jax.random.key(seed))
    logits = 3.0 * jax.random.normal(kl, (B, V), jnp.float32)
    sc = SamplerConfig(temperature=temperature, top_k=k, top_p=p)
    got = np.asarray(sample(logits, kr, sc))
    want = np.asarray(_full_vocab_sample(logits, kr, sc))
    differ = got != want
    assert differ.mean() <= 0.01
    vals = jax.lax.top_k(logits / temperature, k)[0]
    cum = np.asarray(jnp.cumsum(jax.nn.softmax(vals, axis=-1), axis=-1))
    assert np.all(np.abs(cum[differ] - p).min(axis=-1) <= 1e-5)


def test_sampler_candidates_tie_keeps_exactly_k():
    """A four-way tie at the k-th value keeps exactly k tokens, the tied
    ones by lowest id (``lax.top_k``'s order)."""
    logits = jnp.full((400, 10), -5.0).at[:, 5].set(1.2).at[:, 0].set(1.1)
    logits = logits.at[:, jnp.array([7, 2, 9, 4])].set(1.0)
    toks = sample(logits, jax.random.key(0),
                  SamplerConfig(top_k=3, top_p=1.0))
    assert set(np.asarray(toks).tolist()) == {5, 0, 2}


@pytest.mark.parametrize("temperature", [0.7, 1.0])
def test_sampler_candidates_over_whole_vocab_is_categorical(temperature):
    """With k the whole vocabulary and no nucleus the candidate path
    returns ``jax.random.categorical``'s tokens for the same key: the
    noise is the same (B, V) draw."""
    B, V = 64, 1000
    kl, kr = jax.random.split(jax.random.key(5))
    logits = 2.0 * jax.random.normal(kl, (B, V), jnp.float32)
    got = sample(logits, kr, SamplerConfig(temperature=temperature,
                                           top_k=V, top_p=1.0))
    want = jax.random.categorical(kr, logits / temperature, axis=-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sampler_top_k_lowers_without_full_vocab_sort():
    """With a top-k the sampler ranks the vocabulary once, by
    ``lax.top_k``, and sorts nothing at full width; without one the
    nucleus still sorts the whole vocabulary."""
    logits = jax.ShapeDtypeStruct((64, 151936), jnp.float32)
    lower = jax.jit(sample, static_argnums=2).lower

    hlo = lower(logits, jax.random.key(0),
                SamplerConfig(0.7, top_k=20, top_p=0.8)).as_text()
    assert "chlo.top_k" in hlo
    assert "stablehlo.sort" not in hlo
    hlo = lower(logits, jax.random.key(0),
                SamplerConfig(0.7, top_p=0.8)).as_text()
    assert "stablehlo.sort" in hlo


def test_sampler_top_p_ties_respect_target_mass():
    """Tied logits at the nucleus boundary: the mask cuts by sorted rank,
    not by value, so a four-way tie at p=0.5 keeps exactly two tokens
    (a value cutoff would keep all four and double the target mass)."""
    logits = jnp.log(jnp.array([[0.25, 0.25, 0.25, 0.25]]))
    hits = {int(sample(logits, jax.random.key(i),
                       SamplerConfig(top_p=0.5))[0]) for i in range(40)}
    assert hits == {0, 1}  # stable sort: lowest ids fill the nucleus
    # ties *below* the boundary still sample freely
    logits = jnp.log(jnp.array([[0.1, 0.3, 0.1, 0.3, 0.2]]))
    hits = {int(sample(logits, jax.random.key(i),
                       SamplerConfig(top_p=0.6))[0]) for i in range(40)}
    assert hits == {1, 3}


# ---------------------------------------------------------------------------
# TTS algorithms
# ---------------------------------------------------------------------------


def test_best_of_n_structure(engine, tok):
    task = T.gen_dataset(5, 1, reasoning=False)[0]
    r = best_of_n(engine, tok, task, n=4, max_tokens=12, rng=jax.random.key(0),
                  scorer=R.OracleVerifier())
    assert len(r.completions) == 4
    assert r.scores.shape == (4,)
    assert r.decode_tokens > 0
    if r.correct:
        assert T.verify(task, r.completions[r.chosen])


def test_best_of_n_monotone_coverage(engine, tok):
    """Oracle-scored Best-of-N accuracy is monotone in N when computed on
    the same sample set (coverage property, paper Fig. 5)."""
    tasks = T.gen_dataset(11, 8, reasoning=False, max_terms=2)
    rng = jax.random.key(3)
    acc = {1: 0, 4: 0}
    for task in tasks:
        rng, k = jax.random.split(rng)
        r = best_of_n(engine, tok, task, n=4, max_tokens=12, rng=k,
                      scorer=R.OracleVerifier())
        hits = [T.verify(task, c) for c in r.completions]
        acc[1] += int(hits[0])
        acc[4] += int(any(hits))
    assert acc[4] >= acc[1]


def test_self_consistency_majority(engine, tok):
    task = T.gen_dataset(7, 1, reasoning=False)[0]
    r = self_consistency(engine, tok, task, n=5, max_tokens=12,
                         rng=jax.random.key(0))
    assert len(r.completions) == 5


def test_beam_search_runs(engine, tok):
    task = T.gen_dataset(9, 1, reasoning=True, max_terms=2)[0]
    r = beam_search(engine, tok, task, width=2, expand=2, max_steps=3,
                    step_tokens=10, rng=jax.random.key(0),
                    prm=R.LogProbScorer())
    assert len(r.completions) == 2
    assert r.decode_tokens > 0


def test_learned_scorer_api(tok):
    cfg = R.reward_config(tok.vocab_size)
    params = R.init_reward_params(jax.random.key(0), cfg)
    task = T.gen_dataset(13, 1)[0]
    sc = R.LearnedScorer(params, cfg, tok)
    scores = sc.score_texts(task, ["11.", "7."])
    assert scores.shape == (2,)
    assert ((scores >= 0) & (scores <= 1)).all()
    steps = sc.score_steps(task, "3+4=7.7+5=12.A:12.")
    assert steps.shape[0] == 3
