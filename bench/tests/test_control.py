"""The comparison that decides ``correct`` fails what it must.

Whole runs of a CPU-sized cell (``data/tiny.json``, qwen2.5-1.5b's shape
at 2 layers of width 64, Best-of-4 behind a shared header), skipping only
the command's look for a chip.  A sound run is correct; the fp8 control
and each fault the cell can have, planted in the timed path, are not.
The exchange between chips does not exist on one chip.
"""
import dataclasses

import pytest

from bench.tests.conftest import run_tiny

SEED = 2 ** 33 + 17


def test_sound_run_is_correct_and_the_fp8_control_is_not():
    result, seen = run_tiny(SEED, control=True)
    n = result["check"]["max_gap_logit"]
    assert result["correct"], result["check"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert seen["tokens_compared"] > 40
    assert seen["control_max_gap_logit"] > n["limit"]
    assert seen["control_correct"] is False
    assert list(result)[-1] == "check"


def _stale_step(orig):
    """The decode step returns its state and the pool as they came in."""

    def step(self, params, state, pool_k, pool_v, rng, row_stops=None, *,
             sc, stop_ids=()):
        _, tok, _, _ = orig(self, params, state, pool_k, pool_v, rng,
                            row_stops, sc=sc, stop_ids=stop_ids)
        st = dataclasses.replace(state, cache_len=state.cache_len + 1,
                                 n_gen=state.n_gen + 1)
        return st, tok, pool_k, pool_v
    return step


def _half_batch(orig):
    """The second half of the batch keeps its old logits."""

    def step(self, params, state, pool_k, pool_v, rng, row_stops=None, *,
             sc, stop_ids=()):
        st, tok, k, v = orig(self, params, state, pool_k, pool_v, rng,
                             row_stops, sc=sc, stop_ids=stop_ids)
        half = state.pending_logits.shape[0] // 2
        pl = st.pending_logits.at[half:].set(state.pending_logits[half:])
        return dataclasses.replace(st, pending_logits=pl), tok, k, v
    return step


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.serving import engine

    if fault == "token_altered":
        sample = engine.sample
        monkeypatch.setattr(engine, "sample", lambda logits, rng, sc: (
            sample(logits, rng, sc) + 1) % logits.shape[-1])
    else:
        wrap = _stale_step if fault == "state_unchanged" else _half_batch
        monkeypatch.setattr(engine.DecodeEngine, "_step_paged_impl",
                            wrap(engine.DecodeEngine._step_paged_impl))
    result, _ = run_tiny(SEED)
    assert not result["correct"], result["check"]
