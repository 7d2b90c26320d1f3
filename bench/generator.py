"""Traffic generation from a mix's parameter file and a seed.

One general generator serves every mix: a mix file (``bench/traffic/<mix>.json``)
gives the lengths as clipped lognormal distributions, and the generator turns
them into a fixed *set* of sizes (``sizes`` quantiles, rounded to the
``granularity``).  Every seed serves that same set in the same order, with
its own token ids: a window holds a fraction of a request's lifetime, so a
seed that reordered the sizes would change which requests finish in it and
how much work it does.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

# Independent random streams of one seed.
HEADER, WINDOW, WARMUP, SAMPLER, CHECK = range(5)
ORDER_SEED = 0  # the order of sizes, the same for every seed


def stream(seed: int, which: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), which])


def length_set(dist: dict, n: int) -> np.ndarray:
    """``n`` quantiles of a lognormal with the given median and sigma,
    clipped to [min, max] and rounded to the granularity."""
    g = int(dist.get("granularity", 1))
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = [dist["median"] * math.exp(dist["sigma"] * zi) for zi in z]
    x = np.clip(np.rint(np.asarray(x) / g) * g, dist["min"], dist["max"])
    return x.astype(np.int64)


@dataclass
class Item:
    """One request to submit: its prompt and its output length."""

    req_id: int
    prompt: np.ndarray       # (S,) int32
    max_new_tokens: int
    n_samples: int


class Generator:
    """Requests of one mix for one seed.

    ``header`` is the mix's shared prefix (one per seed), ``problems`` and
    ``outputs`` the length sets in this stream's order.  ``next()`` cycles
    through them, so the k-th request of a stream is the same for every
    run of a seed."""

    def __init__(self, mix: dict, seed: int, which: int, vocab: int,
                 first_id: int = 0):
        self.mix = mix
        self.vocab = vocab
        hrng = stream(seed, HEADER)
        self.header = hrng.integers(0, vocab, mix["header_tokens"],
                                    dtype=np.int64).astype(np.int32)
        self.rng = stream(seed, which)
        n = mix["sizes"]
        order = stream(ORDER_SEED, which)
        self.problems = order.permutation(length_set(mix["problem_tokens"], n))
        self.outputs = order.permutation(length_set(mix["output_tokens"], n))
        self.k = 0
        self.next_id = first_id

    def make(self, problem_len: int, max_new: int) -> Item:
        body = self.rng.integers(0, self.vocab, problem_len,
                                 dtype=np.int64).astype(np.int32)
        item = Item(self.next_id, np.concatenate([self.header, body]),
                    int(max_new), int(self.mix["n_samples"]))
        self.next_id += 1
        return item

    def next(self) -> Item:
        n = len(self.problems)
        item = self.make(int(self.problems[self.k % n]),
                         int(self.outputs[self.k % n]))
        self.k += 1
        return item


def check_mix(mix: dict) -> None:
    """Refuse a mix whose longest request cannot fit the engine, which
    keeps the last position of ``max_len`` as scratch."""
    n = mix["sizes"]
    longest_prompt = mix["header_tokens"] + int(
        length_set(mix["problem_tokens"], n).max())
    longest_output = int(length_set(mix["output_tokens"], n).max())
    if longest_prompt > mix["prompt_len"]:
        raise ValueError(f"prompt_len {mix['prompt_len']} < longest prompt "
                         f"{longest_prompt}")
    if longest_prompt + longest_output > mix["max_len"] - 1:
        raise ValueError(f"max_len {mix['max_len']} cannot hold the longest "
                         f"request")
