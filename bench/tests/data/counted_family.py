"""A model family that is the dense one, counting the calls made to it:
a stand-in for a family a configuration brings as a new file."""
from collections import Counter

from bench.families import dense

CALLS = Counter()
NAMES = ("layout", "hidden", "head", "weight_bytes", "kv_bytes_per_token",
         "decode_cost", "prefill_cost", "scope_cost")


def _counted(name):
    fn = getattr(dense, name)

    def inner(*args, **kwargs):
        CALLS[name] += 1
        return fn(*args, **kwargs)
    return inner


globals().update({name: _counted(name) for name in NAMES})
