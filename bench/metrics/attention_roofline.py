"""Share of the decode program's device time in its ``attention`` scope that
the scope's needed work would take at the chip's roofline: over the traced
decode steps, the sum of max(needed FLOPs / peak, needed bytes /
bandwidth) of the model family's ``scope_cost`` (``bench/cost.py``) over
the scope's device seconds in the trace (``bench/trace_scopes.py``)."""
from bench import cost
from bench.trace_scopes import decode_scope_s


def read(run):
    got = decode_scope_s(run, "attention")
    return None if got is None else cost.scope_roofline(run, "attention", got[0])
