"""Device ms per decode step in the program's ``sampler`` scope: self time
of the decode program's operations under it in the traced window over its
runs there (``bench/trace_scopes.py``)."""
from bench.trace_scopes import decode_scope_ms


def read(run):
    return decode_scope_ms(run, "sampler")
