"""Share of the decode program's device time that its needed work would
take at the chip's roofline: over the traced steps, the sum of
max(needed FLOPs / peak, needed bytes / bandwidth) (the model family's
``decode_cost``, KV that rows share counted once) over the device seconds
of the decode program in the trace."""
from bench import cost
from bench.trace_scopes import DECODE


def read(run):
    if run.trace is None:
        return None
    device_s = sum(t for name, t in run.trace["programs"].items()
                   if DECODE.search(name))
    steps = [s for s in run.traced if run.ctx.get(s)]
    if not device_s or not steps:
        return None
    least = sum(cost.least_time(*run.family.decode_cost(
        run.m, run.ctx[s], run.kv_tokens.get(s), run.counters.get(s, {})),
        run.peaks)[0] for s in steps)
    return 100.0 * least / device_s
