"""Share of the decode program's device time that its needed work would
take at the chip's roofline: over the traced steps, the sum of
max(needed FLOPs / peak, needed bytes / bandwidth) (``bench/cost.py``,
KV that rows share counted once)
over the device seconds of the decode program in the trace."""
import re

from bench import cost

# the scheduler's decode step, DecodeEngine._step_paged_impl (not the
# forced or speculative variants, whose names end alike)
PROGRAM = re.compile(r"(?<![A-Za-z])_step_paged_impl")


def read(run):
    if run.trace is None:
        return None
    device_s = sum(t for name, t in run.trace["programs"].items()
                   if PROGRAM.search(name))
    steps = [s for s in run.traced if run.ctx.get(s)]
    if not device_s or not steps:
        return None
    least = sum(cost.least_time(*cost.decode_cost(run.m, run.ctx[s],
                                                run.kv_tokens.get(s)),
                                run.peaks)[0] for s in steps)
    return 100.0 * least / device_s
