"""Whole scheduler steps' share of the chip's bf16 peak: needed FLOPs of
every prefill and decode in the traced steps over the summed ``step`` span
time times the peak."""
from bench import cost


def read(run):
    if run.traced is None:
        return None
    steps = [s for s in run.traced if s in run.step_spans]
    if not steps:
        return None
    flops = sum(cost.decode_cost(run.m, run.ctx.get(s, []))[0]
                + sum(cost.prefill_cost(run.m, suf, cac)[0]
                      for suf, cac in run.prefills.get(s, []))
                for s in steps)
    wall = sum(run.step_spans[s][1] - run.step_spans[s][0] for s in steps)
    return 100.0 * flops / (wall * run.peaks["bf16_flops_per_s"])
