"""Whole scheduler steps' share of the chip's bf16 peak: needed FLOPs of
every prefill and decode in the traced steps (the model family's counts)
over the summed ``step`` span time times the peak."""


def read(run):
    if run.traced is None:
        return None
    steps = [s for s in run.traced if s in run.step_spans]
    if not steps:
        return None
    fam = run.family
    flops = 0
    for s in steps:
        counters = run.counters.get(s, {})
        flops += fam.decode_cost(run.m, run.ctx.get(s, []), None, counters)[0]
        flops += sum(fam.prefill_cost(run.m, suf, cac, counters)[0]
                     for suf, cac in run.prefills.get(s, []))
    wall = sum(run.step_spans[s][1] - run.step_spans[s][0] for s in steps)
    return 100.0 * flops / (wall * run.peaks["bf16_flops_per_s"])
