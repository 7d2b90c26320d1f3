"""Mean over the window's steps of the scheduler's ``step`` span less its
``sync`` span: host time per step in which the device waits for the host
(``ContinuousScheduler.step_once``; the tracer's spans as ``run.spans``)."""


def read(run):
    step = {sp.step: sp.t1 - sp.t0 for sp in run.spans if sp.name == "step"}
    sync = {sp.step: sp.t1 - sp.t0 for sp in run.spans if sp.name == "sync"}
    steps = [s for s in run.steps if s in step and s in sync]
    if not steps:
        return None
    return 1e3 * sum(step[s] - sync[s] for s in steps) / len(steps)
