"""Tokens sampled by all rows in the window over the window's seconds."""


def read(run):
    tokens = sum(run.occupancy.get(s, 0) for s in run.steps)
    return tokens / (run.t1 - run.t0) if run.t1 > run.t0 else None
