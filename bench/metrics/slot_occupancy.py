"""Mean share of the scheduler's slots that decoded, over the window's
steps (StepRecord.occupancy / slots)."""


def read(run):
    steps = [s for s in run.steps if s in run.occupancy]
    if not steps:
        return None
    return 100.0 * sum(run.occupancy[s] for s in steps) / (len(steps) * run.n_slots)
