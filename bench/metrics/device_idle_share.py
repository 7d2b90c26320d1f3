"""Share of the traced window in which no operation ran on the device
(1 - union of operation intervals / window, from ``bench/trace_reduce``)."""


def read(run):
    if run.trace is None or run.trace["idle_share"] is None:
        return None
    return 100.0 * run.trace["idle_share"]
