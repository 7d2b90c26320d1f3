"""95th percentile of every gap between consecutive tokens of a request
whose later token falls in the window (all gaps, not medians of chunks)."""
import numpy as np


def read(run):
    gaps = []
    for times in run.token_times.values():
        t = np.asarray(times)
        later = t[1:]
        gaps.extend((later - t[:-1])[(later >= run.t0) & (later <= run.t1)])
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
