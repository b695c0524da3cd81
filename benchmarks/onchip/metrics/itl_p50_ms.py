"""Median of every gap between consecutive output tokens of a request, both
inside the window (host clock): the pace a user reads at. At the cells'
loads most gaps are one engine step, so it follows the step's time."""
import numpy as np


def read(run):
    gaps = []
    for tr in run.tracks:
        ts = [t for t in tr.times if run.in_window(t)]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    if not gaps:
        return None
    return float(np.percentile(np.asarray(gaps) * 1e3, 50))
