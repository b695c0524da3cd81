"""90th percentile, over every request due in the window, of the time from
its due time to its admission into a slot (host clock, read after the step
that admitted it). A request still queued when the window closes counts the
window's remaining time."""
import numpy as np


def read(run):
    waits = []
    for tr in run.tracks:
        if not tr.in_window:
            continue
        t = tr.admit if tr.admit is not None else run.seconds
        waits.append(min(t, run.seconds) - tr.due)
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits) * 1e3, 90))
