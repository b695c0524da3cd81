"""Median, over every request due in the window, of the time from its due
time to its first token (host clock). A request still without a token
when the window closes counts the window's remaining time. The median and
not a tail: below the knee only some tens of requests are due in a
window, too few for ten samples beyond a high percentile."""
import numpy as np


def read(run):
    ttft = []
    for tr in run.tracks:
        if not tr.in_window:
            continue
        first = tr.times[0] if tr.times else None
        if first is None or first > run.seconds:
            first = run.seconds
        ttft.append(first - tr.due)
    if not ttft:
        return None
    return float(np.percentile(np.asarray(ttft) * 1e3, 50))
