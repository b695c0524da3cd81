"""99th percentile of every gap between consecutive output tokens of a
request, both inside the window (host clock). Tokens that one engine step
emits together (an admission's first token and the decode tick after it)
are a gap of zero."""
import numpy as np


def read(run):
    gaps = []
    for tr in run.tracks:
        ts = [t for t in tr.times if run.in_window(t)]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    if not gaps:
        return None
    return float(np.percentile(np.asarray(gaps) * 1e3, 99))
