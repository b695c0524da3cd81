"""Bytes the window's forward calls need (the configuration's cost model,
by default ``costs.py``: each prefill or decode call reads the weights
once, the KV at the rows' live lengths, and writes its new KV), over the
window's seconds times the cell's chips times one chip's HBM bandwidth, in
percent."""


def read(run):
    if run.bytes <= 0:
        return None
    return 100.0 * run.bytes / (run.seconds * run.chips
                                * run.peaks["hbm_bytes_per_s"])
