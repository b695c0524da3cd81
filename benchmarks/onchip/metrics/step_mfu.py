"""Model FLOPs of the real tokens processed in the window, prefill and
decode (the configuration's cost model, by default ``costs.py``: matmuls at
2 per multiply-add, attention at each token's real context, the head only
where logits are used), over the window's seconds times the cell's chips
times one chip's peak bf16 FLOP/s, in percent."""


def read(run):
    if run.flops <= 0:
        return None
    return 100.0 * run.flops / (run.seconds * run.chips
                                * run.peaks["bf16_flops_per_s"])
