"""Paged decode attention's share of its HBM roofline: the least bytes the
traced decode steps' attention moves (the cost model's
``paged_attention_bytes``: each row's live KV read once, queries and
outputs) over the self time of the ``paged_attention`` scope's operations
in ``jit_decode_step`` (profiler trace, summed over the device planes)
times one chip's HBM bandwidth, in percent. It is bound by bytes: the
attention's FLOPs take far less time at the chip's peak."""

SCOPE = "jit_decode_step/paged_attention"


def read(run):
    if not run.trace or run.traced_attn_bytes <= 0:
        return None
    seconds = dict(run.trace.get("scopes", [])).get(SCOPE, 0.0) \
        * run.trace["devices"]
    if seconds <= 0:
        return None
    return 100.0 * run.traced_attn_bytes / (
        seconds * run.peaks["hbm_bytes_per_s"])
