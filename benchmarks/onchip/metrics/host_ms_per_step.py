"""Host time of a decode step in the window: the engine's ``serve.step``
seconds less its ``serve.device_wait`` seconds (program spans, host clock),
over the decode calls (``serve.dispatch``'s count), in ms. What the host
adds to each token's gap beyond the device's work."""


def read(run):
    c = run.counters
    calls = c.get("span_n.serve.dispatch", 0)
    if "span_s.serve.step" not in c or "span_s.serve.device_wait" not in c \
            or calls <= 0:
        return None
    return 1e3 * (c["span_s.serve.step"] - c["span_s.serve.device_wait"]) \
        / calls
