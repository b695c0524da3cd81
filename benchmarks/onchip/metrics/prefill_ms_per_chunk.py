"""Device time of one prefill chunk: the traced window's device seconds of
the program ``jit_prefill_chunk`` over its runs there (profiler trace), in
ms."""


def read(run):
    if not run.trace:
        return None
    for name, seconds, runs in run.trace.get("programs", []):
        if name == "jit_prefill_chunk" and runs > 0:
            return 1e3 * seconds / runs
    return None
