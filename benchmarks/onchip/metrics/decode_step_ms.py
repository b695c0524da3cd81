"""Mean time of the engine's decode step in the window: its own telemetry's
wall time (host clock around the step, ended by ``block_until_ready``) over
the steps it recorded."""


def read(run):
    n = run.counters["decode_steps"]
    if n <= 0:
        return None
    return 1e3 * run.counters["decode_wall_s"] / n
