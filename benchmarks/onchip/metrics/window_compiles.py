"""XLA compilations inside the window (the AOT ledger's post-warm-up
count). Every shape is warmed in set-up, so this should read 0."""


def read(run):
    return float(run.counters["compiles"])
