"""Seconds from process start to the first due request: weights, engine
build, warm-up (and compilation where the cache is cold), and set-up the
traffic needs, such as prefilled contexts."""


def read(run):
    return run.setup_s
