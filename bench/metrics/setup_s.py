"""Seconds from process start to the window: weights, head encoding or its
cache, warm-up and every compile."""


def read(run):
    return run.setup_s
