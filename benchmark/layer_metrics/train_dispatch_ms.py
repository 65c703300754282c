"""Median host time of one ``engine.train_batch`` call until it returns
(the enqueue), over the window's steps: the training engine's host cost."""

from benchmark.harness import median


def read(ctx):
    t0 = ctx["window"][0]
    d = ctx["spans"].durations("train_batch", since=t0)
    return 1e3 * median(d) if d else None
