"""Median host time of the program's own ``prepare_batch`` span (validate,
curriculum, device placement) in the traced stretch, in ms: read from the
profiler's host plane, where the engine's spans lie since PR 24."""

from benchmark.trace import scopes


def read(ctx):
    return scopes.span_median_ms(ctx, "prepare_batch")
