"""Median host time of the program's own ``fused_dispatch`` span (the call
of the fused step until it returns) in the traced stretch, in ms: read
from the profiler's host plane, where the engine's spans lie since PR 24."""

from benchmark.trace import scopes


def read(ctx):
    return scopes.span_median_ms(ctx, "fused_dispatch")
