"""Device time per step of the operations under the program's ``attn`` scope
(projections, scores-softmax-values, output projection; forward, recompute
and backward), first chip, in ms. None where the program names nothing
(benchmark/trace/scopes.py)."""

from benchmark.trace import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "attn")
