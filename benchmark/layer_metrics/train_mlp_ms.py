"""Device time per step of the operations under the program's ``mlp`` scope,
first chip, in ms (benchmark/trace/scopes.py)."""

from benchmark.trace import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "mlp")
