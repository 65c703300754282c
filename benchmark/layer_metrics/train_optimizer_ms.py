"""Device time per step of the operations under the fused step's
``optimizer`` scope (gradient norm, clip, the update with its Pallas
launches and the reshapes around them, the parameter cast), first chip, in
ms (benchmark/trace/scopes.py)."""

from benchmark.trace import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "optimizer")
