"""Device time per step under the no-drop expert layer's ``moe/experts``
scope (the three grouped matmuls and the activation; forward, recompute
and backward), first chip, in ms. With ``train_moe_route_ms`` and
``train_moe_dispatch_ms`` it adds up to ``train_mlp_ms`` within the fusions
that straddle. None where the program names no such scope
(benchmark/trace/moe.py)."""

from benchmark.trace import moe


def read(ctx):
    return moe.ms_per_step(ctx, "experts")
