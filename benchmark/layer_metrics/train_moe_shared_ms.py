"""Device time per step under the program's ``moe/shared`` scope: the
shared expert every token passes, three dense matmuls and the activation
(forward, recompute and backward; no grouped matmul), first chip, in ms; it
is part of ``train_mlp_ms`` beside the three ``train_moe_*_ms``. None where
the program names no such scope (benchmark/trace/paths.py)."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "moe", "shared")
