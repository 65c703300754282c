"""Device time per step under the program's ``attn/gate`` scope: the
attention output's sigmoid gate (its projection of the sub-block's input,
the sigmoid and the product; forward, recompute and backward), first chip,
in ms; it is part of ``train_attn_ms``. None where the program names no
such scope (benchmark/trace/paths.py)."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "gate")
