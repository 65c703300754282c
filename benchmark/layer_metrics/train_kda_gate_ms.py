"""Device time per step under the program's ``attn/kda_gate`` scope: a Kimi Delta
Attention layer's two low-rank gates (the decay's, with its softplus, and the
output's), and ``beta``, forward, recompute and backward, first chip, in ms; it
is part of ``train_attn_ms``. None where the program names no such scope
(benchmark/trace/paths.py): every other cell, and the parent of PR 68."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "kda_gate")
