"""Device time per step under the program's ``gmu`` scope: the gated memory
units of the cross-decoder, ``(silu(u W_1) * m) W_2`` over another layer's scan
output (forward, recompute and backward), first chip, in ms. The scope stands
inside ``block``, so this time is part of ``train_unscoped_ms``. None where the
program names no such scope (benchmark/trace/paths.py): every other cell, and
the parent of PR 57."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "gmu")
