"""Device time per step under the program's ``attn/core_dsa`` scope: the
attention core over each query's selected keys (the ``flash_*_dsa`` launches,
whose tiles read the selection's operand, the operand's transpose for the
backward and the layout copies around them; forward, recompute and backward),
first chip, in ms; it is part of ``train_attn_ms``. None where the program names
no such scope (benchmark/trace/paths.py): every other cell, and the parent of
PR 48."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "core_dsa")
