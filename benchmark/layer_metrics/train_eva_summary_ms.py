"""Device time per step under the program's ``attn/eva_summaries`` scope: an
EVA layer's one summary key and value a chunk (the in-chunk softmax of k . phi
and the two weighted sums over the whole of k and v; forward, recompute and
backward), first chip, in ms; it is part of ``train_attn_ms``. The scope is found by its own name wherever it stands under
``attn`` (where the program takes a long row's heads in groups, the group
scan's ``while/body/checkpoint`` stands between the two). None where the
program names no such scope (benchmark/trace/paths.py): every other cell, and
the parent of PR 42."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "eva_summaries")
