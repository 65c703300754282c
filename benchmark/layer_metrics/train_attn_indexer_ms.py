"""Device time per step under the program's ``attn/indexer`` scope: a learned
selection's indexer (its three projections of the layer's input, the
LayerNorm and rope of its key, and the scores of every visible pair as the
selection reads them, a block of queries at a time; forward and recompute: no
gradient passes here), first chip, in ms; it is part of ``train_attn_ms``. None
where the program names no such scope (benchmark/trace/paths.py): every other
cell, and the parent of PR 48."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "indexer")
