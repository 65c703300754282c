"""Device time per step under the program's ``attn/indexer_kl`` scope: the
indexer's objective (its scores again, differentiably, every main head's
probabilities over the selected keys as the KL's target, the KL and its three
gradients, a block of queries at a time), first chip, in ms; it is part of
``train_attn_ms``. None where the program names no such scope
(benchmark/trace/paths.py): every other cell, and the parent of PR 48."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "indexer_kl")
