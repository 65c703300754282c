"""Device time per step under the program's ``attn/select`` scope: the
selection of each query's keys from the indexer's scores (the visibility rule,
the threshold of a row's k-th largest score, the picked pairs as the int8
operand the attention reads), first chip, in ms; it is part of
``train_attn_ms``. None where the program names no such scope
(benchmark/trace/paths.py): every other cell, and the parent of PR 48."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "select")
