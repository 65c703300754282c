"""Device time per step under the program's ``mtp`` scope: the
multi-token-prediction module whole (the merge of the final stream with the
next token's embedding, its block, its norm, the shared head and its loss;
forward, recompute and backward), first chip, in ms. It cuts ACROSS the
five classes: inside it the usual ``attn``, ``mlp``, ``head`` and ``loss``
scopes file its time under their classes, the merge under unscoped. None
where the program names no such scope (benchmark/trace/paths.py)."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "mtp")
