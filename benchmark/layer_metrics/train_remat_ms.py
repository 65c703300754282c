"""Device time per step of the operations the backward pass recomputes
(``rematted_computation`` in the name stack: what ``jax.checkpoint`` costs),
first chip, in ms. Cuts across attn, mlp and head
(benchmark/trace/scopes.py)."""

from benchmark.trace import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "remat")
