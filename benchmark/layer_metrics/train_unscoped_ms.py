"""Device time per step of the leaf operations under none of ``attn``,
``mlp``, ``embed``, ``head``, ``loss``, ``optimizer``: what the program's
names do not cover, first chip, in ms (benchmark/trace/scopes.py)."""

from benchmark.trace import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "unscoped")
