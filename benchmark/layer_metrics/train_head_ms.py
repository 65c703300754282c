"""Device time per step of the operations under ``embed``, ``head`` and
``loss`` (embeddings, final norm, the vocabulary-wide head and the
cross-entropy), first chip, in ms (benchmark/trace/scopes.py)."""

from benchmark.trace import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "head")
