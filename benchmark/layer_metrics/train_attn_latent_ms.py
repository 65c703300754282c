"""Device time per step under the program's ``attn/latent`` scope: latent
attention's down-projection to the compressed key-value vector, its norm
and the up-projection to every head's keys and values (forward, recompute
and backward), first chip, in ms; it is part of ``train_attn_ms``. None
where the program names no such scope (benchmark/trace/paths.py)."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "latent")
