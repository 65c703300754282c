"""Device time per step under the program's ``attn/core_mla`` scope: latent
attention's core where the value heads are narrower than the key heads (the
``flash_*_mla`` launches and the layout copies around them; forward, recompute
and backward), first chip, in ms; it is part of ``train_attn_ms``. None where
the program names no such scope (benchmark/trace/paths.py): every other cell,
and the parent of PR 55."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "core_mla")
