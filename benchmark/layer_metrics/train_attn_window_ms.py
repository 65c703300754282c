"""Device time per step under the program's ``attn/core_window`` scope: the
attention core of the layers whose kind is a sliding window (the flash
kernels cut to the window and the layout copies around them; forward,
recompute and backward), first chip, in ms; it is part of ``train_attn_ms``.
The layers that attend over the whole row run under ``attn/core``
(``train_attn_full_ms``). None where the program names no such scope
(benchmark/trace/paths.py)."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "core_window")
