"""Device time per step under the program's ``attn/core`` scope in a model
that also has ``attn/core_window``: the attention core of the layers that
attend over the whole row (forward, recompute and backward), first chip, in
ms; it is part of ``train_attn_ms``. A component is matched whole, so
``core_window`` is not ``core``. None where the program names no such scope
(benchmark/trace/paths.py)."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "core")
