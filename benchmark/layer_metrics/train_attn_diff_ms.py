"""Device time per step under the program's ``attn/core_diff`` scope:
differential attention's core, the two two-width launches a layer
(``flash_*_diff``, ``flash_*_diff_window``), the heads' pairing by parity and
the layout copies around the launches, the subtraction under lambda and its
norm (forward, recompute and backward), first chip, in ms; it is part of
``train_attn_ms``. None where the program names no such scope
(benchmark/trace/paths.py): every other cell, and the parent of PR 57."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "core_diff")
