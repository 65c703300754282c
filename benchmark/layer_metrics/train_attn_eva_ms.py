"""Device time per step under the program's ``attn/core_eva`` scope: the
attention core of an EVA layer (the ``flash_*_eva_local`` launches over a
window's exact keys, the ``flash_*_eva_far`` launches over the row's summaries,
the merge of the two partial softmaxes and the layout copies around them;
forward, recompute and backward), first chip, in ms; it is part of
``train_attn_ms``. The scope is found by its own name wherever it stands under
``attn`` (where the program takes a long row's heads in groups, the group
scan's ``while/body/checkpoint`` stands between the two). None where the program names no such scope
(benchmark/trace/paths.py): every other cell, and the parent of PR 42."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "core_eva")
