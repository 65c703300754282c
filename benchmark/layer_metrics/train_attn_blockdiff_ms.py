"""Device time per step under the program's ``attn/core_blockdiff`` scope: the
attention core of a block-diffusion layer over the clean and the noised copy
of a row (the ``flash_*_blockdiff`` launches over the clean keys, the noised
copy's own-block einsum, the merge of the two partial softmaxes and the layout
copies around them; forward, recompute and backward), first chip, in ms; it is
part of ``train_attn_ms``. None where the program names no such scope
(benchmark/trace/paths.py): every other cell, and the parent of PR 39."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "core_blockdiff")
