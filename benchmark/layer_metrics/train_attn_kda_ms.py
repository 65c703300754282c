"""Device time per step under the program's ``attn/core_kda`` scope: a Kimi Delta
Attention layer's chunked recurrence (the ``kda_fwd`` / ``kda_bwd`` launches, which
normalise q and k a head and make ``beta k``, ``beta v``, the running sums of the
log-decays inside a chunk and the triangular inverse themselves, and what XLA
lays around them: the rows padded to whole spans and the resets' count a chunk
in its two layouts), forward, recompute and backward, first chip, in ms; it is part of
``train_attn_ms``. None where the program names no such scope
(benchmark/trace/paths.py): every other cell, and the parent of PR 68."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "core_kda")
