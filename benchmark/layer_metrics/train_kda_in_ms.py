"""Device time per step under the program's ``attn/kda_in`` scope: a Kimi Delta
Attention layer's three projections, their causal convolutions inside a
document and SiLU (q and k are normalised a head where the core reads them,
inside its launches: ``train_attn_kda_ms``), forward, recompute and backward,
first chip, in ms; it is part of ``train_attn_ms``. ``attn/kda_out``
(the gated norm a head and the out projection) has no reader of its own: it is
left to ``train_attn_ms``. None where the program names no such scope
(benchmark/trace/paths.py): every other cell, and the parent of PR 68."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "attn", "kda_in")
