"""Device time per step under the program's ``hc`` scope: the hyper-connected
residual streams' whole cost, every sub-layer's coefficients (``hc/coeff``), its
weighted read of the n streams (``hc/pre``) and its mix and write-back
(``hc/post``; the stack's last sum of the streams too), forward, recompute and
backward, first chip, in ms. The scopes stand inside ``block``, so this time is
part of ``train_unscoped_ms``. None where the program names no such scope
(benchmark/trace/paths.py): every other cell, and the parent of PR 55."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "hc")
