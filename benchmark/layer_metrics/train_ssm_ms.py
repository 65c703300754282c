"""Device time per step under the program's ``ssm`` scope: the selective-scan
layers' mixers whole, the in projection and the short convolution (``ssm/in``),
``x_proj``, ``dt_proj`` and the scan's launches (``ssm/scan``), the gate and the
out projection (``ssm/out``), forward, recompute and backward, first chip, in
ms. The scopes stand inside ``block``, so this time is part of
``train_unscoped_ms``. None where the program names no such scope
(benchmark/trace/paths.py): every other cell, and the parent of PR 57."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "ssm")
