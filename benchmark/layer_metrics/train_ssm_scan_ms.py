"""Device time per step under the program's ``ssm/scan`` scope: a scan layer's
``x_proj`` and ``dt_proj`` and the selective scan itself (the ``ssm_scan_fwd`` /
``ssm_scan_bwd`` launches and what XLA lays around them: the stacked ``B^T, C^T``
operand, the sums of the partial gradients), forward, recompute and backward,
first chip, in ms; it is part of ``train_ssm_ms``. None where the program names
no such scope (benchmark/trace/paths.py): every other cell, and the parent of
PR 57."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "ssm", "scan")
