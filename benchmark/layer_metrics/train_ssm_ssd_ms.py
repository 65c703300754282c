"""Device time per step under the program's ``ssm/ssd`` scope: a Mamba-2 layer's
dt (the softplus), its cumulative log-decays and the chunked state-space-duality
core (the ``ssd_fwd`` / ``ssd_bwd`` launches and what XLA lays around them: the
per-row scalars in both layouts, the sums of the tiles' partial gradients),
forward, recompute and backward, first chip, in ms; it is part of
``train_ssm_ms``. None where the program names no such scope
(benchmark/trace/paths.py): every other cell, and the parent of PR 65."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "ssm", "ssd")
