"""Device time per step under the program's ``ssm/in`` scope: a scan layer's in
projection and its short causal convolution with the SiLU (a Mamba-2 layer's
over ``Di + 2 G N`` channels, B and C among them: 4,352 at Granite 4.0-H's
widths, XLA's own fusions), forward, recompute and backward, first chip, in ms;
it is part of ``train_ssm_ms``. None where the program names no such scope
(benchmark/trace/paths.py): a cell without scan layers, a parent before PR 57."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "ssm", "in")
