"""Device time per step under the program's ``ssm/out`` scope: a scan layer's
gate and its out projection (a Mamba-2 layer's gate BEFORE a float32 norm over
all ``Di`` channels, 4,096 at Granite 4.0-H's widths: XLA's own fusions),
forward, recompute and backward, first chip, in ms. With ``train_ssm_in_ms`` and
``train_ssm_ssd_ms`` it adds up to the scan layers' mixers whole (what
``train_ssm_ms`` reads in the cell that lists it). None where the program names
no such scope (benchmark/trace/paths.py): a cell without scan layers, a parent
before PR 57."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "ssm", "out")
