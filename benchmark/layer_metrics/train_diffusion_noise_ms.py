"""Device time per step under the program's ``diffusion/noise`` scope: the
block-diffusion objective's draws (a block's t, a token's Bernoulli), the
masked copy of the row, the loss weights 1 / t and the step's two statistics,
first chip, in ms. It is counted under ``train_unscoped_ms`` by the five
classes (benchmark/trace/scopes.py knows no class ``diffusion``). None where
the program names no such scope (benchmark/trace/paths.py): every other cell,
and the parent of PR 39."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "diffusion", "noise")
