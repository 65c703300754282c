"""Device time per step under the program's ``hc/coeff`` scope: the
hyper-connections' coefficients of every sub-layer, the RMS norm over the n
streams, the product with ``Phi``, the sigmoids and the Sinkhorn rounds
(forward, recompute and backward), first chip, in ms; it is part of
``train_hc_ms``. None where the program names no such scope
(benchmark/trace/paths.py): every other cell, and the parent of PR 55."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "hc", "coeff")
