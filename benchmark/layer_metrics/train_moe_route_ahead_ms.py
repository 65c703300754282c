"""Device time per step under the program's ``moe/route/ahead`` scope: what an
expert layer whose router reads the block's un-normed input decides BEFORE the
token mixer runs (the router matmul, the softmax, the top-k, both router
losses and the rows per expert; forward, recompute and backward), first chip,
in ms. It is part of ``train_moe_route_ms``, whose rest (the sort by expert
and its inverse) stays with the rows it moves, after the mixer: beside it this
says how much of the routing stands in front of attention. None where the
program names no such scope (benchmark/trace/paths.py): every cell whose
router reads the FFN's own input, and the parent of PR 62."""

from benchmark.trace import paths


def read(ctx):
    return paths.ms_per_step(ctx, "moe", "route", "ahead")
