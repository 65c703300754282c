"""Device time per step under the no-drop expert layer's ``moe/route`` scope
(router matmul, softmax, top-k, both router losses, the sort by expert and
the rows per expert; forward, recompute and backward), first chip, in ms.
None where the program names no such scope (benchmark/trace/moe.py)."""

from benchmark.trace import moe


def read(ctx):
    return moe.ms_per_step(ctx, "route")
