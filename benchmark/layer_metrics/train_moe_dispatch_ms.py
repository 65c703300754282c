"""Device time per step of the no-drop expert layer's row movement: the
``moe/dispatch`` scope (each assignment's token row gathered in expert
order) and the ``moe/combine`` scope (the experts' rows back in token
order, weighted and summed), forward, recompute and backward, first chip,
in ms. Bound by memory bandwidth, no FLOPs to speak of. None where the
program names no such scope (benchmark/trace/moe.py)."""

from benchmark.trace import moe


def read(ctx):
    return moe.ms_per_step(ctx, "dispatch", "combine")
