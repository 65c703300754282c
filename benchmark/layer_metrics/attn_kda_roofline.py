"""The Kimi Delta Attention core's launches against the chip's peaks, in percent:
the least time the chip could take for what a KDA layer's recurrence HAS to move
and to multiply, over the launches' summed device time.

What it has to move is the reference file's ``kda_bytes_per_row`` (forward: q, k,
v and o of the heads' width, ``g`` in float32, ``beta`` of the heads a token;
backward: those, ``do`` and the gradients) and what it has to multiply
``kda_flops_per_row`` (the decay, ``k^T S``, the rank-1 write and the read-out, 7
an element of ``H x d x d`` forward, 19 backward), whatever implements it and
whatever its chunks are, times the tokens of a step. A launch's least time is
the LARGER of its bytes over the HBM bandwidth and its FLOPs over the matrix
unit's peak, summed over the launches of each kind a step as the trace has them
(a forward that the backward runs again is counted again: it ran). Nothing the
kernel moves or multiplies besides (the spans' entry states, the pairs' products
inside a chunk, the triangular solve) is counted, so the share cannot read over
100 %.

The launches are the trace's ``kda_fwd.N`` / ``kda_bwd.N`` events
(``ops/transformer/pallas_kda.py``). None without a trace, without the
program's step annotations, where the reference has no such functions, or where
no such launch ran (every other cell, a program on the core's XLA route, the
parent of PR 68)."""

import re

from benchmark.peaks import peaks_of
from benchmark.trace import reduce, scopes

FORWARD = re.compile(r"^kda_fwd(\.|$)")
BACKWARD = re.compile(r"^kda_bwd(\.|$)")


def read(ctx):
    if scopes.of_run(ctx) is None:
        return None
    first = sorted(ctx["trace"]["devices"])[0]
    events = reduce.leaf_events(ctx["trace"]["devices"][first])
    fwd = [e for e in events if FORWARD.match(e[0])]
    bwd = [e for e in events if BACKWARD.match(e[0])]
    seconds = sum(e[2] for e in fwd + bwd) / 1e9
    if not seconds:
        return None
    cell = ctx["cell"]
    ref = cell.load_module("reference", cell.config["reference"])
    if not hasattr(ref, "kda_bytes_per_row"):
        return None
    peaks = peaks_of(ctx["device_kind"])
    moved, multiplied = ref.kda_bytes_per_row(cell.config), ref.kda_flops_per_row(cell.config)
    least = {kind: max(moved[kind] / peaks["hbm_bytes_per_s"],
                       multiplied[kind] / peaks["bf16_flops_per_s"])
             for kind in ("forward", "backward")}
    tokens = ctx["rows"] * ctx["seq"]
    need = tokens * (len(fwd) * least["forward"] + len(bwd) * least["backward"])
    return 100.0 * need / seconds
