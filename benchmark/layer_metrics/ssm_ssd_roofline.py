"""The state-space-duality core's launches against the chip's peaks, in percent:
the least time the chip could take for what a Mamba-2 layer's recurrence HAS to
move and to multiply, over the launches' summed device time.

What it has to move is the reference file's ``ssd_bytes_per_row`` (forward: ``a``
and ``m`` of the channels, ``B`` and ``C`` of the states, ``dt`` of the heads a
token; backward: those, ``dm`` and the gradients) and what it has to multiply
``ssd_flops_per_row`` (the state's update and its read-out, 4 an element of ``H x
P x N`` forward, 10 backward), whatever implements it and whatever its chunks
are, times the tokens of a step. A launch's least time is the LARGER of its
bytes over the HBM bandwidth and its FLOPs over the matrix unit's peak, summed
over the launches of each kind a step as the trace has them (a forward that the
backward runs again is counted again: it ran). Nothing the kernel moves or
multiplies besides (the chunks' entry states, ``C B^T``, the masked product's
depth) is counted, so the share cannot read over 100 %.

The launches are the trace's ``ssd_fwd.N`` / ``ssd_bwd.N`` events
(``ops/transformer/pallas_ssd.py``). None without a trace, without the
program's step annotations, where the reference has no such functions, or where
no such launch ran (every other cell, a program on the core's XLA route, the
parent of PR 65)."""

import re

from benchmark.peaks import peaks_of
from benchmark.trace import reduce, scopes

FORWARD = re.compile(r"^ssd_fwd(\.|$)")
BACKWARD = re.compile(r"^ssd_bwd(\.|$)")


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
    if not hasattr(ref, "ssd_bytes_per_row"):
        return None
    peaks = peaks_of(ctx["device_kind"])
    moved, multiplied = ref.ssd_bytes_per_row(cell.config), ref.ssd_flops_per_row(cell.config)
    least = {kind: max(moved[kind] / peaks["hbm_bytes_per_s"],
                       multiplied[kind] / peaks["bf16_flops_per_s"])
             for kind in ("forward", "backward")}
    tokens = ctx["rows"] * ctx["seq"]
    need = tokens * (len(fwd) * least["forward"] + len(bwd) * least["backward"])
    return 100.0 * need / seconds
