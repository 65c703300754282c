"""The selective scan's launches against the chip's memory bandwidth, in
percent: the least time the chip could take to move what a scan HAS to move,
over the launches' summed device time.

What a scan has to move is the reference file's ``scan_bytes_per_row`` (forward:
``a``, ``dt`` and ``m`` of the channels and ``B``, ``C`` of the states a token;
backward: those, ``dm`` and the four gradients), whatever implements it, times
the tokens of a step, times the launches of each kind a step as the trace has
them (a forward that the backward runs again is counted again: it ran). The
scan's operations (``scan_flops_per_row``) run on the vector unit, and
benchmark/peaks.py has no vector peak: the bound is the bytes alone, over the
HBM bandwidth, so the share reads LOW where the scan is bound by the vector
unit (PERF.md says which it is). The same work whatever the chunks and tiles
are, and nothing the kernel moves besides (the chunks' entry states, the
stacked operand) is counted, so the share cannot read over 100 %.

The launches are the trace's ``ssm_scan_fwd.N`` / ``ssm_scan_bwd.N`` events
(``ops/transformer/pallas_scan.py``). None without a trace, without the
program's step annotations, or where no such launch ran (every other cell, a
program on the scan's XLA route, the parent of PR 57)."""

import re

from benchmark.peaks import peaks_of
from benchmark.trace import reduce, scopes

FORWARD = re.compile(r"^ssm_scan_fwd(\.|$)")
BACKWARD = re.compile(r"^ssm_scan_bwd(\.|$)")


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
    cost = ref.scan_bytes_per_row(cell.config)
    tokens = ctx["rows"] * ctx["seq"]
    need = tokens * (len(fwd) * cost["forward"] + len(bwd) * cost["backward"])
    return 100.0 * need / peaks_of(ctx["device_kind"])["hbm_bytes_per_s"] / seconds
