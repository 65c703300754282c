"""The EVA layers' flash-attention launches against the chip's peaks, in
percent: the least time the chip could take for what they HAD to compute,
over their summed device time.

What they had to compute is counted from the run's shapes and from no tile:
the (query, exact key) and (query, summary) pairs EVA's mask makes visible in
a row of the cell's length (the reference file's ``eva_pairs``: a query sees
the exact keys of its own window up to itself and one summary a chunk of every
window before it; no document enters, so every row of a length has the same
pairs), times the matmul FLOPs a pair costs each kernel
(``attention_pair_flops``: the forward's two matmuls, the fused backward's
five), launch by launch as the trace has them: a forward that the backward
runs again is counted again (it ran), and a launch over some of a layer's
heads (the program takes a long row's heads in groups) or some of its folded
rows counts what IT held: a folded row of a ``*_eva_local`` launch is one
(batch row, window, head), a window's share of a head's exact pairs; a folded
row of a ``*_eva_far`` launch one (batch row, head), a head's summary pairs.
The bytes are each launch's operands and results, read once and written once,
from the shapes in its own HLO text (benchmark/flops.py). The bound is the
larger of FLOPs over the bf16 peak and bytes over the HBM bandwidth
(benchmark/peaks.py). Counting real pairs only (never a padded tile, never a
key the mask hides), the share cannot read over 100 %: a tile on a window's
diagonal or on the edge of a query's visible summaries multiplies pairs that
do not count.

The launches are the trace's ``flash_fwd_eva_local.N`` /
``flash_bwd_eva_local.N`` / ``flash_fwd_eva_far.N`` / ``flash_bwd_eva_far.N``
events (``pallas_flash.py`` names a tagged launch so). None without a trace,
without the program's step annotations, or where no such launch ran (a program
without the kernels: every other cell, and the parent of PR 42)."""

import re

from benchmark.flops import custom_call_io_bytes
from benchmark.peaks import peaks_of
from benchmark.trace import reduce, scopes

LAUNCH = re.compile(r"^flash_(fwd|bwd)_eva_(local|far)(\.|$)")
_SHAPE = re.compile(r"\b[a-z]+[0-9a-z]*\[([0-9,]+)\]")


def folded_rows(hlo: str) -> int:
    """The leading dimension of a launch's FIRST array of rank 3 (the keys,
    or their gradient: ``[folded rows, keys, head]``). 1 where the text names
    no such array."""
    for dims in _SHAPE.findall(hlo.split(", custom_call_target")[0]):
        shape = [int(d) for d in dims.split(",")]
        if len(shape) == 3:
            return shape[0]
    return 1


def launch_flops(name: str, hlo: str, pairs: dict, cost: dict, windows: int) -> float:
    """The FLOPs ONE launch had to compute: its folded rows times the real
    pairs of one (``pairs``: a whole row's, one head's; ``windows``: the
    windows of a row)."""
    kernel, part = LAUNCH.match(name).group(1, 2)
    per_row = pairs["exact"] / windows if part == "local" else pairs["summary"]
    return folded_rows(hlo) * per_row * cost["forward" if kernel == "fwd" else "backward"]


def read(ctx):
    if scopes.of_run(ctx) is None:
        return None
    first = sorted(ctx["trace"]["devices"])[0]
    events = [e for e in reduce.leaf_events(ctx["trace"]["devices"][first])
              if LAUNCH.match(e[0])]
    seconds = sum(e[2] for e in events) / 1e9
    if not seconds:
        return None
    cell = ctx["cell"]
    ref = cell.load_module("reference", cell.config["reference"])
    seq = int(ctx["seq"])
    pairs, cost = ref.eva_pairs(cell.config, seq), ref.attention_pair_flops(cell.config)
    windows = max(1, -(-seq // int(cell.config["window_size"])))
    flops = sum(launch_flops(e[0], e[3], pairs, cost, windows) for e in events)
    need = sum(custom_call_io_bytes(e[3]) for e in events)
    peaks = peaks_of(ctx["device_kind"])
    least = max(flops / peaks["bf16_flops_per_s"], need / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
