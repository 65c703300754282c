"""The block-diffusion layers' flash-attention launches against the chip's
peaks, in percent: the least time the chip could take for what they HAD to
compute, over their summed device time.

What they had to compute is counted from the run and from no tile: the
(query, clean key) pairs the KERNEL's part of the mask makes visible in the
traced steps' own rows (a clean query sees the clean keys of its own and of
earlier blocks of its document, a noised query those of strictly earlier
blocks; the reference file's ``kernel_pairs``, from each row's pieces of
documents, where each lies in the row, and the block length), times the query
heads, times the matmul FLOPs a pair costs each kernel
(``attention_pair_flops``: the forward's two matmuls, the fused backward's
five), times the launches of each kernel a step as the trace has them (a
forward that the backward runs again is counted again: it ran; a backward the
program runs a few folded rows at a time is as many launches of as much less
work, so the launches are counted in units of one whole call: the largest
number of folded rows any launch of either kernel took). The bytes are each launch's
operands and results, read once and written once, from the shapes in its own
HLO text (benchmark/flops.py). The bound is the larger of FLOPs over the bf16
peak and bytes over the HBM bandwidth (benchmark/peaks.py). Counting real
pairs only (never a padded tile, never a key the mask hides, never the noised
copy's own-block pairs, which an einsum beside the kernel multiplies), the
share cannot read over 100 %: a tile on a block's edge or across two documents
multiplies pairs that do not count.

The launches are the trace's ``flash_fwd_blockdiff.N`` /
``flash_bwd_blockdiff.N`` events (``pallas_flash.py`` names the kernels under
the block-diffusion mask so). The rows are made again from the run's ``--seed``
as ``jobs/train.py`` draws them, by ``attn_window_roofline``'s helpers (the
same need: ``seed_of_run``, ``traced_rows``, ``document_lengths``; found by
name through the cell, as every reader is). None without a trace, without the
program's step annotations, or where no such launch ran (a program without the
kernels: every other cell, and the parent of PR 39)."""


import itertools
import re

from benchmark.flops import custom_call_io_bytes
from benchmark.peaks import peaks_of
from benchmark.trace import reduce, scopes

FORWARD = re.compile(r"^flash_fwd_blockdiff(\.|$)")
BACKWARD = re.compile(r"^flash_bwd_blockdiff(\.|$)")
_SHAPE = re.compile(r"\b[a-z]+[0-9a-z]*\[([0-9,]+)\]")


def pieces_of(lengths) -> list:
    """(first position, length) of a row's pieces of documents, from their
    lengths in order: blocks are counted from the ROW's start, so where a
    piece lies matters."""
    return list(zip(itertools.accumulate(lengths, initial=0), lengths))


def folded_rows(hlo: str) -> int:
    """The leading dimension of a launch's FIRST array of rank 3 (the
    keys, or their gradient: ``[folded batch x key heads, keys, head]``):
    what share of one whole call the launch is. 1 where the text names no
    such array."""
    for dims in _SHAPE.findall(hlo.split(", custom_call_target")[0]):
        shape = [int(d) for d in dims.split(",")]
        if len(shape) == 3:
            return shape[0]
    return 1


def whole_calls(events, whole: int) -> float:
    """Launches in units of one whole call of ``whole`` folded rows: a call
    split over its folded rows counts once in all."""
    return sum(folded_rows(e[3]) for e in events) / whole


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
    cell, steps = ctx["cell"], ctx["scopes"]["steps"]
    ref = cell.load_module("reference", cell.config["reference"])
    rows_of = cell.load_module("layer_metrics", "attn_window_roofline")
    cost = ref.attention_pair_flops(cell.config)
    # a whole call's folded rows: the most any launch of either kernel took
    # (the forward is never split)
    whole = max(folded_rows(e[3]) for e in fwd + bwd)
    a_step = (whole_calls(fwd, whole) * cost["forward"]
              + whole_calls(bwd, whole) * cost["backward"]) / steps
    separator = int(cell.traffic["separator"]) % cell.config["vocab_size"]
    seed = ctx["seed"] if "seed" in ctx else rows_of.seed_of_run()
    flops = sum(
        a_step * cost["heads"] * sum(
            ref.kernel_pairs(pieces_of(rows_of.document_lengths(row, separator)), cell.config)
            for row in batch)
        for batch in rows_of.traced_rows(cell, seed, ctx["rows"], steps))
    need = sum(custom_call_io_bytes(e[3]) for e in fwd + bwd)
    peaks = peaks_of(ctx["device_kind"])
    least = max(flops / peaks["bf16_flops_per_s"], need / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
