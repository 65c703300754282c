"""A learned selection's flash-attention launches against the chip's peaks, in
percent: the least time the chip could take for what they HAD to compute, over
their summed device time.

What they had to compute is counted from the run and from no tile: the
SELECTED (query, key) pairs of the traced steps' own rows (a query with v
visible keys of its own document picks ``min(v, topk)``: the reference file's
``dsa_pairs`` over each row's pieces of documents; which keys a query picks
moves the count by nothing), times the query heads, times the matmul FLOPs a
pair costs each kernel (``attention_pair_flops``: the forward's two matmuls,
the fused backward's five), times the launches of each kernel a step as the
trace has them (a forward that the backward runs again is counted again: it
ran). The bytes are each launch's operands and results, read once and written
once, from the shapes in its own HLO text (benchmark/flops.py: the selection's
int8 operand among them). The bound is the larger of FLOPs over the bf16 peak
and bytes over the HBM bandwidth (benchmark/peaks.py). Counting real pairs
only, the share cannot read over 100 %, and it reads LOW: the kernel multiplies
whole tiles under the mask, a causal layer's, of which a query's 2,048 picked
keys are a part. That is the finding it is there to state.

The launches are the trace's ``flash_fwd_dsa.N`` / ``flash_bwd_dsa.N`` events
(``pallas_flash.py`` names the kernels that read a selection's operand so). The
rows are made again from the run's ``--seed`` as ``jobs/train.py`` draws them,
by ``attn_window_roofline``'s helpers (``seed_of_run``, ``traced_rows``,
``document_lengths``) and ``attn_blockdiff_roofline``'s count of launches in
whole calls (found by name through the cell, as every reader is). None without
a trace, without the program's step annotations, or where no such launch ran (a
program without the kernels: every other cell, and the parent of PR 48)."""

import re

from benchmark.flops import custom_call_io_bytes
from benchmark.peaks import peaks_of
from benchmark.trace import reduce, scopes

FORWARD = re.compile(r"^flash_fwd_dsa(\.|$)")
BACKWARD = re.compile(r"^flash_bwd_dsa(\.|$)")


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
    calls = cell.load_module("layer_metrics", "attn_blockdiff_roofline")
    cost = ref.attention_pair_flops(cell.config)
    whole = max(calls.folded_rows(e[3]) for e in fwd + bwd)
    a_step = (calls.whole_calls(fwd, whole) * cost["forward"]
              + calls.whole_calls(bwd, whole) * cost["backward"]) / steps
    separator = int(cell.traffic["separator"]) % cell.config["vocab_size"]
    topk = int(cell.config["sa_config"]["topk"])
    seed = ctx["seed"] if "seed" in ctx else rows_of.seed_of_run()
    flops = sum(
        a_step * cost["heads"] * sum(
            ref.dsa_pairs(rows_of.document_lengths(row, separator), topk) for row in batch)
        for batch in rows_of.traced_rows(cell, seed, ctx["rows"], steps))
    need = sum(custom_call_io_bytes(e[3]) for e in fwd + bwd)
    peaks = peaks_of(ctx["device_kind"])
    least = max(flops / peaks["bf16_flops_per_s"], need / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
