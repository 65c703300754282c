"""The sliding layers' flash-attention launches against the chip's peaks, in
percent: the least time the chip could take for what they HAD to compute,
over their summed device time.

What they had to compute is counted from the run and from no tile: the
(query, key) pairs that exist under causal AND window AND same document in
the traced steps' own rows, from their document lengths (the reference
file's ``window_pairs``), times the query heads, times the matmul FLOPs a
pair costs each kernel (``attention_pair_flops``: the forward's two matmuls,
the fused backward's five), times the launches of each kernel a step as the
trace has them (a forward that the backward runs again is counted again: it
ran). The bytes are each launch's operands and results, read once and
written once, from the shapes in its own HLO text (benchmark/flops.py). The
bound is the larger of FLOPs over the bf16 peak and bytes over the HBM
bandwidth (benchmark/peaks.py); at 16,384 under a window of 2,048 it is the
FLOPs by two orders of magnitude. Counting real pairs only (never a padded
tile, never a key the mask hides), the share cannot read over 100 %: a tile
on the window's edge or across two documents multiplies pairs that do not
count.

The launches are the trace's ``flash_fwd_window.N`` / ``flash_bwd_window.N``
events (``pallas_flash.py`` names the kernels whose grids are cut to a static
window so). The rows are made again here from the run's ``--seed`` (the
harness's own argument, read off the command line; the traffic generator is
deterministic) as ``jobs/train.py`` draws them: the checked first step, one
more, then the traced ``trace_steps``. None without a trace, without the
program's step annotations, or where no such launch ran (a program without
the kernels, every other cell)."""

import re
import sys

from benchmark import traffic
from benchmark.flops import custom_call_io_bytes
from benchmark.peaks import peaks_of
from benchmark.trace import reduce, scopes

FORWARD = re.compile(r"^flash_fwd_window(\.|$)")
BACKWARD = re.compile(r"^flash_bwd_window(\.|$)")
#: the batches ``jobs/train.py`` draws before the traced ones
BATCHES_BEFORE_THE_TRACE = 2


def seed_of_run(argv=None) -> int:
    """``--seed`` of the command line (``--seed N`` or ``--seed=N``; 0
    without one, as ``run.py`` defaults)."""
    argv = sys.argv if argv is None else argv
    for i, a in enumerate(argv):
        if a == "--seed" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith("--seed="):
            return int(a.split("=", 1)[1])
    return 0


def document_lengths(row, separator: int) -> list:
    """The lengths of the pieces of documents in one packed row: a document
    ends WITH its separator; what is left at the row's end is a piece too."""
    out, start = [], 0
    for at in [i for i, t in enumerate(row) if t == separator]:
        out.append(at + 1 - start)
        start = at + 1
    if start < len(row):
        out.append(len(row) - start)
    return out


def traced_rows(cell, seed: int, rows: int, steps: int):
    """The batches of the traced steps, each a list of rows."""
    stream = traffic.train_batches(cell.traffic, seed, cell.config["vocab_size"], rows)
    batches = [next(stream)["input_ids"].tolist()
               for _ in range(BATCHES_BEFORE_THE_TRACE + steps)]
    return batches[BATCHES_BEFORE_THE_TRACE:]


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
    cost = ref.attention_pair_flops(cell.config)
    if len(fwd) % steps or len(bwd) % steps:
        raise ValueError(f"{len(fwd)} + {len(bwd)} windowed flash launches do "
                         f"not divide into {steps} traced steps")
    a_step = (len(fwd) // steps * cost["forward"] + len(bwd) // steps * cost["backward"])
    separator = int(cell.traffic["separator"]) % cell.config["vocab_size"]
    seed = ctx["seed"] if "seed" in ctx else seed_of_run()
    flops = sum(
        a_step * cost["heads"] * ref.window_pairs(
            [n for row in batch for n in document_lengths(row, separator)], cell.config)
        for batch in traced_rows(cell, seed, ctx["rows"], steps))
    need = sum(custom_call_io_bytes(e[3]) for e in fwd + bwd)
    peaks = peaks_of(ctx["device_kind"])
    least = max(flops / peaks["bf16_flops_per_s"], need / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
