"""Differential attention's two-width flash launches against the chip's peaks,
in percent: the least time the chip could take for what they HAD to compute,
over their summed device time.

What they had to compute is counted from the run and from no tile: the (query,
key) pairs that exist under causal AND same document AND, for a launch under
the window, the window's latest keys, in the traced steps' own rows, from their
document lengths (the reference file's ``diff_pairs``), times the query heads
of a launch (one half of the pairs), times the matmul FLOPs a pair costs each
kernel at the launch's TWO widths (``diff_pair_flops``: keys of 64, the pair's
two value heads side by side, 128), times the launches of each kernel a step as
the trace has them (a forward that the backward runs again is counted again: it
ran). The bytes are each launch's operands and results, read once and written
once, from the shapes in its own HLO text (benchmark/flops.py). The bound is
the larger of FLOPs over the bf16 peak and bytes over the HBM bandwidth
(benchmark/peaks.py), taken for the windowed and the full launches apart and
added (a windowed launch is nearer its bytes). Counting real pairs at the real
widths only (never a padded lane, never a key the mask hides), the share cannot
read over 100 %.

The launches are the trace's ``flash_fwd_diff.N`` / ``flash_bwd_diff.N`` events
and, under a static window, ``flash_fwd_diff_window.N`` /
``flash_bwd_diff_window.N`` (``pallas_flash.py``). The rows are made again from
the run's ``--seed`` as ``jobs/train.py`` draws them, by
``attn_window_roofline``'s helpers (found by name through the cell, as every
reader is). None without a trace, without the program's step annotations, or
where no such launch ran (every other cell, and the parent of PR 57)."""

import re

from benchmark.flops import custom_call_io_bytes
from benchmark.peaks import peaks_of
from benchmark.trace import reduce, scopes

#: (forward, backward) launches: over whole documents, and under the window
FULL = (re.compile(r"^flash_fwd_diff(\.|$)"), re.compile(r"^flash_bwd_diff(\.|$)"))
WINDOWED = (re.compile(r"^flash_fwd_diff_window(\.|$)"),
            re.compile(r"^flash_bwd_diff_window(\.|$)"))


def read(ctx):
    if scopes.of_run(ctx) is None:
        return None
    first = sorted(ctx["trace"]["devices"])[0]
    events = reduce.leaf_events(ctx["trace"]["devices"][first])
    found = {window: tuple([e for e in events if kind.match(e[0])] for kind in kinds)
             for window, kinds in ((False, FULL), (True, WINDOWED))}
    seconds = sum(e[2] for pair in found.values() for side in pair for e in side) / 1e9
    if not seconds:
        return None
    cell, steps = ctx["cell"], ctx["scopes"]["steps"]
    ref = cell.load_module("reference", cell.config["reference"])
    rows_of = cell.load_module("layer_metrics", "attn_window_roofline")
    cost = ref.diff_pair_flops(cell.config)
    separator = int(cell.traffic["separator"]) % cell.config["vocab_size"]
    seed = ctx["seed"] if "seed" in ctx else rows_of.seed_of_run()
    pieces = [[n for row in batch for n in rows_of.document_lengths(row, separator)]
              for batch in rows_of.traced_rows(cell, seed, ctx["rows"], steps)]
    peaks = peaks_of(ctx["device_kind"])
    least = 0.0
    for windowed, (fwd, bwd) in found.items():
        if len(fwd) % steps or len(bwd) % steps:
            raise ValueError(f"{len(fwd)} + {len(bwd)} two-width flash launches do "
                             f"not divide into {steps} traced steps")
        a_step = len(fwd) // steps * cost["forward"] + len(bwd) // steps * cost["backward"]
        window = int(cell.config["sliding_window"]) if windowed else None
        flops = sum(a_step * cost["heads"] * ref.diff_pairs(lens, window) for lens in pieces)
        need = sum(custom_call_io_bytes(e[3]) for e in fwd + bwd)
        least += max(flops / peaks["bf16_flops_per_s"], need / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
