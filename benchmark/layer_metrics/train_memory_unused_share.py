"""What a traced step leaves of the chip unused, in percent of the
allocator's limit: ``100 x memory.headroom_bytes / memory.limit_bytes`` of the
program's ``engine_totals`` annotation (benchmark/trace/totals.py), headroom =
limit - (residents + the step's extra). What the remat budget's rule could
still spend on values kept for the backward. Never over 100 by construction;
None where the trace has no such keys (a parent's, or a step whose extra the
engine left None because the reservation was not known to be its own:
``train_step_temp_gb``), and where the headroom reads negative: a peak over
the limit is a wrong reading, not a share. Moves ``train_tokens_per_s``."""

from benchmark.trace import totals


def read(ctx):
    headroom = totals.value(ctx, "memory.headroom_bytes")
    limit = totals.value(ctx, "memory.limit_bytes")
    if headroom is None or not limit or headroom < 0:
        return None
    return 100.0 * headroom / limit
