"""Of ``setup_trace_s``, the seconds the remat budget spends reading the blocks:
each kind of block differentiated once more for names and bytes, and the
liveness walk over it (the program's ``remat_plan`` spans).
Read from the program's ``engine_totals`` annotation in the profiler trace
(``setup.remat_plan_s``, benchmark/trace/totals.py); None where the trace has none. Moves
``setup_s``."""

from benchmark.trace import totals


def read(ctx):
    return totals.value(ctx, "setup.remat_plan_s")
