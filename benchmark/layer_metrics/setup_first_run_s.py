"""The first calls' wall seconds less tracing, lowering and compiling: argument
transfers, the first step's run and whatever else the call waited for.
Read from the program's ``engine_totals`` annotation in the profiler trace
(``setup.run_s``, benchmark/trace/totals.py); None where the trace has none. Moves
``setup_s``."""

from benchmark.trace import totals


def read(ctx):
    return totals.value(ctx, "setup.run_s")
