"""Seconds inside the program's ``initialize``, the whole call: configuration and
topology, the ZeRO plan, the jitted make of master weights and moments with
its own trace and compile, and the operations it dispatches one by one.
Read from the program's ``engine_totals`` annotation in the profiler trace
(``setup.initialize_s``, benchmark/trace/totals.py); None where the trace has none. Moves
``setup_s``."""

from benchmark.trace import totals


def read(ctx):
    return totals.value(ctx, "setup.initialize_s")
