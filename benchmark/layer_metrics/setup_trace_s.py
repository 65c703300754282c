"""Seconds of Python tracing of the program's own programs before its first
optimizer step returned: the outermost trace of each first call (the remat
budget's reading of the blocks and the kernels' bodies included), from JAX's
``jaxpr_trace_duration`` events as the program's first-call spans collect
them.
Read from the program's ``engine_totals`` annotation in the profiler trace
(``setup.trace_s``, benchmark/trace/totals.py); None where the trace has none. Moves
``setup_s``."""

from benchmark.trace import totals


def read(ctx):
    return totals.value(ctx, "setup.trace_s")
