"""Seconds converting the program's own programs from jaxpr to MLIR before its
first optimizer step returned (JAX's ``jaxpr_to_mlir_module_duration``, inside
the program's first-call spans).
Read from the program's ``engine_totals`` annotation in the profiler trace
(``setup.lower_s``, benchmark/trace/totals.py); None where the trace has none. Moves
``setup_s``."""

from benchmark.trace import totals


def read(ctx):
    return totals.value(ctx, "setup.lower_s")
