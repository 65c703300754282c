"""Seconds in the backend compiler, or loading from the compile cache when it
hits, for the program's OWN programs before its first optimizer step returned
(``backend_compile_duration`` inside the program's first-call spans). The
harness's ``compile_s`` beside it also counts the reference's and the
comparison's compiles.
Read from the program's ``engine_totals`` annotation in the profiler trace
(``setup.compile_s``, benchmark/trace/totals.py); None where the trace has none. Moves
``setup_s``."""

from benchmark.trace import totals


def read(ctx):
    return totals.value(ctx, "setup.compile_s")
