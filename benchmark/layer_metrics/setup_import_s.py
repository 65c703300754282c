"""Seconds of the program's own import: the package under test's ``__init__`` from
its first line to its last, less whatever the process had imported before
it (JAX, in the benchmark).
Read from the program's ``engine_totals`` annotation in the profiler trace
(``setup.import_s``, benchmark/trace/totals.py); None where the trace has none. Moves
``setup_s``."""

from benchmark.trace import totals


def read(ctx):
    return totals.value(ctx, "setup.import_s")
