"""The fullest device's bytes while a traced step runs, in GB (1e9 bytes):
what is resident between the traced steps plus what the runtime has set
aside for the temporaries of the dearest program LOADED, both the
allocator's own (``memory.step_peak_bytes`` of the program's
``engine_totals`` annotation, benchmark/trace/totals.py). The reservation is
the device's and not a program's: the engine prints it as the step's only
where the step's own programs raised it at their first call and it has not
moved since; else None, as where the trace has no such key (every parent of
the PR that brought it, a back end whose allocator has no reservation). The
harness's ``memory_peak_bytes`` does not see a running program's temporaries
on the TPU; this does. Moves ``train_tokens_per_s``: what a step may keep for
its backward is decided by these bytes."""

from benchmark.trace import totals


def read(ctx):
    got = totals.value(ctx, "memory.step_peak_bytes")
    return None if got is None else got / 1e9
