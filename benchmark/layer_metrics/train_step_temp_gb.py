"""What the running step needs beyond what is resident before it, in GB (1e9
bytes): the runtime's reservation for the temporaries of the dearest program
LOADED (``memory.step_extra_bytes`` of the program's ``engine_totals``
annotation, benchmark/trace/totals.py: the allocator's ``bytes_reserved``,
within one filling of what ``tools/remat_fill_probe.py`` finds by filling the
chip, the number PRs 44 and 50 fetched by hand). The engine prints it only
where the step's own programs raised the reservation at their first call and
it has not moved since: a process that keeps a dearer program loaded reads
None, not that program's bytes. None too where the trace has no such key.
Moves ``train_tokens_per_s``: it is what ``checkpointing.WORKING_SHARE`` and
``STACK_COST`` stand in for."""

from benchmark.trace import totals


def read(ctx):
    got = totals.value(ctx, "memory.step_extra_bytes")
    return None if got is None else got / 1e9
