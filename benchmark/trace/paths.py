"""Device time under a scope the program names BELOW the five classes of
``scopes.py``: ``attn/latent``, ``attn/gate``, ``moe/shared``, ``mtp``. An
operation belongs to a path when its ``op_name``'s components
(``scopes.components``) hold the path's names one after the other,
anywhere: forward, recomputed forward and backward alike, a fusion whole to
the path its ``op_name`` carries, as in ``scopes.py``. A program that names
no such scope (every configuration without the mechanism, and the parent of
PR 32) has nothing to read: ``ms_per_step`` then returns None."""

from __future__ import annotations

from typing import Optional, Sequence

from benchmark.trace import reduce, scopes


def under(parts: Sequence[str], path: Sequence[str]) -> bool:
    """Whether ``path``'s names stand one after the other in ``parts``."""
    n = len(path)
    return any(tuple(parts[i:i + n]) == tuple(path) for i in range(len(parts) - n + 1))


def seconds_under(trace: reduce.Trace, names: dict, path: Sequence[str]) -> float:
    """Summed leaf-operation seconds on the first chip under ``path``."""
    first = sorted(trace["devices"])[0]
    return sum(e[2] / 1e9 for e in reduce.leaf_events(trace["devices"][first])
               if names.get(e[0]) and under(scopes.components(names[e[0]]), path))


def ms_per_step(ctx: dict, *path: str) -> Optional[float]:
    """Device ms a step under ``path``; None without a trace, without the
    program's step annotations, or where nothing ran under the path."""
    sums = scopes.of_run(ctx)
    if sums is None:
        return None
    if "op_names" not in ctx:
        ctx["op_names"] = scopes.op_names(ctx["trace_out"]["trace_file"])
    secs = seconds_under(ctx["trace"], ctx["op_names"], path)
    return 1e3 * secs / sums["steps"] if secs else None
