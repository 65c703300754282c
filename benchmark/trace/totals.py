"""The program's own counters, read out of the profiler trace.

While a profiler session runs, the program under test writes one
zero-length host annotation a step, named ``engine_totals``, whose stats
are its counters as one flat dict of scalars and short strings, keys
dotted by counter (``setup.import_s``, ``setup.trace_s``,
``moe.products_kernel.forward``, ``remat.saved_bytes``, ...). It is the one
way from the program to a reader that needs no engine in hand.
``reduce.load`` drops an event's stats, so this opens the file itself: an
``.xplane.pb`` with ``jax.profiler.ProfileData``, or a ``.json`` dump
(a fixture) whose ``"host_stats"`` lists ``[name, start_ns, {stat: value}]``.

Where a trace holds no such event (a program from before the annotation,
as every parent of the PR that brought it), ``load`` gives None, and so
does every reader over it: the metric is then left out of the line.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Optional

EVENT = "engine_totals"


def load(path: str) -> Optional[Dict[str, Any]]:
    """The stats of the LAST ``engine_totals`` event of the host planes
    (the counters as they stood at the last traced step), or None."""
    if path.endswith(".json"):
        with open(path) as f:
            found = [(start, stats) for name, start, stats
                     in json.load(f).get("host_stats", []) if name == EVENT]
    else:
        from jax.profiler import ProfileData
        found = [(int(e.start_ns), dict(e.stats))
                 for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name == EVENT]
    return max(found, key=lambda pair: pair[0])[1] if found else None


def value(ctx: dict, key: str) -> Optional[float]:
    """One number of the run's ``engine_totals``; None where the run has no
    trace, the trace no such event, or the event no such key. The event is
    read once a run and kept on ``ctx``."""
    if "engine_totals" not in ctx:
        path = (ctx.get("trace_out") or {}).get("trace_file")
        ctx["engine_totals"] = load(path) if path else None
    got = (ctx["engine_totals"] or {}).get(key)
    return None if got is None else float(got)
