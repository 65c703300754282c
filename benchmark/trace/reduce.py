"""From a profiler trace to numbers. Two stages, so that the arithmetic can
be checked on a small recorded fixture without a chip:

``load(path)`` reads an ``.xplane.pb`` (with ``jax.profiler.ProfileData``)
or a ``.json`` dump of the same structure into a ``Trace``::

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns, hlo], ...], ...},
     "host":    [[name, start_ns, dur_ns], ...]}

``devices`` holds each chip's line "XLA Ops": one event per executed HLO
instruction. The profiler names an event by the instruction's whole text
(``%fusion.7 = bf16[...] fusion(...)``); ``name`` is the part before
`` = `` without the ``%`` and ``hlo`` the whole text (shapes, custom-call
target). ``host`` holds the host-side annotations; the harness's own
spans appear there by name. (The line "Async XLA Ops", copies and
collectives in flight between ``-start`` and ``-done``, is not read yet:
no cell has collectives.)

Everything else is plain arithmetic over those lists.
"""

from __future__ import annotations

import json
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Sequence  # [name, start_ns, dur_ns]
Trace = Dict[str, object]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: wrappers whose time is that of the operations inside them
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")


def load(path: str) -> Trace:
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[list]] = {}
    host: List[list] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [short_name(e.name), int(e.start_ns),
                         int(e.duration_ns), e.name] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                         for e in line.events]
    return {"devices": devices, "host": host}


def short_name(hlo: str) -> str:
    """``%fusion.7 = bf16[...] fusion(...)`` -> ``fusion.7``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def leaf_events(events: Iterable[Event]) -> List[Event]:
    """Events that are work, not wrappers around other events."""
    return [e for e in events if not CONTAINER.match(e[0])]


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def spans_of(events: Iterable[Event]) -> List[Tuple[int, int]]:
    return [(e[1], e[1] + e[2]) for e in events]


def window_ns(trace: Trace) -> Tuple[int, int]:
    """First start to last end over every chip's operations."""
    starts = [e[1] for ev in trace["devices"].values() for e in ev]
    ends = [e[1] + e[2] for ev in trace["devices"].values() for e in ev]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    per_chip = [total(union(spans_of(leaf_events(ev))))
                for ev in trace["devices"].values()]
    return sum(per_chip) / len(per_chip) / 1e9


def window_s(trace: Trace) -> float:
    s, e = window_ns(trace)
    return (e - s) / 1e9


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / window_s(trace)


def matching(trace: Trace, pattern: str) -> List[Event]:
    """The first chip's operations whose whole HLO text matches."""
    rx = re.compile(pattern)
    first = sorted(trace["devices"])[0]
    return [e for e in leaf_events(trace["devices"][first]) if rx.search(e[3])]


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """The operations that took most time on the first chip."""
    first = sorted(trace["devices"])[0]
    secs: Dict[str, float] = {}
    for e in leaf_events(trace["devices"][first]):
        secs[e[0]] = secs.get(e[0], 0.0) + e[2] / 1e9
    return [[k, v] for k, v in sorted(secs.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, span_names: Iterable[str], n: int = 10) -> List[list]:
    """The first chip's idle time by what the host was doing: each gap
    between device operations is laid against the host annotations of the
    given names and charged to the one that overlaps it most
    (``(no span)`` if none does)."""
    first = sorted(trace["devices"])[0]
    busy = union(spans_of(leaf_events(trace["devices"][first])))
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    names = set(span_names)
    host = sorted((e for e in trace["host"] if e[0] in names), key=lambda e: e[1])
    secs: Dict[str, float] = {}
    i = 0
    for gs, ge in gaps:                      # both lists are in time order
        while i < len(host) and host[i][1] + host[i][2] <= gs:
            i += 1
        best, best_overlap = "(no span)", 0
        j = i
        while j < len(host) and host[j][1] < ge:
            name, hs, hd = host[j]
            overlap = min(ge, hs + hd) - max(gs, hs)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
            j += 1
        secs[best] = secs.get(best, 0.0) + (ge - gs) / 1e9
    return [[k, v] for k, v in sorted(secs.items(), key=lambda kv: -kv[1])[:n]]


def summary(trace: Trace, span_records) -> dict:
    names = {r[0] for r in span_records}
    return {"busy_s": busy_s(trace), "window_s": window_s(trace),
            "breakdown": {"device_ops": top_ops(trace),
                          "idle_gaps": idle_gaps(trace, names)}}
