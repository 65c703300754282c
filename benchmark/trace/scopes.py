"""Device time by the program's own names.

``jax.named_scope`` puts a component on the name stack of every operation
traced inside it, XLA keeps the stack as each HLO instruction's ``op_name``,
and the TPU profiler records it: every event of a chip's "XLA Ops" line
points at an event-metadata entry of its plane (``XPlane.event_metadata``)
whose name is the instruction's whole text and whose stat ``tf_op`` is that
``op_name``. That is the source used here, found on the chip (my chip run,
PR 24). Inside the layer scan a scope stands beside its transform wrapper,
``jit(_train_step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/
rematted_computation/block/attn/core/sub:``; outside it, inside:
``jit(_train_step_fn)/jvp(head)/dot_general:``. The HLO module the profiler
also embeds was not needed. ``jax.profiler.
ProfileData`` gives an event's own stats but not its metadata's, so
``op_names`` reads the metadata out of the ``.xplane.pb`` with a protobuf
wire decoder of its own (five message types of ``xplane.proto``, no
dependency); times still come from ``reduce.load``'s events, joined by the
instruction's short name.

A fusion carries the ``op_name`` of one of its members (its root). Where a
fusion straddles two scopes its whole time goes to the scope that name
carries; nothing is split. An operation whose ``op_name`` has none of the
program's scopes (the layer scan's slicing, a copy the compiler added, an
operation with no metadata at all) is ``unscoped``.

Classes, by the first scope component found in the path:
``attn`` -> attn, ``mlp`` -> mlp, ``embed`` / ``head`` / ``loss`` -> head,
``optimizer`` -> optimizer, else unscoped. ``remat`` cuts across them: the
path has ``rematted_computation``, the component JAX gives the recomputation
that ``jax.checkpoint`` schedules in the backward pass.

Steps are counted from the program's own ``train_step`` annotations
(``jax.profiler.StepTraceAnnotation`` in ``engine._train_batch_fused``) in
the host plane. A program without them (the parent of PR 24) has no named
work to read: every reader then returns None.
"""

from __future__ import annotations

import json
import re
import statistics
from typing import Dict, Iterator, Optional, Tuple

from benchmark.trace import reduce

CLASS_OF = {"attn": "attn", "mlp": "mlp", "embed": "head", "head": "head",
            "loss": "head", "optimizer": "optimizer"}
CLASSES = ("attn", "mlp", "head", "optimizer", "unscoped")
REMAT = "rematted_computation"
STEP_ANNOTATION = "train_step"


# ---------------------------------------------------------------------------
# the .xplane.pb, as far as the names need it (xplane.proto field numbers)
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is a ``memoryview`` slice, a varint an int, fixed widths bytes."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} is not in an xplane")
        yield field, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _plane_names(plane) -> Tuple[str, Dict[str, str]]:
    """(plane name, {instruction short name: op_name}) of one XPlane; the
    map is read only for a chip's plane."""
    name, stat_names, metadata = "", {}, []
    for field, _, value in _fields(plane):
        if field == 2:                                   # XPlane.name
            name = _text(value)
        elif field == 5:                                 # stat_metadata entry
            for f, _, v in _fields(value):
                if f == 2:                               # the XStatMetadata
                    sid, sname = 0, ""
                    for g, _, w in _fields(v):
                        if g == 1:
                            sid = w
                        elif g == 2:
                            sname = _text(w)
                    stat_names[sid] = sname
        elif field == 4:                                 # event_metadata entry
            metadata += [v for f, _, v in _fields(value) if f == 2]
    out: Dict[str, str] = {}
    if not reduce.DEVICE_PLANE.match(name):
        return name, out
    for md in metadata:                                  # XEventMetadata
        hlo, op_name = "", None
        for field, _, value in _fields(md):
            if field == 2:
                hlo = _text(value)
            elif field == 5:                             # XStat
                sid, text = 0, None
                for f, _, v in _fields(value):
                    if f == 1:
                        sid = v
                    elif f == 5:                         # str_value
                        text = _text(v)
                    elif f == 7:                         # ref_value
                        text = stat_names.get(v)
                if stat_names.get(sid) == "tf_op" and text is not None:
                    op_name = text
        if op_name is not None:
            out[reduce.short_name(hlo)] = op_name
    return name, out


def op_names(path: str) -> Dict[str, str]:
    """{instruction short name: op_name} for the first chip of the trace
    at ``path``: an ``.xplane.pb``, or a ``.json`` fixture that keeps the
    same map under ``"op_names"``."""
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)["op_names"]
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = dict(_plane_names(plane)                    # XSpace.planes
                  for field, _, plane in _fields(space) if field == 1)
    chips = sorted(p for p in planes if reduce.DEVICE_PLANE.match(p))
    return planes[chips[0]] if chips else {}


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

_WRAPPED = re.compile(r"\(([^()]*)\)")


def components(op_name: str) -> list:
    """``jit(f)/transpose(jvp(attn))/core/dot_general:`` -> ``["attn",
    "core", "dot_general"]``: the path without its ``jit(...)`` head, each
    transform wrapper reduced to the name it wraps."""
    parts = op_name.split(":", 1)[0].split("/")
    if parts and re.match(r"^p?jit\(", parts[0]):
        parts = parts[1:]
    out = []
    for p in parts:
        inner = _WRAPPED.findall(p)
        out.append(inner[-1] if inner else p)
    return [p for p in out if p]


def classify(op_name: Optional[str]) -> Tuple[str, bool]:
    """(class, recomputed?) of one operation's ``op_name``."""
    if not op_name:
        return "unscoped", False
    parts = components(op_name)
    cls = next((CLASS_OF[p] for p in parts if p in CLASS_OF), "unscoped")
    return cls, REMAT in parts


def steps(trace: reduce.Trace) -> int:
    """How many of the program's own step annotations the host plane has."""
    return sum(1 for e in trace["host"] if e[0] == STEP_ANNOTATION)


def seconds_by_scope(trace: reduce.Trace, names: Dict[str, str]) -> Dict[str, float]:
    """Summed leaf-operation seconds on the first chip by class, plus
    ``remat`` (cutting across) and ``total`` (the five classes' sum)."""
    first = sorted(trace["devices"])[0]
    out = dict.fromkeys(CLASSES + ("remat",), 0.0)
    for e in reduce.leaf_events(trace["devices"][first]):
        cls, remat = classify(names.get(e[0]))
        out[cls] += e[2] / 1e9
        if remat:
            out["remat"] += e[2] / 1e9
    out["total"] = sum(out[c] for c in CLASSES)
    return out


def unscoped_ops(trace: reduce.Trace, names: Dict[str, str], n: int = 12) -> list:
    """The unscoped operations that took most time: [name, seconds, hlo]."""
    first = sorted(trace["devices"])[0]
    secs: Dict[str, list] = {}
    for e in reduce.leaf_events(trace["devices"][first]):
        if classify(names.get(e[0]))[0] == "unscoped":
            row = secs.setdefault(e[0], [e[0], 0.0, e[3]])
            row[1] += e[2] / 1e9
    return sorted(secs.values(), key=lambda r: -r[1])[:n]


# ---------------------------------------------------------------------------
# what the per-layer readers call
# ---------------------------------------------------------------------------

def of_run(ctx: dict) -> Optional[dict]:
    """The run's sums, computed once and kept on ``ctx``: seconds by class
    and ``steps``. None without a trace, or where the program has no step
    annotations (it has no scopes either)."""
    if "scopes" not in ctx:
        ctx["scopes"] = None
        trace = ctx.get("trace")
        n = steps(trace) if trace is not None else 0
        if n:
            want = ctx["cell"].traffic.get("trace_steps")
            if want is not None and n != int(want):
                raise ValueError(f"the trace holds {n} train_step "
                                 f"annotations, the traffic file says {want}")
            names = op_names(ctx["trace_out"]["trace_file"])
            ctx["scopes"] = dict(seconds_by_scope(trace, names), steps=n)
    return ctx["scopes"]


def ms_per_step(ctx: dict, cls: str) -> Optional[float]:
    sums = of_run(ctx)
    return None if sums is None else 1e3 * sums[cls] / sums["steps"]


def span_median_ms(ctx: dict, name: str) -> Optional[float]:
    """Median length of the program's span ``name`` in the traced stretch
    (the host plane's annotations), in ms; None if it is not there."""
    trace = ctx.get("trace")
    if trace is None:
        return None
    d = [e[2] for e in trace["host"] if e[0] == name]
    return statistics.median(d) / 1e6 if d else None
