"""Span/trace recorder: host-side phase spans + comm/metric events.

The step is decomposed into the phases a TPU training loop actually has
(data, gather, fwd, bwd, scatter, optimizer, checkpoint — plus serving
phases for the inference engine). Spans are HOST-side intervals around
dispatches: they measure what the host observes (dispatch + any
backpressure), which is the honest measurement under XLA's async runtime —
device-internal attribution belongs to the XLA profiler, and collective
attribution comes from the comm records (:meth:`TraceRecorder.comm`) fed
by ``dist.record_collective`` at trace time.

Exports: Chrome-trace JSON (``chrome://tracing`` / Perfetto — spans as
``X`` duration events, comm records as instant events, metrics as counter
tracks) and JSONL (one record per line; ``tools/trace_view.py``
summarizes it).

One clock with the profiler: a span used as a context manager is also a
``jax.profiler.TraceAnnotation`` of the same (static) name, so while a
profiler session runs the program's spans lie in the profiler's own trace
beside the device's operations, and an idle gap can be charged to what the
host was doing. Arguments stay with the recorder: a TraceMe name with
``#k=v#`` in it would be a thousand names.

Every span has an ``id`` and the ``parent`` it was opened under (the top of
the opening thread's stack), and serving spans carry ``req``, the uids of
the requests in the wave; both exports keep all three.

Thread safety: spans may begin/end on any thread (async checkpoint writes
record their spans from the worker); the recorder keeps a per-thread span
stack under one lock. The watchdog reads a *snapshot* of the live stacks
when it fires, so a stalled step dumps exactly which phase it is stuck in.
"""

from __future__ import annotations

import itertools
import json
import threading
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from . import clock

# -- canonical phases --------------------------------------------------------
PHASE_DATA = "data"              # host batch pipeline (validate/curriculum/H2D)
PHASE_GATHER = "gather"          # param all-gather (comm records)
PHASE_FWD = "fwd"                # forward/micro-step dispatch
PHASE_BWD = "bwd"                # backward boundary
PHASE_SCATTER = "scatter"        # grad reduce-scatter/all-reduce (comm records)
PHASE_STEP = "step"              # fused train-step dispatch
PHASE_OPTIMIZER = "optimizer"    # apply/optimizer dispatch
PHASE_CHECKPOINT = "checkpoint"  # save/load, incl. async write-behind
PHASE_SERVING = "serving"        # inference wave/dispatch
PHASE_OFFLOAD = "offload"        # out-of-core optimizer step pipeline
PHASE_SETUP = "setup"            # initialize and every program's first call
PHASE_OTHER = "other"

# collective op -> phase attribution for comm records
_COMM_PHASE = {
    "all_gather": PHASE_GATHER,
    "broadcast": PHASE_GATHER,
    "reduce_scatter": PHASE_SCATTER,
    "all_reduce": PHASE_SCATTER,
    "all_to_all": PHASE_SCATTER,
}


class Span:
    """One open interval. Closed via the context-manager protocol or
    :meth:`TraceRecorder.end`."""

    __slots__ = ("name", "phase", "t0", "t1", "step", "args", "id", "parent",
                 "req", "_rec", "_tid", "_ann")

    def __init__(self, rec: "TraceRecorder", name: str, phase: str,
                 step: Optional[int], args: Optional[Dict[str, Any]],
                 req: Optional[List[int]] = None):
        self._rec = rec
        self._tid = threading.get_ident()
        self._ann = None
        self.name = name
        self.phase = phase
        self.step = step
        self.args = args
        self.req = req
        self.id = 0              # set by the recorder, under its lock
        self.parent: Optional[int] = None
        self.t0 = clock.now()
        self.t1 = 0.0

    @property
    def duration(self) -> float:
        return (self.t1 or clock.now()) - self.t0

    def __enter__(self) -> "Span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        self._rec.end(self)


class _NullSpan:
    """Reusable zero-work span for the disabled path."""

    duration = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL_SPAN = _NullSpan()


class TraceRecorder:

    def __init__(self, max_events: int = 100_000):
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=max(max_events, 1))
        self.dropped = 0
        self._epoch = clock.now()
        self._ids = itertools.count(1)
        # live span stacks by thread id — the watchdog's dump source, and
        # where a new span or instant finds its parent
        self._active: Dict[int, List[Span]] = {}

    # -- recording -------------------------------------------------------
    def span(self, name: str, phase: str = PHASE_OTHER,
             step: Optional[int] = None, req: Optional[List[int]] = None,
             **args) -> Span:
        s = Span(self, name, phase, step, args or None, req)
        with self._lock:
            stack = self._active.setdefault(s._tid, [])
            s.id = next(self._ids)
            s.parent = stack[-1].id if stack else None
            stack.append(s)
        return s

    def _parent_here(self) -> Optional[int]:
        """The innermost open span of the calling thread (lock held)."""
        stack = self._active.get(threading.get_ident())
        return stack[-1].id if stack else None

    def end(self, span: Span) -> None:
        span.t1 = clock.now()
        with self._lock:
            stack = self._active.get(span._tid, [])
            if span in stack:
                stack.remove(span)
            if not stack:
                self._active.pop(span._tid, None)
            self._push({
                "kind": "span", "name": span.name, "phase": span.phase,
                "ts": span.t0 - self._epoch, "dur": span.t1 - span.t0,
                "step": span.step, "tid": span._tid,
                "id": span.id, "parent": span.parent,
                **({"req": list(span.req)} if span.req is not None else {}),
                **({"args": span.args} if span.args else {}),
            })

    def start_no_later_than(self, t: float) -> None:
        """Move the epoch back to ``t`` (a ``clock.now()`` reading) while
        nothing is recorded yet: set-up's first phases close before the
        engine has built its recorder, and are written into it afterwards
        (``setup_spans.SetupTotals.replay``)."""
        with self._lock:
            if not self._events:
                self._epoch = min(self._epoch, t)

    def complete_span(self, name: str, phase: str, dur: float,
                      step: Optional[int] = None, end: Optional[float] = None,
                      **args) -> None:
        """Record an already-measured interval as a span (duration events
        accumulated across a step — the offload pipeline's per-phase
        seconds land here post-hoc rather than as hundreds of per-bucket
        live spans). ``ts`` is backdated so the span ends at ``end`` (a
        ``clock.now()`` reading; 'now' when None)."""
        t = clock.now() if end is None else end
        with self._lock:
            self._push({
                "kind": "span", "name": name, "phase": phase,
                "ts": max(0.0, t - self._epoch - dur), "dur": float(dur),
                "step": step, "tid": threading.get_ident(),
                "id": next(self._ids), "parent": self._parent_here(),
                **({"args": args} if args else {}),
            })

    def instant(self, name: str, phase: str = PHASE_OTHER,
                step: Optional[int] = None, **args) -> None:
        with self._lock:
            self._push({"kind": "instant", "name": name, "phase": phase,
                        "ts": clock.now() - self._epoch, "step": step,
                        "parent": self._parent_here(),
                        **({"args": args} if args else {})})

    def comm(self, op: str, nbytes: int, axes, overlapped: Optional[bool],
             count: int = 1, wire_bytes: Optional[int] = None) -> None:
        """One ``record_collective`` record (trace-time: sizes/schedule
        class, not wall time — see utils/comms_logging.py). ``wire``
        carries the on-link bytes when the transport plan narrows the
        width (docs/COLLECTIVES.md)."""
        with self._lock:
            self._push({"kind": "comm", "op": op,
                        "phase": _COMM_PHASE.get(op, PHASE_OTHER),
                        "bytes": int(nbytes),
                        "wire": int(nbytes if wire_bytes is None
                                    else wire_bytes),
                        "axes": str(axes),
                        "overlapped": overlapped, "count": int(count),
                        "ts": clock.now() - self._epoch})

    def metric(self, name: str, value: float,
               step: Optional[int] = None) -> None:
        with self._lock:
            self._push({"kind": "metric", "name": name, "value": float(value),
                        "step": step, "ts": clock.now() - self._epoch})

    def _push(self, rec: Dict[str, Any]) -> None:
        if len(self._events) == self._events.maxlen:
            self.dropped += 1
        self._events.append(rec)

    # -- introspection ---------------------------------------------------
    def active_stacks(self) -> Dict[int, List[Tuple[str, float]]]:
        """Snapshot of live spans: {thread_id: [(name, open-for-seconds)]}
        — what the watchdog dumps when a step blows its deadline."""
        t = clock.now()
        with self._lock:
            return {tid: [(s.name, t - s.t0) for s in stack]
                    for tid, stack in self._active.items() if stack}

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    # -- export ----------------------------------------------------------
    def export_jsonl(self, path: str) -> int:
        """One record per line; returns the record count."""
        events = self.events()
        with open(path, "w") as f:
            for rec in events:
                f.write(json.dumps(rec) + "\n")
        return len(events)

    def export_chrome_trace(self, path: str, pid: int = 0) -> int:
        """Chrome-trace/Perfetto JSON (``{"traceEvents": [...]}``):
        spans → ``X`` complete events, instants/comm → ``i`` instants,
        metrics → ``C`` counter tracks. Timestamps in microseconds."""
        out = []
        for rec in self.events():
            base = {"pid": pid, "ts": rec["ts"] * 1e6}
            if rec["kind"] == "span":
                out.append({**base, "ph": "X", "name": rec["name"],
                            "cat": rec["phase"], "dur": rec["dur"] * 1e6,
                            "tid": rec["tid"] % (1 << 31),
                            "args": {**rec.get("args", {}),
                                     "step": rec.get("step"),
                                     "id": rec["id"], "parent": rec["parent"],
                                     **({"req": rec["req"]}
                                        if "req" in rec else {})}})
            elif rec["kind"] == "instant":
                out.append({**base, "ph": "i", "s": "t", "tid": 0,
                            "name": rec["name"], "cat": rec["phase"],
                            "args": rec.get("args", {})})
            elif rec["kind"] == "comm":
                out.append({**base, "ph": "i", "s": "t", "tid": 0,
                            "name": f"comm:{rec['op']}", "cat": rec["phase"],
                            "args": {"bytes": rec["bytes"],
                                     "axes": rec["axes"],
                                     "overlapped": rec["overlapped"],
                                     "count": rec["count"]}})
            elif rec["kind"] == "metric":
                out.append({**base, "ph": "C", "tid": 0, "name": rec["name"],
                            "args": {"value": rec["value"]}})
        with open(path, "w") as f:
            json.dump({"traceEvents": out,
                       "displayTimeUnit": "ms",
                       "otherData": {"dropped_events": self.dropped}}, f)
        return len(out)
