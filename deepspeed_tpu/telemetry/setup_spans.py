"""Set-up seen from inside: what building an engine and the first call of
each of its programs cost, phase by phase.

Three pieces, all on the one primitive ``telemetry.phase`` (a TraceMe
always, a recorder span when telemetry is on) and all kept as plain
values with telemetry off:

* :class:`SetupTotals` owns ``engine.setup_totals``: the seconds of the
  package's import, of ``initialize`` and its parts, one entry for every
  program's first call, their sums, and the ten dearest traced functions.
  It is written during set-up and when something compiles later, never on
  a step. "Set-up" ends when the first optimizer step has returned
  (:meth:`SetupTotals.finish`).
* :class:`FirstCall` is the ONE first-call mechanism of the repository
  (the training engine and ``inference/v2`` both go through it): a span
  ``first_call`` around the call that traces, lowers, compiles or loads
  and runs a program for shapes not met before, and on closing an instant
  ``compile:<program>`` with the seconds of each.
* The seconds inside a first call come from JAX's own monitoring events
  (``jax._src.dispatch``: the durations of tracing to a jaxpr, of the
  conversion to MLIR and of the backend's compile, which is the cache's
  load when it hits). The listeners are registered once per process when
  this module is imported, are the same with telemetry on or off, and run
  only when JAX traces or compiles: the hot path has nothing of them. The
  events are on ``time.time()`` and the recorder on ``perf_counter``, so
  only DURATIONS are taken from them; the enclosing ``first_call`` span
  places them. A jit inside a jit fires its own trace event inside its
  parent's (and a function traced while another is lowered, inside that
  lowering): a depth count per thread gives the seconds to the OUTERMOST
  stretch, so the three never overlap, and keeps every traced function
  by name (``traced_functions``: calls and seconds, a parent's seconds
  holding its children's).

:func:`flat_totals` makes of the engine's counters the one flat dict that
rides in the profiler's trace (``TraceAnnotation("engine_totals", ...)``,
docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import logging
import numbers
import re
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax.monitoring as monitoring

from ..utils.logging import log_dist
from . import clock
from .telemetry import get_telemetry
from .trace import NULL_SPAN, PHASE_SETUP

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: the entry of ``programs`` for what ``initialize`` dispatches one
#: operation at a time, each its own little program
EAGER = "(eager)"
#: the parts of ``initialize`` that are spanned (each read 50 ms or more on
#: the chip in some cell of the benchmark; PERF.md, PR 36)
INITIALIZE_PARTS = ("config_topology", "zero_plan", "init_state")
#: how many of the traced functions ``traced_functions`` keeps
DEAREST = 10

#: seconds of the program's own import: ``import deepspeed_tpu`` (written by
#: the package's ``__init__``, which reads the clock at its top and bottom:
#: no span object can exist while it runs) and what :class:`importing` adds
import_s = 0.0


class importing:
    """``with setup_spans.importing(): from .x import ...`` in the
    ``__init__`` of a subpackage that the package's own import leaves out and
    a user's first line brings in (``deepspeed_tpu.models``, with the Pallas
    kernels behind it): its seconds join ``import_s``."""

    def __enter__(self) -> None:
        self._t0 = clock.now()

    def __exit__(self, *exc) -> None:
        global import_s
        import_s += clock.now() - self._t0


class _Account:
    """What JAX reported while this was its thread's sink."""

    __slots__ = ("trace_s", "lower_s", "compile_s", "traces", "compiles",
                 "cache_hits", "remat_plan_s", "functions")

    def __init__(self):
        self.trace_s = self.lower_s = self.compile_s = self.remat_plan_s = 0.0
        self.traces = self.compiles = self.cache_hits = 0
        self.functions: Dict[str, List[float]] = {}   # name -> [calls, seconds]

    def cache(self) -> Optional[str]:
        if not self.compiles:
            return None
        if self.cache_hits >= self.compiles:
            return "hit"
        return "mixed" if self.cache_hits else "miss"


class _Thread(threading.local):
    sink: Optional[_Account] = None      # where this thread's events go
    depth = 0                            # timed stretches open on this thread
    record: Optional["SetupTotals"] = None   # an ``initialize`` no engine took yet


_thread = _Thread()


_TIMED = {TRACE_EVENT: "trace_s", LOWER_EVENT: "lower_s", COMPILE_EVENT: "compile_s"}


def _on_scalar(event: str, value: float, **_) -> None:
    # JAX announces the START of each timed stretch as a scalar
    if event in _TIMED:
        _thread.depth += 1


def _on_duration(event: str, secs: float, fun_name: str = "", **_) -> None:
    kind = _TIMED.get(event)
    if kind is None:
        return
    depth = _thread.depth = max(_thread.depth - 1, 0)
    sink = _thread.sink
    if sink is None:
        return
    if depth == 0:
        # the OUTERMOST stretch owns its seconds: a jit traced inside a
        # jit, a function traced while another is lowered and a constant
        # compiled while a function is traced are inside their parent's
        setattr(sink, kind, getattr(sink, kind) + secs)
    if event == TRACE_EVENT:
        sink.traces += depth == 0
        entry = sink.functions.setdefault(fun_name, [0, 0.0])
        entry[0] += 1
        entry[1] += secs
    elif event == COMPILE_EVENT:
        sink.compiles += 1


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT and _thread.sink is not None:
        _thread.sink.cache_hits += 1


monitoring.register_scalar_listener(_on_scalar)
monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_listener(_on_event)


def _merge(into: Dict[str, List[float]], functions: Dict[str, List[float]]) -> None:
    for name, (calls, secs) in functions.items():
        entry = into.setdefault(name, [0, 0.0])
        entry[0] += calls
        entry[1] += secs


class FirstCall:
    """``with FirstCall(program, telemetry): jitted(...)`` around the call
    that compiles ``program``: a span ``first_call`` carrying the static
    program name, JAX's events collected into ``numbers`` (``trace_s``,
    ``lower_s``, ``compile_s``, ``cache`` = ``hit`` / ``miss`` / ``mixed``
    / None, ``run_s`` = the call's wall less the three, ``wall_s``,
    ``remat_plan_s``) and on closing one instant ``compile:<program>`` with
    them (and ``args``, e.g. a serving bucket's ``key``)."""

    def __init__(self, program: str, telemetry, phase: str = PHASE_SETUP,
                 on_close: Optional[Callable[["FirstCall"], None]] = None,
                 **args):
        self.program, self.telemetry, self.phase = program, telemetry, phase
        self.on_close, self.args = on_close, args
        self.account = _Account()
        self.numbers: Dict[str, Any] = {}

    def __enter__(self) -> "FirstCall":
        self._span = self.telemetry.phase("first_call", phase=self.phase,
                                          program=self.program)
        self._span.__enter__()
        self._outer, _thread.sink = _thread.sink, self.account
        self._t0 = clock.now()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = clock.now()
        wall = self.t1 - self._t0
        _thread.sink = self._outer
        self._span.__exit__(*exc)
        a = self.account
        self.numbers = {
            "trace_s": a.trace_s, "lower_s": a.lower_s,
            "compile_s": a.compile_s, "cache": a.cache(),
            "run_s": wall - (a.trace_s + a.lower_s + a.compile_s),
            "wall_s": wall, "remat_plan_s": a.remat_plan_s}
        if self.on_close is not None:
            self.on_close(self)
        self.telemetry.instant(
            f"compile:{self.program}", phase=self.phase, **self.args,
            seconds=round(wall, 4),
            **{k: round(v, 4) if isinstance(v, float) else v
               for k, v in self.numbers.items() if k != "wall_s"})


class remat_plan:
    """The span around what ``checkpointing``'s kept block does when it is
    first traced (differentiating the block to read names and bytes, the
    liveness walk, the choice): code with no engine in hand, so the global
    telemetry's span, and the seconds beside the open first call's
    ``trace_s`` (they lie inside it)."""

    def __enter__(self) -> None:
        self._span = get_telemetry().phase("remat_plan", phase=PHASE_SETUP)
        self._span.__enter__()
        self._t0 = clock.now()

    def __exit__(self, *exc) -> None:
        secs = clock.now() - self._t0
        self._span.__exit__(*exc)
        if _thread.sink is not None:
            _thread.sink.remat_plan_s += secs


class _Phase:
    """One part of set-up outside any program's first call."""

    def __init__(self, record: "SetupTotals", name: str):
        self.record, self.name = record, name

    def __enter__(self) -> "SetupTotals":
        tele = get_telemetry()
        self._recorded = tele.enabled
        self._span = tele.phase(self.name, phase=PHASE_SETUP)
        self._span.__enter__()
        record = self.record
        record._open_phases += 1
        self._outer = _thread.sink
        if self._outer is None:
            _thread.sink = record._eager
        self._t0 = clock.now()
        return record

    def __exit__(self, *exc) -> None:
        t1 = clock.now()
        _thread.sink = self._outer
        self._span.__exit__(*exc)
        self.record._close_phase(self.name, t1, t1 - self._t0, self._recorded)


class SetupTotals:
    """The owner of ``engine.setup_totals`` (``totals``; every key is there
    from the start). ``open`` until the first optimizer step has returned;
    ``version`` rises with every write, so that a copy can follow it."""

    def __init__(self):
        self.open = True
        self.version = 0
        #: the first-call book: (program, key of the call's shapes) -> numbers
        self.seen: Dict[Tuple[str, Any], Dict[str, Any]] = {}
        self._eager = _Account()
        self._functions: Dict[str, List[float]] = {}
        self._open_phases = 0
        # phases closed while no recorder existed (the engine builds its
        # telemetry late in its constructor): replay() writes them
        self._early: List[Tuple[str, float, float, Dict[str, Any]]] = []
        self.totals: Dict[str, Any] = {
            "import_s": import_s, "initialize_s": None,
            **{f"{part}_s": 0.0 for part in INITIALIZE_PARTS},
            "programs": {},
            "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0, "run_s": 0.0,
            "remat_plan_s": 0.0, "first_calls_s": 0.0, "program_s": None,
            "programs_compiled": 0, "cache_hits": 0, "cache_misses": 0,
            "compiled_after_setup": 0, "traced_functions": {}}

    # -- phases ----------------------------------------------------------
    def phase(self, name: str) -> _Phase:
        """``with record.phase("initialize"): ...``: a span, and the
        seconds into ``totals["<name>_s"]``. What JAX traces and compiles
        in it outside a first call goes to ``programs["(eager)"]``."""
        return _Phase(self, name)

    def parts(self) -> _Parts:
        """``with record.parts() as part: part("a"); ...; part("b"); ...``"""
        return _Parts(self)

    def _close_phase(self, name: str, t1: float, secs: float,
                     recorded: bool) -> None:
        key = f"{name}_s"
        self.totals[key] = (self.totals.get(key) or 0.0) + secs
        self._open_phases -= 1
        self.version += 1
        if not recorded:
            self._record_late(name, t1, secs)

    def _record_late(self, name: str, t1: float, secs: float, **args) -> None:
        """A span that opened while no recorder existed (the engine builds
        its telemetry late in its constructor)."""
        tele = get_telemetry()
        if tele.enabled:        # the recorder came to be while it was open
            tele.trace.complete_span(name, PHASE_SETUP, secs, end=t1, **args)
        elif self.open:
            self._early.append((name, t1, secs, args))

    def replay(self, telemetry) -> None:
        """Write the spans that closed before ``telemetry``'s recorder
        existed into it, where they were."""
        early, self._early = self._early, []
        if early:
            telemetry.trace.start_no_later_than(
                min(t1 - secs for _, t1, secs, _ in early))
        for name, t1, secs, args in early:
            telemetry.trace.complete_span(name, PHASE_SETUP, secs, end=t1, **args)

    # -- first calls -----------------------------------------------------
    def first_call(self, program: str, key, telemetry, phase: str = PHASE_SETUP):
        """The first-call door: a :class:`FirstCall` for a ``(program,
        key)`` not met before, a constant no-op for one that was."""
        if (program, key) in self.seen:
            return NULL_SPAN
        return FirstCall(program, telemetry, phase,
                         on_close=lambda call: self._close_call(key, call))

    def _close_call(self, key, call: FirstCall) -> None:
        a, t = call.account, self.totals
        self.seen[(call.program, key)] = call.numbers
        if self._open_phases and not call.telemetry.enabled:
            self._record_late("first_call", call.t1, call.numbers["wall_s"],
                              program=call.program)
        compiled = bool(a.traces or a.compiles)
        # a program that compiles again (new shapes) gets an entry of its own
        name, n = call.program, 1
        while name in t["programs"]:
            n += 1
            name = f"{call.program}#{n}"
        t["programs"][name] = dict(
            call.numbers, in_initialize=self._open_phases > 0,
            after_setup=not self.open)
        if compiled:
            t["programs_compiled"] += 1
            t["cache_hits"] += a.cache_hits
            t["cache_misses"] += a.compiles - a.cache_hits
        _merge(self._functions, a.functions)
        self._rank_functions()
        if self.open:
            for k in ("trace_s", "lower_s", "compile_s", "run_s", "remat_plan_s"):
                t[k] += call.numbers[k]
            t["first_calls_s"] += call.numbers["wall_s"]
        elif compiled:
            t["compiled_after_setup"] += 1
            got = call.numbers
            log_dist(
                f"{call.program} compiled after set-up (call shapes {key}): "
                f"{got['wall_s']:.3f} s (trace {got['trace_s']:.3f}, lower "
                f"{got['lower_s']:.3f}, compile {got['compile_s']:.3f}, cache "
                f"{got['cache']})", ranks=[0], level=logging.WARNING)
        self.version += 1

    def _rank_functions(self) -> None:
        ranked = sorted(self._functions.items(), key=lambda kv: -kv[1][1])
        self.totals["traced_functions"] = {
            name: [int(calls), secs] for name, (calls, secs) in ranked[:DEAREST]}

    # -- the end of set-up -----------------------------------------------
    def finish(self) -> None:
        """The first optimizer step has returned: close the account."""
        t, e = self.totals, self._eager
        self.open = False
        self._early = []
        if e.traces or e.compiles:
            t["programs"][EAGER] = {
                "traces": e.traces, "compiles": e.compiles,
                "trace_s": e.trace_s, "lower_s": e.lower_s,
                "compile_s": e.compile_s, "cache_hits": e.cache_hits}
            _merge(self._functions, e.functions)
            self._rank_functions()
        inside = t["initialize_s"]
        if inside is None:      # an engine built without ``initialize``
            inside = sum(t[f"{part}_s"] for part in INITIALIZE_PARTS)
        t["program_s"] = t["import_s"] + inside + sum(
            p["wall_s"] for p in t["programs"].values()
            if "wall_s" in p and not p["in_initialize"] and not p["after_setup"])
        self.version += 1


class _Parts:
    """The parts of one call, one after the other: ``part(name)`` closes
    the part that is open and opens the next (None: none), and leaving the
    block closes the last, whatever ended it."""

    def __init__(self, record: "SetupTotals"):
        self.record = record
        self._open: Optional[_Phase] = None

    def __enter__(self) -> "_Parts":
        return self

    def __call__(self, name: Optional[str]) -> None:
        self.__exit__(None, None, None)
        if name is not None:
            self._open = self.record.phase(name)
            self._open.__enter__()

    def __exit__(self, *exc) -> None:
        part, self._open = self._open, None
        if part is not None:
            part.__exit__(*exc)


class _Initialize(_Phase):
    """The ``initialize`` span, opened before the engine exists: the engine
    built inside it takes its record (:func:`take`)."""

    def __enter__(self) -> "SetupTotals":
        _thread.record = self.record
        return super().__enter__()

    def __exit__(self, *exc) -> None:
        if _thread.record is self.record:    # no engine took it: it raised
            _thread.record = None
        super().__exit__(*exc)


def initializing() -> _Initialize:
    """``with setup_spans.initializing(): engine = Engine(...)`` in
    ``deepspeed_tpu.initialize``."""
    return _Initialize(SetupTotals(), "initialize")


def take() -> SetupTotals:
    """The record of the ``initialize`` this thread is in, once; else a new
    one (an engine built by its constructor alone)."""
    record, _thread.record = _thread.record, None
    return record if record is not None else SetupTotals()


# ---------------------------------------------------------------------------
# the counters as one flat dict (``engine_totals`` in the profiler's trace)
# ---------------------------------------------------------------------------

_SPLIT = re.compile(r"[#,=\s]+")    # the TraceMe encoding splits on these


def flat_totals(**counters: Dict[str, Any]) -> Dict[str, Any]:
    """``flat_totals(setup={...}, moe={...})`` -> ``{"setup.import_s": ..,
    "moe.products_kernel.forward": ..}``: scalars and short strings, keys
    dotted by counter; a tuple of names joined by ``+``; None left out; no
    ``,``, ``#`` or ``=`` in a key or a value."""
    out: Dict[str, Any] = {}

    def put(key: str, value: Any) -> None:
        if value is None:
            return
        if isinstance(value, dict):
            for k, v in value.items():
                put(f"{key}.{_SPLIT.sub('_', str(k))}", v)
        elif isinstance(value, bool):
            out[key] = int(value)
        elif isinstance(value, numbers.Integral):
            out[key] = int(value)
        elif isinstance(value, numbers.Real):
            out[key] = float(value)
        elif isinstance(value, (tuple, list)):
            out[key] = "+".join(_SPLIT.sub("_", f"{v:.6g}" if isinstance(v, float)
                                           else str(v)) for v in value)
        else:
            out[key] = _SPLIT.sub("_", str(value))

    for name, counter in counters.items():
        put(name, counter)
    return out
