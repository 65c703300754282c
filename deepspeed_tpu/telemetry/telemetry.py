"""The telemetry facade: one object the engines talk to.

Composes the recorder (trace.py), derived metrics (metrics.py), memory
tracker (memory.py) and stall watchdog (watchdog.py) behind a small hook
API, and fans derived metrics out to *sinks* — ``MonitorMaster``
(TensorBoard/W&B/CSV) is one sink among several; a JSONL sink writes the
same events for offline tooling (``tools/trace_view.py``).

The off contract lives here: a disabled engine holds
:data:`NULL_TELEMETRY`, which records nothing — no buffers, no locks, no
threads, and (enforced by lint + the Layer-B ``telemetry-off-parity``
audit) nothing injected into traced step code. What it keeps is one
``jax.profiler.TraceAnnotation`` per span (a flag test while no profiler
session runs), so a profiler trace of a run with telemetry off still shows
the program's spans beside the device's work. Telemetry is HOST-side either
way; enabling it must never change a jaxpr.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..utils.logging import log_dist, logger
from . import clock
from .config import TelemetryConfig, telemetry_enabled
from .memory import MemoryTracker
from .metrics import MetricsEngine, peak_flops_per_device
from .trace import (NULL_SPAN, PHASE_CHECKPOINT, PHASE_SERVING, PHASE_STEP,
                    TraceRecorder)
from .watchdog import StallWatchdog


class JsonlMetricsSink:
    """Append derived-metric events to ``metrics.jsonl`` (rank 0)."""

    enabled = True

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()

    def write_events(self, event_list) -> None:
        import json
        with self._lock, open(self.path, "a") as f:
            for tag, value, step in event_list:
                f.write(json.dumps({"tag": tag, "value": float(value),
                                    "step": int(step)}) + "\n")


class Telemetry:

    enabled = True

    def __init__(self, config: Optional[TelemetryConfig] = None,
                 sinks: Optional[List[Any]] = None,
                 rank: int = 0, n_devices: int = 1):
        self.config = config or TelemetryConfig(enabled=True)
        self.rank = rank
        self.flush_every = max(1, self.config.flush_interval or 1)
        self.output_dir = self.config.trace.output_path or "./dstpu_telemetry"
        self.trace = TraceRecorder(max_events=self.config.trace.max_events)
        self.metrics = MetricsEngine(window=self.config.metrics.window)
        self.metrics.peak_flops_total = peak_flops_per_device() * n_devices
        self.memory = MemoryTracker() if self.config.memory.enabled else None
        wd = self.config.watchdog
        self.watchdog = StallWatchdog(
            deadline_factor=wd.deadline_factor,
            min_deadline_s=wd.min_deadline_s, poll_s=wd.poll_s,
            dump_fns=[self._dump_spans], on_stall=self._on_stall,
            escalate_after_s=getattr(wd, "escalate_after_s", 0.0),
            on_escalate=self._on_escalate,
        ) if wd.enabled else None
        # set by the engine (_build_telemetry): the checkpoint-and-exit
        # hard-deadline path (docs/RESILIENCE.md); None → log-only
        self.escalation_handler: Optional[Callable[[int, float], None]] = None
        self.sinks: List[Any] = [s for s in (sinks or [])
                                 if getattr(s, "enabled", True)]
        self._step_span = None
        self._flops_fn: Optional[Callable[[], float]] = None
        self._flops_attempts = 0
        self._closed = False
        # flush-summary subscribers (the tune controller): host-side
        # callbacks fed off the flush fence, never from traced code
        self._subscribers: List[Callable[[int, Dict[str, float]], None]] = []

    # -- flush subscription (dstpu-tune, docs/AUTOTUNING.md) -------------
    def subscribe(self, callback: Callable[[int, Dict[str, float]], None]
                  ) -> Callable[[], None]:
        """Register ``callback(step, summary)`` to run at every flush,
        after the sinks. Returns an unsubscribe callable. Callbacks run
        on the flushing thread and must be cheap; a raising callback is
        logged and kept (parity with the sink contract)."""
        self._subscribers.append(callback)

        def unsubscribe() -> None:
            try:
                self._subscribers.remove(callback)
            except ValueError:
                pass
        return unsubscribe

    # -- spans -----------------------------------------------------------
    def phase(self, name: str, phase: Optional[str] = None,
              step: Optional[int] = None, req=None, **args):
        """A recorder span that is also, while it is open as a context
        manager, a ``TraceAnnotation`` of the same name in the profiler's
        trace. ``name`` is a static string: what varies goes in ``args``
        (or ``req``, the uids a serving span works for)."""
        return self.trace.span(name, phase=phase or name, step=step, req=req,
                               **args)

    def instant(self, name: str, phase: Optional[str] = None, **args) -> None:
        """A point event under the calling thread's open span."""
        self.trace.instant(name, phase=phase or name, **args)

    # -- train-step lifecycle -------------------------------------------
    def step_begin(self, step: int) -> None:
        if self._step_span is not None:
            if self._step_span.step == step:  # split fwd/bwd path re-enters
                return
            # a rejected batch / raised step abandoned its span — close it
            # so it neither leaks in the live stacks nor skews this step
            self.trace.end(self._step_span)
        self._step_span = self.trace.span("train_step", phase=PHASE_STEP,
                                          step=step)
        if self.watchdog is not None:
            self.watchdog.step_begin(step)

    def step_end(self, step: int, tokens: int = 0) -> None:
        span = self._step_span
        if span is None:
            return
        self._step_span = None
        self.trace.end(span)
        dur = span.t1 - span.t0
        excess = (self.watchdog.step_end(step, dur)
                  if self.watchdog is not None else 0.0)
        self.metrics.record_step(dur, tokens=tokens, stall_excess_s=excess)

    def checkpoint_span(self, name: str = "checkpoint", **args):
        """Checkpoint phases pause the watchdog (a long save is a pause,
        not a stall) and charge goodput's checkpoint account on exit."""
        tele = self

        class _CkptSpan:
            def __enter__(self):
                if tele.watchdog is not None:
                    tele.watchdog.pause()
                self._span = tele.trace.span(name, phase=PHASE_CHECKPOINT,
                                             **args)
                return self._span

            def __exit__(self, *exc):
                tele.trace.end(self._span)
                tele.metrics.record_checkpoint_pause(
                    self._span.t1 - self._span.t0)

        return _CkptSpan()

    # -- comm records (dist.record_collective feed) ----------------------
    def record_collective(self, op: str, nbytes: int, axes,
                          overlapped: Optional[bool] = None,
                          count: int = 1,
                          wire_bytes: Optional[int] = None) -> None:
        self.trace.comm(op, nbytes, axes, overlapped, count,
                        wire_bytes=wire_bytes)
        self.metrics.record_comm(nbytes, overlapped, count,
                                 wire_bytes=wire_bytes)

    # -- numerics guardian (resilience/guardian.py, ISSUE 13) ------------
    def record_numerics(self, step: int, loss, gnorm) -> None:
        """Per-step loss/gnorm into the anomaly reservoirs — host scalars
        the step fetched anyway; nothing here touches the device."""
        self.metrics.record_numerics(loss, gnorm)

    def record_anomaly(self, step: int, word: int, kinds) -> None:
        """A guardian sentinel fired: trace instant + anomaly counter
        (the watchdog-stall convention — instants mark the autopsy
        timeline, counters feed the flush summary)."""
        self.trace.instant("guardian:anomaly", phase=PHASE_STEP, step=step,
                           word=int(word), kinds=list(kinds))
        self.metrics.record_anomaly(word)

    def record_rollback(self, step: int, tag) -> None:
        """Guardian escalation: the run is rolling back to ``tag``."""
        self.trace.instant("guardian:rollback", phase=PHASE_STEP, step=step,
                           tag=tag)
        self.metrics.record_guardian_rollback()

    # -- out-of-core offload pipeline (ISSUE 15) -------------------------
    def record_offload_phases(self, step: int,
                              phases: Dict[str, float]) -> None:
        """One offload optimizer boundary's phase decomposition
        (h2d_prefetch / bucket_compute / d2h_writeback / nvme_io seconds,
        accumulated host-side — nothing here touches the device). Each
        phase lands as a completed span under the ``offload`` phase track
        plus a summary accumulator (``offload_*_s`` / the derived
        ``offload_stall_frac``)."""
        from .trace import PHASE_OFFLOAD
        for name, dur in phases.items():
            if dur > 0.0:
                self.trace.complete_span(f"offload/{name}", PHASE_OFFLOAD,
                                         dur, step=step)
        self.metrics.record_offload_phases(phases)

    # -- serving ---------------------------------------------------------
    def record_wave(self, kind: str, tokens: int, duration_s: float,
                    queue_depth: int = 0, running: int = 0,
                    occupancy: float = 0.0, admitted: int = 0,
                    queue_wait_s: float = 0.0,
                    counters: Optional[Dict[str, Any]] = None) -> None:
        """``duration_s`` is EXECUTE time only (compose + dispatch + fetch
        of this wave); ``queue_wait_s`` is the longest submit->schedule
        wait among the ``admitted`` requests this wave first scheduled —
        kept separate so deep queues cannot masquerade as slow forwards.
        ``counters`` is what the engine counted in the wave's dispatches
        (real against bucketed sizes, attention work, bucket keys)."""
        self.trace.instant(f"wave:{kind}", phase=PHASE_SERVING,
                           tokens=tokens, queue_depth=queue_depth,
                           running=running, occupancy=round(occupancy, 4),
                           dur_ms=round(duration_s * 1e3, 3),
                           admitted=admitted,
                           queue_wait_ms=round(queue_wait_s * 1e3, 3),
                           **({"counters": counters} if counters else {}))
        self.metrics.wave_latency.record(duration_s)
        if tokens > 0:
            self.metrics.token_latency.record(duration_s / tokens)

    def record_request(self, queue_wait_s: float, ttft_s: float) -> None:
        """Per-request TTFT attribution at first token: total TTFT, the
        queue-wait component, and the execute remainder each land in
        their own reservoir (the serving SLA scoreboard the scheduler's
        admission policy reads)."""
        self.metrics.ttft_latency.record(ttft_s)
        self.metrics.queue_wait.record(queue_wait_s)
        self.metrics.ttft_execute.record(max(0.0, ttft_s - queue_wait_s))

    # -- MFU plumbing ----------------------------------------------------
    def set_flops_fn(self, fn: Callable[[], float]) -> None:
        """Lazy model-FLOPs source (the engine's cost-analysis helper) —
        evaluated once, at the first flush, off the hot path."""
        self._flops_fn = fn

    _FLOPS_MAX_ATTEMPTS = 3

    def _resolve_flops(self) -> None:
        if (self.metrics.model_flops_per_step > 0 or self._flops_fn is None
                or self._flops_attempts >= self._FLOPS_MAX_ATTEMPTS):
            return
        self._flops_attempts += 1
        try:
            self.metrics.model_flops_per_step = float(self._flops_fn())
        except Exception as e:  # noqa: BLE001 - MFU is best-effort; a
            # transient failure (compile under memory pressure) retries at
            # the next flushes before giving up for good
            last = self._flops_attempts >= self._FLOPS_MAX_ATTEMPTS
            logger.warning(
                f"telemetry: model-FLOPs resolution failed ({e}); "
                + ("MFU unavailable" if last
                   else f"retrying at the next flush "
                        f"({self._flops_attempts}/{self._FLOPS_MAX_ATTEMPTS})"))

    # -- flush / export --------------------------------------------------
    def flush(self, step: int) -> List:
        """Fence point: re-anchor the clock, sample memory, compute the
        derived metrics, and write them to every sink. Returns the event
        list (also recorded as trace counter tracks)."""
        clock.fence("telemetry-flush")
        self._resolve_flops()
        summary = self.metrics.summary()
        events = [(f"Telemetry/{k}", v, step) for k, v in summary.items()]
        if self.memory is not None:
            sample = self.memory.sample(tag=f"step{step}")
            events += [(f"Telemetry/memory/{k}", float(v), step)
                       for k, v in sample.items() if k != "tag"]
        for tag, value, s in events:
            self.trace.metric(tag, value, step=s)
        for sink in self.sinks:
            try:
                sink.write_events(events)
            except Exception as e:  # noqa: BLE001 - a broken sink must not
                logger.warning(f"telemetry sink {type(sink).__name__} "
                               f"failed: {e}")          # kill the training loop
        for cb in list(self._subscribers):
            try:
                cb(step, summary)
            except Exception as e:  # noqa: BLE001 - subscriber parity with
                logger.warning(f"telemetry subscriber failed: {e}")  # sinks
        return events

    def export(self) -> Dict[str, str]:
        """Write the trace exports; returns {kind: path}."""
        os.makedirs(self.output_dir, exist_ok=True)
        chrome = os.path.join(self.output_dir,
                              f"trace.rank{self.rank}.chrome.json")
        jsonl = os.path.join(self.output_dir, f"trace.rank{self.rank}.jsonl")
        self.trace.export_chrome_trace(chrome)
        self.trace.export_jsonl(jsonl)
        return {"chrome": chrome, "jsonl": jsonl}

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self.watchdog is not None:
            self.watchdog.stop()
        try:
            # final flush so serving-only processes (which never hit the
            # training engine's per-step flush) still land their derived
            # metrics — latency percentiles included — in the exports
            if self.metrics.steps or len(self.metrics.wave_latency):
                self.flush(self.metrics.steps)
            paths = self.export()
            log_dist(f"telemetry: trace exported to {paths['chrome']}",
                     ranks=[0])
        except Exception as e:  # noqa: BLE001 - exit paths must not raise
            logger.warning(f"telemetry export failed: {e}")

    # -- watchdog plumbing ----------------------------------------------
    def _dump_spans(self) -> str:
        lines = []
        for tid, stack in self.trace.active_stacks().items():
            chain = " > ".join(f"{name}({open_s:.1f}s)"
                               for name, open_s in stack)
            lines.append(f"  thread {tid}: {chain}")
        return ("live span stacks:\n" + "\n".join(lines)) if lines \
            else "live span stacks: <none>"

    def _on_stall(self, step: int, elapsed: float) -> None:
        self.trace.instant("stall", phase=PHASE_STEP, step=step,
                           elapsed_s=round(elapsed, 3))

    def _on_escalate(self, step: int, elapsed: float) -> None:
        """Hard-deadline escalation: record the event (the trace is about
        to be exported by the handler's exit path), then hand off to the
        engine's checkpoint-and-exit handler."""
        self.trace.instant("stall_escalation", phase=PHASE_STEP, step=step,
                           elapsed_s=round(elapsed, 3))
        if self.escalation_handler is not None:
            self.escalation_handler(step, elapsed)


class NullTelemetry:
    """The disabled path: nothing is recorded, no state, no threads, no
    syncs, nothing for traced code to capture. A span is still a
    ``TraceAnnotation`` (a TraceMe: a flag test while no profiler session
    runs), so a profiler trace shows the program's spans with telemetry
    off; every other hook is a constant no-op."""

    enabled = False
    watchdog = None
    memory = None

    def phase(self, name, phase=None, step=None, req=None, **args):
        return TraceAnnotation(name)

    def instant(self, name, phase=None, **args):
        pass

    def checkpoint_span(self, name="checkpoint", **args):
        return NULL_SPAN

    def step_begin(self, step):
        pass

    def step_end(self, step, tokens=0):
        pass

    def record_collective(self, op, nbytes, axes, overlapped=None, count=1,
                          wire_bytes=None):
        pass

    def record_wave(self, *a, **k):
        pass

    def record_request(self, *a, **k):
        pass

    def record_numerics(self, *a, **k):
        pass

    def record_anomaly(self, *a, **k):
        pass

    def record_rollback(self, *a, **k):
        pass

    def record_offload_phases(self, *a, **k):
        pass

    def set_flops_fn(self, fn):
        pass

    def subscribe(self, callback):
        return lambda: None

    def flush(self, step):
        return []

    def export(self):
        return {}

    def close(self):
        pass


NULL_TELEMETRY = NullTelemetry()

_GLOBAL: Optional[Telemetry] = None


def get_telemetry():
    """The process-global telemetry (NULL when none configured) — how
    code without an engine handle (comm frontend, inference scheduler)
    reaches the active recorder."""
    return _GLOBAL if _GLOBAL is not None else NULL_TELEMETRY


def set_telemetry(tele: Optional[Telemetry]) -> None:
    global _GLOBAL
    if _GLOBAL is not None and tele is not _GLOBAL:
        _GLOBAL.close()
    _GLOBAL = tele


def reset_telemetry() -> None:
    """Drop the global WITHOUT the close-time export — the test harness's
    between-test cleanup (a closing export would litter the cwd)."""
    global _GLOBAL
    if _GLOBAL is not None:
        if _GLOBAL.watchdog is not None:
            _GLOBAL.watchdog.stop()
        _GLOBAL._closed = True
        _GLOBAL = None


def build_telemetry(config: Optional[TelemetryConfig],
                    sinks: Optional[List[Any]] = None,
                    make_global: bool = True):
    """Engine front door: NULL when disabled (config + DSTPU_TELEMETRY
    env), else a live Telemetry registered as the process global."""
    if not telemetry_enabled(config):
        return NULL_TELEMETRY
    try:
        import jax
        rank, n_dev = jax.process_index(), jax.device_count()
    except Exception:  # pragma: no cover - no backend
        rank, n_dev = 0, 1
    tele = Telemetry(config=config, sinks=sinks if rank == 0 else [],
                     rank=rank, n_devices=n_dev)
    if make_global:
        set_telemetry(tele)
        _register_atexit_once()
    return tele


_ATEXIT_REGISTERED = False


def _register_atexit_once() -> None:
    """One process-wide hook closing whatever the CURRENT global is at
    exit — per-instance registration would pin every Telemetry (and its
    event deque) ever built for the process lifetime."""
    global _ATEXIT_REGISTERED
    if _ATEXIT_REGISTERED:
        return
    _ATEXIT_REGISTERED = True
    import atexit
    atexit.register(lambda: _GLOBAL is not None and _GLOBAL.close())


def maybe_enable_from_env() -> None:
    """Serving entry points call this: DSTPU_TELEMETRY=1 with no engine
    in the process still gets a default recorder."""
    if _GLOBAL is None and telemetry_enabled(None):
        build_telemetry(TelemetryConfig(enabled=True))
