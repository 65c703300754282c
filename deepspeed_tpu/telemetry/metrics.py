"""Derived-metrics engine: step percentiles, tokens/sec, MFU, goodput.

Definitions (documented in docs/OBSERVABILITY.md):

- **step time p50/p90/p99** — host wall time per optimizer step over a
  rolling window.
- **tokens/sec** — tokens consumed by the window's steps / window wall
  time.
- **MFU** — ``model_flops_per_step / (step_time * peak_flops_total)``.
  The numerator is the SAME number the flops profiler reports (XLA's
  ``cost_analysis()`` of the compiled micro step × accumulation steps), so
  the two surfaces can never disagree about the model's arithmetic; the
  denominator comes from the per-platform peak table below
  (``DSTPU_PEAK_FLOPS`` overrides, e.g. for a downclocked pod).
- **goodput** — productive fraction of wall time:
  ``productive / (productive + lost)`` where *lost* is stall overrun
  (time beyond the watchdog deadline on flagged steps), checkpoint pauses,
  and any other explicitly-reported non-productive time. A healthy run
  sits near 1.0; goodput diverging from 1.0 while step p50 stays flat
  means the loss is BETWEEN steps, not in them.
- **overlap efficiency** — overlapped / (overlapped + exposed) traced
  collective bytes from ``dist.record_collective`` (see
  docs/ZERO_OVERLAP.md: under XLA the honest unit is bytes by schedule
  class, not per-op wall time).
"""

from __future__ import annotations

import os
from collections import deque
from typing import Dict, List, Optional

# Peak dense bf16 FLOP/s per chip, keyed by ``device_kind`` exactly as JAX
# reports it — the package's ONE peaks table.
# Source: Google Cloud TPU documentation, the system-architecture page of
# each generation ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
PEAK_FLOPS_BY_KIND = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v4 lite": 138e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v6 lite": 918e12,   # v6e / Trillium
    "TPU v6e": 918e12,
}
#: nominal, so MFU stays finite on the CPU test meshes; never a device metric
CPU_NOMINAL_PEAK_FLOPS = 1e12


def peak_flops_per_device(device_kind: Optional[str] = None) -> float:
    """Per-device peak from the table; ``DSTPU_PEAK_FLOPS`` (per-device,
    in FLOPs) overrides. A device that is neither a CPU nor in the table
    is an error, not a default."""
    env = os.environ.get("DSTPU_PEAK_FLOPS")
    if env:
        return float(env)
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    if device_kind.lower() == "cpu":
        return CPU_NOMINAL_PEAK_FLOPS
    if device_kind not in PEAK_FLOPS_BY_KIND:
        raise ValueError(
            f"no peak FLOP/s known for device_kind {device_kind!r}: add it "
            f"to telemetry.metrics.PEAK_FLOPS_BY_KIND with its source, or "
            f"set DSTPU_PEAK_FLOPS")
    return PEAK_FLOPS_BY_KIND[device_kind]


def percentile(sorted_vals: List[float], p: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(p / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class LatencyHistogram:
    """Bounded sample reservoir for serving latencies (per-token /
    per-wave). Keeps the newest ``cap`` samples — serving percentiles are
    about the current regime, not the whole run."""

    def __init__(self, cap: int = 4096):
        self._samples: deque = deque(maxlen=cap)

    def record(self, seconds: float) -> None:
        self._samples.append(float(seconds))

    def __len__(self) -> int:
        return len(self._samples)

    def percentiles(self, ps=(50, 90, 99)) -> Dict[str, float]:
        vals = sorted(self._samples)
        return {f"p{p}": percentile(vals, p) for p in ps}


class MetricsEngine:

    def __init__(self, window: int = 128):
        self.window = max(2, int(window))
        self._durations: deque = deque(maxlen=self.window)
        self._tokens: deque = deque(maxlen=self.window)
        self.steps = 0
        self.total_tokens = 0
        # goodput accounting (seconds)
        self.productive_s = 0.0
        self.stall_lost_s = 0.0
        self.checkpoint_lost_s = 0.0
        self.stalled_steps = 0
        # comm schedule-class byte totals (trace-time records)
        self.comm_overlapped_bytes = 0
        self.comm_exposed_bytes = 0
        # transport accounting: logical vs wire bytes across ALL records
        # (untagged included) — the quantized-transport scoreboard
        self.comm_logical_bytes = 0
        self.comm_wire_bytes = 0
        # model arithmetic for MFU — set once by the engine from the flops
        # profiler's cost-analysis machinery
        self.model_flops_per_step: float = 0.0
        self.peak_flops_total: float = 0.0
        # serving
        self.token_latency = LatencyHistogram()
        self.wave_latency = LatencyHistogram()
        # per-REQUEST serving reservoirs (ISSUE 6): TTFT decomposed into
        # queue wait (submit -> first scheduled) and execute (first
        # scheduled -> first token), so deep queues attribute latency to
        # admission rather than to the forward pass
        self.ttft_latency = LatencyHistogram()
        self.queue_wait = LatencyHistogram()
        self.ttft_execute = LatencyHistogram()
        # numerics anomaly reservoirs (dstpu-guardian, ISSUE 13): rolling
        # loss/gnorm samples — the observability twin of the guardian's
        # own spike-threshold stats — plus escalation counters
        self.loss_values = LatencyHistogram()
        self.gnorm_values = LatencyHistogram()
        self.anomaly_steps = 0
        self.anomaly_word_union = 0
        self.guardian_rollbacks = 0
        # out-of-core offload phase accounting (ISSUE 15): cumulative
        # seconds per pipeline phase — the decomposition of the old
        # scalar offload_stall_frac (docs/OBSERVABILITY.md)
        self.offload_phase_s: Dict[str, float] = {}

    # -- feeding ---------------------------------------------------------
    def record_step(self, duration_s: float, tokens: int = 0,
                    stall_excess_s: float = 0.0) -> None:
        self.steps += 1
        self._durations.append(float(duration_s))
        self._tokens.append(int(tokens))
        self.total_tokens += int(tokens)
        self.productive_s += max(0.0, duration_s - stall_excess_s)
        if stall_excess_s > 0.0:
            self.stall_lost_s += stall_excess_s
            self.stalled_steps += 1

    def record_checkpoint_pause(self, seconds: float) -> None:
        self.checkpoint_lost_s += max(0.0, float(seconds))

    def record_numerics(self, loss: Optional[float],
                        gnorm: Optional[float]) -> None:
        """Per-step loss/gnorm samples into the anomaly reservoirs (only
        finite values — the reservoirs describe the healthy regime the
        spike thresholds are judged against)."""
        import math
        if loss is not None and math.isfinite(loss):
            self.loss_values.record(abs(float(loss)))
        if gnorm is not None and math.isfinite(gnorm) and gnorm > 0.0:
            self.gnorm_values.record(float(gnorm))

    def record_anomaly(self, word: int) -> None:
        self.anomaly_steps += 1
        self.anomaly_word_union |= int(word)

    def record_guardian_rollback(self) -> None:
        self.guardian_rollbacks += 1

    def record_offload_phases(self, phases: Dict[str, float]) -> None:
        """Per-step offload pipeline phase seconds (h2d_prefetch /
        bucket_compute / d2h_writeback / nvme_io)."""
        for k, v in phases.items():
            self.offload_phase_s[k] = \
                self.offload_phase_s.get(k, 0.0) + max(0.0, float(v))

    def record_comm(self, nbytes: int, overlapped: Optional[bool],
                    count: int = 1,
                    wire_bytes: Optional[int] = None) -> None:
        if overlapped is True:
            self.comm_overlapped_bytes += int(nbytes) * int(count)
        elif overlapped is False:
            self.comm_exposed_bytes += int(nbytes) * int(count)
        self.comm_logical_bytes += int(nbytes) * int(count)
        self.comm_wire_bytes += int(nbytes if wire_bytes is None
                                    else wire_bytes) * int(count)

    def wire_ratio(self) -> Optional[float]:
        """wire / logical collective bytes (1.0 = full width everywhere;
        the transport planner's byte win, docs/COLLECTIVES.md)."""
        if self.comm_logical_bytes == 0:
            return None
        return self.comm_wire_bytes / self.comm_logical_bytes

    # -- derived ---------------------------------------------------------
    def step_percentiles(self, ps=(50, 90, 99)) -> Dict[str, float]:
        vals = sorted(self._durations)
        return {f"p{p}": percentile(vals, p) for p in ps}

    def mean_step_s(self) -> float:
        if not self._durations:
            return 0.0
        return sum(self._durations) / len(self._durations)

    def tokens_per_sec(self) -> float:
        wall = sum(self._durations)
        return (sum(self._tokens) / wall) if wall > 0 else 0.0

    def mfu(self) -> float:
        step = self.mean_step_s()
        if step <= 0 or self.model_flops_per_step <= 0 \
                or self.peak_flops_total <= 0:
            return 0.0
        return self.model_flops_per_step / (step * self.peak_flops_total)

    def feasibility_cross_check(self, entry: str,
                                plans_dir: Optional[str] = None,
                                rel_tol: float = 0.5) -> Optional[Dict]:
        """Cross-check the MFU numerator against Layer E's committed
        static prediction (``tools/feasibility/<entry>.json``,
        ``dstpu plan --update-artifacts``).

        ``model_flops_per_step`` is what the engine measured through the
        flops profiler; ``predicted_step_flops`` is what the feasibility
        oracle derived from the compiled HLO without running a step. A
        ratio drifting outside ``[1 - rel_tol, 1 / (1 - rel_tol)]`` means
        the committed verdict no longer describes the program that is
        actually running (stale artifact, diverged config) — the same
        drift the tier-1 freshness gate catches at commit time, caught
        here at run time. Advisory only: never called on the hot path,
        returns None when either side is missing."""
        if self.model_flops_per_step <= 0:
            return None
        from ..analysis.feasibility import (default_plans_dir,
                                            load_verdict_artifact)
        artifact = load_verdict_artifact(plans_dir or default_plans_dir(),
                                         entry)
        if artifact is None:
            return None
        predicted = float(artifact.get("predicted_step_flops") or 0.0)
        if predicted <= 0.0:
            return None
        ratio = self.model_flops_per_step / predicted
        lo = max(0.0, 1.0 - rel_tol)
        hi = 1.0 / lo if lo > 0 else float("inf")
        return {"entry": entry,
                "predicted_step_flops": predicted,
                "model_flops_per_step": self.model_flops_per_step,
                "ratio": ratio,
                "consistent": lo <= ratio <= hi}

    def goodput(self) -> float:
        lost = self.stall_lost_s + self.checkpoint_lost_s
        total = self.productive_s + lost
        return (self.productive_s / total) if total > 0 else 1.0

    def tuning_objective(self) -> float:
        """The autotuner's composite score: ``mfu() * goodput()`` —
        hardware efficiency discounted by the fraction of wall time the
        run actually trained (docs/AUTOTUNING.md). 0.0 until MFU is
        resolvable (no model-FLOPs source, or no steps yet), so a
        candidate that never produced a measurable step never wins."""
        return self.mfu() * self.goodput()

    def overlap_efficiency(self) -> Optional[float]:
        total = self.comm_overlapped_bytes + self.comm_exposed_bytes
        if total == 0:
            return None
        return self.comm_overlapped_bytes / total

    def summary(self) -> Dict[str, float]:
        out = {
            "steps": float(self.steps),
            "step_time_mean_s": self.mean_step_s(),
            "tokens_per_sec": self.tokens_per_sec(),
            "goodput": self.goodput(),
            # always present (0.0 while MFU is unresolved) — the
            # controller and trial runner key on it unconditionally
            "tuning_objective": self.tuning_objective(),
            "stalled_steps": float(self.stalled_steps),
        }
        out.update({f"step_time_{k}_s": v
                    for k, v in self.step_percentiles().items()})
        if self.model_flops_per_step > 0:
            out["mfu"] = self.mfu()
            out["model_flops_per_step"] = self.model_flops_per_step
        ov = self.overlap_efficiency()
        if ov is not None:
            out["comm_overlap_efficiency"] = ov
        wr = self.wire_ratio()
        if wr is not None:
            out["comm_wire_ratio"] = wr
            out["comm_wire_bytes"] = float(self.comm_wire_bytes)
            out["comm_logical_bytes"] = float(self.comm_logical_bytes)
        if len(self.token_latency):
            out.update({f"token_latency_{k}_s": v for k, v in
                        self.token_latency.percentiles().items()})
        if len(self.ttft_latency):
            out.update({f"ttft_{k}_s": v for k, v in
                        self.ttft_latency.percentiles().items()})
            out.update({f"queue_wait_{k}_s": v for k, v in
                        self.queue_wait.percentiles().items()})
        if self.offload_phase_s:
            # the stall-decomposition keys (ISSUE 15): per-phase seconds
            # plus the blocked fraction of the offload boundary — what
            # the double-buffered pipeline exists to shrink
            for k, v in self.offload_phase_s.items():
                out[f"offload_{k}_s"] = v
            compute = self.offload_phase_s.get("bucket_compute", 0.0)
            blocked = sum(v for k, v in self.offload_phase_s.items()
                          if k != "bucket_compute")
            if compute + blocked > 0:
                out["offload_stall_frac"] = blocked / (compute + blocked)
        if self.anomaly_steps or self.guardian_rollbacks:
            out["anomaly_steps"] = float(self.anomaly_steps)
            out["guardian_rollbacks"] = float(self.guardian_rollbacks)
        if len(self.gnorm_values):
            out.update({f"gnorm_{k}": v for k, v in
                        self.gnorm_values.percentiles().items()})
        if len(self.loss_values):
            out.update({f"loss_{k}": v for k, v in
                        self.loss_values.percentiles().items()})
        return out
