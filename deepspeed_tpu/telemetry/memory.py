"""Memory telemetry: the allocator's reading, a step's bytes made of it,
live-buffer watermarks.

Two complementary views, both host-side:

1. **Static** — :func:`compiled_memory_report` asks XLA what a compiled
   entry point will use (``Compiled.memory_analysis()``: argument / output /
   temp / alias bytes), one program's figures, shared with the Layer-C
   auditor. No sum of them is a step's need on the chip (PERF.md section 7,
   row 14: ``temp`` reads 1.8 to 4.1 GB over what a step takes,
   ``argument + temp`` counts a donated state twice).
2. **Dynamic** — :func:`device_memory` is the ONE reading of the
   allocator's ``memory_stats()`` in the program: the fullest of a set of
   devices, every integer counter the runtime reports. On the TPU its
   ``bytes_in_use`` / ``peak_bytes_in_use`` hold live arrays and NOT a
   running program's temporaries; those are ``bytes_reserved``, what the
   runtime sets aside for the programs that are loaded. :func:`step_totals`
   makes ``engine.memory_totals`` of the two. :meth:`MemoryTracker.sample`
   sums the process's live ``jax.Array`` buffers (per-shard addressable
   bytes, so replication is counted the way HBM pays for it) beside that
   reading. Sampling walks host-side bookkeeping only — no device sync —
   but it IS O(live arrays), so the telemetry facade calls it at fence
   points (flush/checkpoint boundaries) only, per the
   ``telemetry-hot-path-sync`` contract.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

import jax

#: ``engine.memory_totals``, key by key (docs/OBSERVABILITY.md)
TOTALS_KEYS = ("limit_bytes", "resident_bytes", "reserved_before_bytes",
               "step_extra_bytes", "step_peak_bytes", "headroom_bytes",
               "account_s")


def compiled_memory_report(compiled) -> Optional[Dict[str, float]]:
    """The raw byte fields of an XLA ``Compiled``'s ``memory_analysis()``
    (``argument`` / ``output`` / ``alias`` / ``temp`` / ``generated_code``
    ``_size_in_bytes``), one device's share; None when the backend doesn't
    expose it. They are the compiler's figures for ONE program and no sum of
    them is a step's peak by itself: ``argument + temp`` counts a donated
    state twice (it is among the arguments and, aliased, among the outputs
    the temporaries are laid beside), not every temporary is live at once,
    and nothing here knows what else the process keeps on the device. What a
    step takes on the chip is the allocator's to say (:func:`step_totals`).
    Thin delegate to :mod:`deepspeed_tpu.analysis.lowering` — telemetry and
    the Layer-C SPMD auditor share ONE lower-and-inspect path, so the bytes
    reported at runtime are the bytes the lint budgets gate on."""
    from ..analysis.lowering import memory_report
    return memory_report(compiled)


def lower_and_report(jitfn, *abstract_args) -> Optional[Dict[str, float]]:
    """Lower+compile ``jitfn`` on abstract avals and report its memory
    analysis. Compilation is cached by signature, so calling this for a
    shape the step already ran is near-free; a NEW shape pays one compile
    — call it per entry point, not per step. (Delegates to
    ``analysis.lowering.lower_and_report`` — the shared path.)"""
    from ..analysis.lowering import lower_and_report as _lar
    return _lar(jitfn, *abstract_args)


# ---------------------------------------------------------------------------
# the allocator's reading, and a step's bytes made of it
# ---------------------------------------------------------------------------

def device_memory(devices: Iterable) -> Optional[Dict[str, int]]:
    """The allocator's counters of the FULLEST of this process's devices
    among ``devices`` (the least ``bytes_limit - bytes_in_use``): every
    integer key the runtime reports (``bytes_limit``, ``bytes_in_use``,
    ``peak_bytes_in_use`` and whatever reservation counters it has). None
    where a device reports none (the CPU). The program's only reading of
    ``memory_stats()``: host bookkeeping, no device sync."""
    me = jax.process_index()
    stats = [d.memory_stats() for d in devices if d.process_index == me]
    if not stats or not all(s and "bytes_limit" in s and "bytes_in_use" in s
                            for s in stats):
        return None
    fullest = min(stats, key=lambda s: s["bytes_limit"] - s["bytes_in_use"])
    return {k: int(v) for k, v in fullest.items()
            if isinstance(v, int) and not isinstance(v, bool)}


def reserved_bytes(device: Optional[Dict[str, int]]) -> Optional[int]:
    """``bytes_reserved`` of a :func:`device_memory` reading: what the
    runtime has set aside, outside ``bytes_in_use`` and kept between steps,
    for the temporaries of the programs that are LOADED: as much as the
    dearest of them needs. None where the allocator has no such counter."""
    return None if device is None else device.get("bytes_reserved")


def empty_totals() -> Dict[str, Any]:
    """``engine.memory_totals`` before the first optimizer step has returned:
    every key, None."""
    return dict.fromkeys(TOTALS_KEYS)


def step_totals(before: Optional[Dict[str, int]],
                after: Optional[Dict[str, int]]) -> Dict[str, Any]:
    """``engine.memory_totals`` of two readings of the allocator
    (:func:`device_memory`): ``before`` the first call of the step's first
    program, ``after`` the first optimizer step has returned.
    ``step_extra_bytes``, what the running step needs beyond what is resident
    before it, is the reservation ``after``, and only where the step's own
    programs RAISED it: the counter is the device's and follows the dearest
    program loaded, so a reservation that stands where it stood (an
    evaluation step, another engine's step, a harness's reference still
    loaded and dearer) is not known to be the step's and gives None, as does
    an allocator without the counter."""
    totals = empty_totals()
    was, now = reserved_bytes(before), reserved_bytes(after)
    totals["reserved_before_bytes"] = was
    if was is not None and now is not None and now > was:
        totals["step_extra_bytes"] = now
    return with_residents(totals, after)


def with_residents(totals: Dict[str, Any],
                   device: Optional[Dict[str, int]]) -> Dict[str, Any]:
    """``totals`` with the device's part renewed from one reading of the
    allocator: ``limit_bytes``, ``resident_bytes`` (what is in use between
    steps: the state, and whatever else the process keeps),
    ``step_peak_bytes`` = residents + the step's extra, ``headroom_bytes`` =
    limit - peak. The step's extra stays what the first step found while the
    reservation reads the same; where it has moved, programs were loaded or
    dropped since and it is no longer known to be the step's: None from then
    on, and with it the peak and the headroom."""
    limit = resident = peak = None
    extra = totals["step_extra_bytes"]
    if extra != reserved_bytes(device):
        extra = None
    if device is not None:
        limit, resident = device["bytes_limit"], device["bytes_in_use"]
        if extra is not None:
            peak = resident + extra
    totals.update(
        step_extra_bytes=extra, limit_bytes=limit, resident_bytes=resident,
        step_peak_bytes=peak,
        headroom_bytes=None if peak is None else limit - peak)
    return totals


def describe(totals: Dict[str, Any]) -> str:
    """``memory_totals`` as the engine's one log line."""
    mb = lambda v: "?" if v is None else f"{v / 1e6:.1f}"
    t = totals
    return (f"step memory: resident {mb(t['resident_bytes'])} + extra "
            f"{mb(t['step_extra_bytes'])} = peak {mb(t['step_peak_bytes'])} "
            f"of limit {mb(t['limit_bytes'])} MB (extra: the runtime's "
            f"reservation, the dearest loaded program's, where the step's own "
            f"raised it from {mb(t['reserved_before_bytes'])}; "
            f"{1e3 * (t['account_s'] or 0):.1f} ms)")


class MemoryTracker:
    """Live-buffer watermark sampling at fence points."""

    def __init__(self):
        self.peak_live_bytes = 0
        self.last_live_bytes = 0
        self.last_allocator: Dict[str, int] = {}
        self.samples = 0

    @staticmethod
    def _live_bytes() -> int:
        total = 0
        for arr in jax.live_arrays():
            shards = getattr(arr, "addressable_shards", None)
            if shards:
                try:
                    total += sum(s.data.nbytes for s in shards)
                    continue
                except Exception:  # deleted/donated mid-walk
                    continue
            total += getattr(arr, "nbytes", 0)
        return total

    @staticmethod
    def _allocator_stats() -> Dict[str, int]:
        try:
            return device_memory(jax.local_devices()) or {}
        except Exception:   # a sample is never worth a failed flush
            return {}

    def sample(self, tag: str = "") -> Dict[str, Any]:
        """Take one watermark sample. Fence-point use only (O(live
        arrays) host walk; never a device sync)."""
        live = self._live_bytes()
        self.samples += 1
        self.last_live_bytes = live
        self.peak_live_bytes = max(self.peak_live_bytes, live)
        self.last_allocator = self._allocator_stats()
        out = {"tag": tag, "live_bytes": live,
               "peak_live_bytes": self.peak_live_bytes}
        out.update(self.last_allocator)
        return out
