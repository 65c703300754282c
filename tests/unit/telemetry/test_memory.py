"""Memory telemetry: compiled-HLO report + live-buffer watermarks."""

import gc

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.telemetry import memory
from deepspeed_tpu.telemetry.memory import (MemoryTracker,
                                            compiled_memory_report,
                                            lower_and_report)


def test_compiled_memory_report_shape():
    compiled = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    report = compiled_memory_report(compiled)
    # the CPU host backend may not expose memory_analysis; when it does,
    # the report must carry byte fields
    if report is not None:
        assert all(k.endswith("_in_bytes") for k in report)
        assert all(v >= 0 for v in report.values())


def test_lower_and_report_accepts_abstract_args():
    report = lower_and_report(jax.jit(lambda x: x + 1),
                              jax.ShapeDtypeStruct((8,), jnp.float32))
    assert report is None or isinstance(report, dict)


def test_lower_and_report_swallow_bad_fn():
    assert lower_and_report(jax.jit(lambda x: x), "not-an-aval") is None


def test_live_bytes_watermark_tracks_allocations():
    tracker = MemoryTracker()
    # the count is of the PROCESS's live arrays: another file's arrays, dead
    # but in a reference cycle, are collected now and not between two samples
    gc.collect()
    base = tracker.sample("t0")["live_bytes"]
    big = jnp.zeros((256, 1024), jnp.float32)  # 1 MiB
    s1 = tracker.sample("t1")
    assert s1["live_bytes"] >= base + big.nbytes
    assert tracker.peak_live_bytes == s1["peak_live_bytes"]
    del big
    s2 = tracker.sample("t2")
    # the watermark never regresses even after the buffer dies
    assert s2["peak_live_bytes"] >= s1["live_bytes"]
    assert tracker.samples == 3


# ---------------------------------------------------------------------------
# a step's memory from inside the program (ISSUE 53): ``memory_totals`` over
# stand-in readings of the allocator, ``device_memory`` over stand-in devices
# ---------------------------------------------------------------------------

GB = 10 ** 9


class _Device:
    def __init__(self, stats, process_index=0):
        self._stats, self.process_index = stats, process_index

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def _allocator(in_use, reserved=None, limit=16 * GB, **more):
    """A reading as the TPU's runtime gives one; ``reserved`` None: an
    allocator without the counter."""
    if reserved is not None:
        more["bytes_reserved"] = reserved
    return {"bytes_limit": limit, "bytes_in_use": in_use,
            "peak_bytes_in_use": in_use + 5, **more}


def test_every_key_of_the_totals_is_there_from_the_start():
    empty = memory.empty_totals()
    assert set(empty) == {"limit_bytes", "resident_bytes", "reserved_before_bytes",
                          "step_extra_bytes", "step_peak_bytes", "headroom_bytes",
                          "account_s"}
    assert set(empty.values()) == {None}


def test_the_totals_are_residents_plus_the_steps_reservation():
    # nothing loaded before the step's first call; its programs reserved 4 GB
    t = memory.step_totals(_allocator(8 * GB, 0), _allocator(7 * GB, 4 * GB))
    assert t["reserved_before_bytes"] == 0 and t["step_extra_bytes"] == 4 * GB
    assert t["limit_bytes"] == 16 * GB and t["resident_bytes"] == 7 * GB
    assert t["step_peak_bytes"] == 11 * GB and t["headroom_bytes"] == 5 * GB
    assert set(t) == set(memory.empty_totals())


@pytest.mark.parametrize("before, after, why", [
    (4 * GB, 4 * GB, "a program as dear was loaded before the step's"),
    (6 * GB, 6 * GB, "a dearer one: an evaluation step, a reference not yet freed"),
    (None, 4 * GB, "no reading before the step's first call"),
    (0, None, "an allocator without the counter"),
])
def test_a_reservation_the_step_did_not_raise_is_not_printed_as_its(before, after, why):
    t = memory.step_totals(None if before is None else _allocator(8 * GB, before),
                           _allocator(7 * GB, after))
    assert t["step_extra_bytes"] is None, why
    assert t["step_peak_bytes"] is None and t["headroom_bytes"] is None
    # the residents and the limit are the allocator's own and stay
    assert t["resident_bytes"] == 7 * GB and t["limit_bytes"] == 16 * GB


def test_a_dearer_step_over_a_loaded_program_is_the_steps():
    # another engine's step held 2 GB; this one's raised the reservation to 4
    t = memory.step_totals(_allocator(8 * GB, 2 * GB), _allocator(7 * GB, 4 * GB))
    assert t["reserved_before_bytes"] == 2 * GB and t["step_extra_bytes"] == 4 * GB


def test_a_back_end_with_no_allocator_has_no_totals():
    assert memory.step_totals(None, None) == memory.empty_totals()
    assert "resident ? + extra ? = peak ? of limit ? MB" in memory.describe(
        memory.empty_totals())


def test_the_residents_are_renewed_and_the_extra_stays():
    t = memory.step_totals(_allocator(8 * GB, 0), _allocator(8 * GB, 4 * GB))
    t["account_s"] = 0.001
    first = dict(t)
    # a harness freed 0.77 GB after the first step: the traced steps' residents
    memory.with_residents(t, _allocator(8 * GB - 770 * 10 ** 6, 4 * GB))
    assert t["resident_bytes"] == first["resident_bytes"] - 770 * 10 ** 6
    assert t["step_peak_bytes"] == first["step_peak_bytes"] - 770 * 10 ** 6
    assert t["headroom_bytes"] == first["headroom_bytes"] + 770 * 10 ** 6
    for key in ("account_s", "limit_bytes", "step_extra_bytes", "reserved_before_bytes"):
        assert t[key] == first[key]
    assert "resident 7230.0 + extra 4000.0 = peak 11230.0 of limit 16000.0 MB" \
        in memory.describe(t)


@pytest.mark.parametrize("now", [6 * GB, 1 * GB, None])
def test_a_reservation_that_moved_is_no_longer_the_steps(now):
    """A dearer program loaded since, or the step's own dropped: the counter
    reads another program's bytes, and no peak is made of it, then or later."""
    t = memory.step_totals(_allocator(8 * GB, 0), _allocator(8 * GB, 4 * GB))
    memory.with_residents(t, _allocator(7 * GB, now))
    assert t["step_extra_bytes"] is None and t["step_peak_bytes"] is None
    assert t["headroom_bytes"] is None and t["resident_bytes"] == 7 * GB
    memory.with_residents(t, _allocator(7 * GB, 4 * GB))
    assert t["step_extra_bytes"] is None


def test_the_fullest_of_several_devices():
    fullest = _allocator(9 * GB, 11, pool_bytes=12, a_share=0.5, a_flag=True)
    devices = [_Device(_allocator(3 * GB)), _Device(fullest),
               _Device(_allocator(15 * GB), process_index=1)]    # another host's
    got = memory.device_memory(devices)
    assert got["bytes_in_use"] == 9 * GB and got["peak_bytes_in_use"] == 9 * GB + 5
    # every integer counter is kept, nothing else
    assert memory.reserved_bytes(got) == 11 and got["pool_bytes"] == 12
    assert "a_share" not in got and "a_flag" not in got
    # fullest by what is FREE, not by what is in use
    small = _Device(_allocator(2 * GB, limit=3 * GB))
    assert memory.device_memory(devices + [small])["bytes_limit"] == 3 * GB


@pytest.mark.parametrize("devices", [
    [], [_Device(None)], [_Device({})], [_Device(_allocator(GB)), _Device(None)],
    [_Device(_allocator(GB), process_index=1)],
])
def test_no_reading_where_a_device_reports_none(devices):
    assert memory.device_memory(devices) is None
    assert memory.reserved_bytes(None) is None


def test_a_reading_that_raises_is_the_callers_to_see(monkeypatch):
    """The remat budget must not take a failed reading for "no allocator"
    (everything kept, an OOM later); only the tracker's sample swallows it."""
    with pytest.raises(RuntimeError, match="lost"):
        memory.device_memory([_Device(RuntimeError("device lost"))])
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_Device(RuntimeError("device lost"))])
    assert MemoryTracker._allocator_stats() == {}


def test_the_cpu_reports_no_memory():
    assert memory.device_memory(jax.local_devices()) is None
    assert MemoryTracker._allocator_stats() == {}
