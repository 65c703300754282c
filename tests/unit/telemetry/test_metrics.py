"""MetricsEngine: percentiles, tokens/sec, MFU, goodput, overlap split."""

import pytest

from deepspeed_tpu.telemetry.metrics import (LatencyHistogram, MetricsEngine,
                                             peak_flops_per_device,
                                             percentile)


def test_percentile_nearest_rank():
    vals = sorted([1.0, 2.0, 3.0, 4.0, 5.0])
    assert percentile(vals, 50) == 3.0
    assert percentile(vals, 0) == 1.0
    assert percentile(vals, 100) == 5.0
    assert percentile([], 50) == 0.0


def test_step_percentiles_and_tokens_per_sec():
    m = MetricsEngine(window=8)
    for d in (0.1, 0.1, 0.1, 0.5):
        m.record_step(d, tokens=100)
    pcts = m.step_percentiles()
    assert pcts["p50"] == pytest.approx(0.1)
    assert pcts["p99"] == pytest.approx(0.5)
    assert m.tokens_per_sec() == pytest.approx(400 / 0.8)


def test_window_is_rolling():
    m = MetricsEngine(window=2)
    m.record_step(10.0)
    m.record_step(0.1)
    m.record_step(0.1)
    assert m.mean_step_s() == pytest.approx(0.1)
    assert m.steps == 3  # lifetime counter keeps counting


def test_mfu_definition():
    m = MetricsEngine()
    m.record_step(0.5)
    m.model_flops_per_step = 1e12
    m.peak_flops_total = 8e12
    # 1e12 flops in 0.5 s against an 8e12/s roofline => 0.25
    assert m.mfu() == pytest.approx(0.25)


def test_mfu_zero_when_unresolved():
    m = MetricsEngine()
    m.record_step(0.5)
    assert m.mfu() == 0.0
    assert "mfu" not in m.summary()


def test_goodput_accounts_stalls_and_checkpoints():
    m = MetricsEngine()
    m.record_step(1.0)
    m.record_step(3.0, stall_excess_s=2.0)  # 1 s productive, 2 s stall
    m.record_checkpoint_pause(2.0)
    # productive 2.0, lost 4.0
    assert m.goodput() == pytest.approx(2.0 / 6.0)
    assert m.stalled_steps == 1
    assert m.summary()["goodput"] == pytest.approx(2.0 / 6.0)


def test_overlap_efficiency_from_comm_records():
    m = MetricsEngine()
    assert m.overlap_efficiency() is None
    m.record_comm(1000, overlapped=True, count=3)
    m.record_comm(1000, overlapped=False)
    m.record_comm(999, overlapped=None)  # unclassified: excluded
    assert m.overlap_efficiency() == pytest.approx(3000 / 4000)
    assert m.summary()["comm_overlap_efficiency"] == pytest.approx(0.75)


def test_peak_flops_table_and_env_override(monkeypatch):
    monkeypatch.delenv("DSTPU_PEAK_FLOPS", raising=False)
    assert peak_flops_per_device("TPU v4") == 275e12
    assert peak_flops_per_device("TPU v5 lite") == 197e12
    assert peak_flops_per_device("TPU v5p") == 459e12
    assert peak_flops_per_device("cpu") == 1e12
    with pytest.raises(ValueError, match="mystery"):
        peak_flops_per_device("mystery")
    monkeypatch.setenv("DSTPU_PEAK_FLOPS", "123e12")
    assert peak_flops_per_device("TPU v4") == 123e12


def test_latency_histogram_percentiles():
    h = LatencyHistogram(cap=10)
    for ms in range(1, 11):
        h.record(ms / 1000)
    p = h.percentiles()
    assert p["p50"] == pytest.approx(0.006, abs=1e-3)
    assert p["p99"] == pytest.approx(0.010, abs=1e-3)
    # bounded: newest samples win
    for _ in range(20):
        h.record(0.001)
    assert h.percentiles()["p99"] == pytest.approx(0.001)


def test_offload_phase_split_summary_keys():
    """ISSUE 15: the offload stall decomposition accumulates per-phase
    seconds and derives offload_stall_frac = blocked / total (blocked =
    everything but bucket_compute)."""
    m = MetricsEngine()
    assert "offload_stall_frac" not in m.summary()  # absent when unused
    m.record_offload_phases({"h2d_prefetch": 0.2, "bucket_compute": 0.6,
                             "d2h_writeback": 0.1, "nvme_io": 0.1})
    m.record_offload_phases({"h2d_prefetch": 0.2, "bucket_compute": 0.6,
                             "d2h_writeback": 0.1, "nvme_io": 0.1})
    s = m.summary()
    assert s["offload_h2d_prefetch_s"] == pytest.approx(0.4)
    assert s["offload_bucket_compute_s"] == pytest.approx(1.2)
    assert s["offload_d2h_writeback_s"] == pytest.approx(0.2)
    assert s["offload_nvme_io_s"] == pytest.approx(0.2)
    assert s["offload_stall_frac"] == pytest.approx(0.8 / 2.0)


def test_offload_phase_spans_reach_trace_and_view():
    """record_offload_phases lands completed spans the trace export (and
    tools/trace_view.py's breakdown line) can see."""
    import os
    import sys

    from deepspeed_tpu.telemetry.config import TelemetryConfig
    from deepspeed_tpu.telemetry.telemetry import Telemetry

    tele = Telemetry(TelemetryConfig(enabled=True,
                                     watchdog={"enabled": False}))
    tele.record_offload_phases(3, {"h2d_prefetch": 0.02,
                                   "bucket_compute": 0.05,
                                   "d2h_writeback": 0.01,
                                   "nvme_io": 0.0})
    spans = [r for r in tele.trace.events()
             if r.get("kind") == "span"
             and r["name"].startswith("offload/")]
    names = {s["name"] for s in spans}
    # zero-duration phases are elided; the rest land with their duration
    assert names == {"offload/h2d_prefetch", "offload/bucket_compute",
                     "offload/d2h_writeback"}, names
    assert all(s["phase"] == "offload" for s in spans)
    by = {s["name"]: s["dur"] for s in spans}
    assert by["offload/bucket_compute"] == pytest.approx(0.05)
    # the trace_view breakdown line renders from these records
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "..", "..", "tools"))
    import trace_view
    out = trace_view.summarize([dict(r) for r in tele.trace.events()])
    assert "offload stall decomposition" in out
    assert "blocked fraction" in out
    tele.close()


def _verdict_dir(tmp_path, entry="engine-train-step", flops=1e9):
    import json
    d = tmp_path / "feasibility"
    d.mkdir(parents=True)
    (d / f"{entry}.json").write_text(json.dumps(
        {"entry": entry, "feasible": True,
         "predicted_step_flops": flops}))
    return str(d)


def test_feasibility_cross_check_consistent(tmp_path):
    m = MetricsEngine()
    m.model_flops_per_step = 1.2e9
    out = m.feasibility_cross_check(
        "engine-train-step", plans_dir=_verdict_dir(tmp_path))
    assert out["consistent"] is True
    assert out["ratio"] == pytest.approx(1.2)
    assert out["predicted_step_flops"] == pytest.approx(1e9)


def test_feasibility_cross_check_flags_drift(tmp_path):
    # measured flops 4x the committed static prediction: the artifact no
    # longer describes the running program
    m = MetricsEngine()
    m.model_flops_per_step = 4e9
    out = m.feasibility_cross_check(
        "engine-train-step", plans_dir=_verdict_dir(tmp_path))
    assert out["consistent"] is False
    assert out["ratio"] == pytest.approx(4.0)
    # a tighter tolerance tightens the band symmetrically (ratio bands:
    # [1-tol, 1/(1-tol)])
    out = m.feasibility_cross_check(
        "engine-train-step", plans_dir=_verdict_dir(tmp_path / "b"),
        rel_tol=0.9)
    assert out["consistent"] is True


def test_feasibility_cross_check_none_when_either_side_missing(tmp_path):
    m = MetricsEngine()
    # no measured flops
    assert m.feasibility_cross_check(
        "engine-train-step", plans_dir=_verdict_dir(tmp_path)) is None
    m.model_flops_per_step = 1e9
    # no committed artifact for the entry
    assert m.feasibility_cross_check("no-such-entry",
                                     plans_dir=str(tmp_path)) is None
    # artifact with no usable prediction
    zero = _verdict_dir(tmp_path / "z", flops=0)
    assert m.feasibility_cross_check("engine-train-step",
                                     plans_dir=zero) is None
