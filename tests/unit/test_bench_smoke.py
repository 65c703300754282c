"""BENCH_SMOKE.json routing (ISSUE 11 satellite).

The committed BENCH_SUMMARY.json holds TPU measurements; a chipless host
running the CPU smoke path used to clobber it with 3-step smoke numbers.
``_write_summary(..., smoke=True)`` must route to BENCH_SMOKE.json, and
the CPU tail of ``_run_configs`` must pass the flag. The CPU smoke runs
only under ``--cpu-smoke``: without a chip ``python bench.py`` fails, and a
failed child is a failure (the one-process-per-chip protocol).
"""

import importlib.util
import inspect
import json
import os
import subprocess
import sys


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)  # module top level is stdlib-only
    return mod


def test_smoke_summary_routes_to_bench_smoke(tmp_path, monkeypatch):
    bench = _load_bench()
    monkeypatch.setattr(bench, "_BENCH_DIR", str(tmp_path))
    lines = [{"metric": "m", "value": 1.0}]
    bench._write_summary(lines, smoke=True)
    assert json.loads(
        (tmp_path / "BENCH_SMOKE.json").read_text()) == lines
    assert not (tmp_path / "BENCH_SUMMARY.json").exists(), \
        "smoke run clobbered the committed TPU summary"


def test_tpu_summary_keeps_its_name(tmp_path, monkeypatch):
    bench = _load_bench()
    monkeypatch.setattr(bench, "_BENCH_DIR", str(tmp_path))
    bench._write_summary([{"metric": "m", "value": 2.0}])
    assert (tmp_path / "BENCH_SUMMARY.json").exists()
    assert not (tmp_path / "BENCH_SMOKE.json").exists()


def test_cpu_smoke_tail_passes_the_flag():
    # wiring pin: the in-process tail of _run_configs is the CPU smoke
    # (reached only through --cpu-smoke) and must write BENCH_SMOKE.json —
    # a refactor that drops the flag regresses to the clobber
    bench = _load_bench()
    src = inspect.getsource(bench._run_configs)
    assert "_write_summary(lines, smoke=True)" in src
    # and the dispatcher's TPU write stays on the committed file
    src_tpu = inspect.getsource(bench._dispatch_tpu)
    assert "_write_summary(lines)" in src_tpu


def test_one_child_without_a_chip_fails():
    # the rest of the one-process-per-chip protocol is pinned in
    # tests/unit/accelerator/test_device_selection.py
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--one", "0"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "metric" not in r.stdout
