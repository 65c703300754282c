"""The command itself, end to end, as the driver runs it: on the CPU at the
tiny preset under tests/benchmark/data, refusing to run a real cell without
a TPU, and taking a new configuration, cell and per-layer metric as files
alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from tests.benchmark.helpers import (DATA, REPO, RESULT_KEYS, TINY_MANIFEST,
                                     json_lines, run_cli)

SEEDS = [5, 2147483659, 3000000013]


@pytest.mark.parametrize("seed", SEEDS)
def test_tiny_cell_end_to_end(seed):
    """Last line: exactly the contract's keys, correct, nothing failed, no
    metric under any name off the chip, nothing compiled in the window;
    every compared number is printed beside its limit. Seeds above 2**31
    are the driver's kind."""
    proc = run_cli("run.py", "--manifest", TINY_MANIFEST, "--workload",
                   "gpt2-tiny.train", "--seed", seed, "--seconds", 1, "--trace", 0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["off_chip"]["window_compiles"] == 0
    assert "Compiling" not in proc.stderr
    checks = {l["check"]: l for l in json_lines(proc) if "check" in l}
    assert {"first_loss_abs_err", "first_grad_norm_rel_err",
            "first_step_uphill_share", "last_loss_minus_first"} <= set(checks)
    assert all({"value", "limit", "ok"} <= set(c) for c in checks.values())


def test_a_cell_across_chips_needs_a_job_of_its_own(tmp_path):
    """``jobs/train.py`` drives one chip: a four-chip cell that names it is
    refused, not run on one chip and divided by four."""
    shutil.copytree(DATA, tmp_path / "data")
    with open(tmp_path / "data/BENCHMARK.json") as f:
        m = json.load(f)
    m["workloads"][0]["chips"] = 4
    (tmp_path / "data/BENCHMARK.json").write_text(json.dumps(m))
    proc = run_cli("run.py", "--manifest", tmp_path / "data/BENCHMARK.json", "--workload",
                   "gpt2-tiny.train", "--seed", 1, "--seconds", 1, devices=4)
    assert proc.returncode != 0 and "one chip" in proc.stderr
    assert not [l for l in json_lines(proc) if "correct" in l]


def test_real_cell_without_a_tpu_fails_and_prints_no_result():
    proc = run_cli("run.py", "--workload", "gpt2-large.train.seq1k", "--seed", 1,
                   "--seconds", 1, "--trace", 0)
    assert proc.returncode != 0
    assert not [l for l in json_lines(proc) if "correct" in l]
    assert "TPU" in proc.stderr


def test_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    """BENCHMARK.json and the files under ``paths`` alone are not a
    checkout: the command exits non-zero and prints no result."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "benchmark/run.py", "--manifest",
                           TINY_MANIFEST, "--workload", "gpt2-tiny.train",
                           "--seconds", "1"], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not [l for l in proc.stdout.splitlines() if '"correct"' in l]


def test_a_new_config_cell_and_metric_are_files_only(tmp_path):
    """A later PR adds a configuration, a traffic mix, a cell and a
    per-layer metric as NEW files and manifest entries; no file that is
    there is edited. Here they live in a throw-away directory."""
    bench = tmp_path / "benchmark"
    for sub in ("configs", "traffic", "layer_metrics"):
        (bench / sub).mkdir(parents=True)
    with open(os.path.join(DATA, "benchmark/configs/gpt2-tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(name="gpt2-wee", n_layer=1, n_embd=32, n_head=2)
    (bench / "configs/gpt2-wee.json").write_text(json.dumps(cfg))
    with open(os.path.join(DATA, "benchmark/traffic/train.tiny.json")) as f:
        mix = json.load(f)
    mix.update(seq_len=16, sync_every=3)
    (bench / "traffic/train.wee.json").write_text(json.dumps(mix))
    (bench / "layer_metrics/steps_done.py").write_text(
        '"""Steps the window completed (a count)."""\n\n\n'
        'def read(ctx):\n    return ctx["steps"]\n')
    with open(TINY_MANIFEST) as f:
        m = json.load(f)
    m["configs"] = [{"name": "gpt2-wee", "source": cfg["source"],
                     "file": "benchmark/configs/gpt2-wee.json",
                     "reduced": cfg["reduced"], "why": "toy"}]
    m["workloads"] = [{"name": "gpt2-wee.train", "config": "gpt2-wee",
                       "traffic": "train.wee", "chips": 1, "why": "toy"}]
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e:
            e["workloads"] = ["gpt2-wee.train"]
    m["per_layer"].append({"name": "steps_done", "unit": "steps", "better": "higher",
                           "source": "program_counter", "layer": "training engine",
                           "moves": "train_tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    proc = run_cli("run.py", "--manifest", tmp_path / "BENCHMARK.json",
                   "--workload", "gpt2-wee.train", "--seed", 5, "--seconds", 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] % 3 == 0

    # the new reader is found by the metric's name, beside the old ones
    from benchmark import harness
    cell = harness.Cell(str(tmp_path / "BENCHMARK.json"), "gpt2-wee.train")
    ctx = {"steps": 7, "setup_compile": {"compile_s": 1.5},
           "window_compile": {"compile_s": 0.0}, "cell": cell, "chips": 1,
           "spans": harness.Spans(), "window": (0.0, 1.0), "seq": 16,
           "train_tokens_per_s": None}
    got = harness.read_per_layer(cell, ctx)
    assert got["steps_done"] == {"value": 7.0, "unit": "steps"}
    assert got["compile_s"]["value"] == 1.5
    assert "train_mfu" not in got and "device_idle_share.train" not in got
