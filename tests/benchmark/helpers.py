"""Shared by the benchmark's tests: run its commands as a user would, in a
fresh process on the CPU (one device unless asked), and parse what they
print."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(REPO, "tests", "benchmark", "data")
TINY_MANIFEST = os.path.join(DATA, "BENCHMARK.json")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cli(script, *args, devices=1, cwd=REPO, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    else:
        env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, os.path.join(REPO, "benchmark", script),
                           *map(str, args)], cwd=cwd, env=env, timeout=timeout,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def json_lines(proc):
    out = []
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out
