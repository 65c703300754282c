"""Shared by the benchmark's tests: run its commands as a user would, in a
fresh process on the CPU (one device unless asked), and parse what they
print."""

import glob
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(REPO, "tests", "benchmark", "data")
TINY_MANIFEST = os.path.join(DATA, "BENCHMARK.json")
# a second architecture, as files only: rotary, RMSNorm, gated SiLU, GQA
ROPE_MANIFEST = os.path.join(DATA, "BENCHMARK.rope-tiny.json")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
#: the names a configuration file counts its ROUTED experts under: a closed
#: set, the five that the rows of the model-configs guide's catalog use
#: (test_manifest.py has the rule for cutting one, and where the set is from)
EXPERT_COUNT = {"num_experts", "num_local_experts", "n_routed_experts",
                "moe_num_primary_experts", "moe_num_experts"}


def data_manifests(data=DATA):
    """Every ``BENCHMARK*.json`` under the tests' data, by its file's name:
    a later PR's CPU preset is one more file there, and the manifest tests
    and the reference tests find it without an edit."""
    return {os.path.basename(p)[:-len(".json")]: p
            for p in sorted(glob.glob(os.path.join(data, "BENCHMARK*.json")))}


def reference_cells(data=DATA):
    """(reference file's name -> (manifest, cell), names left untested).
    The map is read from data: a cell of a manifest under ``data`` whose
    configuration file declares itself a ``cpu_test_preset`` stands for the
    reference that file names. A reference file, in the checkout's
    ``benchmark/reference`` or under ``data``, that no preset names is
    untested."""
    cells = {}
    for path in data_manifests(data).values():
        with open(path) as f:
            m = json.load(f)
        files = {c["name"]: c["file"] for c in m["configs"]}
        for w in m["workloads"]:
            with open(os.path.join(os.path.dirname(path), files[w["config"]])) as f:
                cfg = json.load(f)
            if cfg.get("cpu_test_preset"):
                cells.setdefault(cfg["reference"], (path, w["name"]))
    found = {os.path.basename(p)[:-len(".py")]
             for d in (os.path.join(REPO, "benchmark"), os.path.join(data, "benchmark"))
             for p in glob.glob(os.path.join(d, "reference", "*.py"))}
    return cells, found - set(cells)


def one_lap_manifest(root, manifest, steps):
    """A copy of one of the tests' presets under ``root`` whose traffic mixes
    fetch the losses every ``steps`` steps. A window ends at the first fetch
    past ``--seconds``, so under a ``--seconds`` no lap can meet it holds
    exactly ``steps`` steps, however loaded the machine is: a window counted
    in seconds holds as many as the machine's other work leaves room for,
    and a toy's loss at its end is then the toss of a coin."""
    shutil.copytree(os.path.join(DATA, "benchmark"), os.path.join(root, "benchmark"))
    with open(manifest) as f:
        m = json.load(f)
    for w in m["workloads"]:
        path = os.path.join(root, "benchmark", "traffic", w["traffic"] + ".json")
        with open(path) as f:
            mix = json.load(f)
        with open(path, "w") as f:
            json.dump(dict(mix, sync_every=steps), f)
    return shutil.copy(manifest, os.path.join(root, "BENCHMARK.json"))


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


def run_one_lap(root, manifest, cell, seed, steps=30):
    """``run.py`` on one of the tests' presets over a window of exactly
    ``steps`` steps (``one_lap_manifest``): its result line, and by how much
    the window's last loss lies under the first step's."""
    proc = run_cli("run.py", "--manifest", one_lap_manifest(str(root), manifest, steps),
                   "--workload", cell, "--seed", seed, "--seconds", 0.001, "--trace", 0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["attempted"] == steps
    fell, = [c["value"] for c in line["checks"] if c["check"] == "last_loss_minus_first"]
    return line, -fell
