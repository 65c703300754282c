"""BENCHMARK.json and the files it names, held to the contract's letter."""

import importlib.util
import json
import os
import re

import pytest

from tests.benchmark.helpers import REPO, TINY_MANIFEST

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


MANIFESTS = {"real": os.path.join(REPO, "BENCHMARK.json"), "tiny": TINY_MANIFEST}


@pytest.fixture(params=sorted(MANIFESTS))
def manifest(request):
    path = MANIFESTS[request.param]
    return os.path.dirname(path), load(path)


def metrics_of(m):
    return m["end_to_end"] + m["per_layer"]


def cells_reporting(m, metric):
    return metric.get("workloads") or [w["name"] for w in m["workloads"]]


def test_keys_are_exactly_the_contracts(manifest):
    _, m = manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}


def test_names_units_and_lines(manifest):
    _, m = manifest
    names = [x["name"] for x in metrics_of(m)]
    assert len(names) == len(set(names))
    for kind in ("workloads", "configs"):
        ns = [x["name"] for x in m[kind]]
        assert len(ns) == len(set(ns))
    for x in metrics_of(m):
        assert NAME.match(x["name"]), x["name"]
        assert UNIT.match(x["unit"]), x["unit"]
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in m["configs"]:
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert not any(k.endswith(("_dim", "_rank")) or "hidden" in k or k == "n_embd"
                       for k in c["reduced"])


def test_bounds_and_setup(manifest):
    _, m = manifest
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_what_it_must(manifest):
    _, m = manifest
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for w in m["workloads"]:
        mine = [e for e in m["end_to_end"] if w["name"] in cells_reporting(m, e)]
        assert len(mine) >= 2, w["name"]
        assert any(w["name"] in cells_reporting(m, p) for p in m["per_layer"])
    for p in m["per_layer"]:
        assert p["moves"] in e2e, p
        moved = set(cells_reporting(m, e2e[p["moves"]]))
        assert set(cells_reporting(m, p)) <= moved, p["name"]
    layers = {}
    for p in m["per_layer"]:
        layers.setdefault(p["layer"].lower(), set()).add(p["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_at_most_one_four_chip_cell(manifest):
    _, m = manifest
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)


def test_every_named_file_is_there(manifest):
    root, m = manifest
    paths = [os.path.join(root, p) for p in m["paths"]]
    assert all(os.path.isdir(p) for p in paths)
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    for c in m["configs"]:
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        cfg = load(os.path.join(root, c["file"]))
        assert cfg["source"] == c["source"] or cfg.get("cpu_test_preset")
        assert cfg["reduced"] == c["reduced"]
        assert {"reference", "engine", "limits", "assumed", "deployment",
                "stated_precision"} <= set(cfg)
    search = [os.path.join(root, p) for p in m["paths"]] + [os.path.join(REPO, "benchmark")]

    def find(kind, filename):
        hits = [os.path.join(s, kind, filename) for s in search
                if os.path.exists(os.path.join(s, kind, filename))]
        assert hits, (kind, filename)
        return hits[0]

    for w in m["workloads"]:
        assert w["config"] in {c["name"] for c in m["configs"]}
        mix = load(find("traffic", w["traffic"] + ".json"))
        find("jobs", mix["job"] + ".py")
    for p in m["per_layer"]:
        path = find("layer_metrics", p["name"] + ".py")
        spec = importlib.util.spec_from_file_location("reader", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(mod.read) and mod.__doc__


def test_real_manifest_names_are_the_issues():
    m = load(MANIFESTS["real"])
    assert m["command"] == ["python3", "benchmark/run.py"]
    assert m["paths"] == ["benchmark", "tests/benchmark"]
    assert [w["name"] for w in m["workloads"]][:1] == ["gpt2-large.train.seq1k"]
    assert {"train_tokens_per_s", "setup_s"} <= {e["name"] for e in m["end_to_end"]}
    for c in m["configs"]:
        cfg = load(os.path.join(REPO, c["file"]))
        assert cfg["n_embd"] in (1280, 1600) and cfg["vocab_size"] == 50257
        assert cfg["n_positions"] == 1024 and not cfg.get("cpu_test_preset")
    for p in m["per_layer"]:
        if p["name"].endswith("_roofline") or "mfu" in p["name"]:
            assert p["unit"] == "%"
