"""The three readers of ``memory.*`` PR 53 brings, on
``benchmark/trace/memory_fixture.json`` (two ``engine_totals`` events made by
hand: the residents of the first still hold what a harness freed later): each
returns the LAST event's value scaled, each returns None on a trace without
the keys (``benchmark/trace/totals_fixture.json``: every parent's event) and
without the event, the share is None where the headroom reads negative, and
the three entries of the manifest are what ISSUE 53 asked for."""

import json
import os

import pytest

from benchmark import harness
from benchmark.trace import totals
from tests.benchmark.helpers import REPO

FIXTURE = os.path.join(REPO, "benchmark", "trace", "memory_fixture.json")
WITHOUT_KEYS = os.path.join(REPO, "benchmark", "trace", "totals_fixture.json")
WITHOUT_EVENT = os.path.join(REPO, "benchmark", "trace", "fixture.json")
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
CELLS = ["gpt2-large.train.seq1k", "olmoe-1b-7b.train.seq4k",
         "instella-moe-16b-a3b.train.seq8k", "trinity-mini.train.seq16k",
         "sdar-30b-a3b.train.bd8k", "evabyte-6.5b.train.seq32k",
         "keye-vl2-30b-a3b.train.dsa16k"]
#: metric -> (unit, what it reads of the fixture's last event)
READS = {"train_step_peak_gb": ("GB", 12.0),
         "train_step_temp_gb": ("GB", 4.0),
         "train_memory_unused_share": ("%", 25.0)}


def reader(name):
    return harness.Cell(MANIFEST, CELLS[0]).load_module("layer_metrics", name)


def ctx_of(path):
    return {"trace_out": {"trace_file": path}}


def events():
    with open(FIXTURE) as f:
        return json.load(f)["host_stats"]


def ctx_with(tmp_path, **changed):
    """The fixture's last event with some stats changed (None: taken out)."""
    stats = {**events()[-1][2], **{f"memory.{k}": v for k, v in changed.items()}}
    stats = {k: v for k, v in stats.items() if v is not None}
    path = tmp_path / "changed.json"
    path.write_text(json.dumps({"host_stats": [["engine_totals", 5, stats]]}))
    return ctx_of(str(path))


@pytest.mark.parametrize("name", sorted(READS))
def test_a_reader_returns_the_traced_steps_value(name):
    first, _, last = events()
    assert first[1] < last[1]
    assert reader(name).read(ctx_of(FIXTURE)) == pytest.approx(READS[name][1], rel=1e-12)
    # not the first step's, whose residents hold what the harness freed later
    assert first[2]["memory.resident_bytes"] - last[2]["memory.resident_bytes"] == 770000000


@pytest.mark.parametrize("name", sorted(READS))
def test_a_reader_finds_nothing_without_the_keys(name):
    """A parent's trace has the event and no ``memory.*``, an older one no
    event: None, which leaves the metric out of the line; never 0."""
    assert totals.load(WITHOUT_KEYS) is not None
    assert reader(name).read(ctx_of(WITHOUT_KEYS)) is None
    assert totals.load(WITHOUT_EVENT) is None
    assert reader(name).read(ctx_of(WITHOUT_EVENT)) is None
    assert reader(name).read({"trace_out": {}}) is None
    assert reader(name).read({}) is None


@pytest.mark.parametrize("name", sorted(READS))
def test_a_cell_whose_account_is_left_none_prints_no_peak(name, tmp_path):
    """Where the reservation is not known to be the step's (another program
    loaded as dear, an allocator without the counter) the engine leaves
    ``step_extra_bytes`` and what is made of it None: ``flat_totals`` drops
    them, the residents stay, and no reader makes a number of the rest."""
    ctx = ctx_with(tmp_path, step_extra_bytes=None, step_peak_bytes=None,
                   headroom_bytes=None, reserved_before_bytes=4000000000)
    assert totals.value(ctx, "memory.resident_bytes") == 8000000000
    assert reader(name).read(ctx) is None


def test_the_fixture_adds_up():
    for _, _, stats in events()[::2]:
        m = {k[len("memory."):]: v for k, v in stats.items() if k.startswith("memory.")}
        assert m["step_extra_bytes"] > m["reserved_before_bytes"]
        assert m["step_peak_bytes"] == m["resident_bytes"] + m["step_extra_bytes"]
        assert m["headroom_bytes"] == m["limit_bytes"] - m["step_peak_bytes"]


@pytest.mark.parametrize("headroom, want", [
    (-1, None),                 # a peak over the limit is a wrong reading
    (0, 0.0), (16000000000, 100.0)])
def test_the_unused_share_is_a_share(headroom, want, tmp_path):
    got = reader("train_memory_unused_share").read(ctx_with(tmp_path, headroom_bytes=headroom))
    assert got == want
    assert reader("train_memory_unused_share").read(
        ctx_with(tmp_path, limit_bytes=None)) is None


def test_the_manifest_has_the_three():
    """By name, appended after what the parent had, each over the seven cells."""
    with open(MANIFEST) as f:
        per_layer = json.load(f)["per_layer"]
    by_name = {p["name"]: p for p in per_layer}
    for name, (unit, _) in READS.items():
        entry = dict(by_name[name])
        assert set(CELLS) <= set(entry.pop("workloads"))
        assert entry == {"name": name, "unit": unit, "better": "lower",
                         "source": "program_counter", "layer": "training engine",
                         "moves": "train_tokens_per_s"}
    names = [p["name"] for p in per_layer]
    assert names[names.index("train_step_peak_gb"):][:3] == [
        "train_step_peak_gb", "train_step_temp_gb", "train_memory_unused_share"]
    assert names.index("train_step_peak_gb") > names.index("attn_dsa_roofline")
