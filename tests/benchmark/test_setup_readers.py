"""The seven ``setup_*`` readers PR 36 brings, on
``benchmark/trace/totals_fixture.json`` (one ``engine_totals`` event made by
hand and, after it, one recorded on the chip from ``gpt2-large.train.seq1k``):
each returns the LAST event's value, each returns None on a trace without
the event (``benchmark/trace/fixture.json``: every parent's trace), and the
seven entries of the manifest are what ISSUE 36 asked for."""

import json
import os

import pytest

from benchmark import harness
from benchmark.trace import totals
from tests.benchmark.helpers import REPO

FIXTURE = os.path.join(REPO, "benchmark", "trace", "totals_fixture.json")
WITHOUT = os.path.join(REPO, "benchmark", "trace", "fixture.json")
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
CELLS = ["gpt2-large.train.seq1k", "olmoe-1b-7b.train.seq4k",
         "instella-moe-16b-a3b.train.seq8k", "trinity-mini.train.seq16k"]
#: metric -> the key of ``engine_totals`` it reads
READS = {"setup_import_s": "setup.import_s",
         "setup_initialize_s": "setup.initialize_s",
         "setup_trace_s": "setup.trace_s",
         "setup_remat_plan_s": "setup.remat_plan_s",
         "setup_lower_s": "setup.lower_s",
         "setup_program_compile_s": "setup.compile_s",
         "setup_first_run_s": "setup.run_s"}
#: the event made by hand: round numbers, the four parts adding up to the
#: first calls' walls (0.25 + 3.5), the plan inside the trace
BY_HAND = {"setup.import_s": 2.0, "setup.initialize_s": 0.5,
           "setup.trace_s": 1.25, "setup.remat_plan_s": 0.25,
           "setup.lower_s": 0.5, "setup.compile_s": 1.5, "setup.run_s": 0.5,
           "setup.first_calls_s": 3.75}


def reader(name):
    return harness.Cell(MANIFEST, CELLS[0]).load_module("layer_metrics", name)


def ctx_of(path):
    return {"trace_out": {"trace_file": path}}


def events():
    with open(FIXTURE) as f:
        return json.load(f)["host_stats"]


@pytest.mark.parametrize("name", sorted(READS))
def test_a_reader_returns_the_last_events_value(name):
    by_hand, _, recorded = events()
    assert by_hand[1] < recorded[1]                 # by start, not by position
    want = recorded[2][READS[name]]
    assert reader(name).read(ctx_of(FIXTURE)) == pytest.approx(want)
    assert want != by_hand[2][READS[name]]


@pytest.mark.parametrize("name", sorted(READS))
def test_a_reader_on_the_event_made_by_hand(name, tmp_path):
    path = tmp_path / "by_hand.json"
    path.write_text(json.dumps({"host_stats": events()[:1] + [["train_step", 9, {}]]}))
    assert reader(name).read(ctx_of(str(path))) == BY_HAND[READS[name]]


@pytest.mark.parametrize("name", sorted(READS))
def test_a_reader_finds_nothing_in_a_trace_without_the_event(name):
    """The parent's trace has no such event: None, which leaves the metric
    out of the line; never 0, never an exception."""
    assert totals.load(WITHOUT) is None
    assert reader(name).read(ctx_of(WITHOUT)) is None
    assert reader(name).read({"trace_out": {}}) is None     # no trace at all
    assert reader(name).read({}) is None


def test_the_event_is_read_once_a_run(monkeypatch):
    ctx = ctx_of(FIXTURE)
    assert reader("setup_trace_s").read(ctx) is not None
    monkeypatch.setattr(totals, "load", None)       # a second load would raise
    assert reader("setup_lower_s").read(ctx) is not None
    assert totals.value(ctx, "setup.no_such_key") is None


def test_the_recorded_event_adds_up():
    """What the acceptance criteria hold every cell to, on the recorded
    event: the four parts are the first calls' walls, the plan lies inside
    the trace."""
    stats = events()[-1][2]
    parts = sum(stats[f"setup.{k}"] for k in ("trace_s", "lower_s", "compile_s", "run_s"))
    walls = sum(v for k, v in stats.items()
                if k.startswith("setup.programs.") and k.endswith(".wall_s"))
    assert parts == pytest.approx(stats["setup.first_calls_s"], rel=0.01)
    assert parts == pytest.approx(walls, rel=0.01)
    assert 0 < stats["setup.remat_plan_s"] <= stats["setup.trace_s"]
    assert stats["setup.compiled_after_setup"] == 0
    assert stats["setup.programs_compiled"] == 2


def test_the_manifest_has_the_seven():
    """By name: a later PR appends cells and metrics, and a cell it adds
    joins each entry's ``workloads``."""
    with open(MANIFEST) as f:
        per_layer = {p["name"]: p for p in json.load(f)["per_layer"]}
    for name in READS:
        entry = dict(per_layer[name])
        assert set(CELLS) <= set(entry.pop("workloads"))
        assert entry == {"name": name, "unit": "s", "better": "lower",
                         "source": "program_counter", "layer": "entry points",
                         "moves": "setup_s"}
    assert [p for p in per_layer if p in READS] == list(READS)     # in ISSUE 36's order
