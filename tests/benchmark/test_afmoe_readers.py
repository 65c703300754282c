"""The three readers PR 34 brings, on a hand-made fixture
(tests/benchmark/data/afmoe_paths_fixture.json: two steps of five sliding
layers and one full one): device time under ``attn/core_window`` and under
``attn/core`` apart, and the sliding layers' flash launches against the peak,
counted from the real (query, key) pairs of the traced steps' own rows: by
hand here, with a mask built position by position. What each gives where
the program has no such scope or kernel (the parent of PR 34, every other
cell). And the tiny preset under its limits."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, traffic
from benchmark.trace import reduce
from tests.benchmark.helpers import DATA, REPO, json_lines, run_cli

FIXTURE = os.path.join(DATA, "afmoe_paths_fixture.json")
DENSE_FIXTURE = os.path.join(REPO, "benchmark", "trace", "scopes_fixture.json")
TINY = os.path.join(DATA, "BENCHMARK.afmoe-tiny.json")
CELL = "trinity-mini.train.seq16k"


def reader(name):
    return harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL).load_module(
        "layer_metrics", name)


def ctx_of(path, cell=None, **more):
    cell = cell or types.SimpleNamespace(traffic={"trace_steps": 2}, config={})
    return {"trace": reduce.load(path), "trace_out": {"trace_file": path},
            "cell": cell, "device_kind": "TPU v5 lite", **more}


def test_the_two_kinds_of_core_are_read_apart():
    """A step: 5 x 40 + 100 + 5 x 60 ns under ``attn/core_window``, 200 + 400
    + 50 under ``attn/core``; a component is matched whole."""
    assert reader("train_attn_window_ms").read(ctx_of(FIXTURE)) == pytest.approx(600e-6)
    assert reader("train_attn_full_ms").read(ctx_of(FIXTURE)) == pytest.approx(650e-6)


def pairs_by_hand(row, separator, window):
    """Visible pairs of one head, position by position."""
    doc = np.cumsum(np.asarray(row) == separator) - (np.asarray(row) == separator)
    i, j = np.indices((len(row), len(row)))
    return int(np.sum((j <= i) & (i - j < window) & (doc[:, None] == doc[None, :])))


def test_the_roofline_counts_the_pairs_that_exist():
    """The tiny preset's cell (rows of 64 under a window of 16, 8 heads of
    16) with the fixture's launches: 5 forward and 5 backward a step, 1000 ns
    together over the two steps. The pairs come from the rows seed 5 draws
    for the traced steps (the stream's third and fourth batch), counted here
    under the mask itself; a pair costs 4 x 16 FLOPs forward and 10 x 16
    backward. Whole 64 x 64 tiles would be 4096 pairs a row where at most
    904 exist, so the same time over padded tiles would read four times as
    much: real pairs alone are counted, and the share stays under 100."""
    cell = harness.Cell(TINY, "afmoe-tiny.train")
    mod = reader("attn_window_roofline")
    got = mod.read(ctx_of(FIXTURE, cell, rows=2, seed=5))
    stream = traffic.train_batches(cell.traffic, 5, 256, 2)
    batches = [next(stream)["input_ids"] for _ in range(4)][2:]
    pairs = [sum(pairs_by_hand(row, 255, 16) for row in b) for b in batches]
    assert all(0 < p < 2 * (16 * 17 // 2 + 48 * 16) + 1 for p in pairs)
    flops = sum(p * 8 * (5 * 4 * 16 + 5 * 10 * 16) for p in pairs)
    # each launch's HLO: one bf16[2,4,64,16] (the float32 row of 512 is under
    # the reader's floor); at this toy size the bytes bound, at the cell's the FLOPs
    moved = 20 * 2 * 4 * 64 * 16 * 2
    assert flops / 197e12 < moved / 819e9
    assert got == pytest.approx(100.0 * (moved / 819e9) / 1000e-9, rel=1e-9) and 0 < got < 100
    # with operands too small to count, the FLOPs bound stands alone
    bare = ctx_of(FIXTURE, cell, rows=2, seed=5)
    for e in bare["trace"]["devices"]["/device:TPU:0"]:
        e[3] = e[3].replace("bf16[2,4,64,16]", "bf16[2,4,16,16]")
    assert mod.read(bare) == pytest.approx(100.0 * (flops / 197e12) / 1000e-9, rel=1e-9)
    # the pieces: the seed off the command line, a row's documents
    assert mod.seed_of_run(["run.py", "--seed", "3000000013", "--trace", "1"]) == 3000000013
    assert mod.seed_of_run(["run.py", "--seed=7"]) == 7 and mod.seed_of_run(["run.py"]) == 0
    assert mod.document_lengths([1, 2, 9, 3, 9, 9, 4], 9) == [3, 2, 1, 1]
    ref = cell.load_module("reference", "afmoe")
    assert ref.window_pairs([3, 2, 1, 1], cell.config) == 6 + 3 + 1 + 1
    assert ref.window_pairs([20], cell.config) == 16 * 17 // 2 + 4 * 16 \
        == pairs_by_hand([1] * 20, 0, 16)


@pytest.mark.parametrize("name", ["train_attn_window_ms", "attn_window_roofline"])
def test_a_program_without_the_scope_or_the_kernels_reads_nothing(name):
    """The dense fixture (GPT-2's recorded step) has ``attn/core`` and no
    ``attn/core_window``, and no launch cut to a window; a run without a
    trace has nothing to read: None, no raise."""
    dense = ctx_of(DENSE_FIXTURE, types.SimpleNamespace(traffic={}, config={}))
    assert reader(name).read(dense) is None
    assert reader(name).read({"cell": None}) is None


def test_the_manifest_lists_the_three_for_the_new_cell_alone():
    cell = harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)
    mine = {m["name"] for m in cell.per_layer}
    three = {"train_attn_window_ms", "train_attn_full_ms", "attn_window_roofline"}
    assert three <= mine
    assert {"moe_experts_roofline", "train_attn_gate_ms", "moe_held_load_ratio",
            "train_moe_shared_ms", "adam_roofline", "train_mfu"} <= mine
    assert not {"train_attn_latent_ms", "train_mtp_ms"} & mine
    for other in ("olmoe-1b-7b.train.seq4k", "instella-moe-16b-a3b.train.seq8k"):
        theirs = harness.Cell(os.path.join(REPO, "BENCHMARK.json"), other).per_layer
        assert not three & {m["name"] for m in theirs}
    assert cell.config["layer_types"][:6].count("full_attention") == 1
    assert (cell.config["num_experts"], cell.config["vocab_size"],
            cell.config["num_hidden_layers"]) == (16, 25024, 6)


def test_the_tiny_preset_is_held_to_its_limits_and_the_control_is_not():
    """``benchmark/limits.py`` on the CPU preset, as for the GPT-2 preset in
    test_reference.py: the bf16 engine's first step through ``initialize``
    stays under every limit of the preset's file on two seeds, and the fp8
    reference in the program's place breaks the uphill share's."""
    with open(os.path.join(DATA, "benchmark/configs/afmoe-tiny.json")) as f:
        limits = {k: v for k, v in json.load(f)["limits"]["train"].items() if k != "why"}
    proc = run_cli("limits.py", "--manifest", TINY, "--workload", "afmoe-tiny.train",
                   "--seeds", "11,3000000013", "--control-seeds", "12", "--control", "fp8")
    assert proc.returncode == 0, proc.stderr[-2000:]
    readings = [l for l in json_lines(proc) if "seed" in l]
    sound = [r for r in readings if r["control"] is None]
    control = [r for r in readings if r["control"] == "fp8"]
    assert len(sound) == 2 and len(control) == 1
    assert all(r[k] <= limits[k] for r in sound for k in limits), sound
    key = "first_step_uphill_share"
    assert control[0][key] > limits[key] and control[0][key] >= 3 * max(r[key] for r in sound)
