"""The three readers PR 39 brings, on a hand-made fixture
(tests/benchmark/data/sdar_paths_fixture.json: two steps of a two-layer stack
under the block-diffusion objective): device time under ``attn/core_blockdiff``
and under ``diffusion/noise``, and the ``flash_*_blockdiff`` launches against
the peak, counted from the real (query, clean key) pairs of the traced steps'
own rows: by hand here, with the mask built position by position. What each
gives where the program has no such scope or kernel (the parent of PR 39, every
other cell). The manifest's entries, and the tiny preset under its limits."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, traffic
from benchmark.trace import reduce
from tests.benchmark.helpers import DATA, REPO, json_lines, run_cli, run_one_lap

FIXTURE = os.path.join(DATA, "sdar_paths_fixture.json")
DENSE_FIXTURE = os.path.join(REPO, "benchmark", "trace", "scopes_fixture.json")
TINY = os.path.join(DATA, "BENCHMARK.sdar-tiny.json")
CELL = "sdar-30b-a3b.train.bd8k"
THREE = {"train_attn_blockdiff_ms", "attn_blockdiff_roofline", "train_diffusion_noise_ms"}


def reader(name):
    return harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL).load_module(
        "layer_metrics", name)


def ctx_of(path, cell=None, **more):
    cell = cell or types.SimpleNamespace(traffic={"trace_steps": 2}, config={})
    return {"trace": reduce.load(path), "trace_out": {"trace_file": path},
            "cell": cell, "device_kind": "TPU v5 lite", **more}


def test_the_core_and_the_noise_are_read_by_their_scopes():
    """A step: under ``attn/core_blockdiff`` 2 x (1000 + 400 + 100) forward and
    2 x (2 x 1200 + 500) backward = 8800 ns; under ``diffusion/noise`` 300 + 200;
    ``attn/qkv`` is neither's."""
    assert reader("train_attn_blockdiff_ms").read(ctx_of(FIXTURE)) == pytest.approx(8800e-6)
    assert reader("train_diffusion_noise_ms").read(ctx_of(FIXTURE)) == pytest.approx(500e-6)


def pairs_by_hand(row, separator, b):
    """The kernel's pairs of one head, position by position: clean keys
    visible to the clean query and to the noised query of every position."""
    sep = np.asarray(row) == separator
    doc = np.cumsum(sep) - sep
    i, j = np.indices((len(row), len(row)))
    same = doc[:, None] == doc[None, :]
    return int(np.sum(same & (j // b <= i // b)) + np.sum(same & (j // b < i // b)))


def test_the_roofline_counts_the_pairs_that_exist():
    """The tiny preset's cell (rows of 64, b 4, 8 heads of 16) with the
    fixture's launches: a step 2 forward launches and 4 backward ones, each of
    the 4 over half the folded rows: 2 whole forward calls and 2 whole backward
    calls a step, 13,600 ns together over the two steps. The pairs come from the
    rows seed 5 draws for the traced steps (the stream's third and fourth
    batch), counted here under the mask itself; a pair costs 4 x 16 FLOPs
    forward and 10 x 16 backward. Whole 128 x 64 tiles would be 8192 pairs a
    row where at most 64 x 68 exist, so the same time over padded tiles would
    read twice as much: real pairs alone are counted, and the share stays under
    100."""
    cell = harness.Cell(TINY, "sdar-tiny.train")
    mod = reader("attn_blockdiff_roofline")
    got = mod.read(ctx_of(FIXTURE, cell, rows=2, seed=5))
    stream = traffic.train_batches(cell.traffic, 5, 256, 2)
    batches = [next(stream)["input_ids"] for _ in range(4)][2:]
    pairs = [sum(pairs_by_hand(row, 255, 4) for row in b) for b in batches]
    assert all(0 < p <= 2 * 64 * 68 for p in pairs)
    flops = sum(p * 8 * (2 * 4 * 16 + 2 * 10 * 16) for p in pairs)
    # each launch's HLO: forward results and operands bf16[4,4,128,16] twice (the
    # float32 row and the keys' 4096 elements are at or over the reader's
    # floor: k and v count, 4 x 64 x 16); backward f32[2,2,4,128,16] and q
    fwd = 2 * 4 * 4 * 128 * 16 * 2 + 2 * 4 * 64 * 16 * 2
    bwd = 2 * 2 * 4 * 128 * 16 * 4 + 2 * 4 * 128 * 16 * 2
    moved = 4 * fwd + 8 * bwd
    seconds = (4 * 1000 + 8 * 1200) * 1e-9
    assert flops / 197e12 < moved / 819e9
    assert got == pytest.approx(100.0 * (moved / 819e9) / seconds, rel=1e-9) and 0 < got < 100
    # with operands too small to count, the FLOPs bound stands alone; a launch
    # over half the folded rows is half a call
    bare = ctx_of(FIXTURE, cell, rows=2, seed=5)
    for e in bare["trace"]["devices"]["/device:TPU:0"]:
        e[3] = e[3].replace("128,16]", "8,16]").replace("64,16]", "8,16]")
    assert mod.read(bare) == pytest.approx(100.0 * (flops / 197e12) / seconds, rel=1e-9)
    events = [e for e in ctx_of(FIXTURE)["trace"]["devices"]["/device:TPU:0"]]
    assert mod.whole_calls([e for e in events if mod.BACKWARD.match(e[0])], 4) == 4.0
    assert mod.whole_calls([e for e in events if mod.FORWARD.match(e[0])], 4) == 4.0
    assert mod.whole_calls([], 4) == 0.0
    # the pieces: a row's documents (the window reader's helper) and where they lie
    lengths = cell.load_module("layer_metrics", "attn_window_roofline").document_lengths
    assert mod.pieces_of(lengths([1, 2, 9, 3, 9, 9, 4], 9)) == [(0, 3), (3, 2), (5, 1), (6, 1)]
    ref = cell.load_module("reference", "sdar_moe")
    for row in ([1, 2, 9, 3, 9, 9, 4, 1], [1] * 20, [9] + [1] * 14 + [9]):
        assert ref.kernel_pairs(mod.pieces_of(lengths(row, 9)), cell.config) \
            == pairs_by_hand(row, 9, 4)
    # one document of 20 from the row's start: clean 4 x (4 + 8 + 12 + 16 + 20),
    # noised 4 x (0 + 4 + 8 + 12 + 16); cut to start at 2, the blocks stay the row's
    assert ref.kernel_pairs([(0, 20)], cell.config) == 240 + 160
    assert ref.kernel_pairs([(2, 20)], cell.config) == pairs_by_hand([9, 9] + [1] * 20, 9, 4) - 2


@pytest.mark.parametrize("name", sorted(THREE))
def test_a_program_without_the_scope_or_the_kernels_reads_nothing(name):
    """The dense fixture (GPT-2's recorded step) has ``attn/core`` and neither
    new scope, and no launch under the mask's name; a run without a trace has
    nothing to read: None, no raise."""
    dense = ctx_of(DENSE_FIXTURE, types.SimpleNamespace(traffic={}, config={}))
    assert reader(name).read(dense) is None
    assert reader(name).read({"cell": None}) is None


def test_the_manifest_lists_the_three_for_the_new_cell_alone():
    manifest = os.path.join(REPO, "BENCHMARK.json")
    cell = harness.Cell(manifest, CELL)
    mine = {m["name"] for m in cell.per_layer}
    assert THREE <= mine
    assert {"moe_experts_roofline", "moe_held_load_ratio", "train_moe_route_ms",
            "train_moe_dispatch_ms", "train_moe_experts_ms", "adam_roofline", "train_mfu",
            "train_attn_ms", "device_idle_share.train", "setup_trace_s"} <= mine
    assert not {"train_attn_latent_ms", "train_attn_gate_ms", "train_moe_shared_ms",
                "train_mtp_ms", "train_attn_window_ms", "train_attn_full_ms",
                "attn_window_roofline"} & mine
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    with open(manifest) as f:
        m = json.load(f)
    # by name, wherever a later PR's entries have pushed them
    assert cell.entry["config"] == "sdar-30b-a3b" and cell.entry["chips"] == 1
    assert [p["name"] for p in m["per_layer"] if p["name"] in THREE] == [
        "train_attn_blockdiff_ms", "attn_blockdiff_roofline", "train_diffusion_noise_ms"]
    for w in m["workloads"]:
        if w["name"] != CELL:
            theirs = harness.Cell(manifest, w["name"]).per_layer
            assert not THREE & {p["name"] for p in theirs}
    c = cell.config
    assert (c["num_experts"], c["vocab_size"], c["num_hidden_layers"]) == (16, 18992, 8)
    assert (c["hidden_size"], c["moe_intermediate_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["num_experts_per_tok"],
            c["rope_theta"]) == (2048, 768, 32, 4, 128, 8, 1000000)
    assert c["assumed"]["block_length"] == 4 and c["assumed"]["mask_token_id"] == 18992
    assert cell.traffic["seq_len"] == 8192 and cell.traffic["separator"] == 18991


def test_flops_per_token_live_with_the_equations():
    """``train_mfu`` asks the cell's reference file; by hand at the cell's
    size: a row meets 18,874,368 attention parameters, the router's 262,144
    and 8 x 16 / 128 of an expert's 4,718,592; a data token is two rows in 8
    layers and the head's 2048 x 18992 once; its two queries see 8192 + 4
    keys a layer at 12 x 32 x 128 FLOPs a key."""
    cell = harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)
    ref = cell.load_module("reference", "sdar_moe")
    a_row = 18_874_368 + 262_144 + 4_718_592
    assert ref.matmul_params_a_row(cell.config) == a_row
    assert ref.train_flops_per_token(cell.config, 8192) == \
        6 * (2 * 8 * a_row + 2048 * 18992) + 12 * 32 * 128 * 8 * 8196
    assert ref.expert_product_flops_per_row(cell.config) == 2 * 2048 * 768
    assert ref.attention_pair_flops(cell.config) == {"forward": 512.0, "backward": 1280.0,
                                                     "heads": 32}


def test_the_tiny_preset_is_held_to_its_limits_and_the_control_is_not():
    """``benchmark/limits.py`` on the CPU preset, as for the GPT-2 preset in
    test_reference.py: the bf16 engine's first step through ``initialize``
    stays under every limit of the preset's file on two seeds, and the fp8
    reference in the program's place breaks the uphill share's."""
    with open(os.path.join(DATA, "benchmark/configs/sdar-tiny.json")) as f:
        limits = {k: v for k, v in json.load(f)["limits"]["train"].items() if k != "why"}
    proc = run_cli("limits.py", "--manifest", TINY, "--workload", "sdar-tiny.train",
                   "--seeds", "11,3000000013", "--control-seeds", "12", "--control", "fp8")
    assert proc.returncode == 0, proc.stderr[-2000:]
    readings = [l for l in json_lines(proc) if "seed" in l]
    sound = [r for r in readings if r["control"] is None]
    control = [r for r in readings if r["control"] == "fp8"]
    assert len(sound) == 2 and len(control) == 1
    assert all(r[k] <= limits[k] for r in sound for k in limits), sound
    key = "first_step_uphill_share"
    assert control[0][key] > limits[key] and control[0][key] >= 3 * max(r[key] for r in sound)


def test_the_tiny_cell_runs_end_to_end(tmp_path):
    """The command itself on the preset: correct, nothing failed, nothing
    compiled in the window, and the loss lower at the window's end. The window
    is counted in steps, one lap of 30 whatever the machine's load: a batch's
    loss follows the noise level its step drew (over seed 3000000013's first
    60 steps it lies above the first step's after 6, 13, 28, 42, 48 and 53),
    so a window of one second would fail where the machine's other work let
    it end on one of those."""
    line, fell = run_one_lap(tmp_path, TINY, "sdar-tiny.train", 3000000013)
    assert line["correct"] is True and line["failed"] == 0 and fell > 0.05
    assert line["metrics"] == {} and line["off_chip"]["window_compiles"] == 0
