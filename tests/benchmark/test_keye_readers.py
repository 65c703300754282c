"""The five readers PR 48 brings, on a hand-made fixture
(tests/benchmark/data/keye_paths_fixture.json: two steps of a two-layer stack
whose attention is over a learned selection): device time under
``attn/indexer``, ``attn/select``, ``attn/core_dsa`` and ``attn/indexer_kl``,
and the ``flash_*_dsa`` launches against the peak, counted from the SELECTED
pairs of the traced steps' own rows: by hand here, query by query. What each
gives where the program has no such scope or kernel (the parent of PR 48, every
other cell). The manifest's entries, the reference's counts, and the tiny
preset under its limits."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, traffic
from benchmark.trace import reduce
from tests.benchmark.helpers import DATA, REPO, json_lines, run_cli, run_one_lap

FIXTURE = os.path.join(DATA, "keye_paths_fixture.json")
DENSE_FIXTURE = os.path.join(REPO, "benchmark", "trace", "scopes_fixture.json")
SDAR_FIXTURE = os.path.join(DATA, "sdar_paths_fixture.json")
TINY = os.path.join(DATA, "BENCHMARK.keye-vl2-tiny.json")
CELL = "keye-vl2-30b-a3b.train.dsa16k"
BY_SCOPE = {"train_attn_indexer_ms": 2600e-6, "train_attn_select_ms": 1600e-6,
            "train_attn_dsa_ms": 9200e-6, "train_indexer_kl_ms": 1200e-6}
FIVE = set(BY_SCOPE) | {"attn_dsa_roofline"}


def reader(name):
    return harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL).load_module(
        "layer_metrics", name)


def ctx_of(path, cell=None, **more):
    cell = cell or types.SimpleNamespace(traffic={"trace_steps": 2}, config={})
    return {"trace": reduce.load(path), "trace_out": {"trace_file": path},
            "cell": cell, "device_kind": "TPU v5 lite", **more}


@pytest.mark.parametrize("name", sorted(BY_SCOPE))
def test_each_part_is_read_by_its_scope(name):
    """A step, two layers: under ``attn/indexer`` 2 x (300 + 500) forward and 2
    x 500 again in the backward's recompute; under ``attn/select`` 2 x 400 twice;
    under ``attn/core_dsa`` 2 x 1000 forward and 2 x (1000 + 200 + 2400); under
    ``attn/indexer_kl`` 2 x 600. The selection's two scopes stand inside a
    loop's body (``attn/while/body/attn/select``); ``attn/qkv`` is nobody's."""
    assert reader(name).read(ctx_of(FIXTURE)) == pytest.approx(BY_SCOPE[name])


def pairs_by_hand(row, separator, topk):
    """The selected pairs of one head, query by query: min(visible, topk)."""
    sep = np.asarray(row) == separator
    doc = np.cumsum(sep) - sep
    i, j = np.indices((len(row), len(row)))
    visible = np.sum((doc[:, None] == doc[None, :]) & (j <= i), axis=1)
    return int(np.minimum(visible, topk).sum())


def test_the_roofline_counts_the_selected_pairs():
    """The tiny preset's cell (rows of 64, topk 8, 4 heads of 16) with the
    fixture's launches: a step 4 forward launches (two of them the backward's
    recompute) and 2 backward ones, 13,600 ns together over the two steps. The
    pairs come from the rows seed 5 draws for the traced steps (the stream's
    third and fourth batch); a pair costs 4 x 16 FLOPs forward and 10 x 16
    backward. A row of 64 has at most 8 x 64 selected pairs where a causal
    tile holds 2,080: the share reads low, and under 100."""
    cell = harness.Cell(TINY, "keye-vl2-tiny.train")
    mod = reader("attn_dsa_roofline")
    got = mod.read(ctx_of(FIXTURE, cell, rows=2, seed=5))
    stream = traffic.train_batches(cell.traffic, 5, 256, 2)
    batches = [next(stream)["input_ids"] for _ in range(4)][2:]
    pairs = [sum(pairs_by_hand(row, 255, 8) for row in b) for b in batches]
    assert all(0 < p <= 2 * 64 * 8 for p in pairs)
    flops = sum(p * 4 * (4 * 4 * 16 + 2 * 10 * 16) for p in pairs)
    # each launch's HLO, arrays of 4096 elements and more: forward the output
    # and q bf16[4,2,64,16], k and v bf16[4,64,16], the q ids s32[2,64,128], the
    # selection s8[2,64,64]; backward dq, dk, dv, q, k, v, do, the k ids and
    # the selection transposed
    big, kv = 4 * 2 * 64 * 16 * 2, 4 * 64 * 16 * 2
    fwd = 2 * big + 2 * kv + 2 * 64 * 128 * 4 + 2 * 64 * 64
    bwd = 3 * big + 4 * kv + 2 * 64 * 128 * 4 + 2 * 64 * 64
    moved = 8 * fwd + 4 * bwd
    seconds = (8 * 1000 + 4 * 2400) * 1e-9
    assert flops / 197e12 < moved / 819e9
    assert got == pytest.approx(100.0 * (moved / 819e9) / seconds, rel=1e-9) and 0 < got < 100
    # with operands too small to count, the FLOPs bound stands alone
    bare = ctx_of(FIXTURE, cell, rows=2, seed=5)
    for e in bare["trace"]["devices"]["/device:TPU:0"]:
        for shape in ("64,16]", "64,128]", "64,64]"):
            e[3] = e[3].replace(shape, "8,8]")
    assert mod.read(bare) == pytest.approx(100.0 * (flops / 197e12) / seconds, rel=1e-9)
    ref = cell.load_module("reference", "keye_vl2")
    lengths = cell.load_module("layer_metrics", "attn_window_roofline").document_lengths
    for row in ([1, 2, 9, 3, 9, 9, 4, 1], [1] * 20, [9] + [1] * 14 + [9]):
        for topk in (1, 3, 8, 64):
            assert ref.dsa_pairs(lengths(row, 9), topk) == pairs_by_hand(row, 9, topk)
    # one document of 20 under topk 8: 1 + 2 + ... + 8, then 12 times 8
    assert ref.dsa_pairs([20], 8) == 36 + 96


@pytest.mark.parametrize("name", sorted(FIVE))
def test_a_program_without_the_scope_or_the_kernels_reads_nothing(name):
    """The dense fixture (GPT-2's recorded step) and the block-diffusion one
    have neither a new scope nor a launch under the selection's name; a run
    without a trace has nothing to read: None, no raise."""
    bare = types.SimpleNamespace(traffic={}, config={})
    assert reader(name).read(ctx_of(DENSE_FIXTURE, bare)) is None
    assert reader(name).read(ctx_of(SDAR_FIXTURE)) is None
    assert reader(name).read({"cell": None}) is None


def test_the_manifest_lists_the_five_for_the_new_cell_alone():
    manifest = os.path.join(REPO, "BENCHMARK.json")
    cell = harness.Cell(manifest, CELL)
    mine = {m["name"] for m in cell.per_layer}
    assert FIVE <= mine
    assert {"moe_experts_roofline", "moe_held_load_ratio", "train_moe_route_ms",
            "train_moe_dispatch_ms", "train_moe_experts_ms", "adam_roofline", "train_mfu",
            "train_attn_ms", "device_idle_share.train", "setup_trace_s",
            "compile_s", "window_compile_s", "train_input_ms"} <= mine
    assert not {"train_attn_latent_ms", "train_attn_gate_ms", "train_moe_shared_ms",
                "train_mtp_ms", "train_attn_window_ms", "train_attn_full_ms",
                "attn_window_roofline", "train_attn_blockdiff_ms", "attn_blockdiff_roofline",
                "train_diffusion_noise_ms", "train_attn_eva_ms", "attn_eva_roofline"} & mine
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    with open(manifest) as f:
        m = json.load(f)
    assert cell.entry == {"name": CELL, "config": "keye-vl2-30b-a3b",
                          "traffic": "train.dsa16k", "chips": 1, "why": cell.entry["why"]}
    assert [p["name"] for p in m["per_layer"] if p["name"] in FIVE] == [
        "train_attn_indexer_ms", "train_attn_select_ms", "train_attn_dsa_ms",
        "train_indexer_kl_ms", "attn_dsa_roofline"]
    assert all(p["workloads"] == [CELL] and p["moves"] == "train_tokens_per_s"
               for p in m["per_layer"] if p["name"] in FIVE)
    for w in m["workloads"]:
        if w["name"] != CELL:
            theirs = harness.Cell(manifest, w["name"]).per_layer
            assert not FIVE & {p["name"] for p in theirs}
    c = cell.config
    assert (c["num_experts"], c["num_local_experts"], c["vocab_size"],
            c["num_hidden_layers"]) == (16, 16, 18992, 8)
    assert c["share"]["published"] == {"num_experts": 128, "num_local_experts": 128,
                                       "vocab_size": 151936, "num_hidden_layers": 48}
    assert (c["hidden_size"], c["moe_intermediate_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["num_experts_per_tok"],
            c["rope_theta"], c["max_position_embeddings"]) == (
                2048, 768, 32, 4, 128, 8, 10000000, 262144)
    assert c["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert c["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16,
                              "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                              "q_chunk_size": 512, "topk": 2048}
    assert c["model_type"] == "KeyeVL2" and c["assumed"]["separator"] == 18991
    t = cell.traffic
    assert (t["seq_len"], t["separator"], t["docs_per_cycle"], t["sync_every"]) == (
        16384, 18991, 512, 5) and "order_seed" in t
    assert t["doc_len"] == {"dist": "lognormal", "median": 4096, "sigma": 1.3,
                            "min": 8, "max": 16384}


def test_flops_per_token_live_with_the_equations():
    """``train_mfu`` asks the cell's reference file; by hand at the cell's size:
    a row meets 18,874,368 attention parameters, the indexer's 2,260,992, the
    router's 262,144 and 8 x 16 / 128 of an expert's 4,718,592, in 8 layers,
    and the head's 2048 x 18992; of its 8,192.5 visible keys a layer (a row as
    one document) it picks a mean 1,920.06; a picked key costs the main heads
    (12 + 2) x 32 x 128 and the indexer 6 x 16 x 64, a visible one 2 x 16 x 64."""
    cell = harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)
    ref = cell.load_module("reference", "keye_vl2")
    a_row = 18_874_368 + 2_260_992 + 262_144 + 4_718_592
    assert ref.matmul_params_a_row(cell.config) == a_row
    picked = (2048 * 2049 // 2 + (16384 - 2048) * 2048) / 16384
    assert ref.dsa_pairs([16384], 2048) / 16384 == picked
    assert ref.train_flops_per_token(cell.config, 16384) == pytest.approx(
        6 * (8 * a_row + 2048 * 18992)
        + 8 * (14 * 32 * 128 * picked + 2 * 16 * 64 * 8192.5 + 6 * 16 * 64 * picked), rel=1e-12)
    assert ref.expert_product_flops_per_row(cell.config) == 2 * 2048 * 768
    assert ref.attention_pair_flops(cell.config) == {"forward": 512.0, "backward": 1280.0,
                                                     "heads": 32}


def test_the_tiny_preset_is_held_to_its_limits_and_the_control_is_not():
    """``benchmark/limits.py`` on the CPU preset: the bf16 engine's first step
    through ``initialize`` stays under every limit of the preset's file on two
    seeds, and the fp8 reference in the program's place breaks the uphill
    share's."""
    with open(os.path.join(DATA, "benchmark/configs/keye-vl2-tiny.json")) as f:
        limits = {k: v for k, v in json.load(f)["limits"]["train"].items() if k != "why"}
    proc = run_cli("limits.py", "--manifest", TINY, "--workload", "keye-vl2-tiny.train",
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
    is counted in steps, one lap of 30 whatever the machine's load: this toy's
    loss wanders by 0.1 about its first value (over seed 3000000013's first 120
    steps it lies above it after 6, 7, 19, 20, 22, 26 and 28 steps and after
    most from 39 to 100), so a window of one second read it where the
    machine's other work let it end, and failed when that was a step of
    those."""
    line, fell = run_one_lap(tmp_path, TINY, "keye-vl2-tiny.train", 3000000013)
    assert line["correct"] is True and line["failed"] == 0 and fell > 0.05
    assert line["metrics"] == {} and line["off_chip"]["window_compiles"] == 0
