"""The six readers PR 57 brings, on a hand-made fixture
(tests/benchmark/data/phi4flash_paths_fixture.json: two steps of a scan layer, a
windowed and a full differential-attention layer and a gated memory unit):
device time under ``ssm``, ``ssm/scan``, ``gmu`` and ``attn/core_diff``; the
``ssm_scan_*`` launches against the bytes a scan has to move and the
``flash_*_diff`` launches against the peak, counted from the causal
same-document (and in-window) pairs of the traced steps' own rows at the
launch's TWO widths: by hand here. What each gives where the program has no such
scope or kernel (the parent of PR 57, every other cell). The manifest's entries
and the reference's counts."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, traffic
from benchmark.trace import reduce
from tests.benchmark.helpers import DATA, REPO

FIXTURE = os.path.join(DATA, "phi4flash_paths_fixture.json")
DENSE_FIXTURE = os.path.join(REPO, "benchmark", "trace", "scopes_fixture.json")
XING_FIXTURE = os.path.join(DATA, "xing4_paths_fixture.json")
TINY = os.path.join(DATA, "BENCHMARK.phi4flash-tiny.json")
CELL = "phi4-mini-flash-reasoning.train.sambay"
BY_SCOPE = {"train_ssm_ms": 4750e-6, "train_ssm_scan_ms": 4100e-6, "train_gmu_ms": 650e-6,
            "train_attn_diff_ms": 9650e-6}
SIX = set(BY_SCOPE) | {"ssm_scan_roofline", "attn_diff_roofline"}


def reader(name):
    return harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL).load_module(
        "layer_metrics", name)


def ctx_of(path, cell=None, **more):
    cell = cell or types.SimpleNamespace(traffic={"trace_steps": 2}, config={})
    return {"trace": reduce.load(path), "trace_out": {"trace_file": path},
            "cell": cell, "device_kind": "TPU v5 lite", **more}


@pytest.mark.parametrize("name", sorted(BY_SCOPE))
def test_each_part_is_read_by_its_scope(name):
    """A step: under ``ssm/in`` 200 + 300, ``ssm/scan`` 100 + 800 + 800 again +
    2,400, ``ssm/out`` 150: 4,750 ns under ``ssm``; ``gmu`` 250 + 400; under
    ``attn/core_diff`` 2 x 300 + 50 + 2 x 900 + 2 x 900 again + 2 x 2,000 + 2 x
    700 = 9,650. ``attn/qkv``, ``attn/out`` and ``attn/shared`` are nobody's here."""
    assert reader(name).read(ctx_of(FIXTURE)) == pytest.approx(BY_SCOPE[name])
    from benchmark.trace import scopes
    sums = scopes.of_run(ctx_of(FIXTURE))
    # the scan's and the unit's scopes stand inside ``block``: the unscoped class,
    # and the five classes still add up
    assert sums["unscoped"] * 1e9 == pytest.approx(2 * (4750 + 650))
    assert sums["attn"] * 1e9 == pytest.approx(2 * (9650 + 240))
    assert sums["total"] * 1e9 == pytest.approx(2 * 16490)
    assert sums["remat"] * 1e9 == pytest.approx(2 * (800 + 1800))


def pairs_by_hand(row, separator, window=None):
    sep = np.asarray(row) == separator
    doc = np.cumsum(sep) - sep
    i, j = np.indices((len(row), len(row)))
    seen = (doc[:, None] == doc[None, :]) & (j <= i)
    if window:
        seen &= i - j < window
    return int(np.sum(seen))


def test_the_scan_is_held_to_the_bytes_it_has_to_move():
    """The tiny preset's cell (2 rows of 64, 128 channels of 4 states, bfloat16
    operands): forward 2 x (3 x 128 + 2 x 4) B a token, backward 2 x (5 x 128 + 2
    x 4) + 4 x 8; a step launches the forward twice (once again in the backward)
    and the backward once: 4 launches of 800 ns and 2 of 2,400."""
    cell = harness.Cell(TINY, "phi4flash-tiny.train")
    ref = cell.load_module("reference", "phi4flash")
    cost = ref.scan_bytes_per_row(cell.config)
    assert cost == {"forward": 2 * (3 * 128 + 8), "backward": 2 * (5 * 128 + 8) + 32}
    got = reader("ssm_scan_roofline").read(ctx_of(FIXTURE, cell, rows=2, seq=64))
    need = 128 * (4 * cost["forward"] + 2 * cost["backward"])
    assert got == pytest.approx(100.0 * need / 819e9 / 8000e-9, rel=1e-12) and 0 < got < 100
    assert ref.scan_flops_per_row(cell.config) == {"forward": 7.0 * 512 + 3 * 128,
                                                   "backward": 20.0 * 512 + 8 * 128}


def test_the_roofline_counts_the_real_pairs_at_two_widths():
    """With the fixture's launches: a step 4 full forward launches (two the
    backward's recompute), 2 full backward, 2 windowed forward and 2 windowed
    backward; a pair and head costs 2 x 16 + 2 x 32 FLOPs forward and 6 x 16 + 4
    x 32 backward; a launch has 2 query heads; the window is 8."""
    cell = harness.Cell(TINY, "phi4flash-tiny.train")
    mod = reader("attn_diff_roofline")
    ref = cell.load_module("reference", "phi4flash")
    assert ref.diff_pair_flops(cell.config) == {"forward": 96.0, "backward": 224.0,
                                                "heads": 2, "launches": 2}
    got = mod.read(ctx_of(FIXTURE, cell, rows=2, seed=5))
    stream = traffic.train_batches(cell.traffic, 5, 96, 2)
    batches = [next(stream)["input_ids"] for _ in range(4)][2:]
    full = [sum(pairs_by_hand(row, 95) for row in b) for b in batches]
    cut = [sum(pairs_by_hand(row, 95, 8) for row in b) for b in batches]
    assert all(0 < c < f <= 2 * 64 * 65 // 2 for c, f in zip(cut, full))
    flops_full = sum(p * 2 * (4 * 96 + 2 * 224) for p in full)
    flops_cut = sum(p * 2 * (2 * 96 + 2 * 224) for p in cut)
    # each launch's HLO, arrays of 4096 elements and more: forward o
    # bf16[2,2,64,32], q bf16[2,2,64,16], v bf16[2,64,32] (k is 2,048 elements)
    # and the q ids s32[2,64,128]; backward dq, dv, q, v, do and the k ids
    q, o, v, ids = 2 * 2 * 64 * 16 * 2, 2 * 2 * 64 * 32 * 2, 2 * 64 * 32 * 2, 2 * 64 * 128 * 4
    fwd, bwd = o + q + v + ids, (q + v) + (q + v) + ids + o
    moved_full, moved_cut = 8 * fwd + 4 * bwd, 4 * fwd + 4 * bwd
    seconds = (8 * 900 + 4 * 2000 + 4 * 300 + 4 * 700) * 1e-9
    least = (max(flops_full / 197e12, moved_full / 819e9)
             + max(flops_cut / 197e12, moved_cut / 819e9))
    assert got == pytest.approx(100.0 * least / seconds, rel=1e-9) and 0 < got < 100
    lengths = cell.load_module("layer_metrics", "attn_window_roofline").document_lengths
    for row in ([1, 2, 9, 3, 9, 9, 4, 1], [1] * 20, [9] + [1] * 14 + [9]):
        for window in (None, 3, 100):
            assert ref.diff_pairs(lengths(row, 9), window) == pairs_by_hand(row, 9, window)
    assert ref.diff_pairs([20, 3]) == 210 + 6 and ref.diff_pairs([20, 3], 4) == 10 + 16 * 4 + 6


@pytest.mark.parametrize("name", sorted(SIX))
def test_a_program_without_the_scope_or_the_kernels_reads_nothing(name):
    bare = types.SimpleNamespace(traffic={}, config={})
    assert reader(name).read(ctx_of(DENSE_FIXTURE, bare)) is None
    assert reader(name).read(ctx_of(XING_FIXTURE)) is None
    assert reader(name).read({"cell": None}) is None


def test_the_manifest_lists_the_six_for_the_new_cell_alone():
    manifest = os.path.join(REPO, "BENCHMARK.json")
    cell = harness.Cell(manifest, CELL)
    mine = {m["name"] for m in cell.per_layer}
    assert SIX <= mine
    assert {"adam_roofline", "train_mfu", "train_attn_ms", "train_mlp_ms", "train_head_ms",
            "train_unscoped_ms", "device_idle_share.train", "setup_trace_s", "compile_s",
            "window_compile_s", "train_input_ms", "train_step_peak_gb"} <= mine
    # (the windowed layers run under ``attn/core_diff`` and launch ``flash_*_diff_window``:
    # the readers of ``attn/core_window`` and ``flash_*_window`` find nothing here)
    assert not {"train_attn_window_ms", "train_attn_full_ms", "attn_window_roofline",
                "train_attn_mla_ms", "attn_mla_roofline", "moe_experts_roofline",
                "train_moe_route_ms", "moe_held_load_ratio", "train_hc_ms"} & mine
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    with open(manifest) as f:
        m = json.load(f)
    # (by name, not by place: a later PR appends its cell and its metrics)
    assert cell.entry == {"name": CELL, "config": "phi4-mini-flash-reasoning",
                          "traffic": "train.sambay", "chips": 1, "why": cell.entry["why"]}
    six = [p for p in m["per_layer"] if p["name"] in SIX]
    assert [p["name"] for p in six] == [
        "train_ssm_ms", "train_ssm_scan_ms", "train_gmu_ms", "train_attn_diff_ms",
        "ssm_scan_roofline", "attn_diff_roofline"]
    assert all(p["workloads"] == [CELL] and p["moves"] == "train_tokens_per_s"
               and p["source"] == "device_trace" for p in six)
    for w in m["workloads"]:
        if w["name"] != CELL:
            theirs = harness.Cell(manifest, w["name"]).per_layer
            assert not SIX & {p["name"] for p in theirs}
    c = cell.config
    assert (c["vocab_size"], c["num_hidden_layers"]) == (25008, 8)
    assert c["share"]["published"] == {"vocab_size": 200064, "num_hidden_layers": 32}
    assert c["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (c["hidden_size"], c["intermediate_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["sliding_window"], c["mb_per_layer"],
            c["layer_norm_eps"], c["max_position_embeddings"], c["tie_word_embeddings"],
            c["mlp_bias"], c["lm_head_bias"], c["hidden_act"], c["embd_pdrop"],
            c["resid_pdrop"], c["model_type"]) == (
                2560, 10240, 40, 20, 512, 2, 1e-5, 262144, True, False, False, "silu", 0, 0,
                "phi4flash")
    a = c["assumed"]
    assert (a["d_inner"], a["d_state"], a["d_conv"], a["dt_rank"], a["separator"]) == (
        5120, 16, 4, 160, 25007)
    assert c["engine"]["train"]["ds_config"]["train_micro_batch_size_per_gpu"] == 1
    t = cell.traffic
    assert (t["seq_len"], t["separator"], t["docs_per_cycle"], t["sync_every"],
            t["trace_steps"]) == (16384, 25007, 512, 2, 3) and "order_seed" in t
    assert t["doc_len"] == {"dist": "lognormal", "median": 4096, "sigma": 1.3,
                            "min": 8, "max": 16384}


def test_flops_per_token_live_with_the_equations():
    """``train_mfu`` asks the cell's reference file; by hand at the cell's size:
    a scan mixer 2560 x 10240 + 5120 x 192 + 160 x 5120 + 5120 x 2560 matmul
    parameters, an attention layer 3 x 2560^2, a memory unit 2 x 2560 x 5120, a
    cross layer 2 x 2560^2, every layer's MLP 3 x 2560 x 10240, the head 2560 x
    25008 once; a scan layer 27 operations a state element and 11 a channel
    trained; a pair 384 + 896 for each of 2 x 20 query heads."""
    cell = harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)
    ref = cell.load_module("reference", "phi4flash")
    scan = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    params = (3 * scan + 3 * 3 * 2560 ** 2 + 2 * 2560 * 5120 + 2 * 2560 ** 2
              + 8 * 3 * 2560 * 10240 + 2560 * 25008)
    assert ref.matmul_params(cell.config) == params
    S = 16384
    windowed = (512 * 513 // 2 + (S - 512) * 512) / S
    want = (6 * params + 3 * (27 * 81920 + 11 * 5120)
            + 40 * 1280 * (2 * windowed + 2 * (S + 1) / 2))
    assert ref.train_flops_per_token(cell.config, S) == pytest.approx(want, rel=1e-12)
    assert ref.counts(ref.sizes(cell.config)) == {"ss": 2, "sw": 2, "ms": 1, "mf": 1,
                                                  "cg": 1, "cx": 1}
    whole = dict(cell.config, num_hidden_layers=32, vocab_size=200064)
    assert ref.counts(ref.sizes(whole)) == {"ss": 8, "sw": 8, "ms": 1, "mf": 1, "cg": 7, "cx": 7}
    assert [ref.depth_of(k, i, ref.sizes(whole)) for k, i in ref.order(ref.sizes(whole))] == list(range(32))
