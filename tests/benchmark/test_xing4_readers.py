"""The four readers PR 55 brings, on a hand-made fixture
(tests/benchmark/data/xing4_paths_fixture.json: two steps of one block under
hyper-connections whose attention core is the two-width launch): device time
under ``hc``, ``hc/coeff`` and ``attn/core_mla``, and the ``flash_*_mla`` launches
against the peak, counted from the causal same-document pairs of the traced
steps' own rows at the launch's TWO widths: by hand here. What each gives where
the program has no such scope or kernel (the parent of PR 55, every other
cell). The manifest's entries and the reference's counts."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, traffic
from benchmark.trace import reduce
from tests.benchmark.helpers import DATA, REPO

FIXTURE = os.path.join(DATA, "xing4_paths_fixture.json")
DENSE_FIXTURE = os.path.join(REPO, "benchmark", "trace", "scopes_fixture.json")
KEYE_FIXTURE = os.path.join(DATA, "keye_paths_fixture.json")
TINY = os.path.join(DATA, "BENCHMARK.xing4-tiny.json")
CELL = "xing4-29b-a4b.train.mhc"
BY_SCOPE = {"train_hc_ms": 2950e-6, "train_hc_coeff_ms": 1700e-6,
            "train_attn_mla_ms": 4750e-6}
FOUR = set(BY_SCOPE) | {"attn_mla_roofline"}


def reader(name):
    return harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL).load_module(
        "layer_metrics", name)


def ctx_of(path, cell=None, **more):
    cell = cell or types.SimpleNamespace(traffic={"trace_steps": 2}, config={})
    return {"trace": reduce.load(path), "trace_out": {"trace_file": path},
            "cell": cell, "device_kind": "TPU v5 lite", **more}


@pytest.mark.parametrize("name", sorted(BY_SCOPE))
def test_each_part_is_read_by_its_scope(name):
    """A step: under ``hc/coeff`` 2 x 300 forward, 600 made again and 500
    backward; under ``hc/pre`` 2 x 100 and 150; under ``hc/post`` 2 x 250 and
    400: 2,950 ns under ``hc``. Under ``attn/core_mla`` the forward launch
    twice, the transpose and the backward launch: 4,750. ``attn/latent`` and
    ``attn/qkv`` are nobody's here."""
    assert reader(name).read(ctx_of(FIXTURE)) == pytest.approx(BY_SCOPE[name])
    assert reader("train_attn_latent_ms").read(ctx_of(FIXTURE)) == pytest.approx(200e-6)
    from benchmark.trace import scopes
    sums = scopes.of_run(ctx_of(FIXTURE))
    # the streams' scopes stand inside ``block``: the unscoped class, and the
    # five classes still add up
    assert sums["unscoped"] * 1e9 == pytest.approx(2 * 2950)
    assert sums["total"] * 1e9 == pytest.approx(2 * 9350)


def pairs_by_hand(row, separator):
    sep = np.asarray(row) == separator
    doc = np.cumsum(sep) - sep
    i, j = np.indices((len(row), len(row)))
    return int(np.sum((doc[:, None] == doc[None, :]) & (j <= i)))


def test_the_roofline_counts_the_real_pairs_at_two_widths():
    """The tiny preset's cell (2 rows of 64, 2 heads of 32 with values of 16)
    with the fixture's launches: a step 2 forward launches (one the backward's
    recompute) and 1 backward, 9,200 ns over the two steps. A pair costs 2 x 32
    + 2 x 16 FLOPs forward and 6 x 32 + 4 x 16 backward."""
    cell = harness.Cell(TINY, "xing4-tiny.train")
    mod = reader("attn_mla_roofline")
    got = mod.read(ctx_of(FIXTURE, cell, rows=2, seed=5))
    stream = traffic.train_batches(cell.traffic, 5, 256, 2)
    batches = [next(stream)["input_ids"] for _ in range(4)][2:]
    pairs = [sum(pairs_by_hand(row, 255) for row in b) for b in batches]
    assert all(0 < p <= 2 * 64 * 65 // 2 for p in pairs)
    flops = sum(p * 2 * (2 * 96 + 256) for p in pairs)
    # each launch's HLO, arrays of 4096 elements and more: forward o
    # bf16[4,1,64,16], q bf16[4,1,64,32], k bf16[4,64,32], v bf16[4,64,16] and the
    # q ids s32[2,64,128]; backward dq, dk, dv, q, k, v, do and the k ids
    q, v = 4 * 64 * 32 * 2, 4 * 64 * 16 * 2
    fwd = v + q + q + v + 2 * 64 * 128 * 4
    bwd = (q + q + v) + (q + q + v) + v + 2 * 64 * 128 * 4
    moved = 4 * fwd + 2 * bwd
    seconds = (4 * 1000 + 2 * 2600) * 1e-9
    assert flops / 197e12 < moved / 819e9
    assert got == pytest.approx(100.0 * (moved / 819e9) / seconds, rel=1e-9) and 0 < got < 100
    # with operands too small to count, the FLOPs bound stands alone
    bare = ctx_of(FIXTURE, cell, rows=2, seed=5)
    for e in bare["trace"]["devices"]["/device:TPU:0"]:
        for shape in ("64,16]", "64,32]", "64,128]"):
            e[3] = e[3].replace(shape, "8,8]")
    assert mod.read(bare) == pytest.approx(100.0 * (flops / 197e12) / seconds, rel=1e-9)
    ref = cell.load_module("reference", "xing4")
    lengths = cell.load_module("layer_metrics", "attn_window_roofline").document_lengths
    for row in ([1, 2, 9, 3, 9, 9, 4, 1], [1] * 20, [9] + [1] * 14 + [9]):
        assert ref.mla_pairs(lengths(row, 9)) == pairs_by_hand(row, 9)
    assert ref.mla_pairs([20, 3]) == 210 + 6


@pytest.mark.parametrize("name", sorted(FOUR))
def test_a_program_without_the_scope_or_the_kernels_reads_nothing(name):
    bare = types.SimpleNamespace(traffic={}, config={})
    assert reader(name).read(ctx_of(DENSE_FIXTURE, bare)) is None
    assert reader(name).read(ctx_of(KEYE_FIXTURE)) is None
    assert reader(name).read({"cell": None}) is None


def test_the_manifest_lists_the_four_for_the_new_cell_alone():
    manifest = os.path.join(REPO, "BENCHMARK.json")
    cell = harness.Cell(manifest, CELL)
    mine = {m["name"] for m in cell.per_layer}
    assert FOUR <= mine
    assert {"moe_experts_roofline", "moe_held_load_ratio", "train_moe_route_ms",
            "train_moe_dispatch_ms", "train_moe_experts_ms", "train_moe_shared_ms",
            "train_attn_latent_ms", "train_mtp_ms", "adam_roofline", "train_mfu",
            "train_attn_ms", "device_idle_share.train", "setup_trace_s", "compile_s",
            "window_compile_s", "train_input_ms", "train_step_peak_gb"} <= mine
    assert not {"train_attn_gate_ms", "train_attn_window_ms", "train_attn_full_ms",
                "attn_window_roofline", "train_attn_blockdiff_ms", "train_attn_eva_ms",
                "train_attn_dsa_ms", "attn_dsa_roofline"} & mine
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    with open(manifest) as f:
        m = json.load(f)
    # (by name, not by place: a later PR appends its cell and its metrics)
    assert cell.entry == {"name": CELL, "config": "xing4-29b-a4b",
                          "traffic": "train.mhc", "chips": 1, "why": cell.entry["why"]}
    four = [p for p in m["per_layer"] if p["name"] in FOUR]
    assert [p["name"] for p in four] == [
        "train_hc_ms", "train_hc_coeff_ms", "train_attn_mla_ms", "attn_mla_roofline"]
    assert all(p["workloads"] == [CELL] and p["moves"] == "train_tokens_per_s"
               and p["source"] == "device_trace" for p in four)
    for w in m["workloads"]:
        if w["name"] != CELL:
            theirs = harness.Cell(manifest, w["name"]).per_layer
            assert not FOUR & {p["name"] for p in theirs}
    c = cell.config
    assert (c["n_routed_experts"], c["vocab_size"], c["num_hidden_layers"]) == (8, 16384, 6)
    assert c["share"]["published"] == {"n_routed_experts": 64, "vocab_size": 131072,
                                       "num_hidden_layers": 40}
    assert c["reduced"] == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"],
            c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["num_experts_per_tok"], c["num_nextn_predict_layers"],
            c["first_k_dense_replace"]) == (3584, 9216, 1024, 32, 768, 512, 128, 64, 128, 4, 1, 2)
    assert (c["hc_mult"], c["hc_sinkhorn_iters"], c["hc_eps"], c["mhc_h_res_clamp_min"],
            c["mhc_h_res_clamp_max"]) == (4, 20, 1e-6, -30, 30)
    assert c["rope_scaling"]["factor"] == 64 and c["model_type"] == "xing4_0"
    assert c["assumed"]["separator"] == 16383
    assert c["engine"]["train"]["ds_config"]["train_micro_batch_size_per_gpu"] == 1
    t = cell.traffic
    assert (t["seq_len"], t["separator"], t["docs_per_cycle"], t["sync_every"],
            t["trace_steps"]) == (8192, 16383, 512, 5, 3) and "order_seed" in t
    assert t["doc_len"] == {"dist": "lognormal", "median": 2048, "sigma": 1.3,
                            "min": 8, "max": 8192}


def test_flops_per_token_live_with_the_equations():
    """``train_mfu`` asks the cell's reference file; by hand at the cell's size:
    latent attention 28,409,856 parameters (3584 x 768 + 768 x 6144 + 3584 x 576
    + 512 x 8192 + 4096 x 3584), the two sub-layers' Phi 2 x 14336 x 24, a dense
    MLP 3 x 3584 x 9216; an expert layer's router 3584 x 64, shared expert and 4
    x 8 / 64 of an expert 3 x 3584 x 1024 each; the merge 2 x 3584^2; the head
    twice. A sub-layer's mixings cost (2 x 16 + 16) x 3584 a token forward; a
    pair and head 2 x (192 + 128)."""
    cell = harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)
    ref = cell.load_module("reference", "xing4")
    attn = 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584 + 2 * 14336 * 24
    expert = attn + 3584 * 64 + 3 * 3584 * 1024 + 4 * 8 / 64 * 3 * 3584 * 1024
    params = 2 * (attn + 3 * 3584 * 9216) + 5 * expert + 2 * 3584 ** 2 + 2 * 3584 * 16384
    assert ref.matmul_params(cell.config) == params
    assert ref.mixing_flops_per_token(cell.config) == 48 * 3584
    assert ref.train_flops_per_token(cell.config, 8192) == pytest.approx(
        6 * params + 3 * 14 * 48 * 3584 + 3 * 7 * 32 * 320 * 8192, rel=1e-12)
    assert ref.expert_product_flops_per_row(cell.config) == 2 * 3584 * 1024
    assert ref.mla_pair_flops(cell.config) == {"forward": 640.0, "backward": 1664.0,
                                               "heads": 32}
