"""The reader PR 62 brings, on a hand-made fixture
(tests/benchmark/data/smallthinker_paths_fixture.json: two steps of one
expert layer routed ahead of its mixer): device time under ``moe/route/ahead``
beside ``train_moe_route_ms`` whole, the three ``train_moe_*_ms`` against
``train_mlp_ms``, the five classes against the trace's busy time; what the
reader gives where the program has no such scope (every other cell, the
parent of PR 62); the new cell's place on the manifest's lists; and what the
new reference counts for the readers, by hand at the published sizes."""

import json
import os
import types

import pytest

from benchmark import harness
from benchmark.trace import moe, reduce, scopes
from tests.benchmark.helpers import DATA, REPO

FIXTURE = os.path.join(DATA, "smallthinker_paths_fixture.json")
DENSE_FIXTURE = os.path.join(REPO, "benchmark", "trace", "scopes_fixture.json")
OLMOE_FIXTURE = os.path.join(REPO, "benchmark", "trace", "moe_fixture.json")
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
CELL = "smallthinker-21b-a3b.train.win16k"


def reader(name):
    return harness.Cell(MANIFEST, CELL).load_module("layer_metrics", name).read


def ctx_of(path, steps=2):
    cell = types.SimpleNamespace(traffic={"trace_steps": steps}, config={})
    return {"trace": reduce.load(path), "trace_out": {"trace_file": path},
            "cell": cell, "device_kind": "TPU v5 lite"}


def test_what_is_routed_ahead_is_read_beside_the_whole_routing():
    """A step: 30 + 20 + 15 ns under ``moe/route/ahead``, and two sorts of 25
    under ``moe/route`` alone; the new reader is part of the old one."""
    ahead = reader("train_moe_route_ahead_ms")(ctx_of(FIXTURE))
    route = reader("train_moe_route_ms")(ctx_of(FIXTURE))
    assert ahead == pytest.approx(65e-6) and route == pytest.approx(115e-6)
    assert 0 < ahead <= route


def test_the_three_expert_readers_add_up_to_the_mlp_class():
    """``mlp`` holds the routing made ahead of the mixer too: the three
    readers and the one copy outside their scopes are ``train_mlp_ms``, and
    the five classes are the device's busy time."""
    ctx = ctx_of(FIXTURE)
    parts = [reader(n)(ctx) for n in ("train_moe_route_ms", "train_moe_dispatch_ms",
                                      "train_moe_experts_ms")]
    assert parts == pytest.approx([115e-6, 160e-6, 540e-6])
    assert reader("train_mlp_ms")(ctx) == pytest.approx(sum(parts) + 10e-6)
    sums = scopes.of_run(ctx)
    assert sums["total"] == pytest.approx(2 * 1560e-9)
    # (a grouped matmul is classed by its name: never as recomputed)
    assert sums["attn"] == pytest.approx(2 * 700e-9) and sums["remat"] == pytest.approx(2 * 500e-9)
    assert moe.products_a_step(ctx) == 2
    assert reader("train_attn_full_ms")(ctx) == pytest.approx(600e-6)
    assert reader("train_attn_window_ms")(ctx) == pytest.approx(100e-6)


@pytest.mark.parametrize("path,steps", [(DENSE_FIXTURE, None), (OLMOE_FIXTURE, 1)],
                         ids=["dense", "routed-from-the-ffn-input"])
def test_a_program_without_the_scope_reads_nothing(path, steps):
    """GPT-2's recorded step has no expert layer, OLMoE's routes inside the
    layer (``moe/route`` and no ``ahead``), as every program before PR 62: None,
    no raise; a run without a trace has nothing to read either."""
    ctx = ctx_of(path, steps)
    if steps is None:
        ctx["cell"] = types.SimpleNamespace(traffic={}, config={})
    assert reader("train_moe_route_ahead_ms")(ctx) is None
    assert reader("train_moe_route_ahead_ms")({"cell": None}) is None


def test_the_manifest_lists_the_new_cell_where_its_readers_find_something():
    cell = harness.Cell(MANIFEST, CELL)
    mine = {m["name"] for m in cell.per_layer}
    assert {"train_moe_route_ahead_ms", "train_moe_route_ms", "train_moe_dispatch_ms",
            "train_moe_experts_ms", "moe_experts_roofline", "moe_held_load_ratio",
            "train_attn_window_ms", "train_attn_full_ms", "attn_window_roofline",
            "train_mfu", "adam_roofline", "train_step_peak_gb",
            "train_memory_unused_share"} <= mine
    assert not {"train_moe_shared_ms", "train_attn_gate_ms", "train_attn_latent_ms",
                "train_mtp_ms", "attn_blockdiff_roofline"} & mine
    with open(MANIFEST) as f:
        manifest = json.load(f)
    new, = [m for m in manifest["per_layer"] if m["name"] == "train_moe_route_ahead_ms"]
    assert new["workloads"] == [CELL] and new["moves"] == "train_tokens_per_s"
    assert [w["chips"] for w in manifest["workloads"] if w["name"] == CELL] == [1]
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s", "setup_s"]
    cfg = cell.config
    assert (cfg["moe_num_primary_experts"], cfg["vocab_size"], cfg["num_hidden_layers"]) \
        == (16, 37984, 4)
    assert cfg["sliding_window_layout"][:4] == [0, 1, 1, 1] == cfg["rope_layout"][:4]
    assert len(cfg["sliding_window_layout"]) == 52          # the layouts are kept whole
    assert cell.traffic["separator"] == cfg["assumed"]["separator"] == cfg["vocab_size"] - 1
    assert cfg["engine"]["train"]["ds_config"]["train_micro_batch_size_per_gpu"] == 2


def test_what_the_reference_counts_for_the_readers():
    """By hand at the published widths: one expert product over one row is
    2 x 2560 x 768; a pair costs 4 x 128 FLOPs forward and 10 x 128 backward
    over 28 query heads; a document of 5,000 tokens under the window of 4,096
    holds 4096 x 4097 / 2 + 904 x 4096 pairs; a token meets 6 x 16 / 64 of an
    expert's 3 x 2560 x 768 parameters here."""
    cell = harness.Cell(MANIFEST, CELL)
    ref = cell.load_module("reference", "smallthinker")
    cfg = cell.config
    assert ref.expert_product_flops_per_row(cfg) == 2 * 2560 * 768
    assert ref.attention_pair_flops(cfg) == {"forward": 512.0, "backward": 1280.0, "heads": 28}
    assert ref.window_pairs([5000, 3, 1], cfg) == 4096 * 4097 // 2 + 904 * 4096 + 6 + 1
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert ref.matmul_params(cfg) == 4 * (attn + 2560 * 64 + 1.5 * 3 * 2560 * 768) + 2560 * 37984
    keys = (16385 / 2) + 3 * (4096 * 4097 / 2 + (16384 - 4096) * 4096) / 16384
    assert ref.train_flops_per_token(cfg, 16384) == pytest.approx(
        6 * ref.matmul_params(cfg) + 12 * 28 * 128 * keys)
    s = ref.sizes(cfg)
    assert (s["E"], s["Eh"], s["lo"], s["k"], s["windowed"]) == (64, 16, 0, 6, (False, True, True, True))
