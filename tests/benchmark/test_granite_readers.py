"""The four readers PR 65 brings, on a hand-made fixture
(tests/benchmark/data/granite_paths_fixture.json: two steps of one Mamba-2 layer
and one attention layer): device time under ``ssm/ssd``, ``ssm/in`` and
``ssm/out``, beside ``ssm`` whole; the ``ssd_*`` launches against the LARGER of the
bytes the recurrence has to move and the products it has to make, by hand; what
each gives where the program has no such scope or kernel (the parent of PR 65,
every other cell); the manifest's entries, found by name; and the reference's
counts at the published sizes."""

import json
import os
import types

import pytest

from benchmark import harness
from benchmark.trace import reduce, scopes
from tests.benchmark.helpers import DATA, REPO

FIXTURE = os.path.join(DATA, "granite_paths_fixture.json")
DENSE_FIXTURE = os.path.join(REPO, "benchmark", "trace", "scopes_fixture.json")
PHI4_FIXTURE = os.path.join(DATA, "phi4flash_paths_fixture.json")
TINY = os.path.join(DATA, "BENCHMARK.granite-hybrid-tiny.json")
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
CELL = "granite-4.0-h-micro.train.ssd32k"
BY_SCOPE = {"train_ssm_ssd_ms": 3130e-6, "train_ssm_in_ms": 850e-6, "train_ssm_out_ms": 470e-6}
FOUR = set(BY_SCOPE) | {"ssm_ssd_roofline"}


def reader(name):
    return harness.Cell(MANIFEST, CELL).load_module("layer_metrics", name)


def ctx_of(path, cell=None, **more):
    cell = cell or types.SimpleNamespace(traffic={"trace_steps": 2}, config={})
    return {"trace": reduce.load(path), "trace_out": {"trace_file": path},
            "cell": cell, "device_kind": "TPU v5 lite", **more}


@pytest.mark.parametrize("name", sorted(BY_SCOPE))
def test_each_part_is_read_by_its_scope(name):
    """A step: under ``ssm/in`` 200 + 300 + 350, ``ssm/ssd`` 50 + 600 + 600 again +
    1,800 + 80, ``ssm/out`` 120 + 150 + 200: 4,450 ns under ``ssm``, which the three
    add up to; ``attn`` and ``mlp`` are nobody's here."""
    assert reader(name).read(ctx_of(FIXTURE)) == pytest.approx(BY_SCOPE[name])
    whole = reader("train_ssm_ms").read(ctx_of(FIXTURE))
    assert whole == pytest.approx(sum(BY_SCOPE.values())) == pytest.approx(4450e-6)
    sums = scopes.of_run(ctx_of(FIXTURE))
    # the scan layer's scopes stand inside ``block``: the unscoped class, and the
    # five classes still add up
    assert sums["total"] * 1e9 == pytest.approx(2 * 7150)
    assert sums["remat"] * 1e9 == pytest.approx(2 * 600)
    assert sums["unscoped"] * 1e9 == pytest.approx(2 * 4450)


def test_the_core_is_held_to_the_larger_of_its_bytes_and_its_products():
    """The tiny preset's cell (2 rows of 64, 2 heads of 64 over 16 states in one
    group, bfloat16 operands): forward 2 x (2 x 128 + 2 x 16) + 4 x 2 B and 4 x
    2,048 operations a token, backward 2 x (3 x 128 + 32) + 4 x 32 + 4 x 3 x 2 B and
    10 x 2,048; a step launches the forward twice (once again in the backward) and
    the backward once: 4 launches of 600 ns and 2 of 1,800."""
    cell = harness.Cell(TINY, "granite-hybrid-tiny.train")
    ref = cell.load_module("reference", "granite_hybrid")
    moved, made = ref.ssd_bytes_per_row(cell.config), ref.ssd_flops_per_row(cell.config)
    assert moved == {"forward": 2 * (256 + 32) + 8, "backward": 2 * (384 + 32) + 128 + 24}
    assert made == {"forward": 4.0 * 2048, "backward": 10.0 * 2048}
    got = reader("ssm_ssd_roofline").read(ctx_of(FIXTURE, cell, rows=2, seq=64))
    least = {k: max(moved[k] / 819e9, made[k] / 197e12) for k in moved}
    assert least["forward"] == moved["forward"] / 819e9       # the bytes bind, both ways
    need = 128 * (4 * least["forward"] + 2 * least["backward"])
    assert got == pytest.approx(100.0 * need / 6000e-9, rel=1e-12) and 0 < got < 100


@pytest.mark.parametrize("name", sorted(FOUR))
def test_a_program_without_the_scope_or_the_kernels_reads_nothing(name):
    bare = types.SimpleNamespace(traffic={}, config={})
    assert reader(name).read(ctx_of(DENSE_FIXTURE, bare)) is None
    assert reader(name).read({"cell": None}) is None
    if name in ("train_ssm_ssd_ms", "ssm_ssd_roofline"):
        # a selective-scan layer (the Phi-4 cell's) has ``ssm/in`` and ``ssm/out``
        # and neither this scope nor these launches
        tiny = harness.Cell(os.path.join(DATA, "BENCHMARK.phi4flash-tiny.json"),
                            "phi4flash-tiny.train")
        assert reader(name).read(ctx_of(PHI4_FIXTURE, tiny, rows=2, seq=64)) is None
    else:
        assert reader(name).read(ctx_of(PHI4_FIXTURE)) > 0


def test_the_manifest_lists_the_four_for_the_new_cell_alone():
    cell = harness.Cell(MANIFEST, CELL)
    mine = {m["name"] for m in cell.per_layer}
    assert FOUR <= mine
    assert {"adam_roofline", "train_mfu", "train_attn_ms", "train_mlp_ms", "train_head_ms",
            "train_unscoped_ms", "device_idle_share.train", "setup_trace_s", "compile_s",
            "window_compile_s", "train_input_ms", "train_step_peak_gb"} <= mine
    # (``train_ssm_ms`` stays the Phi-4 cell's: tests/benchmark/test_phi4flash_readers.py
    # holds its list to that cell, and no PR but a benchmark PR edits that file)
    assert not {"train_ssm_ms", "train_ssm_scan_ms", "ssm_scan_roofline", "train_gmu_ms",
                "train_attn_window_ms", "train_attn_full_ms", "attn_window_roofline",
                "moe_experts_roofline", "train_moe_route_ms", "train_hc_ms"} & mine
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    with open(MANIFEST) as f:
        m = json.load(f)
    # (by name, not by place: a later PR appends its cell and its metrics)
    assert cell.entry == {"name": CELL, "config": "granite-4.0-h-micro",
                          "traffic": "train.ssd32k", "chips": 1, "why": cell.entry["why"]}
    four = [p for p in m["per_layer"] if p["name"] in FOUR]
    assert sorted(p["name"] for p in four) == sorted(FOUR)
    assert all(p["workloads"] == [CELL] and p["moves"] == "train_tokens_per_s"
               and p["source"] == "device_trace" for p in four)
    assert {p["name"]: (p["unit"], p["layer"]) for p in four}["ssm_ssd_roofline"] == (
        "%", "kernels")
    for w in m["workloads"]:
        if w["name"] != CELL:
            theirs = harness.Cell(MANIFEST, w["name"]).per_layer
            assert not FOUR & {p["name"] for p in theirs}
    entry = {c["name"]: c for c in m["configs"]}["granite-4.0-h-micro"]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/"
                               "blob/main/config.json")
    c = cell.config
    assert (c["vocab_size"], c["num_hidden_layers"]) == (25088, 10)
    assert c["share"]["published"] == {"vocab_size": 100352, "num_hidden_layers": 40}
    assert c["share"]["chips_sharing_a_layer"] == 4
    assert c["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (c["hidden_size"], c["intermediate_size"], c["shared_intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"], c["mamba_n_heads"],
            c["mamba_d_head"], c["mamba_d_state"], c["mamba_n_groups"], c["mamba_d_conv"],
            c["mamba_expand"], c["mamba_chunk_size"], c["attention_multiplier"],
            c["embedding_multiplier"], c["residual_multiplier"], c["logits_scaling"],
            c["rms_norm_eps"], c["max_position_embeddings"], c["num_local_experts"],
            c["position_embedding_type"], c["tie_word_embeddings"], c["model_type"]) == (
                2048, 8192, 8192, 32, 8, 64, 64, 128, 1, 4, 2, 256, 0.015625, 12, 0.22, 8,
                1e-5, 131072, 0, "nope", True, "granitemoehybrid")
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert c["layer_types"] == period * 4
    assert c["assumed"]["separator"] == 25087
    assert c["engine"]["train"]["ds_config"]["train_micro_batch_size_per_gpu"] == 1
    t = cell.traffic
    assert (t["seq_len"], t["separator"], t["docs_per_cycle"], t["sync_every"],
            t["trace_steps"], t["order_seed"]) == (32768, 25087, 512, 2, 3, 293)
    assert t["doc_len"] == {"dist": "lognormal", "median": 4096, "sigma": 1.3,
                            "min": 8, "max": 32768}
    assert t["token_dist"] == {"dist": "zipf", "a": 1.2}


def test_flops_bytes_and_parameters_live_with_the_equations():
    """``train_mfu`` and ``ssm_ssd_roofline`` ask the cell's reference file; by hand
    at the cell's size: a Mamba-2 mixer 2048 x 8512 + 4096 x 2048 matmul parameters,
    the attention layer 2 x 2048^2 + 2 x 2048 x 512, every layer's MLP 3 x 2048 x
    8192, the head 2048 x 25088 once; a scan layer 14 operations a state element of
    64 x 64 x 128 trained; a pair 4 x 64 + 10 x 64 for each of 32 query heads;
    forward 17,152 B a token (0.69 ms a layer at 32,768 rows and 819 GB/s)."""
    cell = harness.Cell(MANIFEST, CELL)
    ref = cell.load_module("reference", "granite_hybrid")
    mamba, attention = 2048 * 8512 + 4096 * 2048, 2 * 2048 ** 2 + 2 * 2048 * 512
    params = 9 * mamba + attention + 10 * 3 * 2048 * 8192 + 2048 * 25088
    assert ref.matmul_params(cell.config) == params
    S = 32768
    want = 6 * params + 9 * 14 * 524288 + 32 * 14 * 64 * (S + 1) / 2
    assert ref.train_flops_per_token(cell.config, S) == pytest.approx(want, rel=1e-12)
    moved = ref.ssd_bytes_per_row(cell.config)
    assert moved == {"forward": 17152, "backward": 2 * (3 * 4096 + 256) + 4 * 256 + 12 * 64}
    assert S * moved["forward"] / 819e9 == pytest.approx(0.686e-3, rel=2e-3)
    assert ref.ssd_flops_per_row(cell.config) == {"forward": 4.0 * 524288,
                                                  "backward": 10.0 * 524288}
    s = ref.sizes(cell.config)
    assert ref.stretches(s) == [("r0", "mamba", 5), ("r1", "attention", 1), ("r2", "mamba", 4)]
    whole = ref.sizes(dict(cell.config, num_hidden_layers=40, vocab_size=100352))
    assert [n for _, _, n in ref.stretches(whole)] == [5, 1, 9, 1, 9, 1, 9, 1, 4]
    assert ref.attention_pairs([20, 3]) == 210 + 6
    # the program's own tree at the cell's size (the configuration file's params_note)
    adapter = cell.load_module("adapters", "granite_hybrid")
    model = adapter.model(cell.config, remat=True, dtype="bfloat16")
    assert model.config.num_parameters() == 797_850_560
    assert [(unit[0][2], n) for unit, n in model.run_plan] == [("ssd", 5), ("mha", 1), ("ssd", 4)]
