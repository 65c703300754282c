"""The four readers PR 68 brings, on a hand-made fixture
(tests/benchmark/data/kimi_paths_fixture.json: two steps of one Kimi Delta
Attention layer and one attention layer): device time under ``attn/core_kda``,
``attn/kda_in`` and ``attn/kda_gate``, beside ``attn`` whole; the ``kda_*`` launches
against the LARGER of the bytes the recurrence has to move and the products it has
to make, by hand; what each gives where the program has no such scope or kernel
(the parent of PR 68, every other cell); the manifest's entries, found by NAME;
and the reference's counts at the published sizes."""

import json
import os
import types

import pytest

from benchmark import harness
from benchmark.trace import reduce, scopes
from tests.benchmark.helpers import DATA, REPO

FIXTURE = os.path.join(DATA, "kimi_paths_fixture.json")
DENSE_FIXTURE = os.path.join(REPO, "benchmark", "trace", "scopes_fixture.json")
GRANITE_FIXTURE = os.path.join(DATA, "granite_paths_fixture.json")
TINY = os.path.join(DATA, "BENCHMARK.kimi-linear-tiny.json")
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
CELL = "kimi-linear-48b-a3b.train.kda32k"
BY_SCOPE = {"train_attn_kda_ms": 3080e-6, "train_kda_in_ms": 850e-6, "train_kda_gate_ms": 50e-6}
FOUR = set(BY_SCOPE) | {"attn_kda_roofline"}


def reader(name):
    return harness.Cell(MANIFEST, CELL).load_module("layer_metrics", name)


def ctx_of(path, cell=None, **more):
    cell = cell or types.SimpleNamespace(traffic={"trace_steps": 2}, config={})
    return {"trace": reduce.load(path), "trace_out": {"trace_file": path},
            "cell": cell, "device_kind": "TPU v5 lite", **more}


@pytest.mark.parametrize("name", sorted(BY_SCOPE))
def test_each_part_is_read_by_its_scope(name):
    """A step: under ``attn/kda_in`` 200 + 300 + 350, ``attn/kda_gate`` 50,
    ``attn/core_kda`` 600 + 600 again + 1,800 + 80; with ``attn/kda_out``'s 120 + 150 +
    200 and the attention layer's 100 + 400 + 100 + 900 they are ``attn``'s 5,950 ns:
    nothing of a KDA layer is unscoped (a scan layer's ``ssm`` scopes are)."""
    assert reader(name).read(ctx_of(FIXTURE)) == pytest.approx(BY_SCOPE[name])
    whole = reader("train_attn_ms").read(ctx_of(FIXTURE))
    assert whole == pytest.approx(sum(BY_SCOPE.values()) + 470e-6 + 1500e-6) == pytest.approx(
        5950e-6)
    sums = scopes.of_run(ctx_of(FIXTURE))
    assert sums["total"] * 1e9 == pytest.approx(2 * 7150)
    assert sums["remat"] * 1e9 == pytest.approx(2 * 600)
    assert sums["unscoped"] == 0


def test_the_core_is_held_to_the_larger_of_its_bytes_and_its_products():
    """The tiny preset's cell (2 rows of 64, 2 heads of 16 x 16, bfloat16 operands):
    forward 2 x 4 x 32 + 4 x 32 + 4 x 2 B and 7 x 512 operations a token, backward
    2 x 8 x 32 + 8 x 32 + 8 x 2 B and 19 x 512; a step launches the forward twice (once
    again in the backward) and the backward once: 4 launches of 600 ns and 2 of 1,800."""
    cell = harness.Cell(TINY, "kimi-linear-tiny.train")
    ref = cell.load_module("reference", "kimi_linear")
    moved, made = ref.kda_bytes_per_row(cell.config), ref.kda_flops_per_row(cell.config)
    assert moved == {"forward": 256 + 128 + 8, "backward": 512 + 256 + 16}
    assert made == {"forward": 7.0 * 512, "backward": 19.0 * 512}
    got = reader("attn_kda_roofline").read(ctx_of(FIXTURE, cell, rows=2, seq=64))
    least = {k: max(moved[k] / 819e9, made[k] / 197e12) for k in moved}
    assert least["forward"] == moved["forward"] / 819e9       # the bytes bind, both ways
    need = 128 * (4 * least["forward"] + 2 * least["backward"])
    assert got == pytest.approx(100.0 * need / 6000e-9, rel=1e-12) and 0 < got < 100


@pytest.mark.parametrize("name", sorted(FOUR))
def test_a_program_without_the_scope_or_the_kernels_reads_nothing(name):
    """The dense fixture, no trace at all, and a Mamba-2 stack (its launches are
    ``ssd_*``, its scopes ``ssm/*``): None, never an error."""
    bare = types.SimpleNamespace(traffic={}, config={})
    assert reader(name).read(ctx_of(DENSE_FIXTURE, bare)) is None
    assert reader(name).read({"cell": None}) is None
    tiny = harness.Cell(os.path.join(DATA, "BENCHMARK.granite-hybrid-tiny.json"),
                        "granite-hybrid-tiny.train")
    assert reader(name).read(ctx_of(GRANITE_FIXTURE, tiny, rows=2, seq=64)) is None


def test_the_manifest_lists_the_four_for_the_new_cell_alone():
    cell = harness.Cell(MANIFEST, CELL)
    mine = {m["name"] for m in cell.per_layer}
    assert FOUR <= mine
    assert {"adam_roofline", "train_mfu", "train_attn_ms", "train_mlp_ms", "train_head_ms",
            "train_unscoped_ms", "device_idle_share.train", "setup_trace_s", "compile_s",
            "window_compile_s", "train_input_ms", "train_step_peak_gb",
            "train_moe_route_ms", "train_moe_dispatch_ms", "train_moe_experts_ms",
            "moe_experts_roofline", "train_moe_shared_ms", "moe_held_load_ratio",
            "train_attn_latent_ms"} <= mine
    # (``train_attn_mla_ms`` and ``attn_mla_roofline`` stay the hyper-connected cell's:
    # tests/benchmark/test_xing4_readers.py holds their lists to that cell, and no PR
    # but a benchmark PR edits that file; the latent layer's core here is in
    # ``train_attn_ms`` and in PERF.md's breakdown)
    assert not {"train_attn_mla_ms", "attn_mla_roofline", "train_ssm_ms", "train_ssm_ssd_ms", "ssm_ssd_roofline", "train_attn_gate_ms",
                "train_mtp_ms", "train_attn_window_ms", "attn_window_roofline",
                "train_hc_ms", "train_moe_route_ahead_ms"} & mine
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    with open(MANIFEST) as f:
        m = json.load(f)
    # (by name, not by place: a later PR appends its cell and its metrics)
    assert cell.entry == {"name": CELL, "config": "kimi-linear-48b-a3b",
                          "traffic": "train.kda32k", "chips": 1, "why": cell.entry["why"]}
    four = [p for p in m["per_layer"] if p["name"] in FOUR]
    assert sorted(p["name"] for p in four) == sorted(FOUR)
    assert all(p["workloads"] == [CELL] and p["moves"] == "train_tokens_per_s"
               and p["source"] == "device_trace" for p in four)
    assert {p["name"]: (p["unit"], p["layer"]) for p in four}["attn_kda_roofline"] == (
        "%", "kernels")
    for w in m["workloads"]:
        if w["name"] != CELL:
            theirs = harness.Cell(MANIFEST, w["name"]).per_layer
            assert not FOUR & {p["name"] for p in theirs}
    entry = {c["name"]: c for c in m["configs"]}["kimi-linear-48b-a3b"]
    assert entry["reduced"] == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
                               "blob/main/config.json")
    c = cell.config
    assert (c["vocab_size"], c["num_hidden_layers"], c["num_experts"]) == (20480, 5, 16)
    assert c["share"]["published"] == {"num_experts": 256, "vocab_size": 163840,
                                       "num_hidden_layers": 27}
    assert c["share"]["chips_sharing_a_layer"] == 16
    assert c["reduced"] == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"], c["q_lora_rank"],
            c["num_experts_per_token"], c["num_shared_experts"], c["routed_scaling_factor"],
            c["first_k_dense_replace"], c["mla_use_nope"], c["rms_norm_eps"],
            c["tie_word_embeddings"], c["model_type"]) == (
                2304, 9216, 1024, 32, 32, 512, 128, 64, 128, None, 8, 1, 2.446, 1, True, 1e-5,
                False, "kimi_linear")
    lin = c["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) == list(range(1, 28))
    assert c["assumed"]["separator"] == 20479
    assert c["engine"]["train"]["ds_config"]["train_micro_batch_size_per_gpu"] == 1
    t = cell.traffic
    assert (t["seq_len"], t["separator"], t["docs_per_cycle"], t["sync_every"],
            t["trace_steps"], t["order_seed"]) == (32768, 20479, 512, 2, 3, 293)
    assert t["doc_len"] == {"dist": "lognormal", "median": 4096, "sigma": 1.3,
                            "min": 8, "max": 32768}
    assert t["token_dist"] == {"dist": "zipf", "a": 1.2}


def test_flops_bytes_and_parameters_live_with_the_equations():
    """``train_mfu`` and ``attn_kda_roofline`` ask the cell's reference file; by hand
    at the cell's size: a KDA mixer 4 x 2304 x 4096 + 2 x (2304 x 128 + 128 x 4096) +
    2304 x 32 matmul parameters, the latent one 2304 x 6144 + 2304 x 576 + 512 x 8192 +
    4096 x 2304, the dense MLP 3 x 2304 x 9216, an expert layer's router 2304 x 256, its
    shared expert and 8 x 16 / 256 of an expert a token 3 x 2304 x 1024 each, the head
    2304 x 20480 once; a KDA layer 26 operations a state element of 32 x 128 x 128
    trained; forward 49,280 B a token (1.97 ms a layer at 32,768 rows and 819 GB/s)."""
    cell = harness.Cell(MANIFEST, CELL)
    ref = cell.load_module("reference", "kimi_linear")
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    latent = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    expert = 3 * 2304 * 1024
    experts = 2304 * 256 + expert + 8 * 16 / 256 * expert
    params = 4 * kda + latent + 3 * 2304 * 9216 + 4 * experts + 2304 * 20480
    assert ref.matmul_params(cell.config) == pytest.approx(params, rel=1e-12)
    S = 32768
    want = 6 * params + 4 * 26 * 524288 + 32 * (640 + 1664) * (S + 1) / 2
    assert ref.train_flops_per_token(cell.config, S) == pytest.approx(want, rel=1e-12)
    moved = ref.kda_bytes_per_row(cell.config)
    assert moved == {"forward": 2 * 4 * 4096 + 4 * 4096 + 4 * 32,
                     "backward": 2 * 8 * 4096 + 8 * 4096 + 8 * 32}
    assert S * moved["forward"] / 819e9 == pytest.approx(1.97e-3, rel=2e-3)
    assert ref.kda_flops_per_row(cell.config) == {"forward": 7.0 * 524288,
                                                  "backward": 19.0 * 524288}
    s = ref.sizes(cell.config)
    assert ref.stretches(s) == [("r0", ("kda", "dense"), 1), ("r1", ("kda", "experts"), 2),
                                ("r2", ("latent", "experts"), 1), ("r3", ("kda", "experts"), 1)]
    assert (s["E"], s["Eh"], s["lo"], s["k"]) == (256, 16, 0, 8)
    assert ref.mla_pairs([20, 3]) == 210 + 6
    # the program's own tree at the cell's size (the configuration file's params_note)
    adapter = cell.load_module("adapters", "kimi_linear")
    model = adapter.model(cell.config, remat=True, dtype="bfloat16")
    assert model.config.num_parameters() == 828_926_848
    assert "828,926,848" in cell.config["params_note"]
    assert [tuple(kind[2] for kind in unit) + (n,) for unit, n in model.run_plan] == [
        ("kda", 1), ("kda", 2), ("latent", "kda", 1)]
    assert model.config.moe.experts_held == (0, 16) and model.config.moe.num_experts == 256
