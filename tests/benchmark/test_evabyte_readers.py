"""The three readers PR 42 brings, on a hand-made fixture
(tests/benchmark/data/evabyte_paths_fixture.json: two steps of a one-layer
stack under EVA attention, its heads in two groups): device time under
``core_eva`` and under ``eva_summaries``, and the ``flash_*_eva_*`` launches
against the peak, counted from the real (query, exact key) and (query,
summary) pairs of a row: by hand here, position by position. What each gives
where the program has no such scope or kernel (the parent of PR 42, every
other cell). The manifest's entries, the FLOPs a token requires, and the tiny
preset under its limits."""

import json
import os
import types

import pytest

from benchmark import harness
from benchmark.trace import reduce
from tests.benchmark.helpers import DATA, REPO, json_lines, run_cli

FIXTURE = os.path.join(DATA, "evabyte_paths_fixture.json")
DENSE_FIXTURE = os.path.join(REPO, "benchmark", "trace", "scopes_fixture.json")
TINY = os.path.join(DATA, "BENCHMARK.evabyte-tiny.json")
CELL = "evabyte-6.5b.train.seq32k"
THREE = {"train_attn_eva_ms", "train_eva_summary_ms", "attn_eva_roofline"}


def reader(name):
    return harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL).load_module(
        "layer_metrics", name)


def ctx_of(path, cell=None, **more):
    cell = cell or types.SimpleNamespace(traffic={"trace_steps": 2}, config={})
    return {"trace": reduce.load(path), "trace_out": {"trace_file": path},
            "cell": cell, "device_kind": "TPU v5 lite", **more}


def test_the_core_and_the_summaries_are_read_by_their_scopes():
    """A step, two groups: under ``core_eva`` 2 x 2 x (800 + 600 + 100) forward
    and again and 2 x (120 + 1100 + 1500) backward = 11,440 ns; under
    ``eva_summaries`` 2 x 2 x 150 + 2 x 250 = 1,100; ``qkv`` is neither's,
    and the group scan's components between ``attn`` and a scope hide
    neither."""
    assert reader("train_attn_eva_ms").read(ctx_of(FIXTURE)) == pytest.approx(11440e-6)
    assert reader("train_eva_summary_ms").read(ctx_of(FIXTURE)) == pytest.approx(1100e-6)


def pairs_by_hand(L, W, c):
    """One head's pairs over a row, position by position."""
    exact = sum(1 for i in range(L) for j in range(L) if j // W == i // W and j <= i)
    summary = sum(1 for i in range(L) for g in range(L // c) if (g * c) // W < i // W)
    return exact, summary


def test_the_roofline_counts_the_pairs_that_exist():
    """The tiny preset's cell (rows of 128, window 32, chunk 4, 4 heads of 16)
    with the fixture's launches: a step has 4 local and 4 far forward launches
    (two groups, the forward run again) and 2 of each backward, each over 2 of
    the 4 heads. The pairs are a row's own, counted here under the mask itself
    (2,112 exact and 1,536 summary a head), a pair 4 x 16 FLOPs forward and 10
    x 16 backward. A launch's whole 32 x 32 tiles would be 4,096 exact pairs a
    head where 2,112 exist: real pairs alone are counted, and the share stays
    under 100."""
    cell = harness.Cell(TINY, "evabyte-tiny.train")
    mod = reader("attn_eva_roofline")
    got = mod.read(ctx_of(FIXTURE, cell, rows=2, seq=128))
    exact, summary = pairs_by_hand(128, 32, 4)
    assert (exact, summary) == (4 * 32 * 33 // 2, 32 * 8 * 6)
    ref = cell.load_module("reference", "evabyte")
    assert ref.eva_pairs(cell.config, 128) == {"exact": exact, "summary": summary}
    # a step: each launch holds 2 rows x 2 heads; two groups make the 4 heads
    heads_rows = 2 * 4
    a_step = heads_rows * ((2 * 4 * 16 + 10 * 16) * exact + (2 * 4 * 16 + 10 * 16) * summary)
    flops = 2 * a_step
    seconds = 2 * (4 * 800 + 4 * 600 + 2 * 1100 + 2 * 1500) * 1e-9
    # a launch's bytes: a forward local launch writes bf16[16,1,32,16] and
    # reads q of that shape and k, v bf16[16,32,16]: four arrays of 8,192
    # elements (its float32 row is under the reader's floor)
    events = [e for e in ctx_of(FIXTURE)["trace"]["devices"]["/device:TPU:0"]
              if mod.LAUNCH.match(e[0])]
    assert len(events) == 2 * 12
    local = next(e for e in events if e[0].startswith("flash_fwd_eva_local"))
    far = next(e for e in events if e[0].startswith("flash_bwd_eva_far"))
    assert mod.custom_call_io_bytes(local[3]) == 4 * 8192 * 2
    moved = sum(mod.custom_call_io_bytes(e[3]) for e in events)
    assert moved / 819e9 > flops / 197e12       # at toy sizes the bytes bound
    assert got == pytest.approx(100.0 * (moved / 819e9) / seconds, rel=1e-9) and 0 < got < 100
    # with operands too small to count, the FLOPs bound stands alone
    bare = ctx_of(FIXTURE, cell, rows=2, seq=128)
    for e in bare["trace"]["devices"]["/device:TPU:0"]:
        e[3] = e[3].replace(",32,16]", ",4,16]").replace(",128,16]", ",4,16]")
    assert mod.read(bare) == pytest.approx(100.0 * (flops / 197e12) / seconds, rel=1e-9)
    # a launch's FLOPs by its own folded rows: a local row is one window of a head
    cost = ref.attention_pair_flops(cell.config)
    pairs = {"exact": exact, "summary": summary}
    assert mod.folded_rows(local[3]) == 16 and mod.folded_rows(far[3]) == 4
    assert mod.launch_flops(local[0], local[3], pairs, cost, 4) == 16 * exact / 4 * 64
    assert mod.launch_flops(far[0], far[3], pairs, cost, 4) == 4 * summary * 160


@pytest.mark.parametrize("name", sorted(THREE))
def test_a_program_without_the_scope_or_the_kernels_reads_nothing(name):
    """The dense fixture (GPT-2's recorded step) has ``attn/core`` and neither
    new scope, and no launch under EVA's names; a run without a trace has
    nothing to read: None, no raise."""
    dense = ctx_of(DENSE_FIXTURE, types.SimpleNamespace(traffic={}, config={}),
                   rows=4, seq=1024)
    assert reader(name).read(dense) is None
    assert reader(name).read({"cell": None}) is None


def test_the_manifest_lists_the_three_for_the_new_cell_alone():
    manifest = os.path.join(REPO, "BENCHMARK.json")
    cell = harness.Cell(manifest, CELL)
    mine = {m["name"] for m in cell.per_layer}
    assert THREE <= mine
    assert {"adam_roofline", "train_mfu", "train_attn_ms", "train_mlp_ms", "train_head_ms",
            "train_remat_ms", "device_idle_share.train", "setup_trace_s",
            "setup_import_s", "compile_s", "window_compile_s"} <= mine
    assert not {m for m in mine if m.startswith(("moe_", "train_moe_"))}
    assert not {"train_attn_latent_ms", "train_attn_gate_ms", "train_mtp_ms",
                "train_attn_window_ms", "train_attn_full_ms", "attn_window_roofline",
                "train_attn_blockdiff_ms", "attn_blockdiff_roofline",
                "train_diffusion_noise_ms"} & mine
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    with open(manifest) as f:
        m = json.load(f)
    # by name, wherever a later PR's entries have pushed them
    assert cell.entry["config"] == "evabyte-6.5b" and cell.entry["chips"] == 1
    config = {c["name"]: c for c in m["configs"]}["evabyte-6.5b"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert [p["name"] for p in m["per_layer"] if p["name"] in THREE] == [
        "train_attn_eva_ms", "train_eva_summary_ms", "attn_eva_roofline"]
    for w in m["workloads"]:
        if w["name"] != CELL:
            theirs = harness.Cell(manifest, w["name"]).per_layer
            assert not THREE & {p["name"] for p in theirs}
    c = cell.config
    assert (c["hidden_size"], c["intermediate_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["window_size"], c["chunk_size"], c["num_pred_heads"],
            c["vocab_size"], c["max_position_embeddings"], c["rope_theta"],
            c["num_hidden_layers"]) == (4096, 11008, 32, 32, 2048, 16, 8, 320, 32768, 100000, 4)
    assert "share" not in c
    t = cell.traffic
    assert (t["seq_len"], t["separator"], t["sync_every"], t["trace_steps"]) == (32768, 319, 2, 3)
    assert t["doc_len"] == {"dist": "lognormal", "median": 16384, "sigma": 1.3,
                            "min": 32, "max": 32768}


def test_the_configuration_keeps_every_number_of_the_catalog_row():
    """Every key of the driver's catalog row under its own name, the depth
    alone changed (the row is copied here: the guide is not in the checkout)."""
    row = {"attention_bias": False, "attention_class": "eva", "chunk_size": 16,
           "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True, "hidden_act": "silu",
           "hidden_size": 4096, "init_cutoff_factor": None, "init_fn": "v2",
           "init_std": 0.01275, "intermediate_size": 11008, "lazy_init": True,
           "max_position_embeddings": 32768, "max_seq_length": 32768, "mixedp_attn": True,
           "model_type": "evabyte", "norm_add_unit_offset": True, "num_attention_heads": 32,
           "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
           "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
           "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
           "window_size": 2048}
    c = harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL).config
    assert {k for k in row if c.get(k, "missing") != row[k]} == {"num_hidden_layers"}
    assert c["reduced"] == ["num_hidden_layers"]


def test_flops_per_token_live_with_the_equations():
    """``train_mfu`` asks the cell's reference file; by hand at the cell's
    size: a layer's matmuls are 4 x 4096^2 + 3 x 4096 x 11008 parameters, the
    head 4096 x 2560; a row of 32,768 has 16 x 2048 x 2049 / 2 exact pairs and
    2048 x 128 x 120 summary pairs a head, 12 x 128 FLOPs a pair, and the
    summaries' own products 18 x 128 a key and head."""
    cell = harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)
    ref = cell.load_module("reference", "evabyte")
    pairs = ref.eva_pairs(cell.config, 32768)
    assert pairs == {"exact": 33_570_816, "summary": 31_457_280}
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008
    want = (6 * (4 * layer + 4096 * 2560)
            + 4 * 32 * 128 * (12 * (33_570_816 + 31_457_280) / 32768 + 18))
    assert ref.train_flops_per_token(cell.config, 32768) == pytest.approx(want, rel=1e-12)
    assert ref.attention_pair_flops(cell.config) == {"forward": 512.0, "backward": 1280.0,
                                                     "heads": 32}
    # a row that ends inside a window: the last, partial window's queries too
    assert ref.eva_pairs(cell.config, 2048 + 5) == {
        "exact": 2048 * 2049 // 2 + 15, "summary": 5 * 128}
    assert ref.eva_pairs({**cell.config, "window_size": 32, "chunk_size": 4}, 101) == dict(
        zip(("exact", "summary"), pairs_by_hand(101, 32, 4)))


def test_the_tiny_preset_is_held_to_its_limits_and_the_control_is_not():
    """``benchmark/limits.py`` on the CPU preset: the bf16 engine's first step
    through ``initialize`` stays under every limit of the preset's file on two
    seeds, and the fp8 reference in the program's place breaks the uphill
    share's."""
    with open(os.path.join(DATA, "benchmark/configs/evabyte-tiny.json")) as f:
        limits = {k: v for k, v in json.load(f)["limits"]["train"].items() if k != "why"}
    proc = run_cli("limits.py", "--manifest", TINY, "--workload", "evabyte-tiny.train",
                   "--seeds", "11,3000000013", "--control-seeds", "12", "--control", "fp8")
    assert proc.returncode == 0, proc.stderr[-2000:]
    readings = [l for l in json_lines(proc) if "seed" in l]
    sound = [r for r in readings if r["control"] is None]
    control = [r for r in readings if r["control"] == "fp8"]
    assert len(sound) == 2 and len(control) == 1
    assert all(r[k] <= limits[k] for r in sound for k in limits), sound
    key = "first_step_uphill_share"
    assert control[0][key] > limits[key] and control[0][key] >= 3 * max(r[key] for r in sound)


def test_the_tiny_cell_runs_end_to_end():
    """The command itself on the preset: correct, nothing failed, nothing
    compiled in the window, and the loss lower at the window's end."""
    proc = run_cli("run.py", "--manifest", TINY, "--workload", "evabyte-tiny.train",
                   "--seed", 3000000013, "--seconds", 1, "--trace", 0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and line["off_chip"]["window_compiles"] == 0
