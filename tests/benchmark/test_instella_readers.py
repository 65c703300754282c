"""The five readers PR 32 brings, on a hand-made fixture
(tests/benchmark/data/instella_paths_fixture.json: nine operations of one
step, 1000 ns each but two, with the ``op_name`` each would carry on the
chip): device time under ``attn/latent``, ``attn/gate``, ``moe/shared`` and
``mtp`` by ``benchmark/trace/paths.py``, the held experts' load ratio from
the rows the program counted, and what each gives where the program names
no such scope or counts no rows (the parent of PR 32, every other cell)."""

import os
import types

import pytest

from benchmark import harness
from benchmark.trace import paths, reduce, scopes
from tests.benchmark.helpers import DATA, REPO

FIXTURE = os.path.join(DATA, "instella_paths_fixture.json")
DENSE_FIXTURE = os.path.join(REPO, "benchmark", "trace", "scopes_fixture.json")
CELL = "instella-moe-16b-a3b.train.seq8k"


def ctx_of(path):
    trace = reduce.load(path)
    return {"trace": trace, "trace_out": {"trace_file": path},
            "cell": types.SimpleNamespace(traffic={"trace_steps": 1}, config={}),
            "moe_expert_rows": trace.get("moe_expert_rows")}


def reader(name):
    return harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL).load_module(
        "layer_metrics", name).read


@pytest.mark.parametrize("parts,path,want", [
    (["block", "attn", "latent", "dot_general"], ("attn", "latent"), True),
    (["mtp", "block", "attn", "latent", "mul"], ("attn", "latent"), True),
    (["mtp", "block", "attn", "latent", "mul"], ("mtp",), True),
    (["block", "attn", "qkv", "latent"], ("attn", "latent"), False),
    (["block", "mlp", "moe", "shared", "dot_general"], ("moe", "shared"), True),
    (["block", "mlp", "moe", "experts", "shared"], ("moe", "shared"), False),
    ([], ("mtp",), False), (["mtp"], ("mtp", "merge"), False),
])
def test_under(parts, path, want):
    assert paths.under(parts, path) is want


@pytest.mark.parametrize("name,want_ms", [
    ("train_attn_latent_ms", 0.003),      # forward, recomputed and the module's
    ("train_attn_gate_ms", 0.001),
    ("train_moe_shared_ms", 0.0025),      # 1000 + 1500 ns
    ("train_mtp_ms", 0.0045),             # merge 500, latent 1000, head 1000, loss 2000
])
def test_the_trace_readers_on_the_fixture(name, want_ms):
    assert reader(name)(ctx_of(FIXTURE)) == pytest.approx(want_ms)


def test_the_module_cuts_across_the_classes_and_they_still_add_up():
    """``scopes.classify`` files the module's time under the five classes
    (its merge under unscoped), so the classes' sum is every leaf's time."""
    ctx = ctx_of(FIXTURE)
    sums = scopes.of_run(ctx)
    assert {k: round(sums[k] * 1e9) for k in scopes.CLASSES} == {
        "attn": 4000, "mlp": 3500, "head": 3000, "optimizer": 0, "unscoped": 500}
    assert round(sums["total"] * 1e9) == 11000 and sums["steps"] == 1


def test_load_ratio_from_the_rows_the_program_counted():
    read = reader("moe_held_load_ratio")
    # layer 0: hottest 30 over a mean of 15 = 2; layer 1 even = 1
    assert read({"moe_expert_rows": [[30, 10, 10, 10], [5, 5, 5, 5]]}) == pytest.approx(1.5)
    assert read(ctx_of(FIXTURE)) == pytest.approx((4 * 4 / 10 + 1) / 2)
    assert read({"moe_expert_rows": None}) is None and read({}) is None
    assert read({"moe_expert_rows": [[0, 0], [1, 3]]}) is None       # a layer drew no row


@pytest.mark.parametrize("name", ["train_attn_latent_ms", "train_attn_gate_ms",
                                  "train_moe_shared_ms", "train_mtp_ms"])
def test_a_program_without_the_scopes_reads_nothing(name):
    """The dense fixture (GPT-2's recorded step) names none of the four
    paths, and a run without a trace has nothing to read: None, no raise."""
    assert reader(name)(ctx_of(DENSE_FIXTURE)) is None
    assert reader(name)({"cell": None}) is None


def test_the_manifest_lists_the_five_for_the_new_cell_alone():
    cell = harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)
    mine = {m["name"] for m in cell.per_layer}
    five = {"train_attn_latent_ms", "train_attn_gate_ms", "train_moe_shared_ms",
            "train_mtp_ms", "moe_held_load_ratio"}
    assert five <= mine and {"moe_experts_roofline", "train_moe_route_ms"} <= mine
    other = harness.Cell(os.path.join(REPO, "BENCHMARK.json"), "olmoe-1b-7b.train.seq4k")
    assert not five & {m["name"] for m in other.per_layer}


def test_the_tiny_preset_is_held_to_its_limits_and_the_control_is_not():
    """``benchmark/limits.py`` on the CPU preset, as for the GPT-2 preset in
    test_reference.py: the bf16 engine's first step through ``initialize``
    stays under every limit of the preset's file on two seeds, and the fp8
    reference in the program's place breaks the uphill share's."""
    import json
    from tests.benchmark.helpers import json_lines, run_cli
    manifest = os.path.join(DATA, "BENCHMARK.instella-tiny.json")
    with open(os.path.join(DATA, "benchmark/configs/instella-tiny.json")) as f:
        limits = {k: v for k, v in json.load(f)["limits"]["train"].items() if k != "why"}
    proc = run_cli("limits.py", "--manifest", manifest, "--workload", "instella-tiny.train",
                   "--seeds", "11,3000000013", "--control-seeds", "12", "--control", "fp8")
    assert proc.returncode == 0, proc.stderr[-2000:]
    readings = [l for l in json_lines(proc) if "seed" in l]
    sound = [r for r in readings if r["control"] is None]
    control = [r for r in readings if r["control"] == "fp8"]
    assert len(sound) == 2 and len(control) == 1
    assert all(r[k] <= limits[k] for r in sound for k in limits), sound
    key = "first_step_uphill_share"
    assert control[0][key] > limits[key] and control[0][key] >= 3 * max(r[key] for r in sound)
