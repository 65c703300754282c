"""benchmark/trace/moe.py and the four MoE readers on the recorded fixture:
the sums worked out by hand in benchmark/trace/moe_fixture.md, the FLOP count
by hand, and what the readers give where the program names no MoE scope."""

import os
import types

import pytest

from benchmark import harness
from benchmark.trace import moe, reduce, scopes
from tests.benchmark.helpers import REPO

FIXTURE = os.path.join(REPO, "benchmark", "trace", "moe_fixture.json")
DENSE_FIXTURE = os.path.join(REPO, "benchmark", "trace", "scopes_fixture.json")
CELL = "olmoe-1b-7b.train.seq4k"


def ctx_of(path, cell=None):
    cell = cell or types.SimpleNamespace(traffic={"trace_steps": 1}, config={})
    return {"trace": reduce.load(path), "cell": cell, "trace_out": {"trace_file": path},
            "rows": 1, "seq": 4096, "device_kind": "TPU v5 lite"}


def reader(name):
    return harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL).load_module(
        "layer_metrics", name).read


def test_sums_by_moe_scope_are_the_hand_worked_ones():
    secs = moe.seconds_by_scope(reduce.load(FIXTURE), scopes.op_names(FIXTURE))
    assert {k: round(v * 1e9) for k, v in secs.items()} == {
        "route": 311185, "dispatch": 1327337, "experts": 4910413, "combine": 2438623}


@pytest.mark.parametrize("name,op_name,want", [
    ("fusion.1", "jit(f)/jvp()/while/body/closed_call/block/mlp/moe/route/top_k:", "route"),
    ("fusion.2", "jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/block/mlp/moe/combine/gather:", "combine"),
    ("ragged-dot-none.7", "ragged-dot-none:", "experts"),
    ("ragged-dot-none", None, "experts"),
    ("copy.370", "jit(f)/jvp()/while/body/closed_call/block/mlp/reshape:", None),
    ("fusion.3", "jit(f)/jvp()/while/body/closed_call/block/moe/other:", None),
    ("fusion.4", "jit(f)/optimizer/moe:", None), ("fusion.5", "", None), ("fusion.6", None, None),
])
def test_scope_of(name, op_name, want):
    assert moe.scope_of(name, op_name) == want


def test_the_readers_on_the_fixture():
    cell = harness.Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)
    cell.traffic = dict(cell.traffic, trace_steps=1)      # the fixture is one step
    ctx = ctx_of(FIXTURE, cell)
    assert reader("train_moe_route_ms")(ctx) == pytest.approx(0.311185)
    assert reader("train_moe_dispatch_ms")(ctx) == pytest.approx(3.76596)
    assert reader("train_moe_experts_ms")(ctx) == pytest.approx(4.910413)
    assert reader("moe_experts_roofline")(ctx) == pytest.approx(
        100 * 3_298_534_883_328 / 0.004910413 / 197e12)


def test_flops_are_counted_from_the_rows_that_exist():
    """By hand (benchmark/trace/moe_fixture.md): never a padded tile, never
    the 64 experts that exist."""
    kw = dict(tokens=4096, experts_per_token=8, hidden=2048, width=1024, layers=2)
    assert moe.expert_matmul_flops_a_step(remat=True, **kw) == 3_298_534_883_328
    assert moe.expert_matmul_flops_a_step(remat=False, **kw) == 2_473_901_162_496


@pytest.mark.parametrize("name", ["train_moe_route_ms", "train_moe_dispatch_ms",
                                  "train_moe_experts_ms", "moe_experts_roofline"])
def test_a_program_without_the_scopes_gives_nothing(name):
    """A dense model's trace (the GPT-2 fixture), a run without a trace, and
    a program without step annotations (the parent of PR 24): None, no raise."""
    read = reader(name)
    assert read(ctx_of(DENSE_FIXTURE)) is None
    assert read({"trace": None, "cell": types.SimpleNamespace(config={}, traffic={})}) is None
    bare = ctx_of(FIXTURE)
    bare["trace"] = dict(bare["trace"], host=[])
    assert read(bare) is None
