"""What a traced step writes into ``attn_totals``, ``moe_totals`` and
``diffusion_totals`` for the six benchmark architectures' tiny presets, under
``DSTPU_ATTN=''`` and ``'pallas'``, as ``setup_spans.flat_totals`` carries it
into the ``engine_totals`` annotation: equal to ``launch_records.json``, which
was taken from the parent of PR 45 (the engine then reckoned these itself)
before the model and the launches' own plans took the reckoning over (the
``layout`` keys of PR 51 aside, which that parent did not have).
"""

import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import models
from deepspeed_tpu.telemetry import setup_spans

FIXTURE = pathlib.Path(__file__).with_name("launch_records.json")
# preset -> (its factory, the cell's own keys beside the preset's, a row's tokens)
PRESETS = {
    "gpt2-tiny": (models.gpt2_model, {}, 128),
    "olmoe-tiny": (models.olmoe_model, {}, 128),
    "instella-tiny": (models.instella_moe_model, {}, 128),
    "afmoe-tiny": (models.afmoe_model, dict(document_separator=1), 256),
    "sdar-tiny": (models.sdar_moe_model, dict(document_separator=1, experts_held=(0, 8)), 128),
    "evabyte-tiny": (models.evabyte_model, {}, 128),
}
MODES = {"default": "", "pallas": "pallas"}


def trace_step(engine, batch):
    """Trace (lower, never compile or run) the fused train step for
    ``batch``: what fills the counters. -> the lowered step."""
    batch = engine._prepare_batch(batch)
    engine._build_fused_jit()
    with engine.mesh:
        return engine._jit_train_step.lower(
            engine.state, batch, jnp.asarray(1e-3, jnp.float32))


def records_of(engine):
    return setup_spans.flat_totals(
        attn=engine.attn_totals, moe=engine.moe_totals,
        diffusion=engine.diffusion_totals or {})


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_a_traced_step_writes_the_parents_records(eight_devices, monkeypatch, preset, mode):
    import deepspeed_tpu
    factory, keys, row = PRESETS[preset]
    monkeypatch.setenv("DSTPU_ATTN", MODES[mode])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=factory(preset, dtype=jnp.float32, remat=True, **keys), config={
            "train_micro_batch_size_per_gpu": 1,
            "zero_optimization": {"stage": 1},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    before = records_of(engine)
    trace_step(engine, {"input_ids": np.ones((8, row), np.int32)})
    traced = records_of(engine)
    # since PR 51 a launch's plan also says where its query side's heads lie;
    # the parent of PR 45 had no such key: the tiny presets' narrow heads are
    # transposed to lead, and off the kernel route nothing is said
    layouts = {k: traced.pop(k) for k in sorted(traced) if k.endswith("layout")
               or ".layout." in k}
    assert set(layouts.values()) <= {"heads"} and bool(layouts) == (mode == "pallas")
    # since PR 62 the records also say the query heads to a key head, what the
    # router reads and the experts' activation, from construction on: that
    # parent had no such keys either, and a traced step does not move them
    said = {k: (before.pop(k), traced.pop(k))
            for k in ("attn.group", "moe.router_input", "moe.activation") if k in before}
    assert all(was == now for was, now in said.values())
    assert "attn.group" in said and ("moe.activation" in said) == ("moe.path" in before)
    assert said.get("moe.router_input", ("ffn_input",))[0] == "ffn_input"
    # since PR 64 an EVA layer says over what its four projections run (from
    # construction on) and, once traced, in how many groups its heads' cores
    # (the tiny preset's row takes them at once)
    if preset == "evabyte-tiny":
        assert before.pop("attn.eva.projected") == traced.pop("attn.eva.projected") == "whole"
        assert "attn.eva.head_groups" not in before
        assert traced.pop("attn.eva.head_groups") == 1
    got = {"before": before, "traced": traced}
    assert got == json.loads(FIXTURE.read_text())[f"{preset}/{mode}"]
