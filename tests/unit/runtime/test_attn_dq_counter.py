"""How the flash backward makes dq, as the engine's counters say it
(``attn_totals["dq"]``, ``attn_totals["eva"]["dq_local"]`` / ``["dq_far"]``,
``diffusion_totals["dq"]``): host arithmetic from static shapes
(the model's ``attention_records`` over the launches' own plans, ``attention.Plan.dq``), kept with
telemetry off, and in the ``engine_totals`` annotation's dotted keys."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import setup_spans

BASE = dict(vocab_size=64, num_layers=2, num_heads=2, hidden_size=32, position="rope",
            norm="rmsnorm", dtype=jnp.float32, remat=False)
# the kind of attention -> (the model's own keys, the row asked of it, what
# `flat_totals` then carries); the tiles are interpret mode's (the CPU's): a
# row of 1,024 is one 1024-wide k-block, a window of 256 caps the tiles at 512
# (two k-blocks a q-block: summed), a row of 8,192 is eight (added to in place)
KINDS = {
    "window_and_full": (
        dict(max_seq_len=1024, attn_windows=(256, 0)), 1024,
        {"attn.dq.window": "summed", "attn.dq.full": "one_block"}),
    "full_alone": (
        dict(max_seq_len=8192), 8192, {"attn.dq.full": "in_place"}),
    "block_diffusion": (
        dict(max_seq_len=8192, objective="block_diffusion", block_length=4, mask_token_id=63,
             tie_embeddings=False),
        8192, {"diffusion.dq": "in_place"}),
    "block_diffusion_short": (
        dict(max_seq_len=1024, objective="block_diffusion", block_length=4, mask_token_id=63,
             tie_embeddings=False),
        1024, {"diffusion.dq": "one_block"}),
    # a window's exact keys are one 32-wide block; 8,192 / 4 summaries are two
    "eva": (
        dict(max_seq_len=8192, attention="eva", eva_window=32, eva_chunk=4, attn_bias=False),
        8192, {"attn.eva.dq_local": "one_block", "attn.eva.dq_far": "summed"}),
    "eva_one_window": (
        dict(max_seq_len=32, attention="eva", eva_window=32, eva_chunk=4, attn_bias=False),
        32, {"attn.eva.dq_local": "one_block"}),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_counters_carry_the_dq_mode_of_each_kind(eight_devices, monkeypatch, kind):
    import deepspeed_tpu
    keys, row, want = KINDS[kind]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=TransformerLM(TransformerConfig(**{**BASE, **keys})), config={
            "train_micro_batch_size_per_gpu": 1,
            "zero_optimization": {"stage": 1},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    assert not engine.telemetry.enabled
    flat = lambda: {k: v for k, v in setup_spans.flat_totals(
        attn=engine.attn_totals, diffusion=engine.diffusion_totals or {}).items()
        if ".dq" in k}
    assert flat() == {}                 # before a step is traced: None, left out
    batch = {"input_ids": np.zeros((8, row), np.int32)}
    engine._count_launches(batch)      # the CPU's route is XLA's: no kernel, no mode
    assert flat() == {}
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    engine._count_launches(batch)
    assert flat() == want


@pytest.mark.parametrize("hidden,kv_heads,layout", [
    (32, 1, "heads"), (256, 2, "heads"), (256, 1, "rows")])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_records_carry_the_layout_of_each_kind(monkeypatch, kind, hidden, kv_heads, layout):
    """Where a kind's launches take the query side's heads, beside its dq mode
    and off the same plan (``attention.Plan.layout``): two heads of 16 are
    transposed to lead, and two of 128 over two key heads; two of 128 over ONE
    key head stay where the projections leave them (EVA has as many key heads
    as query heads)."""
    keys, row, want = KINDS[kind]
    if "eva" in kind:
        kv_heads, layout = 2, "heads"
    model = TransformerLM(TransformerConfig(**{
        **BASE, **keys, "hidden_size": hidden, "num_kv_heads": kv_heads}))

    def carried():
        attn, diffusion = model.attention_records(1, row)
        return {k: v for k, v in setup_spans.flat_totals(
            attn=attn, diffusion=diffusion or {}).items() if "layout" in k}
    assert carried() == {}              # the CPU's route is XLA's: no launch, no layout
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    at = lambda key: key.replace(".dq_local", ".layout").replace(".dq", ".layout")
    assert carried() == {at(key): layout for key in want if not key.endswith("dq_far")}
