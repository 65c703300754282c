"""End-to-end engine tests on the 8-device CPU mesh.

Counterpart of the reference's engine-level tests
(tests/unit/runtime/test_ds_initialize.py + test_zero.py training loops with
SimpleModel). Uses a tiny GPT-2 so each test jit-compiles in seconds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2_model


def tiny_model(**overrides):
    return gpt2_model("gpt2-tiny", max_seq_len=32, vocab_size=256, remat=False, **overrides)


def make_batch(batch=8, seq=16, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, size=(batch, seq))}


BASE_CONFIG = {
    "train_micro_batch_size_per_gpu": 1,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
    "gradient_clipping": 1.0,
}


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_train_loss_decreases(eight_devices, stage):
    config = dict(BASE_CONFIG, zero_optimization={"stage": stage})
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=config)
    batch = make_batch()
    losses = []
    for _ in range(5):
        loss = engine.forward(batch)
        engine.backward()
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert engine.global_steps == 5


@pytest.mark.parametrize("stage", [0, 2])
def test_zero_stages_agree(eight_devices, stage):
    """All stages must compute identical updates — partitioning is a memory
    layout, not a different algorithm (reference semantics)."""
    batch = make_batch(seed=3)
    cfg0 = dict(BASE_CONFIG, zero_optimization={"stage": stage})
    cfg3 = dict(BASE_CONFIG, zero_optimization={"stage": 3})
    e_a, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=cfg0, seed=7)
    e_b, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=cfg3, seed=7)
    for e in (e_a, e_b):
        e.forward(batch)
        e.backward()
        e.step()
    la = float(e_a.forward(batch))
    lb = float(e_b.forward(batch))
    np.testing.assert_allclose(la, lb, rtol=2e-5)


def test_gradient_accumulation(eight_devices):
    config = dict(BASE_CONFIG, gradient_accumulation_steps=4,
                  zero_optimization={"stage": 1})
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=config)
    batch = make_batch()
    for i in range(4):
        engine.forward(batch)
        engine.backward()
        engine.step()  # only applies on the 4th
        expected = 1 if i == 3 else 0
        assert engine.global_steps == expected
    assert engine.is_gradient_accumulation_boundary()


def test_train_batch_api(eight_devices):
    config = dict(BASE_CONFIG, gradient_accumulation_steps=2)
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=config)
    loss = engine.train_batch(make_batch())
    assert jnp.isfinite(loss)
    assert engine.global_steps == 1


def test_fused_step_matches_split(eight_devices, monkeypatch):
    """The one-dispatch fused step (gas==1) must match the split
    forward/backward/step path, and must not engage when ineligible."""
    def run(fused, stage=1):
        monkeypatch.setenv("DSTPU_FUSED_STEP", "1" if fused else "0")
        cfg = dict(BASE_CONFIG, zero_optimization={"stage": stage})
        e, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=cfg,
                                              seed=7)
        batch = make_batch(seed=4)
        losses = [float(e.train_batch(batch)) for _ in range(3)]
        assert (e._jit_train_step is not None) == fused
        assert e.global_steps == 3 and e.micro_steps == 3
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=2e-5)
    # gas>1 must stay on the split path even when fusing is enabled
    monkeypatch.setenv("DSTPU_FUSED_STEP", "1")
    cfg = dict(BASE_CONFIG, gradient_accumulation_steps=2)
    e, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=cfg)
    e.train_batch(make_batch())
    assert e._jit_train_step is None


def test_bf16_training(eight_devices):
    config = dict(BASE_CONFIG, bf16={"enabled": True}, zero_optimization={"stage": 2})
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(dtype=jnp.bfloat16), config=config)
    batch = make_batch()
    l0 = float(engine.train_batch(batch))
    l1 = float(engine.train_batch(batch))
    assert np.isfinite(l0) and np.isfinite(l1)
    assert engine.state["params"]["wte"]["embedding"].dtype == jnp.bfloat16
    # master stays fp32
    assert engine.state["opt"]["master"]["wte"]["embedding"].dtype == jnp.float32


def test_tensor_parallel_matches_dense(eight_devices):
    batch = make_batch(seed=5)
    cfg_dp = dict(BASE_CONFIG)
    cfg_tp = dict(BASE_CONFIG, topology={"model": 2})
    e_dp, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=cfg_dp, seed=11)
    e_tp, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=cfg_tp, seed=11)
    l_dp = float(e_dp.forward(batch))
    l_tp = float(e_tp.forward(batch))
    np.testing.assert_allclose(l_dp, l_tp, rtol=2e-5)


def test_checkpoint_roundtrip(eight_devices, tmp_path):
    config = dict(BASE_CONFIG, zero_optimization={"stage": 2})
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=config)
    batch = make_batch()
    engine.train_batch(batch)
    engine.train_batch(batch)
    loss_before = float(engine.eval_batch(batch))
    engine.save_checkpoint(str(tmp_path), tag="ckpt1")

    # fresh engine under a DIFFERENT zero stage: topology-independent load
    config2 = dict(BASE_CONFIG, zero_optimization={"stage": 3})
    engine2, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=config2, seed=999)
    tag, _ = engine2.load_checkpoint(str(tmp_path))
    assert tag == "ckpt1"
    assert engine2.global_steps == 2
    loss_after = float(engine2.eval_batch(batch))
    np.testing.assert_allclose(loss_before, loss_after, rtol=2e-5)


def test_out_of_range_input_ids_rejected(eight_devices):
    """An id >= vocab_size must raise with the offending value, not poison
    training with NaN-filled embedding rows (jnp.take's OOB fill mode) —
    regression for the silent-NaN quickstart."""
    from deepspeed_tpu.models import llama_model
    m = llama_model("llama2-tiny", dtype=jnp.float32, remat=False)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=m, config={"train_micro_batch_size_per_gpu": 1,
                         "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                         "zero_optimization": {"stage": 1}})
    bad = np.full((8, 16), m.config.vocab_size + 7, np.int32)
    with pytest.raises(ValueError, match="out of range"):
        engine.train_batch({"input_ids": bad})
    with pytest.raises(ValueError, match="min id -1"):
        engine.train_batch({"input_ids": np.full((8, 16), -1, np.int32)})
    # device arrays are validated too (np.asarray pulls them back)
    with pytest.raises(ValueError, match="out of range"):
        engine.train_batch({"input_ids": jnp.asarray(bad)})
    ok = np.random.default_rng(0).integers(0, m.config.vocab_size, (8, 16))
    assert np.isfinite(float(engine.train_batch({"input_ids": ok})))


def test_overlength_learned_positions_rejected(eight_devices):
    """seq > max_seq_len on a learned-position model must raise (positions
    would silently clip to the last table row)."""
    from deepspeed_tpu.models.gpt2 import gpt2_model
    m = gpt2_model("gpt2-tiny", max_seq_len=16, vocab_size=128, remat=False,
                   dtype=jnp.float32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=m, config={"train_micro_batch_size_per_gpu": 1,
                         "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                         "zero_optimization": {"stage": 1}})
    long_ids = np.random.default_rng(1).integers(0, 128, (8, 32))
    with pytest.raises(ValueError, match="exceeds the learned"):
        engine.train_batch({"input_ids": long_ids})
