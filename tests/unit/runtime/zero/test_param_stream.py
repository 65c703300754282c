"""ZeRO-Infinity in-training parameter streaming (zero/param_stream.py).

The reference's flagship scale claim — training models whose parameters
exceed device memory (40B on one V100-32GB,
reference docs/_posts/2021-03-08-zero3-offload.md:9) — rides on
``AsyncPartitionedParameterSwapper`` (partitioned_param_swapper.py:36) and
the coordinator's NVMe prefetch (partitioned_param_coordinator.py:503).
These tests hold the TPU-native per-layer streaming runner to the same
bar: device param residency provably below total param bytes, loss parity
with the resident-param engine, clipping, checkpoint/resume, and sharded
meshes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt2_model, llama_model


def _model(layers=4, fp32=True, **over):
    return gpt2_model("gpt2-tiny", max_seq_len=32, vocab_size=128,
                      num_layers=layers, remat=False,
                      **({"dtype": jnp.float32} if fp32 else {}), **over)


def _batch(seed=0, batch=8, seq=16, vocab=128):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, size=(batch, seq))}


def _cfg(paged, gas=1, clip=0.0, extra_zero=None, topology=None):
    zero = {"stage": 3,
            "offload_param": {"device": "cpu", "paged_training": True}} \
        if paged else {"stage": 0}
    if extra_zero:
        zero.update(extra_zero)
    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-3, "weight_decay": 0.01}},
        "zero_optimization": zero,
    }
    if clip:
        cfg["gradient_clipping"] = clip
    if topology:
        cfg["topology"] = topology
    return cfg


def _shared_init(model, seed=11):
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        return jax.tree.map(np.asarray,
                            model.init(jax.random.PRNGKey(seed), jnp.float32))


class TestParity:

    def test_losses_match_resident_engine(self, eight_devices):
        """Same init, same data: the paged step must trace the resident
        engine's loss trajectory (same AdamW math, fp32)."""
        m = _model()
        init = _shared_init(m)
        paged, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=_cfg(True), model_parameters=init)
        dense, _, _, _ = deepspeed_tpu.initialize(
            model=_model(), config=_cfg(False), model_parameters=init)
        pl, dl = [], []
        for i in range(6):
            b = _batch(seed=i)
            pl.append(float(paged.train_batch(b)))
            dl.append(float(dense.train_batch(b)))
        np.testing.assert_allclose(pl, dl, rtol=2e-3, atol=2e-4)

    def test_losses_match_resident_engine_with_a_head_in_slices(self, eight_devices):
        """A device with less free than the head's float32 logits take:
        the trainer's ``vjp`` of `head_loss` runs through the head that
        takes its gradient in the forward (4 slices of the 128 rows) and
        the trajectory is still the resident engine's."""
        m = _model()
        init = _shared_init(m)
        paged, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=_cfg(True), model_parameters=init)
        paged._param_stream.__dict__["_head_room_bytes"] = 2 * (2 * 4 * 128 * 128) - 1
        seen, head_loss = [], m.head_loss
        m.head_loss = lambda *a, **kw: (seen.append(kw["slices"]), head_loss(*a, **kw))[1]
        dense, _, _, _ = deepspeed_tpu.initialize(
            model=_model(), config=_cfg(False), model_parameters=init)
        pl, dl = [], []
        for i in range(4):
            b = _batch(seed=i)
            pl.append(float(paged.train_batch(b)))
            dl.append(float(dense.train_batch(b)))
        assert seen and set(seen) == {4}
        np.testing.assert_allclose(pl, dl, rtol=2e-3, atol=2e-4)

    def test_gradient_accumulation_parity(self, eight_devices):
        m = _model()
        init = _shared_init(m)
        paged, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=_cfg(True, gas=2), model_parameters=init)
        dense, _, _, _ = deepspeed_tpu.initialize(
            model=_model(), config=_cfg(False, gas=2), model_parameters=init)
        it1 = iter([_batch(seed=i) for i in range(4)])
        it2 = iter([_batch(seed=i) for i in range(4)])
        l1 = [float(paged.train_batch(it1)) for _ in range(2)]
        l2 = [float(dense.train_batch(it2)) for _ in range(2)]
        np.testing.assert_allclose(l1, l2, rtol=2e-3, atol=2e-4)

    def test_eval_batch(self, eight_devices):
        m = _model()
        init = _shared_init(m)
        paged, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=_cfg(True), model_parameters=init)
        dense, _, _, _ = deepspeed_tpu.initialize(
            model=_model(), config=_cfg(False), model_parameters=init)
        b = _batch(seed=3)
        np.testing.assert_allclose(float(paged.eval_batch(b)),
                                   float(dense.eval_batch(b)),
                                   rtol=1e-4)


class TestOutOfCore:

    def test_device_residency_below_param_bytes(self, eight_devices):
        """THE ZeRO-Infinity claim: train with device param residency a
        fraction of total param bytes. 8 layers deep, peak residency must
        stay under half the param bytes (globals + a few block buffers)."""
        m = _model(layers=8)
        eng, _, _, _ = deepspeed_tpu.initialize(model=m, config=_cfg(True))
        for i in range(2):
            eng.train_batch(_batch(seed=i))
        rs = eng._param_stream
        budget = rs.total_param_bytes // 2  # simulated small-HBM cap
        assert 0 < rs.peak_param_bytes < budget < rs.total_param_bytes, (
            rs.peak_param_bytes, budget, rs.total_param_bytes)

    def test_loss_descends_under_budget(self, eight_devices):
        m = _model(layers=8)
        eng, _, _, _ = deepspeed_tpu.initialize(model=m, config=_cfg(True))
        b = _batch(seed=0)  # fixed batch: descent must be monotone-ish
        losses = [float(eng.train_batch(b)) for _ in range(5)]
        assert losses[-1] < losses[0], losses


class TestMechanics:

    def test_grad_clipping_and_norm(self, eight_devices):
        m = _model()
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=_cfg(True, clip=1e-4))
        eng.train_batch(_batch())
        assert eng.get_global_grad_norm() > 0
        # a second engine without clip must take a LARGER step
        m2 = _model()
        init = _shared_init(m2)
        e1, _, _, _ = deepspeed_tpu.initialize(
            model=m2, config=_cfg(True, clip=1e-4), model_parameters=init)
        e2, _, _, _ = deepspeed_tpu.initialize(
            model=_model(), config=_cfg(True), model_parameters=init)
        b = _batch(seed=5)
        e1.train_batch(b); e2.train_batch(b)
        p1 = e1.module_state_dict()["blocks"]["fc_in"]["kernel"]
        p2 = e2.module_state_dict()["blocks"]["fc_in"]["kernel"]
        assert not np.allclose(np.asarray(p1), np.asarray(p2))

    def test_checkpoint_resume(self, eight_devices, tmp_path):
        m = _model()
        init = _shared_init(m)
        e1, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=_cfg(True), model_parameters=init)
        for i in range(3):
            e1.train_batch(_batch(seed=i))
        e1.save_checkpoint(str(tmp_path))
        cont = [float(e1.train_batch(_batch(seed=i))) for i in range(3, 6)]

        e2, _, _, _ = deepspeed_tpu.initialize(
            model=_model(), config=_cfg(True))
        tag, client = e2.load_checkpoint(str(tmp_path))
        assert tag is not None and e2.global_steps == 3
        resumed = [float(e2.train_batch(_batch(seed=i))) for i in range(3, 6)]
        np.testing.assert_allclose(resumed, cont, rtol=1e-4, atol=1e-5)

    def test_module_state_dict_matches_master(self, eight_devices):
        m = _model()
        eng, _, _, _ = deepspeed_tpu.initialize(model=m, config=_cfg(True))
        eng.train_batch(_batch())
        sd = eng.module_state_dict()
        leaves = jax.tree.leaves(sd)
        assert all(np.all(np.isfinite(np.asarray(l, np.float32)))
                   for l in leaves)

    def test_sharded_mesh_dp_sp(self, eight_devices):
        """Paged streaming with Ulysses sequence parallelism: the block
        programs run ulysses_attention's all-to-alls inside the per-layer
        jits over a dp=2 x sp=4 mesh."""
        m = llama_model("llama2-tiny", max_seq_len=32, vocab_size=128,
                        remat=False, dtype=jnp.float32, num_heads=4,
                        num_kv_heads=4)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=_cfg(True, topology={"data": 2, "seq": 4}))
        b = _batch(seed=0, batch=2, seq=32)
        losses = [float(eng.train_batch(b)) for _ in range(3)]
        assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses

    def test_sharded_mesh_dp_tp(self, eight_devices):
        """Paged streaming over a dp=2 x tp=2 mesh: per-layer device_put
        scatters into the NamedShardings; grads come back reduced."""
        m = llama_model("llama2-tiny", max_seq_len=32, vocab_size=128,
                        remat=False, dtype=jnp.float32)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=_cfg(True, topology={"data": 4, "model": 2}))
        b = _batch(seed=0, batch=4)
        losses = [float(eng.train_batch(b)) for _ in range(3)]
        assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses


class TestNVMeParamStore:
    """device=nvme: block params live on DISK as per-layer bf16 blobs read
    ahead through the C++ AIO engine (reference
    partitioned_param_swapper.py:36) — the full ZeRO-Infinity NVMe story,
    not just host RAM."""

    def _nvme_cfg(self, tmp_path):
        cfg = _cfg(True)
        cfg["zero_optimization"]["offload_param"] = {
            "device": "nvme", "nvme_path": str(tmp_path),
            "paged_training": True}
        return cfg

    def test_losses_match_ram_paged_engine(self, eight_devices, tmp_path):
        m = _model()
        init = _shared_init(m)
        nv, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=self._nvme_cfg(tmp_path), model_parameters=init)
        ram, _, _, _ = deepspeed_tpu.initialize(
            model=_model(), config=_cfg(True), model_parameters=init)
        rs = nv._param_stream
        assert rs._bstore is None  # disk is canonical
        import os as _os
        assert _os.path.exists(rs._unit_path(0))
        b = _batch(seed=0)
        l_nv = [float(nv.train_batch(b)) for _ in range(4)]
        l_ram = [float(ram.train_batch(b)) for _ in range(4)]
        np.testing.assert_allclose(l_nv, l_ram, rtol=1e-4, atol=1e-5)

    def test_checkpoint_roundtrip_nvme(self, eight_devices, tmp_path):
        m = _model()
        cfg = self._nvme_cfg(tmp_path / "swap")
        e1, _, _, _ = deepspeed_tpu.initialize(model=m, config=cfg)
        b = _batch(seed=1)
        for _ in range(2):
            e1.train_batch(b)
        e1.save_checkpoint(str(tmp_path / "ckpt"))
        cont = [float(e1.train_batch(b)) for _ in range(2)]
        cfg2 = self._nvme_cfg(tmp_path / "swap2")
        e2, _, _, _ = deepspeed_tpu.initialize(model=_model(), config=cfg2)
        e2.load_checkpoint(str(tmp_path / "ckpt"))
        resumed = [float(e2.train_batch(b)) for _ in range(2)]
        np.testing.assert_allclose(resumed, cont, rtol=1e-4, atol=1e-5)

    def test_eval_and_state_dict(self, eight_devices, tmp_path):
        m = _model()
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=self._nvme_cfg(tmp_path))
        eng.train_batch(_batch(seed=2))
        assert np.isfinite(float(eng.eval_batch(_batch(seed=3))))
        sd = eng.module_state_dict()
        assert all(np.all(np.isfinite(np.asarray(l, np.float32)))
                   for l in jax.tree.leaves(sd))


class TestNarrowHostState:

    def test_bf16_moments_and_acc_track_fp32(self, eight_devices):
        """bf16 host moments (SR store) + bf16 grad accumulators: the
        loss trajectory must track the fp32-state paged engine closely —
        this is the knob that fits a 7B-dims host state in 125 GB RAM."""
        m = _model()
        init = _shared_init(m)
        cfg16 = _cfg(True)
        cfg16["data_types"] = {"optimizer_moment_dtype": "bf16",
                               "grad_accum_dtype": "bf16"}
        e32, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=_cfg(True), model_parameters=init)
        e16, _, _, _ = deepspeed_tpu.initialize(
            model=_model(), config=cfg16, model_parameters=init)
        rs = e16._param_stream
        assert rs._mdt != np.float32 and rs._gadt != np.float32
        b = _batch(seed=2)
        l32 = [float(e32.train_batch(b)) for _ in range(6)]
        l16 = [float(e16.train_batch(b)) for _ in range(6)]
        np.testing.assert_allclose(l16, l32, rtol=3e-2)
        assert l16[-1] < l16[0]

    def test_bf16_state_checkpoint_roundtrip(self, eight_devices, tmp_path):
        m = _model()
        cfg = _cfg(True)
        cfg["data_types"] = {"optimizer_moment_dtype": "bf16"}
        e1, _, _, _ = deepspeed_tpu.initialize(model=m, config=cfg)
        b = _batch(seed=0)
        for _ in range(2):
            e1.train_batch(b)
        e1.save_checkpoint(str(tmp_path))
        cont = [float(e1.train_batch(b)) for _ in range(2)]
        e2, _, _, _ = deepspeed_tpu.initialize(model=_model(), config=cfg)
        e2.load_checkpoint(str(tmp_path))
        resumed = [float(e2.train_batch(b)) for _ in range(2)]
        np.testing.assert_allclose(resumed, cont, rtol=1e-4, atol=1e-5)


class TestRejections:

    def test_fp16_rejected(self, eight_devices):
        cfg = _cfg(True)
        cfg["fp16"] = {"enabled": True}
        with pytest.raises(ValueError, match="bf16/fp32"):
            deepspeed_tpu.initialize(model=_model(fp32=False), config=cfg)

    def test_offload_optimizer_rejected(self, eight_devices):
        cfg = _cfg(True, extra_zero={"offload_optimizer": {"device": "cpu"}})
        with pytest.raises(ValueError, match="remove offload_optimizer"):
            deepspeed_tpu.initialize(model=_model(), config=cfg)

    def test_forward_step_rejected(self, eight_devices):
        eng, _, _, _ = deepspeed_tpu.initialize(model=_model(),
                                                config=_cfg(True))
        with pytest.raises(RuntimeError, match="train_batch"):
            eng.forward(_batch())
        with pytest.raises(RuntimeError, match="train_batch"):
            eng.step()

    def test_moe_rejected(self, eight_devices):
        from deepspeed_tpu.models import mixtral_model
        m = mixtral_model("mixtral-tiny", max_seq_len=32, vocab_size=128,
                          remat=False)
        with pytest.raises(ValueError, match="MoE"):
            deepspeed_tpu.initialize(model=m, config=_cfg(True))

    def test_hybrid_engine_rejected(self, eight_devices):
        cfg = _cfg(True)
        cfg["hybrid_engine"] = {"enabled": True}
        with pytest.raises(ValueError, match="hybrid_engine"):
            deepspeed_tpu.initialize(model=_model(), config=cfg)

    def test_gnorm_matches_dense_under_gas(self, eight_devices):
        """The clip norm is of the ACCUMULATED (mean-over-micros) gradient
        — same convention as the resident engine (r5 review fix: summing
        per-micro norms differs under gas > 1)."""
        m = _model()
        init = _shared_init(m)
        paged, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=_cfg(True, gas=2, clip=1.0),
            model_parameters=init)
        dense, _, _, _ = deepspeed_tpu.initialize(
            model=_model(), config=_cfg(False, gas=2, clip=1.0),
            model_parameters=init)
        batches = [_batch(seed=i) for i in range(2)]
        paged.train_batch(iter(batches))
        dense.train_batch(iter(batches))
        np.testing.assert_allclose(paged.get_global_grad_norm(),
                                   dense.get_global_grad_norm(), rtol=1e-3)


class TestNVMeWorkerQueue:
    """ISSUE 15: the pipelined NVMe worker queue (one thread owns the
    AIO handle; `_nvme_take`/`_flush_nvme_dirty` never fence on the main
    thread) against the serial main-thread schedule
    (DSTPU_OFFLOAD_PIPELINE=0) — a schedule change only, trajectories
    identical."""

    def _nvme_cfg(self, tmp_path):
        cfg = _cfg(True)
        cfg["zero_optimization"]["offload_param"] = {
            "device": "nvme", "nvme_path": str(tmp_path),
            "paged_training": True}
        return cfg

    def _run(self, monkeypatch, tmp_path, pipelined, steps=3):
        monkeypatch.setenv("DSTPU_OFFLOAD_PIPELINE",
                           "1" if pipelined else "0")
        m = _model()
        init = _shared_init(m)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=self._nvme_cfg(tmp_path),
            model_parameters=init)
        rs = eng._param_stream
        assert (rs._nvme_exec is not None) == pipelined
        b = _batch(seed=0)
        losses = [float(eng.train_batch(b)) for _ in range(steps)]
        rs.fence()
        tree = rs.params_host_tree()
        leaves = [np.asarray(l) for l in jax.tree.leaves(tree)]
        rs.close()
        return losses, leaves

    def test_worker_queue_matches_serial(self, eight_devices, monkeypatch,
                                         tmp_path):
        l_on, p_on = self._run(monkeypatch, tmp_path / "on", True)
        l_off, p_off = self._run(monkeypatch, tmp_path / "off", False)
        np.testing.assert_allclose(l_on, l_off, rtol=0, atol=0)
        for a, b in zip(p_on, p_off):
            np.testing.assert_array_equal(a, b)

    def test_nvme_wait_accounted(self, eight_devices, monkeypatch,
                                 tmp_path):
        monkeypatch.setenv("DSTPU_OFFLOAD_PIPELINE", "1")
        m = _model()
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=self._nvme_cfg(tmp_path))
        eng.train_batch(_batch(seed=0))
        rs = eng._param_stream
        assert rs.last_nvme_wait_s >= 0.0
        rs.close()

    def test_failed_flush_surfaces_loudly(self, eight_devices, monkeypatch,
                                          tmp_path):
        """A write-back that dies on the worker queue must raise at the
        next fence/take — never train on silently-stale disk state."""
        monkeypatch.setenv("DSTPU_OFFLOAD_PIPELINE", "1")
        m = _model()
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=m, config=self._nvme_cfg(tmp_path))
        rs = eng._param_stream
        eng.train_batch(_batch(seed=0))
        def boom():
            raise OSError("injected ENOSPC")
        monkeypatch.setattr(rs, "_flush_nvme_dirty_task", boom)
        import pytest as _pytest
        with _pytest.raises(OSError, match="ENOSPC"):
            # the step submits the poisoned flush; the very next NVMe
            # take (or, at the latest, fence) surfaces it
            eng.train_batch(_batch(seed=0))
            rs.fence()
        monkeypatch.undo()
        rs.close()
