"""ZeRO-Offload / Infinity: parameters on the host, a leaf at a time, and the
chunks' re-cut (split from test_offload.py by class at PR 59, every case kept: no
file ends the tier-1 run alone)."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import gpt2_model
from tests.unit.runtime.offload_cases import make_engine as _make_engine


class TestParamOffload:
    """ZeRO-Infinity offload_param wiring (reference
    partitioned_param_swapper.py:36): phase-boundary paging of bf16 param
    shards, freeing HBM between train/generate flips."""

    def _engine(self, tmp_path=None, device="nvme", offload_opt=True, seed=7):
        zero = {"stage": 3,
                "offload_param": {"device": device,
                                  **({"nvme_path": str(tmp_path)}
                                     if tmp_path else {})}}
        if offload_opt:
            zero["offload_optimizer"] = {"device": "cpu"}
        m = gpt2_model("gpt2-tiny", max_seq_len=16, vocab_size=128, remat=False)
        eng, _, _, _ = deepspeed_tpu.initialize(model=m, config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": zero,
        }, seed=seed)
        return eng

    def test_requires_stage3(self):
        m = gpt2_model("gpt2-tiny", max_seq_len=16, vocab_size=128, remat=False)
        with pytest.raises(ValueError, match="offload_param requires ZeRO stage 3"):
            deepspeed_tpu.initialize(model=m, config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2,
                                      "offload_param": {"device": "cpu"}}})

    @pytest.mark.parametrize("device", ["cpu", "nvme"])
    def test_page_out_frees_hbm_and_roundtrips(self, tmp_path, device):
        b = {"input_ids": np.random.default_rng(0).integers(0, 128, size=(8, 8))}
        eng = self._engine(tmp_path if device == "nvme" else None, device=device)
        ctl = self._engine(tmp_path / "ctl" if device == "nvme" else None,
                           device=device)
        float(eng.train_batch(b)); float(ctl.train_batch(b))
        bytes_resident = eng.device_state_bytes()
        import jax
        param_bytes = sum(
            sum(s.data.nbytes for s in l.addressable_shards)
            for l in jax.tree.leaves(eng.state["params"]))
        eng.offload_param_cache()
        assert eng.device_state_bytes() <= bytes_resident - param_bytes
        with pytest.raises(RuntimeError, match="paged out"):
            eng.train_batch(b)
        eng.reload_param_cache()
        # the flip is lossless: both engines continue identically
        l1, l2 = float(eng.train_batch(b)), float(ctl.train_batch(b))
        assert abs(l1 - l2) < 1e-5, (l1, l2)

    def test_reload_pools_swap_buffers_after_fence(self, tmp_path):
        """reload_param_cache donates the swap-in buffers back to the pool
        ONLY after fencing the device transfers (ADVICE r4 use-after-
        release): a second page-out/page-in cycle must reuse the pooled
        host memory (no fresh allocation) without corrupting the uploaded
        params."""
        b = {"input_ids": np.random.default_rng(0).integers(0, 128, size=(8, 8))}
        eng = self._engine(tmp_path, device="nvme")
        l0 = float(eng.train_batch(b))
        eng.offload_param_cache()
        eng.reload_param_cache()
        sw = eng._param_swapper
        pooled = sw.available_swap_in_buffers()
        assert pooled > 0  # fenced buffers re-entered the free list
        eng.offload_param_cache()
        eng.reload_param_cache()  # second cycle reuses the pooled buffers
        assert sw.available_swap_in_buffers() == pooled
        # the flip stayed lossless through buffer reuse
        l1 = float(eng.train_batch(b))
        assert np.isfinite(l1) and l1 < l0 + 1.0, (l0, l1)

    def test_overflow_gnorm_is_zero_not_nan(self):
        """fp16 overflow in the host offload step: sq-norm is inf, and
        (inf ** 0.5) * 0.0 is NaN in Python floats — the reported grad
        norm must be 0.0 like the device path (ADVICE r4)."""
        m = gpt2_model("gpt2-tiny", max_seq_len=16, vocab_size=128,
                       remat=False)
        eng, _, _, _ = deepspeed_tpu.initialize(model=m, config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1,
                                  "offload_optimizer": {"device": "cpu"}},
            # scale 2^40 overflows fp16 grads on the first step
            "fp16": {"enabled": True, "initial_scale_power": 40},
        }, seed=7)
        b = {"input_ids": np.random.default_rng(0).integers(0, 128, size=(8, 8))}
        eng.train_batch(b)
        assert eng.skipped_steps >= 1  # the step did overflow
        gnorm = eng._last_grad_norm
        assert gnorm == 0.0 and not np.isnan(gnorm), gnorm

    def test_footprint_fits_synthetic_device_cap(self):
        """ZeRO-Infinity's memory claim: with optimizer on host and params
        pageable, device bytes fit a cap the non-offload config exceeds."""
        eng = self._engine(None, device="cpu")
        m = gpt2_model("gpt2-tiny", max_seq_len=16, vocab_size=128, remat=False)
        dense, _, _, _ = deepspeed_tpu.initialize(model=m, config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}})
        # synthetic device cap: a quarter of what the replicated fp32
        # master+m+v configuration needs — the offload engine fits, the
        # dense one cannot
        cap = dense.device_state_bytes() // 4
        resident = eng.device_state_bytes()
        assert resident < cap < dense.device_state_bytes(), (
            resident, cap, dense.device_state_bytes())
        eng.offload_param_cache()
        assert eng.device_state_bytes() < resident  # params' HBM released


class TestDirectLeafOffload:
    def test_single_device_direct_path_matches_device_adam(self):
        """On a 1-device mesh the offload fetch/push moves RAW leaves
        (C-order, no flat transpose programs) — the path that lets 3B+
        full-depth models train on one chip. Trajectory must still match
        the on-device optimizer exactly."""
        from deepspeed_tpu.runtime import topology as topo_mod
        from deepspeed_tpu.runtime.topology import MeshTopology, TopologyConfig

        def make(offload):
            topo_mod.reset()
            import jax
            topo = MeshTopology(TopologyConfig(data=1),
                                devices=jax.devices()[:1])
            zero = {"stage": 3 if offload else 1}
            if offload:
                zero["offload_optimizer"] = {"device": "cpu"}
            m = gpt2_model("gpt2-tiny", max_seq_len=16, vocab_size=128,
                           remat=False)
            eng, _, _, _ = deepspeed_tpu.initialize(model=m, config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw",
                              "params": {"lr": 1e-3, "weight_decay": 0.01}},
                "gradient_clipping": 1.0,
                "zero_optimization": zero,
            }, topology=topo, seed=7)
            assert eng.mesh.size == 1
            return eng

        batch = {"input_ids":
                 np.random.default_rng(0).integers(0, 128, size=(4, 8))}
        off = make(offload=True)
        assert all(off._offload_direct), off._offload_direct
        ref = make(offload=False)
        for _ in range(3):
            l_off = float(off.train_batch(batch))
            l_ref = float(ref.train_batch(batch))
        np.testing.assert_allclose(l_off, l_ref, rtol=2e-5)


class TestOffloadChunkRechunk:
    def test_checkpoint_loads_across_chunk_size_change(self, monkeypatch,
                                                       tmp_path):
        """A tag written at one chunk size loads at another (the
        reduce_bucket_size binding must not strand pre-existing offload
        checkpoints): the loader re-chunks the flat m/v state, and the
        resumed trajectory matches."""
        from deepspeed_tpu.runtime.engine import DeepSpeedEngine

        def full_state(runner):
            n = sum(m.size for m in runner.master)
            slots = runner._slots
            full = [np.empty(n, np.float32) for _ in range(slots)]
            a = 0
            for m, st in zip(runner.master, runner._state):
                for s in range(slots):
                    full[s][a:a + m.size] = st[s * m.size:(s + 1) * m.size]
                a += m.size
            return np.concatenate([m.reshape(-1) for m in runner.master]), \
                full

        b = {"input_ids":
             np.random.default_rng(0).integers(0, 128, size=(8, 8))}
        monkeypatch.setattr(DeepSpeedEngine, "_OFFLOAD_CHUNK_ELEMS", 8192)
        eng = _make_engine("cpu")
        eng.train_batch(b)
        eng.save_checkpoint(str(tmp_path / "ck"))
        m_ref, s_ref = full_state(eng._offload)

        monkeypatch.setattr(DeepSpeedEngine, "_OFFLOAD_CHUNK_ELEMS", 2048)
        eng2 = _make_engine("cpu", seed=99)
        eng2.load_checkpoint(str(tmp_path / "ck"))
        assert len(eng2._offload.master) > len(eng._offload.master)
        m2, s2 = full_state(eng2._offload)
        np.testing.assert_array_equal(m_ref, m2)
        for a, c in zip(s_ref, s2):
            np.testing.assert_array_equal(a, c)
        l1 = float(eng.train_batch(b))
        l2 = float(eng2.train_batch(b))
        assert abs(l1 - l2) < 1e-5, (l1, l2)
