"""ZeRO-Offload / Infinity under a model-parallel axis and under pipeline stages
(split from test_offload.py by class at PR 59, every case kept)."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import gpt2_model
from tests.unit.runtime.offload_cases import make_engine as _make_engine


class TestOffloadModelParallel:
    """Offload x tensor parallel (VERDICT r2 weak #7): the host master
    partitions over dp while tp shards the device params — reference
    composes ZeRO-Offload with an mpu (stage_1_and_2.py:96)."""

    def _engine(self, tp, stage=3, offload=True, seed=7):
        m = gpt2_model("gpt2-tiny", max_seq_len=16, vocab_size=128, remat=False)
        zero = {"stage": stage}
        if offload:
            zero["offload_optimizer"] = {"device": "cpu"}
        eng, _, _, _ = deepspeed_tpu.initialize(model=m, config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw",
                          "params": {"lr": 1e-3, "weight_decay": 0.01}},
            "zero_optimization": zero,
            "topology": {"model": tp},
        }, seed=seed)
        return eng

    def test_stage3_tp2_offload_matches_non_offload(self, eight_devices):
        b = {"input_ids": np.random.default_rng(0).integers(0, 128, size=(8, 8))}
        off = self._engine(tp=2, offload=True)
        ref = self._engine(tp=2, offload=False)
        for _ in range(3):
            l_off = float(off.train_batch(b))
            l_ref = float(ref.train_batch(b))
        assert abs(l_off - l_ref) < 5e-3, (l_off, l_ref)
        import jax
        for a, c in zip(jax.tree.leaves(jax.device_get(off.state["params"])),
                        jax.tree.leaves(jax.device_get(ref.state["params"]))):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(c, np.float32),
                                       rtol=2e-2, atol=2e-3)

    def test_tp2_device_params_stay_model_sharded(self, eight_devices):
        eng = self._engine(tp=2)
        eng.train_batch(
            {"input_ids": np.random.default_rng(0).integers(0, 128, size=(8, 8))})
        specs = [l.sharding.spec for l in
                 __import__("jax").tree.leaves(eng.state["params"])]
        flat_specs = [str(s) for s in specs]
        assert any("model" in s for s in flat_specs), flat_specs

    def test_pipe_expert_still_rejected(self, eight_devices):
        from deepspeed_tpu.models import mixtral_model
        m = mixtral_model("mixtral-tiny", max_seq_len=16, vocab_size=128,
                          remat=False)
        with pytest.raises(ValueError, match="pipe/expert"):
            deepspeed_tpu.initialize(model=m, config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {
                    "stage": 2, "offload_optimizer": {"device": "cpu"}},
                "topology": {"expert": 2},
            })

    def test_zero_to_fp32_with_tp_sharded_offload(self, eight_devices, tmp_path):
        """fp32 export must reassemble column-sharded (offload x tp) span
        pieces correctly — a plain row-major reshape scrambles them."""
        from deepspeed_tpu.utils.zero_to_fp32 import (
            get_fp32_state_dict_from_zero_checkpoint)
        import jax
        eng = self._engine(tp=2)
        eng.train_batch(
            {"input_ids": np.random.default_rng(0).integers(0, 128, size=(8, 8))})
        eng.save_checkpoint(str(tmp_path / "ckpt"), tag="t")
        sd = get_fp32_state_dict_from_zero_checkpoint(str(tmp_path / "ckpt"), "t")
        flat_params = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                eng.state["params"])[0]:
            name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path)
            flat_params[name] = np.asarray(jax.device_get(leaf), np.float32)
        assert set(sd) == set(flat_params)
        for name in sd:
            np.testing.assert_allclose(sd[name], flat_params[name],
                                       rtol=1e-6, atol=1e-7, err_msg=name)


class TestOffloadPipeline:
    """ISSUE 15: the double-buffered offload pipeline (default) against
    the serial fetch→compute→writeback schedule (DSTPU_OFFLOAD_PIPELINE=0
    kill switch). The pipeline only reorders INDEPENDENT transfers — same
    chunk boundaries, same arithmetic order — so the two schedules must
    be BITWISE identical; the kill switch is a schedule A/B, never a
    numerics A/B."""

    def _run(self, monkeypatch, pipeline, device="cpu", nvme_path=None,
             steps=3, chunk_elems=None):
        import jax
        monkeypatch.setenv("DSTPU_OFFLOAD_PIPELINE",
                           "1" if pipeline else "0")
        if chunk_elems is not None:
            from deepspeed_tpu.runtime.engine import DeepSpeedEngine
            monkeypatch.setattr(DeepSpeedEngine, "_OFFLOAD_CHUNK_ELEMS",
                                chunk_elems)
        eng = _make_engine(device, nvme_path=nvme_path)
        b = {"input_ids":
             np.random.default_rng(0).integers(0, 128, size=(8, 8))}
        losses = [float(eng.train_batch(b)) for _ in range(steps)]
        params = [np.asarray(jax.device_get(l))
                  for l in jax.tree.leaves(eng.state["params"])]
        return eng, losses, params

    def test_kill_switch_bitwise_cpu(self, monkeypatch):
        _, l_on, p_on = self._run(monkeypatch, True)
        _, l_off, p_off = self._run(monkeypatch, False)
        assert l_on == l_off, (l_on, l_off)
        for a, b in zip(p_on, p_off):
            np.testing.assert_array_equal(a, b)

    def test_kill_switch_bitwise_nvme_chunked(self, monkeypatch, tmp_path):
        """Multi-chunk NVMe paging under the pipelined feed: the lazy
        chunk consumption must not change a single bit vs the serial
        eager list."""
        e_on, l_on, p_on = self._run(
            monkeypatch, True, "nvme", str(tmp_path / "a"),
            chunk_elems=8192)
        assert len(e_on._offload.master) > 2, "must span several chunks"
        assert len(e_on._offload_fetch_buckets) > 1, \
            "model must span several fetch buckets"
        _, l_off, p_off = self._run(
            monkeypatch, False, "nvme", str(tmp_path / "b"),
            chunk_elems=8192)
        assert l_on == l_off, (l_on, l_off)
        for a, b in zip(p_on, p_off):
            np.testing.assert_array_equal(a, b)

    def test_phase_split_recorded(self, monkeypatch, tmp_path):
        """The stall decomposition (docs/OBSERVABILITY.md): every offload
        step records the four pipeline phases, with real host compute."""
        eng, _, _ = self._run(monkeypatch, True, "nvme",
                              str(tmp_path / "p"))
        ph = eng.last_offload_phase_s
        assert set(ph) == {"h2d_prefetch", "bucket_compute",
                           "d2h_writeback", "nvme_io"}, ph
        assert all(v >= 0.0 for v in ph.values()), ph
        assert ph["bucket_compute"] > 0.0, ph
        # bench continuity: the legacy pair still reports
        assert eng.last_offload_compute_s == ph["bucket_compute"]
        assert eng.last_offload_stall_s == ph["nvme_io"]

    def test_fetch_buckets_tile_leaves(self, monkeypatch):
        """Bucket plan sanity: the fetch buckets are contiguous leaf runs
        tiling 0..n-1 exactly once (the prefix property the chunk feed
        relies on), and the bucket size binds through reduce_bucket_size
        (the overlap.py fused-buffer discipline)."""
        monkeypatch.setenv("DSTPU_OFFLOAD_PIPELINE", "1")
        m = gpt2_model("gpt2-tiny", max_seq_len=16, vocab_size=128,
                       remat=False)
        eng, _, _, _ = deepspeed_tpu.initialize(model=m, config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1, "reduce_bucket_size": 8192,
                                  "offload_optimizer": {"device": "cpu"}},
        }, seed=7)
        eng.train_batch({"input_ids":
                         np.random.default_rng(0).integers(
                             0, 128, size=(8, 8))})
        assert eng._offload_chunk_elems == 8192  # the knob bound
        flat = [k for run in eng._offload_fetch_buckets for k in run]
        assert flat == list(range(len(eng._offload_host_idx)))
        for run in eng._offload_fetch_buckets:
            assert run == list(range(run[0], run[-1] + 1))
        # several buckets at this cap — the pipeline has something to
        # double-buffer
        assert len(eng._offload_fetch_buckets) > 1

    def test_runner_lazy_feed_matches_list(self, tmp_path):
        """OffloadedOptimizerRunner.step_iter with a lazy generator feed
        (the engine pipeline's form) is bitwise the eager-list form, and
        fetch-wait time lands in last_fetch_s, not last_compute_s."""
        from deepspeed_tpu.runtime.zero.offload_optimizer import (
            OffloadedOptimizerRunner)
        rng = np.random.default_rng(0)
        leaves = [rng.standard_normal(257).astype(np.float32)
                  for _ in range(5)]
        grads = [rng.standard_normal(257).astype(np.float32) * 1e-2
                 for _ in range(5)]

        def make():
            return OffloadedOptimizerRunner(
                "adamw", {"lr": 1e-3, "weight_decay": 0.01},
                [l.copy() for l in leaves], device="nvme",
                nvme_path=str(tmp_path), pipeline=True)

        a, b = make(), make()
        for _ in range(2):
            for _ in a.step_iter(list(grads)):
                pass
            for _ in b.step_iter(iter(list(grads))):
                pass
        for ma, mb in zip(a.master, b.master):
            np.testing.assert_array_equal(ma, mb)
        assert b.last_fetch_s >= 0.0
        # a short feed is a hard error, not a silent partial step
        import pytest as _pytest
        with _pytest.raises(ValueError, match="exhausted"):
            for _ in a.step_iter(iter(grads[:2])):
                pass
