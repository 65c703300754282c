"""ZeRO-Offload / Infinity tests (reference tests/unit/runtime/zero
offload matrix + swap_tensor tests). Parameters on the host are
test_offload_param.py's, a model-parallel axis and pipeline stages
test_offload_parallel.py's (split by class at PR 59, every case kept)."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import gpt2_model
from deepspeed_tpu.runtime.swap_tensor import (AsyncPartitionedParameterSwapper,
                                               AsyncTensorSwapper,
                                               OptimizerStateSwapper,
                                               SwapBufferManager)
from tests.unit.runtime.offload_cases import make_engine as _make_engine


class TestSwapBuffers:

    def test_pool_alloc_release(self):
        pool = SwapBufferManager(num_elems=100, count=2)
        a = pool.allocate(50)
        b = pool.allocate()
        assert pool.free_count == 0
        with pytest.raises(RuntimeError):
            pool.allocate()
        pool.release(a)
        pool.release(b)
        assert pool.free_count == 2

    def test_async_swapper_staged_write(self, tmp_path):
        pool = SwapBufferManager(num_elems=1000, count=2)
        sw = AsyncTensorSwapper(buffer_manager=pool)
        t = np.arange(1000, dtype=np.float32)
        sw.swap_out(t, str(tmp_path / "a.swp"))
        t[...] = -1  # caller may clobber immediately (staged copy)
        sw.wait()
        out = np.empty(1000, np.float32)
        sw.swap_in(out, str(tmp_path / "a.swp"))
        sw.wait()
        np.testing.assert_array_equal(out, np.arange(1000, dtype=np.float32))


class TestOptimizerStateSwapper:

    @pytest.mark.parametrize("pipeline", [True, False])
    def test_swap_groups_roundtrip(self, tmp_path, pipeline):
        sw = OptimizerStateSwapper(str(tmp_path), pipeline=pipeline)
        keys = [f"k{i}" for i in range(5)]
        data = {k: np.full(64, i, np.float32) for i, k in enumerate(keys)}
        for k, v in data.items():
            sw.register(k, v)
        buffers = [np.zeros(64, np.float32) for _ in range(2)]
        # iterate twice: first pass mutates (+10), second pass checks
        for k, buf in sw.swap_groups(keys, buffers):
            np.testing.assert_array_equal(buf, data[k])
            buf += 10
        for k, buf in sw.swap_groups(keys, buffers):
            np.testing.assert_array_equal(buf, data[k] + 10)
        sw.close()


class TestParamSwapper:

    def test_roundtrip_and_prefetch(self, tmp_path):
        sw = AsyncPartitionedParameterSwapper(str(tmp_path))
        a = np.random.default_rng(0).normal(size=(32, 16)).astype(np.float32)
        b = np.random.default_rng(1).normal(size=(8,)).astype(np.float32)
        sw.swap_out("layer0", a)
        sw.swap_out("layer1", b)
        assert sw.resident_params == 0
        sw.swap_in(["layer0", "layer1"], async_op=True)
        sw.synchronize_reads()
        np.testing.assert_array_equal(sw.get("layer0"), a)
        np.testing.assert_array_equal(sw.get("layer1"), b)
        sw.release("layer0")
        assert sw.resident_params == 1
        sw.close()

    def test_buffer_pool_reuse_and_count(self, tmp_path):
        """available_swap_in_buffers counts REAL pooled buffers (reference
        SwapBufferManager, swap_tensor/utils.py:180): a released swap-in
        buffer is reused byte-for-byte by the next same-size swap_in."""
        sw = AsyncPartitionedParameterSwapper(str(tmp_path))
        a = np.arange(64, dtype=np.float32).reshape(8, 8)
        b = -np.arange(64, dtype=np.float32).reshape(8, 8)
        sw.swap_out("a", a)
        sw.swap_out("b", b)
        assert sw.available_swap_in_buffers() == 0
        sw.swap_in(["a"], async_op=False)
        first = sw.get("a")
        first_iface = first.__array_interface__["data"][0]
        sw.release("a", donate=True)
        assert sw.available_swap_in_buffers() == 1  # pooled, not dropped
        sw.swap_in(["b"], async_op=False)
        second = sw.get("b")
        # same backing memory: the pool recycled the released buffer
        assert second.__array_interface__["data"][0] == first_iface
        assert sw.available_swap_in_buffers() == 0
        np.testing.assert_array_equal(second, b)
        sw.close()

    def test_buffer_pool_bounded(self, tmp_path):
        """Retained free-list memory never exceeds pool_bytes."""
        sw = AsyncPartitionedParameterSwapper(str(tmp_path), pool_bytes=256)
        big = np.zeros(512, dtype=np.float32)  # 2 KiB > pool cap
        sw.swap_out("big", big)
        sw.swap_in(["big"], async_op=False)
        sw.release("big", donate=True)
        assert sw.available_swap_in_buffers() == 0  # over cap: not retained
        small = np.zeros(32, dtype=np.float32)  # 128 B fits
        sw.swap_out("small", small)
        sw.swap_in(["small"], async_op=False)
        sw.release("small", donate=True)
        assert sw.available_swap_in_buffers() == 1
        sw.close()

    def test_release_without_donate_never_pools(self, tmp_path):
        """Plain release() must NOT recycle the buffer: a consumer such as
        an async jax.device_put may still be reading the host memory, and a
        pooled buffer would be overwritten by the next same-size swap_in."""
        sw = AsyncPartitionedParameterSwapper(str(tmp_path))
        a = np.arange(64, dtype=np.float32)
        sw.swap_out("a", a)
        sw.swap_in(["a"], async_op=False)
        held = sw.get("a")  # simulate an outstanding consumer reference
        sw.release("a")
        assert sw.available_swap_in_buffers() == 0
        sw.swap_in(["a"], async_op=False)
        # the held view was not overwritten by the new swap_in
        np.testing.assert_array_equal(held, a)
        sw.close()

    def test_caller_arrays_never_pooled(self, tmp_path):
        """swap_out(release=False) keeps the CALLER's array resident; a
        later release must not donate caller memory to the pool."""
        sw = AsyncPartitionedParameterSwapper(str(tmp_path))
        a = np.ones(16, dtype=np.float32)
        sw.swap_out("a", a, release=False)
        sw.synchronize_writes()
        sw.release("a", donate=True)
        assert sw.available_swap_in_buffers() == 0
        sw.close()


class TestOffloadEngine:

    def _batch(self):
        return {"input_ids": np.random.default_rng(0).integers(0, 128, size=(8, 8))}

    def test_cpu_offload_matches_device_path(self):
        """Host CPU-Adam trajectory == device Adam trajectory (same math)."""
        b = self._batch()
        dev = _make_engine(None)
        off = _make_engine("cpu")
        for _ in range(3):
            l_dev = float(dev.train_batch(b))
            l_off = float(off.train_batch(b))
        assert abs(l_dev - l_off) < 5e-3, (l_dev, l_off)
        import jax
        p_dev = jax.tree.leaves(jax.device_get(dev.state["params"]))
        p_off = jax.tree.leaves(jax.device_get(off.state["params"]))
        for a, c in zip(p_dev, p_off):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(c, np.float32),
                                       rtol=2e-2, atol=2e-3)

    def test_nvme_offload_trains(self, tmp_path):
        eng = _make_engine("nvme", nvme_path=str(tmp_path))
        b = self._batch()
        losses = [float(eng.train_batch(b)) for _ in range(3)]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_offload_checkpoint_roundtrip(self, tmp_path):
        b = self._batch()
        eng = _make_engine("cpu")
        eng.train_batch(b)
        eng.save_checkpoint(str(tmp_path / "ckpt"))
        step_before = eng._offload.step_count
        eng2 = _make_engine("cpu", seed=99)  # different init
        eng2.load_checkpoint(str(tmp_path / "ckpt"))
        assert eng2._offload.step_count == step_before
        for a, c in zip(eng._offload.master, eng2._offload.master):
            np.testing.assert_array_equal(a, c)
        l1 = float(eng.train_batch(b))
        l2 = float(eng2.train_batch(b))
        assert abs(l1 - l2) < 1e-4

    def test_offload_master_partitioned_not_replicated(self):
        """The flat master is sharded over devices — each host holds its
        addressable segments exactly once (reference partitions host
        optimizer work per DP rank, stage_1_and_2.py:1771; the old design
        replicated the FULL master on every host)."""
        eng = _make_engine("cpu")
        eng.train_batch(self._batch())
        lay = eng._offload_layout
        # per leaf: local spans tile the 2-D flat exactly once (row-major)
        covered = {}
        for leaf, (row, col), pshape, _ in eng._offload_spans:
            assert col == 0 and row == covered.get(leaf, 0), \
                "spans must tile each leaf without gaps/overlap"
            covered[leaf] = row + pshape[0]
            assert pshape[1] == eng._offload_flat_shapes[leaf][1]
        assert sorted(covered.keys()) == list(range(len(lay["sizes"])))
        local = sum(m.size for m in eng._offload.master)
        # single-host: local segment == the whole flat buffer, held ONCE
        # (not n_dev copies); multi-host it would be total/n_hosts
        assert local == lay["total"]

    def test_offload_nvme_chunked_pipelined(self, tmp_path, monkeypatch):
        """NVMe optimizer state streams through fixed-size chunks so chunk
        i+1's read overlaps chunk i's CPU step (reference
        pipelined_optimizer_swapper.py:51)."""
        from deepspeed_tpu.runtime.engine import DeepSpeedEngine
        monkeypatch.setattr(DeepSpeedEngine, "_OFFLOAD_CHUNK_ELEMS", 8192)
        eng = _make_engine("nvme", nvme_path=str(tmp_path))
        assert len(eng._offload.master) > 2, "model must span several chunks"
        b = self._batch()
        losses = [float(eng.train_batch(b)) for _ in range(3)]
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]
        # parity vs the cpu (non-paged) offload trajectory
        ref = _make_engine("cpu")
        ref_losses = [float(ref.train_batch(b)) for _ in range(3)]
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-4, atol=1e-4)

    def test_zero_to_fp32_joins_by_name(self, tmp_path):
        """fp32 export slices the flat master by recorded names/offsets —
        not positional sorted-key matching."""
        from deepspeed_tpu.utils.zero_to_fp32 import (
            get_fp32_state_dict_from_zero_checkpoint)
        import jax
        eng = _make_engine("cpu")
        eng.train_batch(self._batch())
        eng.save_checkpoint(str(tmp_path / "ckpt"), tag="t")
        sd = get_fp32_state_dict_from_zero_checkpoint(str(tmp_path / "ckpt"), "t")
        # the export must equal the live params (master == params in fp32),
        # with the shard-major flat layout correctly inverted per leaf
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


class TestAsyncSwapOut:

    def test_swap_out_is_async_and_read_fenced(self, tmp_path):
        """swap_out queues without blocking; a read of the same shard fences
        the pending write first (no torn reads)."""
        sw = AsyncPartitionedParameterSwapper(str(tmp_path))
        a = np.random.default_rng(0).normal(size=(256, 64)).astype(np.float32)
        sw.swap_out("w", a)
        # immediately read back: must fence the in-flight write
        np.testing.assert_array_equal(sw.get("w"), a)
        sw.release("w")
        b = a * 2
        sw.swap_out("w", b, release=False)
        assert sw.resident_params == 1
        sw.synchronize_writes()
        np.testing.assert_array_equal(sw.get("w"), b)
        sw.close()


class TestTwinFlow:
    """OffloadPP partial offload (reference stage3.py:814, blogs/
    deepspeed-offloadpp): ratio of the master elements on host, rest
    device-stepped."""

    def _engine(self, ratio, seed=7):
        m = gpt2_model("gpt2-tiny", max_seq_len=16, vocab_size=128, remat=False)
        eng, _, _, _ = deepspeed_tpu.initialize(model=m, config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw",
                          "params": {"lr": 1e-3, "weight_decay": 0.01}},
            "zero_optimization": {"stage": 2, "offload_optimizer": {
                "device": "cpu", "ratio": ratio}},
        }, seed=seed)
        return eng

    def test_ratio_splits_elements_half_and_half(self):
        import jax
        eng = self._engine(0.5)
        assert eng._offload_host_idx and eng._offload_device_idx
        host = sum(eng._offload_layout["sizes"])
        # host gets ~ratio of the elements (leaf-granular greedy)
        frac = host / sum(int(np.prod(l.shape)) or 1
                          for l in jax.tree.leaves(eng.state["params"]))
        assert 0.3 < frac < 0.7, frac
        # the device partition carries a jitted optimizer state keyed by name
        assert set(eng.state["opt"]["master"]) == {
            eng._offload_leaf_names[i] for i in eng._offload_device_idx}

    def test_ratio_trajectory_matches_full_offload(self):
        b = {"input_ids": np.random.default_rng(0).integers(0, 128, size=(8, 8))}
        full = _make_engine("cpu")          # ratio 1.0
        twin = self._engine(0.5)
        for _ in range(3):
            l_full = float(full.train_batch(b))
            l_twin = float(twin.train_batch(b))
        assert abs(l_full - l_twin) < 5e-3, (l_full, l_twin)
        import jax
        for a, c in zip(jax.tree.leaves(jax.device_get(full.state["params"])),
                        jax.tree.leaves(jax.device_get(twin.state["params"]))):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(c, np.float32),
                                       rtol=2e-2, atol=2e-3)

    def test_ratio_zero_rejected(self):
        with pytest.raises(ValueError, match="ratio=0.0"):
            self._engine(0.0)


class TestParamSwapperWorkerQueue:
    """ISSUE 15: grouped read futures on the swapper's worker queue —
    bulk swap_in lands incrementally (get blocks per group, not on the
    whole queue) and the kill switch restores the single-queue form."""

    def _roundtrip(self, tmp_path, monkeypatch, pipelined):
        from deepspeed_tpu.runtime.swap_tensor import (
            AsyncPartitionedParameterSwapper)
        monkeypatch.setenv("DSTPU_OFFLOAD_PIPELINE",
                           "1" if pipelined else "0")
        sw = AsyncPartitionedParameterSwapper(str(tmp_path),
                                              read_group_bytes=256)
        assert (sw._exec is not None) == pipelined
        rng = np.random.default_rng(5)
        data = {f"p{i}": rng.standard_normal(64).astype(np.float32)
                for i in range(6)}
        for k, v in data.items():
            sw.swap_out(k, v)
        sw.synchronize_writes()
        sw.swap_in(list(data), async_op=True)
        if pipelined:
            # 64 fp32 = 256 B per shard -> one group per shard: a bulk
            # prefetch is SEVERAL futures, not one all-or-nothing wait
            assert len(set(sw._read_futs.values())) == len(data)
        out = {k: sw.get(k).copy() for k in data}
        for k, v in data.items():
            np.testing.assert_array_equal(out[k], v)
        # write-after-read ordering: overwrite and read back through the
        # same queue
        sw.swap_out("p0", data["p0"] + 1)
        sw.swap_in(["p0"], async_op=False)
        np.testing.assert_array_equal(sw.get("p0"), data["p0"] + 1)
        sw.close()

    def test_pipelined_grouped_futures(self, tmp_path, monkeypatch):
        self._roundtrip(tmp_path, monkeypatch, True)

    def test_kill_switch_serial(self, tmp_path, monkeypatch):
        self._roundtrip(tmp_path, monkeypatch, False)
