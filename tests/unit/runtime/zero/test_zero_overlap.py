"""Layer-granular ZeRO overlap schedule (ISSUE 3): the pipelined
gather-compute-scatter micro step must reproduce the dense micro step's
gradients — quantized off AND on, including the hpZ secondary-partition
path — while `overlap_comm: false` remains an exact escape hatch to the
whole-tree barrier schedule. Plus the bucket planner (the
reduce/allgather_bucket_size knobs finally bind) and the comms logger's
overlapped/exposed split."""

import contextlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt2_model
from deepspeed_tpu.runtime import topology as topo_mod
from deepspeed_tpu.runtime.topology import MeshTopology, TopologyConfig
from deepspeed_tpu.runtime.zero.partition import BucketEntry, plan_comm_buckets

CFG = dict(max_seq_len=32, vocab_size=256, remat=False)


@contextlib.contextmanager
def transport_off():
    """DSTPU_COMM_QUANT=0 — the transport-planner escape hatch (ISSUE 8):
    collective plans revert to full-width/flat, which is bit-for-bit the
    pre-planner program. The exact-parity tests below run under it; the
    quantized DEFAULT is covered by TestTransportDefaults. The env is read
    at trace time, so it must wrap the first forward, not just the build."""
    old = os.environ.get("DSTPU_COMM_QUANT")
    os.environ["DSTPU_COMM_QUANT"] = "0"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("DSTPU_COMM_QUANT", None)
        else:
            os.environ["DSTPU_COMM_QUANT"] = old


def make_engine(zero_extra=None, topology=None, seed=11):
    topo_mod.reset()
    model = gpt2_model("gpt2-tiny", dtype=jnp.float32, **CFG)
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": dict({"stage": 3,
                                   "stage3_param_persistence_threshold": 0},
                                  **(zero_extra or {})),
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config,
                                               topology=topology, seed=seed)
    return engine


BATCH = {"input_ids": np.random.default_rng(5).integers(0, 256, size=(8, 16))}


def micro_grads(engine):
    """One micro step's accumulated gradient shards, fetched to host."""
    engine.forward(dict(BATCH))
    engine.backward()
    return jax.tree.map(np.asarray, engine.state["grad_acc"])


def assert_grads_close(ref, got, rtol, atol_frac=1e-6):
    """Leaf-wise comparison with an absolute floor scaled to the GLOBAL
    gradient magnitude: analytically-zero leaves (k_proj/bias — softmax
    rows sum to zero, so a constant key shift has zero loss gradient) hold
    only cancellation noise, where relative error is meaningless."""
    scale = max(float(np.max(np.abs(l))) for l in jax.tree.leaves(ref))
    atol = atol_frac * scale
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ref)[0],
                            jax.tree.leaves(got)):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        np.testing.assert_allclose(np.asarray(b), a, rtol=rtol, atol=atol,
                                   err_msg=f"leaf {name}")


# module-scoped gradient references: each engine build + first forward is
# a multi-second CPU-mesh compile, and three tests compare against the
# same dense reference — compute each reference ONCE per module
@pytest.fixture(scope="module")
def dense_grads():
    assert len(jax.devices()) == 8
    return micro_grads(make_engine())


@pytest.fixture(scope="module")
def overlap_grads():
    with transport_off():
        eng = make_engine({"overlap_comm": True})
        g = micro_grads(eng)
    assert eng._stage3_overlap and eng._explicit_micro
    assert eng._overlap_active, eng._overlap_fallback
    return g


class TestOverlapNumerics:

    def test_overlap_matches_dense_micro(self, eight_devices, dense_grads,
                                         overlap_grads):
        """The pipelined stage-3 schedule under the transport escape
        hatch (full-width/flat — the pre-ISSUE-8 program) reproduces the
        dense ``_micro_step_fn`` gradients within fp32 reduction-order
        tolerance: the default-off escape is exact."""
        assert_grads_close(dense_grads, overlap_grads, rtol=2e-5)

    def test_overlap_with_a_head_in_slices_matches_dense_micro(
            self, eight_devices, dense_grads):
        """A device's room that does not hold the head's float32 logits:
        the schedule's own ``vjp`` of `head_loss` runs through the head that
        takes its gradient in the forward (`fused_head_loss`, 4 slices of a
        device's 16 rows) and still gives the dense step's gradients."""
        with transport_off():
            eng = make_engine({"overlap_comm": True})
            eng.__dict__["_remat_room_bytes"] = 2 * (2 * 4 * 16 * 256) - 1
            seen, head_loss = [], eng.model.head_loss
            eng.model.head_loss = lambda *a, **kw: (seen.append(kw["slices"]),
                                                    head_loss(*a, **kw))[1]
            try:
                got = micro_grads(eng)
            finally:
                del eng.model.head_loss
        assert eng._overlap_active, eng._overlap_fallback
        assert seen and set(seen) == {4}
        assert_grads_close(dense_grads, got, rtol=2e-5)

    def test_overlap_quantized_matches_dense_micro(self, eight_devices,
                                                   dense_grads):
        """Quantized ON: int8 collectives bound the error, but the
        schedule must still track the dense gradients within quantization
        tolerance and train."""
        ref = dense_grads
        q = make_engine({"zero_quantized_weights": True,
                         "zero_quantized_gradients": True})
        got = micro_grads(q)
        assert q._zeropp and q._overlap_active  # overlap is the DEFAULT
        # int8 blockwise quantization: coarse bound (measured worst-abs
        # ~1.2e-2 of the max gradient at these dims), but catches layer
        # routing / scatter-layout bugs outright (those are O(1) wrong)
        assert_grads_close(ref, got, rtol=0.25, atol_frac=2e-2)
        losses = [float(q.train_batch(dict(BATCH))) for _ in range(3)]
        assert losses[-1] < losses[0]

    def test_overlap_hpz_matches_dense_micro(self, eight_devices,
                                             dense_grads):
        """hpZ: forward/backward gathers read the mics-sharded SECONDARY
        partition; gradients still land on the primary shards and match
        the dense step (escape hatch: exact fp32 comparison)."""
        with transport_off():
            topo = MeshTopology(TopologyConfig(mics=2, data=-1))
            hpz = make_engine({"zero_hpz_partition_size": 2}, topology=topo)
            got = micro_grads(hpz)
        assert hpz._overlap_active, hpz._overlap_fallback
        assert_grads_close(dense_grads, got, rtol=2e-5)

    def test_chunked_buckets_match_default(self, eight_devices,
                                           overlap_grads):
        """Tiny bucket sizes force splitting (and defeat fusing); the
        gradients must be identical to the default fused plan's."""
        with transport_off():
            ch = make_engine({"overlap_comm": True,
                              "allgather_bucket_size": 2000,
                              "reduce_bucket_size": 2000})
            got = micro_grads(ch)
        assert ch._overlap_active
        assert_grads_close(overlap_grads, got, rtol=2e-5)

    def test_gas_accumulation(self, eight_devices, overlap_grads):
        """gas>1: the pipelined micro accumulates into the donated shard
        buffer exactly like the barrier schedule."""
        with transport_off():
            ov2 = make_engine({"overlap_comm": True})
            ov2.forward(dict(BATCH)); ov2.backward()
            ov2.forward(dict(BATCH)); ov2.backward()
        two = jax.tree.map(np.asarray, ov2.state["grad_acc"])
        assert_grads_close(jax.tree.map(lambda a: 2 * a, overlap_grads),
                           two, rtol=2e-5)


class TestTransportDefaults:
    """ISSUE 8: quantized + hierarchical transport is the DEFAULT for
    gradient reductions — no ZeRO++ config required."""

    def test_default_grad_transport_matches_dense(self, eight_devices,
                                                  dense_grads):
        """Plain stage-3 pipelined engine, planner defaults: grads ride
        the int8 wire and must track the dense gradients within
        quantization tolerance (global-scale atol floor — k_proj/bias
        grads are analytically zero)."""
        eng = make_engine({"overlap_comm": True})
        got = micro_grads(eng)
        assert eng._overlap_active
        assert_grads_close(dense_grads, got, rtol=0.25, atol_frac=2e-2)
        losses = [float(eng.train_batch(dict(BATCH))) for _ in range(3)]
        assert losses[-1] < losses[0]

    def test_default_grad_wire_bytes_reduced(self, eight_devices):
        """The acceptance bar made runtime-visible: tracing the pipelined
        micro under a recording ledger, the gradient-reduction wire bytes
        must be >= 40% below the logical (full-width) bytes."""
        from deepspeed_tpu import comm as dist
        eng = make_engine({"overlap_comm": True})
        eng._build_jits()
        micro = eng._build_zeropp_micro()
        args = (eng.state["grad_acc"], eng.state["loss_scale"]["cur_scale"],
                eng.state["params"], eng._prepare_batch(dict(BATCH)))
        ledger = dist.CollectiveLedger()
        with dist.record_into(ledger):
            with eng.mesh:
                jax.eval_shape(micro, *args)
        red = [r for r in ledger.records
               if r["op"] in ("all_to_all", "reduce_scatter")]
        assert red, "no gradient reductions recorded"
        logical = sum(r["bytes"] * r["count"] for r in red)
        wire = sum(r["wire_bytes"] * r["count"] for r in red)
        assert wire <= 0.6 * logical, (wire, logical)
        # and the quantized wire is declared as the qgZ-style all-to-all
        assert any(r["op"] == "all_to_all" for r in red)

    def test_default_hpz_hierarchical_matches_dense(self, eight_devices,
                                                    dense_grads):
        """mics=2 x data=4: grad buckets whose dp axes span ('data',
        'mics') take the two-tier decomposition (intra-'mics' quantized
        reduce-scatter + cross-'data' leg) and still track dense grads."""
        topo = MeshTopology(TopologyConfig(mics=2, data=-1))
        hpz = make_engine({"zero_hpz_partition_size": 2}, topology=topo)
        got = micro_grads(hpz)
        assert hpz._overlap_active, hpz._overlap_fallback
        assert_grads_close(dense_grads, got, rtol=0.25, atol_frac=2e-2)

    def test_escape_hatch_is_flat_full(self, eight_devices):
        """DSTPU_COMM_QUANT=0 resolves every plan to full/flat (the
        pre-ISSUE-8 program) regardless of kind/size/mesh."""
        from deepspeed_tpu import comm as dist
        with transport_off():
            tp = dist.resolve_transport("grad", "reduce_scatter", 1 << 20,
                                        ("data", "mics"),
                                        axis_sizes={"data": 4, "mics": 2})
        assert tp.width == "full" and tp.algo == "flat"
        # explicit qgZ width requests survive the kill switch (user
        # contract, not planner default)
        with transport_off():
            tp = dist.resolve_transport("grad", "reduce_scatter", 1 << 20,
                                        ("data",), axis_sizes={"data": 8},
                                        requested="int8")
        assert tp.width == "int8"


class TestEscapeHatchAndRouting:

    def test_overlap_comm_false_is_barrier_and_matches(self, eight_devices):
        """`overlap_comm: false` selects the whole-tree barrier schedule,
        which still trains and agrees with the pipelined schedule — same
        math (gather -> grad -> scatter-mean), different op order."""
        bar = make_engine({"zero_quantized_weights": True,
                           "overlap_comm": False})
        ref = micro_grads(bar)
        assert bar._explicit_micro and not bar._overlap_active
        bar.step()
        losses = [float(bar.train_batch(dict(BATCH))) for _ in range(2)]
        assert losses[-1] < losses[0]
        ov = make_engine({"zero_quantized_weights": True})
        got = micro_grads(ov)
        assert ov._overlap_active
        # qwZ quantizes per-leaf (barrier) vs per-fused-buffer (overlap):
        # the per-leaf group padding keeps groups from spanning leaves, so
        # only reduction order and boundary-group statistics differ
        # (measured worst-abs ~1.7e-2 of the max gradient)
        assert_grads_close(ref, got, rtol=0.25, atol_frac=2.5e-2)

    def test_plain_stage3_defaults_stay_declarative(self, eight_devices):
        """Without an EXPLICIT overlap_comm, plain stage-3 engines keep
        the declarative path (overlap_comm's stage-3 default true applies
        to the ZeRO++ shard_map micro only)."""
        eng = make_engine()
        assert eng.config.zero_config.overlap_comm  # stage-3 default
        assert not eng._stage3_overlap and not eng._explicit_micro

    def test_env_kill_switch(self, eight_devices, monkeypatch):
        monkeypatch.setenv("DSTPU_ZERO_OVERLAP", "0")
        eng = make_engine({"zero_quantized_weights": True})
        eng._build_jits()
        assert not eng._overlap_active
        assert "DSTPU_ZERO_OVERLAP" in eng._overlap_fallback


class TestBucketPlanner:

    def test_small_leaves_fuse(self):
        entries, oversize = plan_comm_buckets(
            sizes=[100, 200, 300, 5000], keys=["a", "a", "a", "a"],
            extents=[10, 10, 10, 100], bucket_elems=1000)
        assert not oversize
        assert BucketEntry(leaves=(0, 1, 2)) in entries
        assert any(e.leaves == (3,) and e.chunks == 5 for e in entries)

    def test_incompatible_keys_do_not_fuse(self):
        entries, _ = plan_comm_buckets(
            sizes=[100, 100], keys=["a", "b"], extents=[10, 10],
            bucket_elems=1000)
        assert len(entries) == 2

    def test_replicated_leaves_stand_alone(self):
        entries, oversize = plan_comm_buckets(
            sizes=[100, 100], keys=["a", "a"], extents=[None, None],
            bucket_elems=1000)
        assert entries == [BucketEntry(leaves=(0,)), BucketEntry(leaves=(1,))]
        assert not oversize

    def test_oversize_unsplittable_leaf_reported(self):
        # extent 7 (prime, > max_chunks would not help): 7 chunks of
        # 10000/7 still exceed bucket 1000 -> reported, not silently kept
        entries, oversize = plan_comm_buckets(
            sizes=[10000], keys=["a"], extents=[7], bucket_elems=1000)
        assert oversize == [0]
        assert entries[0].chunks == 7

    def test_fuse_respects_bucket_boundary(self):
        entries, _ = plan_comm_buckets(
            sizes=[400, 400, 400], keys=["a", "a", "a"],
            extents=[10, 10, 10], bucket_elems=1000)
        assert BucketEntry(leaves=(0, 1)) in entries
        assert BucketEntry(leaves=(2,)) in entries

    def test_engine_warns_once_on_oversize(self, eight_devices, monkeypatch):
        from deepspeed_tpu.runtime import engine as engine_mod
        calls = []
        monkeypatch.setattr(engine_mod.logger, "warning",
                            lambda msg, *a, **k: calls.append(str(msg)))
        eng = make_engine({"overlap_comm": True,
                           "allgather_bucket_size": 100,
                           "reduce_bucket_size": 100})
        eng._build_jits()
        assert eng._bucket_warned
        eng._build_zeropp_micro()  # rebuilding must NOT warn again
        assert len([m for m in calls if "bucket plan" in m]) == 1


class TestChunkedQuantizer:

    def test_chunked_quantized_collectives_layout(self, eight_devices):
        """Chunked quantized gather/reduce-scatter reproduce the unchunked
        layout exactly when chunks are group-aligned."""
        import functools
        from jax.sharding import Mesh, PartitionSpec as P
        from deepspeed_tpu.utils.jax_compat import shard_map
        from deepspeed_tpu.ops.quantizer import (quantized_all_gather,
                                                 quantized_reduce_scatter)

        # sized so every chunk boundary is a quantization-group multiple
        # (shard 512x64; unchunked groups of 256 align with the chunked
        # calls' groups) — the layouts must then match BITWISE
        mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4096, 64)), jnp.float32)

        def run(fn, **kw):
            sm = shard_map(functools.partial(fn, axis="data", **kw),
                           mesh=mesh, in_specs=P("data"),
                           out_specs=(P(None) if fn is quantized_all_gather
                                      else P("data")), check_vma=False)
            return np.asarray(jax.jit(sm)(x))

        g1 = run(quantized_all_gather)
        g2 = run(quantized_all_gather, n_chunks=2)
        np.testing.assert_array_equal(g1, g2)
        r1 = run(quantized_reduce_scatter)
        r2 = run(quantized_reduce_scatter, n_chunks=4)
        np.testing.assert_array_equal(r1, r2)

    def test_chunks_must_divide(self):
        from deepspeed_tpu.ops.quantizer import quantized_all_gather
        with pytest.raises(ValueError, match="n_chunks"):
            quantized_all_gather(jnp.zeros((10, 4)), axis="data", n_chunks=3)


class TestCommsLoggerSplit:

    def test_overlapped_exposed_split(self, eight_devices):
        from deepspeed_tpu import comm as dist
        from deepspeed_tpu.utils.comms_logging import CommsLogger

        logger_ = CommsLogger()
        dist.configure(comms_logger=logger_)
        try:
            eng = make_engine({"overlap_comm": True})
            eng.forward(dict(BATCH))
            totals = logger_._sched_totals()
            # block-scan collectives tagged overlapped, edge-of-step rest
            # gathers tagged exposed — both classes must be present
            assert totals.get(True, 0) > 0
            assert totals.get(False, 0) > 0
            logger_.log_all()  # renders the split column without raising
        finally:
            dist.configure(comms_logger=CommsLogger(
                config=type("C", (), {"enabled": False, "verbose": False,
                                      "prof_ops": []})()))
            logger_.reset()


class TestOverflowSkipPipelined:
    """fp16 overflow on the PIPELINED ZeRO micro schedule (ISSUE 13
    satellite): until now only the fused path had the gnorm==0.0 skip
    regression (test_offload). The pipelined apply must skip the update
    (params bitwise unchanged), report gnorm 0.0 — not NaN from
    inf * 0 — and walk the loss scale down with the hysteresis/floor
    semantics, while grads land through the overlap schedule."""

    def _fp16_engine(self, hysteresis=1, scale_power=40):
        topo_mod.reset()
        # model keeps its default dtype so fp16.enabled casts params to
        # f16 — the backward then genuinely overflows at a 2^40 scale
        model = gpt2_model("gpt2-tiny", **CFG)
        config = {
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            # plain stage 3 opts into the pipelined schedule EXPLICITLY
            # (the zeropp default path quantizes weights; the overflow
            # semantics under test are schedule-level, not wire-level)
            "zero_optimization": {"stage": 3,
                                  "stage3_param_persistence_threshold": 0,
                                  "overlap_comm": True},
            "fp16": {"enabled": True, "initial_scale_power": scale_power,
                     "hysteresis": hysteresis, "min_loss_scale": 1.0},
        }
        engine, _, _, _ = deepspeed_tpu.initialize(model=model,
                                                   config=config, seed=11)
        return engine

    def test_overflow_skips_update_and_gnorm_is_zero(self, eight_devices):
        eng = self._fp16_engine()
        before = jax.tree.map(np.asarray, eng.state["params"])
        with transport_off():
            eng.forward(dict(BATCH))
            eng.backward()
            eng.step()
        # the schedule is resolved lazily at the first forward build
        assert eng._explicit_micro and eng._overlap_active, \
            getattr(eng, "_overlap_fallback", None)
        assert eng.skipped_steps == 1
        gnorm = float(eng._last_grad_norm)
        assert gnorm == 0.0 and not np.isnan(gnorm), gnorm
        # the update was skipped: every fp16 param leaf is bitwise
        # untouched (the k_proj/bias convention is moot here — equality
        # is exact by construction on a skipped step)
        after = jax.tree.map(np.asarray, eng.state["params"])
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(before)[0],
                jax.tree.leaves(after)):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            np.testing.assert_array_equal(b, a, err_msg=f"leaf {name}")

    def test_sustained_overflow_decays_scale_to_recovery(self, eight_devices):
        """Three overflowing steps at hysteresis 1: the scale halves each
        step (2^40 -> 2^37) and every one is a skip — the schedule never
        consumes lr steps on overflowed updates."""
        eng = self._fp16_engine(hysteresis=1)
        scales = []
        with transport_off():
            for _ in range(3):
                eng.forward(dict(BATCH))
                eng.backward()
                eng.step()
                scales.append(float(eng.state["loss_scale"]["cur_scale"]))
        assert eng.skipped_steps == 3
        assert scales == [2.0 ** 39, 2.0 ** 38, 2.0 ** 37], scales
        assert eng.lr_scheduler.state_dict().get("last_step", 0) in (0, None) \
            or eng.skipped_steps == 3
