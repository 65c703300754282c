"""Interpret-mode parity suite for the fused Pallas MoE kernel pair
(ISSUE 11, ops/transformer/pallas_moe.py).

The numerics anchor is ``moe/layer.py::moe_reference_forward`` — ONE pure
statement of the dead-EP XLA expert path, itself pinned bitwise against
the production layer here — and the contract ladder is:

- routing (top-k picks, capacity clamps, combine weights, the inverse
  slot map) is BIT-identical to ``top_k_gating_indices``;
- the dispatch gather+cast payload is BYTE-identical to the XLA
  ``astype``/``quantize_rows_int8`` composition it replaces (the
  ``pallas_quant`` wire contract extended to dispatch traffic);
- the fused FFN+combine output matches the reference to fp32/bf16
  elementwise tolerance (fp32 in-register accumulation vs the XLA
  path's compute-dtype einsums);
- the backward IS the reference VJP (``custom_vjp``), so grads match
  tightly;
- ``DSTPU_MOE_KERNEL=xla`` / ``MoE(kernel='xla')`` is the bitwise
  escape hatch, and every unsupported geometry silently keeps XLA.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.layer import MoE, moe_reference_forward
from deepspeed_tpu.moe.sharded_moe import top_k_gating_indices
from deepspeed_tpu.ops.transformer import pallas_moe as pm

T, E, H, F = 32, 4, 16, 32


def _params(activation="silu_gated", dtype=jnp.float32, seed=0):
    moe = MoE(hidden_size=H, intermediate_size=F, num_experts=E, top_k=2,
              activation=activation)
    return moe.init(jax.random.PRNGKey(seed), dtype)


def _tokens(dtype=jnp.float32, seed=1, t=T):
    return jax.random.normal(jax.random.PRNGKey(seed), (t, H), dtype)


class TestRoute:

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_route_matches_gating_indices(self, top_k):
        logits = jax.random.normal(jax.random.PRNGKey(2), (T, E))
        cap = 6  # tight: forces real drops
        src, slot_w, slot_tk, w_tk, me, ce = pm.moe_route(
            logits, top_k=top_k, capacity=cap, interpret=True)
        eidx, pos, keep, weight, aux, me_ref = top_k_gating_indices(
            logits, top_k, cap)
        # inverse slot map: src[slot] = token + 1 for kept choices
        slot = np.where(np.asarray(keep),
                        np.asarray(eidx) * cap + np.asarray(pos), -1)
        src_ref = np.zeros((E * cap,), np.int32)
        slw_ref = np.zeros((E * cap,), np.float32)
        for t in range(T):
            for k in range(top_k):
                if slot[t, k] >= 0:
                    src_ref[slot[t, k]] = t + 1
                    slw_ref[slot[t, k]] = np.asarray(weight)[t, k]
        np.testing.assert_array_equal(np.asarray(src), src_ref)
        np.testing.assert_array_equal(np.asarray(slot_w), slw_ref)
        # token-major combine metadata
        np.testing.assert_array_equal(
            np.asarray(slot_tk),
            np.where(slot >= 0, slot, 0).astype(np.int32))
        np.testing.assert_array_equal(
            np.asarray(w_tk), np.asarray(weight * keep))
        # aux ingredients (GShard): me/ce reproduce the reference aux
        np.testing.assert_allclose(float(jnp.sum(me * ce) * E), float(aux),
                                   rtol=1e-6)

    @pytest.mark.parametrize("tokens,block", [
        (24, 24), (512, 512), (640, 128), (768, 384), (1024, 512),
        (8192, 512), (1000, None)])
    def test_rank_scan_block_split(self, tokens, block):
        # the rank scan (cumsum as a triangular product) walks the token
        # axis in lane blocks: the whole axis, else the widest
        # 128-multiple divisor; none -> the geometry keeps XLA
        assert pm._route_block(tokens) == block

    def test_route_dead_experts_and_overflow(self):
        # every token wants expert 0 at top-1: experts 1..3 are dead and
        # expert 0 overflows its capacity — clamps must match bitwise
        logits = jnp.tile(jnp.array([[9.0, 1.0, 0.5, 0.0]]), (T, 1))
        cap = 4
        src, slot_w, slot_tk, w_tk, _, _ = pm.moe_route(
            logits, top_k=2, capacity=cap, interpret=True)
        eidx, pos, keep, weight, _, _ = top_k_gating_indices(logits, 2, cap)
        assert int(np.sum(np.asarray(keep)[:, 0])) == cap  # overflow clamp
        kept_slots = np.asarray(src) > 0
        # expert 0 full, expert 1 full (all tokens' 2nd choice), 2/3 dead
        assert kept_slots[:cap].all() and kept_slots[cap:2 * cap].all()
        assert not kept_slots[2 * cap:].any()
        np.testing.assert_array_equal(
            np.asarray(w_tk), np.asarray(weight * keep))


class TestDispatchWire:

    def test_bf16_payload_byte_identical(self):
        tokens = _tokens()
        src = pm.moe_route(tokens @ _params()["gate"], top_k=2, capacity=10,
                           interpret=True)[0]

        @jax.jit
        def both(tk, s):
            kern = pm.moe_dispatch_gather(tk, s, wire_dtype=jnp.bfloat16,
                                          interpret=True)
            ref = tk[jnp.maximum(s - 1, 0)].astype(jnp.bfloat16)
            return kern, ref

        kern, ref = both(tokens, src)
        assert kern.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(kern).view(np.uint16), np.asarray(ref).view(np.uint16))

    def test_int8_payload_byte_identical_to_quantize_rows(self):
        from deepspeed_tpu.ops.quantizer.pallas_quant import \
            quantize_rows_int8
        tokens = _tokens()
        src = pm.moe_route(tokens @ _params()["gate"], top_k=2, capacity=10,
                           interpret=True)[0]

        @jax.jit
        def both(tk, s):
            q, sc = pm.moe_dispatch_gather_int8(tk, s, interpret=True)
            qr, scr = quantize_rows_int8(tk[jnp.maximum(s - 1, 0)],
                                         interpret=True)
            return q, sc, qr, scr

        q, sc, qr, scr = both(tokens, src)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
        np.testing.assert_array_equal(np.asarray(sc), np.asarray(scr))

    def test_mask_pad_zeroes_unfilled_slots(self):
        tokens = _tokens()
        src = jnp.array([2, 0, 1] + [0] * 13, jnp.int32)
        out = pm.moe_dispatch_gather(tokens, src, mask_pad=True,
                                     interpret=True)
        np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(tokens[1]))


def _tol(dtype):
    return dict(atol=1e-5, rtol=1e-5) if dtype == jnp.float32 \
        else dict(atol=5e-2, rtol=5e-2)


class TestForwardParity:

    @pytest.mark.parametrize("top_k", [1, 2])
    @pytest.mark.parametrize("activation", ["silu_gated", "gelu"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_fused_matches_reference(self, top_k, activation, dtype):
        params = _params(activation, dtype)
        x = _tokens(dtype)
        cap = 10
        ref, aux_r = moe_reference_forward(
            params, x, top_k=top_k, capacity=cap, activation=activation,
            mask_pad=False)
        fwd = pm.make_moe_forward(top_k=top_k, capacity=cap,
                                  activation=activation, mask_pad=False,
                                  interpret=True)
        out, aux = jax.jit(fwd)(params, x)
        assert out.dtype == dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), **_tol(dtype))
        np.testing.assert_allclose(float(aux), float(aux_r), rtol=1e-5)

    @pytest.mark.parametrize("n_chunks", [2, 5])
    def test_chunked_scan_carry_matches(self, n_chunks):
        # n_chunks=2 divides cap=10; 5 also divides — both exercise the
        # prefetch scan; a non-divisor would clamp (below)
        params, x = _params(), _tokens()
        ref, _ = moe_reference_forward(params, x, top_k=2, capacity=10,
                                       activation="silu_gated",
                                       mask_pad=False)
        fwd = pm.make_moe_forward(top_k=2, capacity=10,
                                  activation="silu_gated", mask_pad=False,
                                  n_chunks=n_chunks, interpret=True)
        out, _ = jax.jit(fwd)(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_odd_capacity_clamps_chunks(self):
        # capacity 7 is prime: n_chunks=4 must clamp to 1, not crash
        params, x = _params(), _tokens(t=28)
        ref, _ = moe_reference_forward(params, x, top_k=1, capacity=7,
                                       activation="silu_gated",
                                       mask_pad=False)
        fwd = pm.make_moe_forward(top_k=1, capacity=7,
                                  activation="silu_gated", mask_pad=False,
                                  n_chunks=4, interpret=True)
        out, _ = jax.jit(fwd)(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_split_combine_path_matches(self, monkeypatch):
        # force the token output over the VMEM residency budget so the
        # FFN writes [E, C, H] and the separate combine kernel runs
        monkeypatch.setattr(pm, "_FUSED_OUT_BUDGET", 1)
        params, x = _params(), _tokens()
        ref, _ = moe_reference_forward(params, x, top_k=2, capacity=10,
                                       activation="silu_gated",
                                       mask_pad=False)
        fwd = pm.make_moe_forward(top_k=2, capacity=10,
                                  activation="silu_gated", mask_pad=False,
                                  interpret=True)
        out, _ = jax.jit(fwd)(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_dead_experts_match(self):
        logit_push = jnp.zeros((H, E)).at[:, 0].set(0.5)
        params = dict(_params(), gate=_params()["gate"] + logit_push)
        x = _tokens()
        ref, _ = moe_reference_forward(params, x, top_k=2, capacity=4,
                                       activation="silu_gated",
                                       mask_pad=False)
        fwd = pm.make_moe_forward(top_k=2, capacity=4,
                                  activation="silu_gated", mask_pad=False,
                                  interpret=True)
        out, _ = jax.jit(fwd)(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_mask_pad_variant_matches(self):
        params, x = _params(), _tokens()
        ref, _ = moe_reference_forward(params, x, top_k=2, capacity=10,
                                       activation="silu_gated",
                                       mask_pad=True)
        fwd = pm.make_moe_forward(top_k=2, capacity=10,
                                  activation="silu_gated", mask_pad=True,
                                  interpret=True)
        out, _ = jax.jit(fwd)(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


class TestBackward:

    def test_grads_are_reference_vjp(self):
        """The kernel path's custom_vjp backward IS the reference VJP —
        grads match the XLA path to float tolerance, not just direction."""
        params, x = _params(), _tokens()
        fwd = pm.make_moe_forward(top_k=2, capacity=10,
                                  activation="silu_gated", mask_pad=False,
                                  n_chunks=2, interpret=True)

        def lk(p, t):
            o, a = fwd(p, t)
            return jnp.sum(o * o) + a

        def lr(p, t):
            o, a = moe_reference_forward(p, t, top_k=2, capacity=10,
                                         activation="silu_gated",
                                         mask_pad=False)
            return jnp.sum(o * o) + a

        gk = jax.jit(jax.grad(lk, argnums=(0, 1)))(params, x)
        gr = jax.jit(jax.grad(lr, argnums=(0, 1)))(params, x)
        for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-5)


class TestReferenceIsLayerPath:

    def test_reference_bitwise_equals_layer_xla_path(self):
        """moe_reference_forward must BE the layer's dead-EP XLA program
        (it anchors both the parity suite and the custom_vjp backward)."""
        from deepspeed_tpu.moe.sharded_moe import capacity as _capacity
        moe = MoE(hidden_size=H, intermediate_size=F, num_experts=E,
                  top_k=2)
        params = moe.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, H))
        out, aux = jax.jit(lambda p, t: moe(p, t))(params, x)
        cap = _capacity(32, E, moe.capacity_factor, moe.min_capacity)
        ref, aux_r = jax.jit(lambda p, t: moe_reference_forward(
            p, t, top_k=2, capacity=cap, activation="silu_gated",
            mask_pad=False))(params, x.reshape(32, H))
        np.testing.assert_array_equal(np.asarray(out).reshape(32, H),
                                      np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(aux), np.asarray(aux_r))


class TestDispatchGates:

    def test_mode_validation(self, monkeypatch):
        monkeypatch.setenv("DSTPU_MOE_KERNEL", "cuda")
        with pytest.raises(ValueError, match="DSTPU_MOE_KERNEL"):
            pm.moe_kernel_mode()

    def test_mode_forced(self, monkeypatch):
        monkeypatch.setenv("DSTPU_MOE_KERNEL", "pallas")
        assert pm.moe_kernel_mode() == "pallas"
        monkeypatch.setenv("DSTPU_MOE_KERNEL", "xla")
        assert pm.moe_kernel_mode() == "xla"

    def test_auto_is_xla_off_tpu(self, monkeypatch):
        monkeypatch.delenv("DSTPU_MOE_KERNEL", raising=False)
        assert pm.moe_kernel_mode() == "xla"  # CPU test backend

    def test_supported_geometry_matrix(self):
        ok = dict(top_k=2, activation="silu_gated", dtype=jnp.float32,
                  tokens=T, num_experts=E, hidden=H)
        assert pm.moe_kernel_supported(**ok)
        assert not pm.moe_kernel_supported(**dict(ok, top_k=3))
        assert not pm.moe_kernel_supported(**dict(ok, activation="relu"))
        assert not pm.moe_kernel_supported(**dict(ok, dtype=jnp.float16))
        assert not pm.moe_kernel_supported(
            **dict(ok, tokens=pm._ROUTE_BUDGET))
        # the rank scan splits the token axis into 128-multiple lane
        # blocks; a long axis with no such divisor keeps XLA
        assert not pm.moe_kernel_supported(**dict(ok, tokens=1000))
        # FFN-grid working set scales with hidden: production-scale H
        # must keep XLA instead of hard-failing the Mosaic compile
        assert not pm.moe_kernel_supported(**dict(ok, hidden=7168))

    def test_resolution_is_the_layer_gate(self, monkeypatch):
        """ONE resolver states the whole gate (mode + pins + geometry);
        the layer and the bench honesty marker both consume it."""
        geom = dict(top_k=2, activation="silu_gated", dtype=jnp.float32,
                    tokens=T, num_experts=E, hidden=H)
        monkeypatch.setenv("DSTPU_MOE_KERNEL", "pallas")
        assert pm.moe_kernel_resolution(**geom) == "pallas"
        monkeypatch.setenv("DSTPU_MOE_MASK_PAD", "1")
        assert pm.moe_kernel_resolution(**geom) == "xla (mask-pad pin)"
        monkeypatch.delenv("DSTPU_MOE_MASK_PAD")
        assert (pm.moe_kernel_resolution(**dict(geom, top_k=3))
                == "xla (unsupported geometry)")
        monkeypatch.setenv("DSTPU_MOE_KERNEL", "xla")
        assert pm.moe_kernel_resolution(**geom) == "xla"
        monkeypatch.delenv("DSTPU_MOE_KERNEL")
        # CPU test backend: auto pins xla; the 8-device mesh earns the
        # multi-device label, a forced per-layer 'xla' stays unlabeled
        assert pm.moe_kernel_resolution(**geom).startswith("xla")
        assert pm.moe_kernel_resolution(**geom, kernel="xla") == "xla"

    def test_layer_forced_pallas_matches_xla_hatch(self, monkeypatch):
        """MoE(kernel='pallas') on a dead mesh runs the kernel path (the
        interpret program off-TPU) and matches MoE(kernel='xla') — which
        is bitwise the untouched layer XLA path."""
        moe_k = MoE(hidden_size=H, intermediate_size=F, num_experts=E,
                    top_k=2, kernel="pallas")
        moe_x = MoE(hidden_size=H, intermediate_size=F, num_experts=E,
                    top_k=2, kernel="xla")
        moe_0 = MoE(hidden_size=H, intermediate_size=F, num_experts=E,
                    top_k=2)
        params = moe_k.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, H))
        ok, ak = jax.jit(lambda p, t: moe_k(p, t))(params, x)
        ox, ax = jax.jit(lambda p, t: moe_x(p, t))(params, x)
        o0, a0 = jax.jit(lambda p, t: moe_0(p, t))(params, x)
        # hatch == default XLA path bitwise (CPU auto resolves to xla)
        np.testing.assert_array_equal(np.asarray(ox), np.asarray(o0))
        np.testing.assert_array_equal(np.asarray(ax), np.asarray(a0))
        # kernel path matches the hatch numerically
        np.testing.assert_allclose(np.asarray(ok), np.asarray(ox),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(float(ak), float(ax), rtol=1e-6)

    def test_live_expert_axis_keeps_xla(self, eight_devices, monkeypatch):
        """A live expert mesh must NEVER take the kernel path — the
        exchange is GSPMD-mediated there (multi-chip note)."""
        from deepspeed_tpu.runtime import topology as topo_mod
        from deepspeed_tpu.runtime.topology import TopologyConfig
        topo_mod.reset()
        topo = topo_mod.initialize(TopologyConfig(expert=2, data=-1),
                                   force=True)
        def boom(**kw):
            raise AssertionError("kernel path taken under live EP")

        called = []
        monkeypatch.setattr(pm, "make_moe_forward", boom)
        moe = MoE(hidden_size=H, intermediate_size=F, num_experts=E,
                  top_k=2, kernel="pallas")
        params = moe.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, H))
        with topo.mesh:
            out, _ = jax.jit(lambda p, t: moe(p, t))(params, x)
        assert not called
        assert np.isfinite(np.asarray(out)).all()

    def test_mask_pad_env_keeps_xla(self, monkeypatch):
        monkeypatch.setenv("DSTPU_MOE_MASK_PAD", "1")
        called = []
        monkeypatch.setattr(pm, "make_moe_forward",
                            lambda **kw: called.append(kw))
        moe = MoE(hidden_size=H, intermediate_size=F, num_experts=E,
                  top_k=2, kernel="pallas")
        params = moe.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, H))
        jax.jit(lambda p, t: moe(p, t))(params, x)
        assert not called
