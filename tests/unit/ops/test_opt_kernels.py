"""Fused Pallas optimizer-update kernels (ISSUE 10 tentpole).

Parity of the bucket kernels (ops/adam/pallas_adam.py, ops/lion/
pallas_lion.py) against the XLA elementwise tree in runtime/optimizers.py,
the stochastic-rounding contract on BOTH narrowing paths (in-kernel hash
PRNG vs the retained XLA ``_sr_to_bf16`` — mean-preservation and
fixed-seed determinism, so the two cannot drift semantically), and the
fused quantize+pack kernel's byte-identity with the int8 wire path.

Everything runs the kernels in interpret mode (CPU tier-1); the compiled
TPU program executes the same jaxpr-level math.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.adam.pallas_adam import (adam_bucket_update,
                                                host_adam_step,
                                                native_layout,
                                                opt_kernel_mode, sr_seed)
from deepspeed_tpu.ops.lion.pallas_lion import lion_bucket_update
from deepspeed_tpu.runtime.optimizers import (Optimizer, _plan_opt_buckets,
                                              _sr_to_bf16)

RNG = np.random.default_rng(7)


def _tree(dtype=jnp.float32):
    """A mixed-shape tree: scalar, unaligned vector, aligned matrix."""
    mk = lambda *s: jnp.asarray(RNG.normal(size=s), dtype)
    return {"w": mk(64, 48), "b": mk(48), "s": mk(), "big": mk(256, 128)}


def _grads(tree, dtype=jnp.float32):
    return jax.tree.map(
        lambda x: jnp.asarray(RNG.normal(size=x.shape), dtype), tree)


def _max_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


class TestKernelParity:
    """Fused fp32-moment path vs the XLA tree, per optimizer."""

    @pytest.mark.parametrize("name", ["adamw", "adam", "lamb", "lion"])
    def test_two_steps_match_xla_tree(self, name):
        tree = _tree()
        grads = _grads(tree)
        opt = Optimizer(name=name, lr=1e-3, weight_decay=0.01)
        st = opt.init(tree)
        mx, sx = opt.update(grads, st, 1e-3,
                            grad_scale=jnp.asarray(0.5), kernel="xla")
        mx, sx = opt.update(grads, sx, 1e-3, kernel="xla")
        mp, sp = opt.update(grads, st, 1e-3,
                            grad_scale=jnp.asarray(0.5), kernel="pallas")
        mp, sp = opt.update(grads, sp, 1e-3, kernel="pallas")
        assert _max_diff(mx, mp) < 1e-6
        assert _max_diff(sx["exp_avg"], sp["exp_avg"]) < 1e-6
        if name != "lion":
            assert _max_diff(sx["exp_avg_sq"], sp["exp_avg_sq"]) < 1e-7

    def test_param_dtype_cast_matches_xla(self):
        """The in-kernel bf16 compute-param cast is the same RTN cast the
        XLA path applies — bitwise equal casts of 1-ulp-equal masters."""
        tree = _tree()
        grads = _grads(tree)
        opt = Optimizer(name="adamw", lr=1e-3)
        st = opt.init(tree)
        px, _ = opt.update(grads, st, 1e-3, param_dtype=jnp.bfloat16,
                           kernel="xla")
        pp, _ = opt.update(grads, st, 1e-3, param_dtype=jnp.bfloat16,
                           kernel="pallas")
        for a, b in zip(jax.tree.leaves(px), jax.tree.leaves(pp)):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))

    def test_bucket_composition_invariance(self):
        """Fused multi-leaf buckets == per-leaf buckets in fp32 (the
        lane-padded segment layout is numerics-inert)."""
        tree = _tree()
        grads = _grads(tree)
        opt = Optimizer(name="adamw", lr=1e-3, weight_decay=0.01)
        st = opt.init(tree)
        m1, s1 = opt.update(grads, st, 1e-3, kernel="pallas",
                            bucket_elems=1)          # every leaf alone
        m2, s2 = opt.update(grads, st, 1e-3, kernel="pallas",
                            bucket_elems=1 << 30)    # max fusion
        assert _max_diff(m1, m2) == 0.0
        assert _max_diff(s1["exp_avg_sq"], s2["exp_avg_sq"]) == 0.0

    def test_bucket_plan_shapes(self):
        plan = _plan_opt_buckets([10, 20, 1000, 5, 5], ["f"] * 5, cap=40)
        assert plan == [[0, 1], [2], [3, 4]]
        # dtype boundary splits a bucket
        plan = _plan_opt_buckets([10, 10], ["a", "b"], cap=100)
        assert plan == [[0], [1]]

    def test_zero_size_leaves_pass_through(self):
        """A 0-element leaf must not enter a bucket (its lane-padded
        segment would shift every later leaf's offset) — it passes
        through like the XLA tree's no-op update, fused or standalone."""
        tree = dict(_tree(), empty=jnp.zeros((0, 4), jnp.float32))
        grads = _grads(tree)
        opt = Optimizer(name="adamw", lr=1e-3, weight_decay=0.01)
        st = opt.init(tree)
        for cap in (1, 1 << 30):   # standalone and max-fusion plans
            mx, sx = opt.update(grads, st, 1e-3, kernel="xla")
            mp, sp = opt.update(grads, st, 1e-3, kernel="pallas",
                                bucket_elems=cap)
            assert mp["empty"].shape == (0, 4)
            assert mp["empty"].dtype == jnp.float32
            assert sp["exp_avg"]["empty"].shape == (0, 4)
            drop = lambda t: {k: v for k, v in t.items() if k != "empty"}
            assert _max_diff(drop(mx), drop(mp)) < 1e-6
            assert _max_diff(drop(sx["exp_avg"]),
                             drop(sp["exp_avg"])) < 1e-6
        pc, _ = opt.update(grads, st, 1e-3, kernel="pallas",
                           param_dtype=jnp.bfloat16)
        assert pc["empty"].dtype == jnp.bfloat16

    def test_update_api_unchanged_without_param_dtype(self):
        """(new_master_fp32, new_state) return preserved for existing
        callers (test_opt_state_dtype.py relies on it)."""
        tree, grads = _tree(), _grads(_tree())
        opt = Optimizer(name="adamw")
        st = opt.init(tree)
        master, state = opt.update(grads, st, 1e-3, kernel="pallas")
        assert jax.tree.leaves(master)[0].dtype == jnp.float32
        assert set(state) == {"step", "master", "exp_avg", "exp_avg_sq"}

    def test_mode_resolution(self, monkeypatch):
        monkeypatch.setenv("DSTPU_OPT_KERNEL", "xla")
        assert opt_kernel_mode() == "xla"
        monkeypatch.setenv("DSTPU_OPT_KERNEL", "pallas")
        assert opt_kernel_mode() == "pallas"
        monkeypatch.setenv("DSTPU_OPT_KERNEL", "")
        assert opt_kernel_mode() == "xla"  # CPU backend -> xla
        monkeypatch.setenv("DSTPU_OPT_KERNEL", "cuda")
        with pytest.raises(ValueError, match="DSTPU_OPT_KERNEL"):
            opt_kernel_mode()

    def test_host_backend_matches_kernel(self):
        """The shim host backend (cpu_adam fallback) and the bucket kernel
        share one statement of the math."""
        n = 640
        g = RNG.normal(size=n).astype(np.float32)
        p = RNG.normal(size=n).astype(np.float32)
        m = (RNG.normal(size=n) * 0.1).astype(np.float32)
        v = np.abs(RNG.normal(size=n)).astype(np.float32) * 0.01
        ph, mh, vh = p.copy(), m.copy(), v.copy()
        host_adam_step(ph, g, mh, vh, step=3, lr=1e-3, weight_decay=0.01,
                       adamw=True)
        pk, _, mk, vk = adam_bucket_update(
            jnp.asarray(g), jnp.asarray(p), jnp.asarray(m), jnp.asarray(v),
            step=jnp.asarray(3, jnp.int32), lr=1e-3, weight_decay=0.01,
            mode="adamw", sr=False, interpret=True)
        np.testing.assert_allclose(np.asarray(pk), ph, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(mk), mh, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(vk), vh, rtol=1e-6, atol=1e-8)


class TestStochasticRounding:
    """The SR contract on BOTH narrowing paths: unbiased in expectation,
    deterministic under a fixed (step, slot, bucket) seed."""

    # a value straddling two bf16 points: 1.0 + 1/1024 (bf16 step at 1.0
    # is 1/128, so RTN always returns 1.0 — the freeze the SR store
    # exists to prevent)
    VAL = 1.0 + 1.0 / 1024

    @staticmethod
    @jax.jit
    def _draw(st):
        """(The step is traced, as the engine's is: interpret mode compiles a
        launch anew at every eager call, and the mean below takes 64.)"""
        g0 = jnp.zeros(4096, jnp.float32)
        m_in = jnp.full((4096,), TestStochasticRounding.VAL / 0.9, jnp.float32)  # b1*m = VAL
        _, _, m_out, _ = adam_bucket_update(
            g0, g0, m_in, g0, step=st, lr=0.0,
            m_dtype=jnp.bfloat16, v_dtype=jnp.float32,
            seed_m=sr_seed(st, 1, 0), seed_v=sr_seed(st, 2, 0),
            interpret=True)
        return m_out

    def _kernel_draw(self, step):
        return np.asarray(self._draw(jnp.asarray(step, jnp.int32)), np.float32)

    def test_in_kernel_sr_mean_preserving(self):
        draws = sum(self._kernel_draw(s) for s in range(64)) / 64
        rtn_err = abs(float(jnp.asarray(self.VAL, jnp.bfloat16)) - self.VAL)
        assert abs(draws.mean() - self.VAL) < rtn_err / 20

    def test_in_kernel_sr_fixed_seed_deterministic(self):
        a, b = self._kernel_draw(5), self._kernel_draw(5)
        np.testing.assert_array_equal(a, b)
        c = self._kernel_draw(6)
        assert (a != c).any()  # the (step,...) seed advances the stream

    def test_in_kernel_sr_slots_are_independent(self):
        """m and v narrow from different (slot) streams: identical inputs
        must not produce identical draw patterns."""
        st = jnp.asarray(2, jnp.int32)
        x = jnp.full((4096,), self.VAL, jnp.float32)
        # craft inputs so m2 == v2 == VAL: g=0, m = VAL/b1, v = VAL/b2
        _, _, m_out, v_out = adam_bucket_update(
            jnp.zeros(4096, jnp.float32), jnp.zeros(4096, jnp.float32),
            x / 0.9, x / 0.999, step=st, lr=0.0,
            m_dtype=jnp.bfloat16, v_dtype=jnp.bfloat16,
            seed_m=sr_seed(st, 1, 0), seed_v=sr_seed(st, 2, 0),
            interpret=True)
        assert (np.asarray(m_out, np.float32)
                != np.asarray(v_out, np.float32)).any()

    def test_xla_sr_mean_preserving(self):
        """The retained ``_sr_to_bf16`` fallback keeps the same contract —
        the two paths cannot drift semantically."""
        x = jnp.full((4096,), self.VAL, jnp.float32)
        acc = np.zeros(4096)
        K = 64
        for s in range(K):
            key = jax.random.fold_in(jax.random.key(0x51AB), s)
            acc += np.asarray(_sr_to_bf16(x, key), np.float32)
        rtn_err = abs(float(jnp.asarray(self.VAL, jnp.bfloat16)) - self.VAL)
        assert abs(acc.mean() / K - self.VAL) < rtn_err / 20

    def test_xla_sr_fixed_seed_deterministic(self):
        x = jnp.asarray(RNG.normal(size=2048), jnp.float32)
        key = jax.random.key(123)
        a = np.asarray(_sr_to_bf16(x, key), np.float32)
        b = np.asarray(_sr_to_bf16(x, key), np.float32)
        np.testing.assert_array_equal(a, b)
        c = np.asarray(_sr_to_bf16(x, jax.random.key(124)), np.float32)
        assert (a != c).any()

    def test_sr_engages_only_for_bf16(self):
        """fp16 moment stores stay plain RTN casts on the kernel path
        (``_narrow_state_tree``'s rule)."""
        st = jnp.asarray(1, jnp.int32)
        g = jnp.asarray(RNG.normal(size=512), jnp.float32)
        z = jnp.zeros(512, jnp.float32)
        _, _, m_out, _ = adam_bucket_update(
            g, z, z, z, step=st, lr=0.0, m_dtype=jnp.float16,
            v_dtype=jnp.float32, seed_m=sr_seed(st, 1, 0), interpret=True)
        ref = (0.1 * g).astype(jnp.float16)
        np.testing.assert_array_equal(np.asarray(m_out), np.asarray(ref))

    def test_lion_sr_moment(self):
        """Lion's single moment rides the same SR stream machinery."""
        st = jnp.asarray(4, jnp.int32)
        m_in = jnp.full((4096,), self.VAL / 0.99, jnp.float32)
        z = jnp.zeros(4096, jnp.float32)
        _, _, m1 = lion_bucket_update(z, z, m_in, lr=0.0,
                                      m_dtype=jnp.bfloat16,
                                      seed_m=sr_seed(st, 1, 0),
                                      interpret=True)
        _, _, m2 = lion_bucket_update(z, z, m_in, lr=0.0,
                                      m_dtype=jnp.bfloat16,
                                      seed_m=sr_seed(st, 1, 0),
                                      interpret=True)
        np.testing.assert_array_equal(np.asarray(m1, np.float32),
                                      np.asarray(m2, np.float32))
        vals = np.unique(np.asarray(m1, np.float32))
        assert len(vals) == 2  # both neighbouring bf16 points drawn


def _dyadic(shape, unit, lo, hi, dtype):
    """Whole multiples of ``unit`` (a power of two) in [lo, hi] x unit:
    few enough mantissa bits that every product the kernels form with a
    power-of-two-ish hyperparameter is exact."""
    return jnp.asarray(RNG.integers(lo, hi + 1, size=shape) * unit, dtype)


# shapes ISSUE 28 names: a stacked leaf, a 2-D leaf whose last row block is
# ragged at 16 rows a block, an expert-like stack of stacks; and a leaf wide
# enough to be cut into column blocks of 2048, the last one ragged
NATIVE_SHAPES = [(3, 32, 256), (50, 384), (2, 4, 32, 128), (20, 2304)]


class TestNativeLayout:
    """A stand-alone leaf is launched in the layout it has (ISSUE 28): no
    flatten to rows of 128 before the launch and none after, and every
    element gets the draw it got in the flat bucket."""

    @pytest.mark.parametrize("name", ["adamw", "adam", "lion"])
    @pytest.mark.parametrize("shape", NATIVE_SHAPES, ids=str)
    def test_native_launch_bitwise_equals_flat(self, shape, name):
        """Master, bf16 weight and the bf16-SR moments of a native launch
        equal the flat launch's bit for bit at the same seeds, at the
        default block (one block a slab here) and at blocks of 16 rows
        (many grid steps; a ragged last row block for 50 and 20 rows, a
        ragged last column block for 2304 columns): this pins the element
        index stream and the block geometry.

        The values are dyadic and the hyperparameters powers of two (0.75
        = 3/4), so every product is exact: XLA's CPU backend, which runs
        the interpreted kernel, contracts a multiply-add into an FMA in one
        block shape and not in another, and an inexact product would then
        differ by an ulp for a reason that is not the kernel's."""
        bf16, f32 = jnp.bfloat16, jnp.float32
        assert native_layout(shape)
        g = _dyadic(shape, 1 / 16, -64, 64, bf16)
        p = _dyadic(shape, 1 / 16, -64, 64, f32)
        m = _dyadic(shape, 1 / 1024, -255, 255, bf16)
        v = _dyadic(shape, 1 / 256, 0, 64, bf16)
        st = jnp.asarray(3, jnp.int32)
        kw = dict(lr=2.0 ** -6, beta1=0.5, beta2=0.75, weight_decay=0.125,
                  grad_scale=jnp.asarray(0.5), seed_m=sr_seed(st, 1, 5),
                  m_dtype=bf16, param_dtype=bf16, interpret=True)
        if name == "lion":
            run = lambda ops, **k: lion_bucket_update(*ops[:3], **kw, **k)
        else:
            run = lambda ops, **k: adam_bucket_update(
                *ops, step=st, mode=name, seed_v=sr_seed(st, 2, 5),
                v_dtype=bf16, **kw, **k)
        ops = (g, p, m, v)
        flat = run([x.reshape(-1) for x in ops])
        for block_rows in (512, 4):
            native = run(ops, block_rows=block_rows)
            for a, b in zip(native, flat):
                assert a.shape == shape and a.dtype == b.dtype
                np.testing.assert_array_equal(
                    np.asarray(a, np.float32).reshape(-1),
                    np.asarray(b, np.float32))
        # the stochastic store is engaged: the first moment is not its
        # round-to-nearest cast everywhere
        g32 = 0.5 * g.astype(f32) + (0.125 * p if name == "adam" else 0.0)
        bm = 0.75 if name == "lion" else 0.5
        rtn = (bm * m.astype(f32) + (1 - bm) * g32).astype(bf16)
        assert (np.asarray(native[2], np.float32)
                != np.asarray(rtn, np.float32)).any()

    @pytest.mark.parametrize("name", ["adamw", "lion"])
    @pytest.mark.parametrize("shape", NATIVE_SHAPES, ids=str)
    def test_update_jaxpr_hands_the_leaf_over_as_it_lies(self, shape, name):
        """In the jaxpr of ``Optimizer.update(kernel="pallas")`` the leaf's
        four operands go straight into a ``pallas_call`` in the leaf's own
        shape, and its results straight out: no ``reshape``, ``pad`` or
        ``concatenate`` touches the leaf (the small 1-D leaf beside it still
        takes the flat bucket, whose rows are 128 wide)."""
        bf16 = jnp.bfloat16
        tree = {"big": jnp.zeros(shape, jnp.float32),
                "small": jnp.zeros((40,), jnp.float32)}
        opt = Optimizer(name=name, moment_dtype=bf16, moment_sq_dtype=bf16)
        st = opt.init(tree)
        grads = jax.tree.map(lambda x: x.astype(bf16), tree)
        cap = int(np.prod(shape))       # the leaf is at the cap: stands alone
        jaxpr = jax.make_jaxpr(lambda g, s: opt.update(
            g, s, 1e-3, param_dtype=bf16, kernel="pallas",
            bucket_elems=cap))(grads, st).jaxpr
        is_leaf = lambda v: getattr(v.aval, "shape", None) == shape
        leaf_in = [v for v in jaxpr.invars if is_leaf(v)]
        assert len(leaf_in) == (3 if name == "lion" else 4)
        calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        native = [e for e in calls if any(is_leaf(v) for v in e.invars)]
        assert len(calls) == 2 and len(native) == 1
        call = native[0]
        assert ({id(v) for v in call.invars if is_leaf(v)}
                == {id(v) for v in leaf_in})
        results = [v for v in call.outvars if is_leaf(v)]
        assert len(results) == len(leaf_in)     # weight, master, moments
        assert all(v in jaxpr.outvars for v in results)
        # beside them only Adam's f32[8,128] tile, by which the benchmark's
        # trace reader knows the kernel
        assert [v.aval.shape for v in call.outvars if not is_leaf(v)] == (
            [] if name == "lion" else [(8, 128)])
        for e in jaxpr.eqns:
            if e.primitive.name in ("reshape", "pad", "concatenate"):
                touched = list(e.invars) + list(e.outvars)
                assert not any(is_leaf(v) for v in touched), e

    @pytest.mark.parametrize("shape", [(4096,), (8, 1000)], ids=str)
    def test_fallbacks_take_the_flat_path(self, shape):
        """A 1-D leaf above the cap and a minor dimension off the 128 lanes
        are flattened to rows of 128 exactly as before, and match the XLA
        tree as the flat path always has."""
        assert not native_layout(shape)
        tree = {"w": jnp.asarray(RNG.normal(size=shape), jnp.float32)}
        grads = _grads(tree)
        opt = Optimizer(name="adamw", lr=1e-3, weight_decay=0.01)
        st = opt.init(tree)
        assert opt.kernel_totals(grads, "pallas", 1024) == {
            "path": "pallas", "launches_native": 0, "launches_flat": 1,
            "elements_native": 0, "elements_flat": int(np.prod(shape))}
        jaxpr = jax.make_jaxpr(lambda g, s: opt.update(
            g, s, 1e-3, kernel="pallas", bucket_elems=1024))(grads, st)
        call, = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        assert all(v.aval.shape[-1] == 128 for v in call.invars[:4])
        mx, sx = opt.update(grads, st, 1e-3, kernel="xla")
        mp, sp = opt.update(grads, st, 1e-3, kernel="pallas",
                            bucket_elems=1024)
        assert _max_diff(mx, mp) < 1e-6
        assert _max_diff(sx["exp_avg"], sp["exp_avg"]) < 1e-6
        assert _max_diff(sx["exp_avg_sq"], sp["exp_avg_sq"]) < 1e-7

    def test_lamb_and_small_leaves_stay_flat(self):
        """LAMB's trust epilogue slices the flat update per leaf; a leaf
        under the cap keeps the flat form even when it is alone."""
        g = {"w": jnp.zeros((16, 256), jnp.float32)}
        assert Optimizer(name="lamb").kernel_totals(
            g, "pallas", 1)["launches_native"] == 0
        assert Optimizer(name="adamw").kernel_totals(
            g, "pallas", 1)["launches_native"] == 1
        # alone in its bucket but under the cap: flat, like every small leaf
        assert Optimizer(name="adamw").kernel_totals(
            g, "pallas", 1 << 20)["launches_flat"] == 1
        assert Optimizer(name="adamw").kernel_totals(g, "xla") == {
            "path": "xla", "launches_native": 0, "launches_flat": 0,
            "elements_native": 0, "elements_flat": 0}

    @pytest.mark.parametrize("kernel", ["pallas", "xla"])
    def test_engine_opt_kernel_totals(self, eight_devices, monkeypatch,
                                      kernel):
        """``engine.opt_kernel_totals`` is filled from the plan of the
        traced step, with telemetry off: every stand-alone leaf with a
        minor dimension on the lanes is counted native, the rest flat."""
        import deepspeed_tpu
        from deepspeed_tpu.models import gpt2_model
        from deepspeed_tpu.runtime import topology as topo_mod

        monkeypatch.setenv("DSTPU_OPT_KERNEL", kernel)
        monkeypatch.setenv("DSTPU_OPT_BUCKET", "1024")
        topo_mod.reset()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=gpt2_model("gpt2-tiny", max_seq_len=32, vocab_size=256,
                             remat=False),
            config={"train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {"stage": 1}}, seed=5)
        assert engine.opt_kernel_totals["path"] is None     # nothing traced
        loss = float(engine.train_batch(
            {"input_ids": RNG.integers(0, 256, size=(8, 16))}))
        assert np.isfinite(loss)
        totals = engine.opt_kernel_totals
        if kernel == "xla":
            assert totals == {"path": "xla", "launches_native": 0,
                              "launches_flat": 0, "elements_native": 0,
                              "elements_flat": 0}
            return
        shapes = [x.shape for x in jax.tree.leaves(engine.state["params"])]
        sizes = [int(np.prod(s)) for s in shapes]
        plan = _plan_opt_buckets(sizes, ["bfloat16"] * len(sizes), 1024)
        native = [s for s, n in zip(shapes, sizes)
                  if n >= 1024 and len(s) >= 2 and s[-1] % 128 == 0]
        assert totals["path"] == "pallas"
        assert totals["launches_native"] == len(native) == 9
        assert totals["launches_flat"] == len(plan) - len(native) > 0
        assert totals["elements_native"] == sum(int(np.prod(s))
                                                for s in native)
        assert totals["elements_native"] + totals["elements_flat"] == sum(
            sizes)
        assert totals["elements_native"] > 0.98 * sum(sizes)


class TestSRModelTrajectory:
    """The fused SR path keeps the long-horizon EMA tracking the fp32
    trajectory (the test_opt_state_dtype freeze scenario, kernel path)."""

    def test_bf16_second_moment_does_not_freeze(self):
        g = jnp.full((4096,), 0.5, dtype=jnp.float32)
        p = jnp.zeros((4096,), dtype=jnp.float32)

        def run(sq_dtype, steps=300):
            opt = Optimizer(name="adam", lr=0.0, betas=(0.9, 0.999),
                            moment_sq_dtype=sq_dtype)
            state = opt.init(p)
            upd = jax.jit(lambda s: opt.update(g, s, 0.0,
                                               kernel="pallas")[1])
            for _ in range(steps):
                state = upd(state)
            return float(jnp.mean(state["exp_avg_sq"].astype(jnp.float32)))

        v32 = run(None)
        v16 = run(jnp.bfloat16)
        assert v32 > 0.04
        np.testing.assert_allclose(v16, v32, rtol=0.10)


class TestQuantKernel:
    """Fused quantize+pack kernel: byte-identical int8 wire payloads
    (jitted contexts — the wire always runs jitted; see pallas_quant.py)."""

    @pytest.mark.parametrize("shape,gs", [
        ((4096,), 256), ((33, 77), 128), ((1000,), 256), ((64, 256), 256),
    ])
    def test_byte_identical_payload(self, shape, gs, monkeypatch):
        from deepspeed_tpu.ops.quantizer.quantizer import quantize_blockwise

        x = jnp.asarray(RNG.normal(size=shape), jnp.float32)
        f = jax.jit(lambda t: quantize_blockwise(t, 8, gs))
        monkeypatch.setenv("DSTPU_QUANT_KERNEL", "xla")
        qx, sx, zx = f(x)
        monkeypatch.setenv("DSTPU_QUANT_KERNEL", "pallas")
        qp, sp, zp = jax.jit(lambda t: quantize_blockwise(t, 8, gs))(x)
        assert qp.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(qx), np.asarray(qp))
        np.testing.assert_array_equal(np.asarray(sx), np.asarray(sp))
        np.testing.assert_array_equal(np.asarray(zx), np.asarray(zp))

    def test_all_zero_group(self, monkeypatch):
        from deepspeed_tpu.ops.quantizer.quantizer import quantize_blockwise

        x = jnp.zeros((512,), jnp.float32)
        monkeypatch.setenv("DSTPU_QUANT_KERNEL", "pallas")
        q, s, z = jax.jit(lambda t: quantize_blockwise(t, 8, 256))(x)
        np.testing.assert_array_equal(np.asarray(q), 0)
        np.testing.assert_array_equal(np.asarray(s), 1.0)

    def test_wire_path_identical_through_reduce_scatter(self, monkeypatch,
                                                        eight_devices):
        """End to end on the mesh: the quantized grad reduce-scatter
        produces identical results with the fused kernel and the XLA
        quantize chain (same wire bytes -> same dequant -> same sum)."""
        from jax.sharding import Mesh, PartitionSpec as P
        from deepspeed_tpu.ops.quantizer.quantizer import \
            quantized_reduce_scatter
        from deepspeed_tpu.utils.jax_compat import shard_map

        mesh = Mesh(np.array(eight_devices), ("dp",))
        x = jnp.asarray(RNG.normal(size=(8 * 1024,)), jnp.float32)
        fn = shard_map(
            lambda t: quantized_reduce_scatter(t, axis="dp",
                                               group_size=256),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False)
        monkeypatch.setenv("DSTPU_QUANT_KERNEL", "xla")
        with mesh:
            a = jax.jit(fn)(x)
        monkeypatch.setenv("DSTPU_QUANT_KERNEL", "pallas")
        with mesh:
            b = jax.jit(fn)(x)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_int4_and_subgroup_fall_back(self, monkeypatch):
        """Geometries off the default wire (int4 pack, sub-lane groups)
        keep the XLA path under the pallas gate — no behavior change."""
        from deepspeed_tpu.ops.quantizer.quantizer import (
            dequantize_blockwise, quantize_blockwise)

        x = jnp.asarray(RNG.normal(size=100), jnp.float32)
        monkeypatch.setenv("DSTPU_QUANT_KERNEL", "pallas")
        q, s, z = quantize_blockwise(x, 4, 50)
        assert q.dtype == jnp.uint8  # packed nibbles
        out = dequantize_blockwise(q, s, z, 4, 50, out_size=100)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                                   atol=float(jnp.max(jnp.abs(x))) / 7)
