"""MoE tests (reference tests/unit/moe/test_moe.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.moe.sharded_moe import capacity, top_k_gating
from deepspeed_tpu.models import mixtral_model


def test_capacity():
    assert capacity(64, 8, 1.0, 4) == 8
    assert capacity(8, 8, 1.0, 4) == 4  # min_capacity floor


def test_top_k_gating_shapes_and_combine():
    rng = jax.random.PRNGKey(0)
    logits = jax.random.normal(rng, (16, 4))
    combine, dispatch, aux, me = top_k_gating(logits, top_k=2, capacity_=8)
    assert combine.shape == (16, 4, 8)
    assert dispatch.shape == (16, 4, 8)
    # with ample capacity every token keeps both choices → weights sum to 1
    np.testing.assert_allclose(np.sum(combine, axis=(1, 2)), 1.0, rtol=1e-5)
    # each (expert, slot) holds at most one token
    assert int(np.max(np.sum(dispatch, axis=0))) <= 1
    assert float(aux) > 0


def test_top_k_gating_respects_capacity():
    # all tokens want expert 0; capacity 2 → only 2 dispatched
    logits = jnp.stack([jnp.array([10.0, 0, 0, 0])] * 8)
    combine, dispatch, _, _ = top_k_gating(logits, top_k=1, capacity_=2)
    assert int(np.sum(dispatch[:, 0, :])) == 2


def test_mixtral_trains_with_expert_parallelism(eight_devices):
    model = mixtral_model("mixtral-tiny", dtype=jnp.float32, remat=False,
                          max_seq_len=32, vocab_size=256)
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "topology": {"expert": 4},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 256, size=(8, 16))}
    losses = [float(engine.train_batch(batch)) for _ in range(4)]
    assert losses[-1] < losses[0], losses
    # expert params sharded over the expert axis
    spec = engine.zero_plan.param_spec_tree()["blocks"]["moe"]["wo"]
    assert "expert" in str(spec)


def test_moe_ep_matches_no_ep(eight_devices):
    """Expert parallelism is a layout change, not an algorithm change."""
    batch = {"input_ids": np.random.default_rng(1).integers(0, 256, size=(8, 16))}
    cfg = {"train_micro_batch_size_per_gpu": 1,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}}
    m1 = mixtral_model("mixtral-tiny", dtype=jnp.float32, remat=False,
                       max_seq_len=32, vocab_size=256)
    m2 = mixtral_model("mixtral-tiny", dtype=jnp.float32, remat=False,
                       max_seq_len=32, vocab_size=256)
    e1, _, _, _ = deepspeed_tpu.initialize(model=m1, config=dict(cfg), seed=5)
    e2, _, _, _ = deepspeed_tpu.initialize(
        model=m2, config=dict(cfg, topology={"expert": 4}), seed=5)
    l1 = float(e1.forward(batch))
    l2 = float(e2.forward(batch))
    np.testing.assert_allclose(l1, l2, rtol=2e-5)


def test_gather_dispatch_matches_dense_einsum():
    """The index-based gather/scatter dispatch must be numerically identical
    to the dense one-hot einsum dispatch (the reference's MOELayer form,
    sharded_moe.py:425) while spending far fewer FLOPs."""
    from deepspeed_tpu.moe.layer import MoE
    moe = MoE(hidden_size=32, intermediate_size=64, num_experts=4, top_k=2)
    params = moe.init(jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)
    out, aux = moe(params, x)

    tokens = x.reshape(-1, 32)
    cap = capacity(32, 4, moe.capacity_factor, moe.min_capacity)
    combine, dispatch, aux_ref, _ = top_k_gating(tokens @ params["gate"], 2, cap)
    ein = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), tokens)
    gate = jax.nn.silu(jnp.einsum("ech,ehf->ecf", ein, params["wi_gate"]))
    up = jnp.einsum("ech,ehf->ecf", ein, params["wi_up"])
    ref = jnp.einsum("tec,ech->th",
                     combine, jnp.einsum("ecf,efh->ech", gate * up, params["wo"]))
    np.testing.assert_allclose(np.asarray(out.reshape(-1, 32)), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-6)


def test_gather_dispatch_flops_beat_dense():
    """Dispatch is O(t*k*h), not the dense O(t*e*cap*h) — at 4k tokens the
    whole layer must cost several times fewer FLOPs than the one-hot form."""
    from deepspeed_tpu.moe.layer import MoE
    moe = MoE(hidden_size=256, intermediate_size=512, num_experts=8, top_k=2)
    p = moe.init(jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 512, 256), jnp.float32)
    def flops(compiled):
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # older jax: one dict per device
            cost = cost[0]
        return cost["flops"]

    new = flops(jax.jit(lambda p, v: moe(p, v)[0]).lower(p, x).compile())

    def dense(p, v):
        t = v.reshape(-1, 256)
        cp = capacity(t.shape[0], 8, moe.capacity_factor, moe.min_capacity)
        cb, dp, _, _ = top_k_gating(t @ p["gate"], 2, cp)
        ein = jnp.einsum("tec,th->ech", dp.astype(v.dtype), t)
        g = jax.nn.silu(jnp.einsum("ech,ehf->ecf", ein, p["wi_gate"]))
        u = jnp.einsum("ech,ehf->ecf", ein, p["wi_up"])
        o = jnp.einsum("tec,ech->th",
                       cb, jnp.einsum("ecf,efh->ech", g * u, p["wo"]))
        return o.reshape(v.shape)

    old = flops(jax.jit(dense).lower(p, x).compile())
    assert new * 3 < old, (new, old)


def test_split_shared_and_expert_params(eight_devices):
    """Expert-sharded leaves split out by spec (reference moe/utils.py:29
    split_params_into_shared_and_expert_params)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe.layer import MoE
    from deepspeed_tpu.moe.utils import (expert_param_mask, is_moe_spec,
                                         split_params_into_shared_and_expert_params)

    moe = MoE(hidden_size=16, intermediate_size=32, num_experts=4, top_k=2)
    params = moe.init(jax.random.PRNGKey(0), jnp.float32)
    specs = moe.specs()
    assert not is_moe_spec(specs["gate"])
    assert is_moe_spec(specs["wo"])
    shared, expert = split_params_into_shared_and_expert_params(params, specs)
    assert shared["gate"] is not None and expert["gate"] is None
    assert shared["wo"] is None and expert["wo"] is not None
    mask = expert_param_mask(specs)
    assert mask["wo"] is True and mask["gate"] is False
    # the masks drive optax.masked: a transform scoped to expert leaves
    import optax
    tx = optax.masked(optax.scale(0.0), mask)
    grads = jax.tree.map(jnp.ones_like, params)
    state = tx.init(params)
    out, _ = tx.update(grads, state, params)
    assert float(jnp.sum(jnp.abs(out["wo"]))) == 0.0      # scaled to zero
    assert float(jnp.sum(jnp.abs(out["gate"]))) > 0.0     # untouched


def test_moe_split_handles_replicated_none_specs(eight_devices):
    """Replicated leaves carry spec None (add_axes_to_spec convention) —
    they must split as shared, not crash the tree map."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.moe.utils import (expert_param_mask,
                                         split_params_into_shared_and_expert_params)
    params = {"a": np.ones(2), "b": np.ones(2)}
    specs = {"a": None, "b": P("expert", None)}
    assert expert_param_mask(specs) == {"a": False, "b": True}
    shared, expert = split_params_into_shared_and_expert_params(params, specs)
    assert shared["a"] is not None and expert["a"] is None
    assert shared["b"] is None and expert["b"] is not None


class TestChunkedDispatch:
    """ISSUE 9: the overlap planner's scan-carry placement chunks the MoE
    dispatch over the capacity dim (chunk c+1's gather+exchange prefetched
    while chunk c's expert FFN computes). The restructuring must be
    EXACT on the forward (same gather rows, same per-slot contractions)
    and tolerance-tight through the backward scan."""

    def _setup(self):
        from deepspeed_tpu.moe.layer import MoE
        from deepspeed_tpu.runtime import topology as topo_mod
        from deepspeed_tpu.runtime.topology import TopologyConfig

        topo_mod.reset()
        topo = topo_mod.initialize(TopologyConfig(expert=2, data=-1),
                                   force=True)
        moe = MoE(hidden_size=16, intermediate_size=32, num_experts=4,
                  top_k=2)
        params = moe.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16),
                              jnp.float32)
        return topo, moe, params, x

    def test_plan_chunks_forward_exactly(self, eight_devices, monkeypatch):
        """The chunked capacity dispatch computes the unchunked one's sums in
        another order: the balance loss exactly, every output within float32
        reassociation (5.7e-7 of the element at most on the CPU mesh, a few
        units in the last place; 2e-6 is the bound held here)."""
        from deepspeed_tpu.runtime import overlap_planner as op
        topo, moe, params, x = self._setup()
        assert op.plan_for("moe-dispatch").n_chunks > 1, \
            "committed map should drive a chunked plan"
        with topo.mesh:
            on, aux_on = jax.jit(lambda p, t: moe(p, t))(params, x)
        monkeypatch.setenv("DSTPU_OVERLAP_PLAN", "0")
        with topo.mesh:
            off, aux_off = jax.jit(lambda p, t: moe(p, t))(params, x)
        np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                                   rtol=2e-6, atol=0)
        np.testing.assert_array_equal(np.asarray(aux_on),
                                      np.asarray(aux_off))

    def test_plan_chunks_grads_match(self, eight_devices, monkeypatch):
        topo, moe, params, x = self._setup()

        def loss(p, t):
            out, aux = moe(p, t)
            return jnp.sum(out * out) + aux

        with topo.mesh:
            g_on = jax.jit(jax.grad(loss))(params, x)
        monkeypatch.setenv("DSTPU_OVERLAP_PLAN", "0")
        with topo.mesh:
            g_off = jax.jit(jax.grad(loss))(params, x)
        for a, b in zip(jax.tree.leaves(g_on), jax.tree.leaves(g_off)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-5)

    def test_top_k_beyond_two_pins_unchunked(self, eight_devices,
                                             monkeypatch):
        """The masked per-chunk combine reassociates a token's k weighted
        terms into chunk order — exact only for k <= 2. top_k=3 must pin
        nc=1 so plan-on stays BITWISE against the unchunked program."""
        from deepspeed_tpu.moe.layer import MoE
        from deepspeed_tpu.runtime import topology as topo_mod
        from deepspeed_tpu.runtime.topology import TopologyConfig

        topo_mod.reset()
        topo = topo_mod.initialize(TopologyConfig(expert=2, data=-1),
                                   force=True)
        moe = MoE(hidden_size=16, intermediate_size=32, num_experts=4,
                  top_k=3)
        params = moe.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16),
                              jnp.float32)
        with topo.mesh:
            on, aux_on = jax.jit(lambda p, t: moe(p, t))(params, x)
        monkeypatch.setenv("DSTPU_OVERLAP_PLAN", "0")
        with topo.mesh:
            off, aux_off = jax.jit(lambda p, t: moe(p, t))(params, x)
        np.testing.assert_array_equal(np.asarray(on), np.asarray(off))
        np.testing.assert_array_equal(np.asarray(aux_on),
                                      np.asarray(aux_off))

    def test_chunk_count_clamps_to_capacity_divisor(self, eight_devices,
                                                    monkeypatch):
        """A capacity the plan's chunk count does not divide must clamp,
        not crash: top_k=1 with a prime-ish capacity."""
        from deepspeed_tpu.moe.layer import MoE
        from deepspeed_tpu.runtime import topology as topo_mod
        from deepspeed_tpu.runtime.topology import TopologyConfig

        topo_mod.reset()
        topo = topo_mod.initialize(TopologyConfig(expert=2, data=-1),
                                   force=True)
        # tokens=20, e=4, k=1, cf=1.0 -> capacity 5 (odd)
        moe = MoE(hidden_size=16, intermediate_size=32, num_experts=4,
                  top_k=1, capacity_factor=1.0, min_capacity=5)
        params = moe.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 5, 16),
                              jnp.float32)
        with topo.mesh:
            out, _ = jax.jit(lambda p, t: moe(p, t))(params, x)
        assert np.all(np.isfinite(np.asarray(out)))
