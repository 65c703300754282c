"""Xing4.0's block on the CPU at the ``xing4-tiny`` preset (the tests' benchmark
data: hidden 64, four streams, 2 heads of 24 + 8 with values of 16, a query
rank of 16, 8 held experts of 16, 2 dense + 4 expert layers + the module): the
program against the plain reference (benchmark/reference/xing4.py) in float32 on
seeded random weights for the loss, every gradient leaf and the first step
through ``initialize``; and the share test: the 8 chips' routed parts and the
shared expert once add up to the uncut layer."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from deepspeed_tpu.moe.layer import MoE
from tests.benchmark.helpers import DATA

MANIFEST = os.path.join(DATA, "BENCHMARK.xing4-tiny.json")
F32 = jnp.float32


@pytest.fixture(scope="module")
def parts():
    """(reference module, adapter module, configuration, weights, ids)."""
    cell = harness.Cell(MANIFEST, "xing4-tiny.train")
    ref = cell.load_module("reference", cell.config["reference"])
    adapter = cell.load_module("adapters", cell.config["adapter"])
    w = ref.make_weights(ref.key_of(7), cell.config, F32)
    ids = np.random.default_rng(0).integers(0, cell.config["vocab_size"] - 1, (8, 32))
    ids[0, 11] = ids[1, 20] = cell.config["assumed"]["separator"]   # packed documents
    return ref, adapter, cell.config, w, jnp.asarray(ids, jnp.int32)


@pytest.fixture(scope="module")
def wanted(parts):
    """The reference's (loss, gradient) on the fixture's weights and ids."""
    ref, _, cfg, w, ids = parts
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(lambda p: ref.next_token_loss(p, ids, cfg)))(w)


def close(a, b, rel=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-12)


def test_first_step_through_initialize_leaf_by_leaf(parts, wanted):
    """``initialize`` -> ``train_batch`` in float32, ONE compile of the program:
    the step's loss and gradient norm are the reference's; every gradient leaf
    is (read back from Adam's first moment, (1 - beta1) x the gradient after one
    step); every weight moves against the reference's gradient; the engine's
    records say what ran. (A gradient under 1e-9 is rounding: the LAST
    sub-layer's H_res moves nothing, since a doubly stochastic matrix keeps the
    streams' sum, which is all the final norm reads.)"""
    import deepspeed_tpu
    ref, adapter, cfg, w, ids = parts
    want, want_g = wanted
    model = adapter.model(cfg, remat=True, dtype="float32")
    c = model.config
    assert (c.residual_streams, c.hc_sinkhorn_iters, c.q_latent_rank, c.head_dim,
            c.v_head_dim, c.first_dense_layers, c.mtp_layers, c.document_separator) == (
                4, cfg["hc_sinkhorn_iters"], 16, 32, 16, 2, 1, 255)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=adapter.to_program(w), config={
            "train_micro_batch_size_per_gpu": 1,
            "zero_optimization": {"stage": 1},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3, "weight_decay": 0.0,
                                                      "betas": [0.9, 0.999]}}})
    loss = float(engine.train_batch({"input_ids": np.asarray(ids)}))
    assert loss == pytest.approx(float(want), rel=2e-5)
    gnorm = np.sqrt(sum(float(jnp.sum(jnp.square(g))) for g in want_g.values()))
    assert float(engine.get_global_grad_norm()) == pytest.approx(gnorm, rel=2e-4)
    got_g = adapter.from_program(engine.state["opt"]["exp_avg"])
    assert set(got_g) == set(w)
    for name, g in want_g.items():
        assert close(np.asarray(got_g[name]) * 10.0, g, rel=5e-4), name
    assert not np.asarray(got_g["router_bias"]).any()     # stop_gradient: exactly 0
    for name in ("a_phi", "f_alpha", "d_a_b", "m_f_phi", "wqa", "q_norm", "d_wqb"):
        assert np.asarray(want_g[name]).any(), name
    last_res = np.asarray(want_g["m_f_phi"])[0, :, 8:]
    assert np.abs(last_res).max() < 1e-9 < np.abs(np.asarray(want_g["m_f_phi"])[0, :, :8]).max()
    new = adapter.from_program(engine.state["opt"]["master"])
    wrong = total = 0
    for name, g in want_g.items():
        s = np.where(np.abs(np.asarray(g)) > 1e-9, np.sign(np.asarray(g)), 0)
        moved = np.sign(np.asarray(new[name], np.float64) - np.asarray(w[name], np.float64))
        wrong += np.sum((moved + s != 0) & (s != 0))
        total += np.sum(s != 0)
    assert wrong / total < 2e-3
    assert engine.attn_totals["hc"] == {"streams": 4, "sublayers": 14,
                                        "sinkhorn_iters": cfg["hc_sinkhorn_iters"],
                                        "route": "xla", "tile_rows": None}
    assert engine.attn_totals["mla"] == {"qk_dim": 32, "v_dim": 16, "q_rank": 16,
                                         "kv_rank": 24, "route": "xla", "dq": None,
                                         "layout": None}
    last = engine.attn_last_step()
    assert 0 <= last["hc_res_row_err"] < 0.5 and "full" in last
    np.testing.assert_array_equal(
        engine.moe_expert_rows(),
        np.asarray(ref.router_load(w, ids, cfg))[:, :8].astype(np.int32))
    assert engine.moe_totals["experts_published"] == 16
    assert engine.remat_totals["policy"] is not None
    assert not {n for n in engine.remat_totals["saved"] if n.startswith("hc")}
    assert {"q_latent", "q_b_proj"} <= set(engine.remat_totals["saved"])


def test_the_eight_shares_add_up_to_the_whole_layer(parts):
    """``chips_sharing_a_layer`` = 8 over 64 published experts: the eight held
    ranges' routed parts (program, each on its own weight stacks) plus the
    shared expert counted once are the uncut reference's whole expert layer."""
    ref, _, cfg, _, _ = parts
    whole = {k: v for k, v in cfg.items() if k != "share"}
    whole["n_routed_experts"] = 64
    s = ref.sizes(whole)
    w = ref.make_weights(ref.key_of(3), whole, F32)
    lw = {k: w[k][1] for k in ("router", "router_bias", "w_gate", "w_up", "w_down",
                               "s_gate", "s_up", "s_down")}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, s["H"]), F32)
    h = x.reshape(-1, s["H"])
    with jax.default_matmul_precision("highest"):
        weight, _, load = ref.route(h, lw["router"], lw["router_bias"], s, 24)
        shared = ref.gated_mlp(h, lw["s_gate"], lw["s_up"], lw["s_down"])
        want = ref.held_experts(h, weight, lw, s) + shared
        assert s["Eh"] == s["E"] == 64 and int(load.sum()) == 48 * s["k"]
        total = shared                                               # once
        for rank in range(8):
            lo, hi = rank * 8, (rank + 1) * 8
            layer = MoE(s["H"], s["I"], num_experts=64, top_k=s["k"], capacity_factor=None,
                        balance_loss="topk_share", router="sigmoid_bias",
                        routed_scale=s["scale"], experts_held=(lo, hi))
            params = {"gate": lw["router"], "bias": lw["router_bias"],
                      "wi_gate": lw["w_gate"][lo:hi], "wi_up": lw["w_up"][lo:hi],
                      "wo": lw["w_down"][lo:hi]}
            out, _, rows = layer.dropless_forward(params, x)
            np.testing.assert_array_equal(np.asarray(rows), np.asarray(load, np.int32))
            total = total + out.reshape(-1, s["H"])
            # the reference given the same share leaves the same experts out
            sr = ref.sizes(dict(cfg, share=dict(cfg["share"], published=dict(
                cfg["share"]["published"], n_routed_experts=64)),
                assumed=dict(cfg["assumed"], share_rank=rank)))
            assert (sr["lo"], sr["Eh"], sr["E"]) == (lo, 8, 64)
            mine = {k: (v[lo:hi] if k.startswith("w_") else v) for k, v in lw.items()}
            assert close(ref.held_experts(h, weight, mine, sr), out.reshape(-1, s["H"]), rel=1e-4)
    assert close(total, want, rel=1e-5)


def test_the_presets_and_the_published_keys():
    from deepspeed_tpu.models import xing4_config
    c = xing4_config()
    assert (c.hidden_size, c.num_layers, c.num_heads, c.head_dim, c.v_head_dim,
            c.q_latent_rank, c.kv_latent_rank, c.qk_nope_dim, c.qk_rope_dim) == (
                3584, 40, 32, 192, 128, 768, 512, 128, 64)
    assert (c.residual_streams, c.hc_sinkhorn_iters, c.hc_eps, c.hc_res_clamp) == (
        4, 20, 1e-6, (-30.0, 30.0))
    assert (c.first_dense_layers, c.dense_intermediate_size, c.ffn_size, c.mtp_layers,
            c.moe.num_experts, c.moe.top_k, c.moe.shared_width, c.moe.routed_scale) == (
                2, 9216, 1024, 1, 64, 4, 1024, 2.0)
    assert c.rope_scaling.factor == 64 and c.rope_style == "interleaved"
    # scores' scale: 192^-0.5 x (0.1 ln 64 + 1)^2
    assert c.rope_scaling.softmax_scale == pytest.approx(0.1 * np.log(64) + 1)
