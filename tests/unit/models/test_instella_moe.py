"""Instella-MoE's block on the CPU at the ``instella-tiny`` preset (the
tests' benchmark data): the program against the plain reference
(benchmark/reference/instella_moe.py) in float32 on seeded random weights,
for the loss, the gradient and the first step through ``initialize``; the
shares of a layer that several chips divide add up to the whole layer;
FarSkip off is the standard block; YaRN's band at the published keys; the
correction bias moves by load alone; a held range of everything is the
program it was."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from deepspeed_tpu.models import instella_moe_model, olmoe_model
from deepspeed_tpu.models.transformer import (MoEConfig, TransformerConfig,
                                              TransformerLM, YarnScaling)
from deepspeed_tpu.moe.layer import MoE, held_capacity
from deepspeed_tpu.moe.sharded_moe import bias_step, sigmoid_bias_router
from tests.benchmark.helpers import DATA

MANIFEST = os.path.join(DATA, "BENCHMARK.instella-tiny.json")
F32 = jnp.float32


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(MANIFEST, "instella-tiny.train")


@pytest.fixture(scope="module")
def parts(cell):
    """(reference module, adapter module, configuration, weights, ids)."""
    ref = cell.load_module("reference", cell.config["reference"])
    adapter = cell.load_module("adapters", cell.config["adapter"])
    w = ref.make_weights(ref.key_of(7), cell.config, F32)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cell.config["vocab_size"], (8, 48)), jnp.int32)
    return ref, adapter, cell.config, w, ids


def close(a, b, rel=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-12)


def test_loss_and_gradient_match_the_reference(parts):
    ref, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype="float32")
    # (each side ONE jitted program: op by op this is a thousand compiles)
    want, want_g = jax.jit(jax.value_and_grad(lambda p: ref.next_token_loss(p, ids, cfg)))(w)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, {"input_ids": ids})))(adapter.to_program(w))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat = adapter.from_program(got_g)
    assert set(flat) == set(w)
    for name, g in want_g.items():
        assert close(flat[name], g), name
    assert not np.asarray(flat["router_bias"]).any()     # stop_gradient: exactly 0
    # logits alone (no prediction module is run) agree too
    logits, _ = jax.jit(lambda p: model.apply(p, ids))(adapter.to_program(w))
    assert close(logits, jax.jit(lambda p: ref.forward(p, ids, cfg))(w), rel=1e-4)


def test_first_step_through_initialize(parts):
    """``initialize`` -> ``train_batch`` in float32: the step's loss and
    gradient norm are the reference's, every weight moves against the
    reference's gradient, and the correction bias moves by gamma against
    the load the reference counts, with no decay and no moment."""
    import deepspeed_tpu
    ref, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype="float32")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=adapter.to_program(w), config={
            "train_micro_batch_size_per_gpu": 1,
            "zero_optimization": {"stage": 1},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3, "weight_decay": 0.1}}})
    loss = float(engine.train_batch({"input_ids": np.asarray(ids)}))
    want, gnorm, signs = jax.jit(lambda p: ref.loss_and_gradient(p, ids, cfg))(w)
    assert loss == pytest.approx(float(want), rel=1e-5)
    assert float(engine.get_global_grad_norm()) == pytest.approx(float(gnorm), rel=1e-4)
    new = adapter.from_program(engine.state["opt"]["master"])
    wrong = total = 0
    for name, s in signs.items():
        s = np.asarray(s)
        moved = np.sign(np.asarray(new[name], np.float64) - np.asarray(w[name], np.float64))
        wrong += np.sum((moved + s != 0) & (s != 0))
        total += np.sum(s != 0)
    assert wrong / total < 2e-3
    # the bias: old + gamma x sign(mean load - load), exactly, on every layer
    load = np.asarray(ref.router_load(w, ids, cfg))
    gamma = cfg["assumed"]["bias_update_gamma"]
    want_bias = np.concatenate([np.asarray(w["router_bias"]), np.asarray(w["m_router_bias"])])
    want_bias = want_bias + gamma * np.sign(load.mean(-1, keepdims=True) - load)
    got_bias = np.concatenate([np.asarray(new["router_bias"]), np.asarray(new["m_router_bias"])])
    np.testing.assert_allclose(got_bias, want_bias, rtol=0, atol=1e-7)
    assert np.abs(got_bias - np.concatenate(
        [np.asarray(w["router_bias"]), np.asarray(w["m_router_bias"])])).max() == pytest.approx(
            gamma, rel=1e-3)
    for slot in ("exp_avg", "exp_avg_sq"):
        moments = adapter.from_program(engine.state["opt"][slot])
        assert not np.asarray(moments["router_bias"]).any()
        assert np.asarray(moments["router"]).any()
    # the weight the model reads is the master's
    np.testing.assert_array_equal(
        np.asarray(adapter.from_program(engine.state["params"])["router_bias"]),
        np.asarray(new["router_bias"]))
    # counters: [expert layers + the module's, experts held], a share of the rows
    rows = engine.moe_expert_rows()
    assert rows.shape == (cfg["num_hidden_layers"] - 1 + 1, 8)
    np.testing.assert_array_equal(rows, load[:, :8].astype(np.int32))
    totals = dict(engine.moe_totals)
    products = totals.pop("products_xla")
    # the rows back to the tokens: the buffer's, gathered once by the combine
    # forward, once where the backward reruns it, once by the dispatch's backward
    cap = held_capacity(8 * 48 * cfg["num_experts_per_tok"], 8, 16)
    assert totals == {"path": "dropless", "steps": 1,
                      "experts_published": 16, "experts_held": 8,
                      "router_input": "ffn_input", "activation": "silu_gated",
                      "grouped_matmul_route": "xla",
                      "products_kernel": dict.fromkeys(products, 0),
                      "combine_route": "xla",
                      "combine_rows_moved": rows.shape[0] * 3 * cap}
    # three products a layer by kind, the forward's once more where the
    # backward reruns the block and kept none of its names
    again = 0 if engine.remat_totals["saved"] or not engine.model.config.remat else 1
    assert products == {"forward": 3 * rows.shape[0] * (1 + again),
                        "row_gradient": 3 * rows.shape[0],
                        "weight_gradient": 3 * rows.shape[0]}
    assert (rows.sum(1) < 8 * 48 * cfg["num_experts_per_tok"]).all()


def test_the_shares_add_up_to_the_whole_layer(parts):
    """``chips_sharing_a_layer`` = 2: the two held ranges' routed parts
    (program, each on its own weight stacks) plus the shared expert counted
    once are the uncut reference's whole expert layer."""
    ref, _, cfg, _, _ = parts
    chips = cfg["share"]["chips_sharing_a_layer"]
    whole = {k: v for k, v in cfg.items() if k != "share"}
    whole["n_routed_experts"] = cfg["share"]["published"]["n_routed_experts"]
    s = ref.sizes(whole)
    w = ref.make_weights(ref.key_of(3), whole, F32)
    lw = {k: w[k][1] for k in ("router", "router_bias", "w_gate", "w_up", "w_down",
                               "s_gate", "s_up", "s_down")}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, s["H"]), F32)
    h = x.reshape(-1, s["H"])
    with jax.default_matmul_precision("highest"):
        weight, _, load = ref.route(h, lw["router"], lw["router_bias"], s, 24)
        want = (ref.held_experts(h, weight, lw, s)
                + ref.gated_mlp(h, lw["s_gate"], lw["s_up"], lw["s_down"]))
    assert s["Eh"] == s["E"] == 16 and int(load.sum()) == 48 * s["k"]
    held = s["E"] // chips
    total = ref.gated_mlp(h, lw["s_gate"], lw["s_up"], lw["s_down"])   # once
    for rank in range(chips):
        lo, hi = rank * held, (rank + 1) * held
        layer = MoE(s["H"], s["I"], num_experts=s["E"], top_k=s["k"], capacity_factor=None,
                    balance_loss="topk_share", router="sigmoid_bias",
                    routed_scale=s["scale"], experts_held=(lo, hi))
        params = {"gate": lw["router"], "bias": lw["router_bias"],
                  "wi_gate": lw["w_gate"][lo:hi], "wi_up": lw["w_up"][lo:hi],
                  "wo": lw["w_down"][lo:hi]}
        out, _, rows = layer.dropless_forward(params, x)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(load, np.int32))
        total = total + out.reshape(-1, s["H"])
    assert close(total, want, rel=1e-5)


def test_the_reference_given_a_share_leaves_the_absent_experts_out(parts):
    """The same weights under rank 0's and rank 1's share: the two partial
    expert sums add up to the uncut one."""
    ref, _, cfg, _, _ = parts
    whole = {k: v for k, v in cfg.items() if k != "share"}
    whole["n_routed_experts"] = 16
    w = ref.make_weights(ref.key_of(3), whole, F32)
    s = ref.sizes(whole)
    lw = {k: w[k][0] for k in ("router", "router_bias", "w_gate", "w_up", "w_down")}
    h = jax.random.normal(jax.random.PRNGKey(2), (32, s["H"]), F32)
    weight, _, _ = ref.route(h, lw["router"], lw["router_bias"], s, 32)
    parts_sum = 0
    for rank in (0, 1):
        shared = dict(cfg, assumed=dict(cfg["assumed"], share_rank=rank))
        sr = ref.sizes(shared)
        assert (sr["lo"], sr["Eh"], sr["E"]) == (8 * rank, 8, 16)
        mine = {k: (v[8 * rank:8 * rank + 8] if k.startswith("w_") else v)
                for k, v in lw.items()}
        parts_sum = parts_sum + ref.held_experts(h, weight, mine, sr)
    assert close(parts_sum, ref.held_experts(h, weight, lw, s), rel=1e-5)


def test_farskip_off_is_the_standard_block(parts):
    ref, adapter, cfg, w, ids = parts
    plain = dict(cfg, farskip=False)
    model = adapter.model(plain, remat=False, dtype="float32")
    assert not model.config.farskip
    loss_of = lambda m: jax.jit(lambda p: m.loss(p, {"input_ids": ids}))(adapter.to_program(w))
    got = loss_of(model)
    assert float(got) == pytest.approx(
        float(jax.jit(lambda p: ref.next_token_loss(p, ids, plain))(w)), rel=1e-5)
    with_flag = loss_of(adapter.model(cfg, remat=False, dtype="float32"))
    assert abs(float(with_flag) - float(got)) > 1e-6
    # and by hand on one block: x + attn(norm(x)), then + mlp(norm(that))
    block = jax.tree.map(lambda a: a[0], adapter.to_program(w)["dense_blocks"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, cfg["hidden_size"]), F32)
    positions = jnp.arange(16)[None]
    carry = (x, positions, model._aux_zero())
    (y, _, _), _ = model._block_fn(None, carry, (block, jnp.ones((), F32)))
    ln = lambda name, t: model._block_layers[name](block[name], t)
    mid = x + model._mixer(block, ln("ln_1", x), positions, None)[0]
    want = mid + model._mlp(block, ln("ln_2", mid))[0]
    assert close(y, want, rel=1e-6)


def test_yarn_band_at_the_published_keys(parts):
    ref = parts[0]
    yarn = YarnScaling(factor=40.0, original_max_position=4096, beta_fast=32.0,
                       beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
    assert yarn.band(32, 8e6) == (3, 7)
    assert ref.yarn_band(32, 8e6, {"original_max_position_embeddings": 4096,
                                   "beta_fast": 32, "beta_slow": 1}) == (3, 7)
    assert yarn.softmax_scale == pytest.approx(0.1 * np.log(40) + 1) == pytest.approx(1.3689, abs=1e-4)
    assert yarn.cos_sin_scale == 1.0
    f = np.asarray(yarn.frequencies(32, 8e6))
    plain = 8e6 ** (-2 * np.arange(16) / 32)
    np.testing.assert_allclose(f[:4], plain[:4], rtol=1e-6)          # short waves: as they are
    np.testing.assert_allclose(f[7:], plain[7:] / 40, rtol=1e-6)     # long ones: over the factor
    assert (f[4:7] < plain[4:7]).all() and (f[4:7] > plain[4:7] / 40).all()
    model = instella_moe_model("instella-moe-16b-a3b", num_layers=2, vocab_size=64)
    assert model.config.rope_scaling == yarn and model.config.head_dim == 128


def test_bias_step_and_router():
    bias = jnp.zeros((2, 4))
    load = jnp.asarray([[4, 0, 2, 2], [1, 1, 1, 1]])
    np.testing.assert_allclose(np.asarray(bias_step(bias, load, 0.5)),
                               [[-0.5, 0.5, 0, 0], [0, 0, 0, 0]])
    # the bias decides the choice and stays out of the weight
    logits = jnp.asarray([[0.0, 0.1, 0.2, 0.3]] * 6)
    idx, weight, losses, rows = sigmoid_bias_router(
        logits, jnp.asarray([1.0, 0, 0, 0]), 2, normalize=True, routed_scale=2.5,
        rows_per_seq=3)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 3]
    s = jax.nn.sigmoid(jnp.asarray([0.0, 0.3]))
    np.testing.assert_allclose(sorted(np.asarray(weight[0])), np.asarray(s / s.sum() * 2.5),
                               rtol=1e-6)
    assert np.asarray(rows).tolist() == [6, 0, 0, 6] and float(losses[1]) == 0.0
    # f_e = 4 / (2 x 3) x 3 = 2 for the two chosen, P their normalised mean score
    p = np.asarray(jax.nn.sigmoid(logits[0]) / jnp.sum(jax.nn.sigmoid(logits[0])))
    assert float(losses[0]) == pytest.approx(2 * (p[0] + p[3]), rel=1e-6)


def test_held_range_of_everything_is_the_program_it_was():
    """``experts_held`` = all of them: OLMoE's tiny preset to the digit,
    loss and every gradient."""
    base = olmoe_model("olmoe-tiny", dtype=F32)
    moe = dataclasses.replace(base.config.moe, experts_held=(0, 8))
    told = TransformerLM(dataclasses.replace(base.config, moe=moe))
    params = base.init(jax.random.PRNGKey(0))
    batch = {"input_ids": jnp.asarray(
        np.random.default_rng(0).integers(0, 512, (2, 32)), jnp.int32)}
    a, ga = jax.jit(jax.value_and_grad(base.loss))(params, batch)
    b, gb = jax.jit(jax.value_and_grad(told.loss))(params, batch)
    assert float(a) == float(b)
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(ga), jax.tree.leaves(gb)))
    import re
    text = lambda m: re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(m.loss)(params, batch)))
    assert text(base) == text(told)


@pytest.mark.parametrize("bias,short", [(0.0, False), (6.0, True)])
def test_a_share_never_drops_a_row(bias, short):
    """2 of 16 experts held, 2048 tokens: the buffer is four times the even
    share, multiplied whole; with the router pushed onto the held experts they draw more rows
    than it has, and the layer computes the same sum, the rest without it."""
    E, k, h, f, lo, hi = 16, 3, 32, 16, 0, 2
    layer = MoE(h, f, num_experts=E, top_k=k, capacity_factor=None,
                balance_loss="topk_share", router="sigmoid_bias", routed_scale=2.5,
                experts_held=(lo, hi))
    params = jax.tree.map(lambda a: a * 20, layer.init(jax.random.PRNGKey(0)))
    params["bias"] = jnp.zeros((E,)).at[lo:hi].set(bias)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1024, h))
    cap = held_capacity(2048 * k, hi - lo, E)
    assert cap == 2560 < 2048 * k

    def dense(p, x):
        t = x.reshape(-1, h)
        idx, w, _, _ = sigmoid_bias_router(t @ p["gate"], p["bias"], k, normalize=True,
                                           routed_scale=2.5, rows_per_seq=1024)
        out = 0
        for e in range(lo, hi):
            we = jnp.sum(jnp.where(idx == e, w, 0), axis=1)
            y = (jax.nn.silu(t @ p["wi_gate"][e - lo]) * (t @ p["wi_up"][e - lo])) @ p["wo"][e - lo]
            out = out + y * we[:, None]
        return jnp.sum(jnp.sin(out))

    loss = lambda p, x: jnp.sum(jnp.sin(layer.dropless_forward(p, x)[0]))
    rows = jax.jit(layer.dropless_forward)(params, x)[2]
    assert (int(rows[lo:hi].sum()) > cap) is short
    got, g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
    want, gd = jax.value_and_grad(dense, argnums=(0, 1))(params, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert close(g[1], gd[1], rel=1e-4)
    assert all(close(g[0][n], gd[0][n], rel=1e-4) for n in ("gate", "wi_gate", "wi_up", "wo"))
    # and under a block's rematerialisation, the loop's backward as well
    again = jax.jit(jax.grad(jax.checkpoint(loss), argnums=1))(params, x)
    assert close(again, g[1], rel=1e-6)
    # held_capacity: a quarter or more of the experts held needs no second path
    assert held_capacity(6144, 6, 16) == 6144 and held_capacity(98304, 8, 64) == 36864


# The sigmoid router's formulas until PR 53, kept as the plain reference of
# what replaced them in PR 54 (tests/unit/moe/test_dropless.py has the
# softmax router's and the sort's): the chosen scores by a gather of scalars,
# a sequence's counts by a scatter-add.

def sigmoid_router_as_it_was(logits, bias, top_k, *, normalize, routed_scale, rows_per_seq):
    tokens, num_experts = logits.shape
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    biased = s + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, expert_idx = jax.lax.top_k(biased, top_k)
    weight = jnp.take_along_axis(s, expert_idx, axis=-1)
    if normalize:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * routed_scale
    seqs = tokens // rows_per_seq
    seq_of = jnp.repeat(jnp.arange(seqs, dtype=jnp.int32), rows_per_seq * top_k)
    chose = jnp.zeros((seqs, num_experts), jnp.int32).at[
        seq_of, expert_idx.reshape(-1)].add(1)
    f = chose.astype(jnp.float32) * (num_experts / (top_k * rows_per_seq))
    p = jnp.mean((s / jnp.sum(s, axis=-1, keepdims=True))
                 .reshape(seqs, rows_per_seq, num_experts), axis=1)
    balance = jnp.mean(jnp.sum(f * p, axis=-1))
    return (expert_idx.astype(jnp.int32), weight,
            jnp.stack([balance, jnp.zeros((), jnp.float32)]),
            jnp.sum(chose, axis=0))


@pytest.mark.parametrize("rows_per_seq", [96, 48], ids=["one-sequence", "two-sequences"])
@pytest.mark.parametrize("normalize", [False, True], ids=["as-is", "renormalised"])
@pytest.mark.parametrize("top_k", [1, 6, 8])
def test_the_sigmoid_router_is_the_integers_and_the_bits_it_was(top_k, normalize, rows_per_seq):
    """Tied logits, a bias that breaks some of the ties, an expert that
    draws nothing: picks and counts as integers; weights, losses and both
    gradients by the logits to the bit."""
    from tests.unit.moe.test_dropless import routed, same_bits, tied_logits
    logits = tied_logits(96, 16, seed=top_k)
    bias = jnp.asarray(np.random.default_rng(1).choice([0.0, 0.05], 16), jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(3), (96, top_k))
    kw = dict(top_k=top_k, normalize=normalize, routed_scale=2.5, rows_per_seq=rows_per_seq)
    got = jax.jit(lambda l, c: routed(
        lambda x, **k: sigmoid_bias_router(x, bias, **k), l, c, **kw))(logits, ct)
    want = jax.jit(lambda l, c: routed(
        lambda x, **k: sigmoid_router_as_it_was(x, bias, **k), l, c, **kw))(logits, ct)
    same_bits(got, want)
    rows = np.asarray(got[0][3])
    assert rows[-1] == 0 and rows.sum() == 96 * top_k
    assert float(jnp.abs(got[1]).max()) > 0 and float(jnp.abs(got[2]).max()) > 0


@pytest.mark.parametrize("held,bias", [(None, 0.0), ((0, 2), 0.0), ((0, 2), 6.0)],
                         ids=["every-expert", "a-share", "a-share-over-its-buffer"])
def test_the_sigmoid_layer_is_the_program_it_was(held, bias, monkeypatch):
    """Both paths under the sigmoid router, two sequences a batch, the last
    case with more rows for the held experts than the buffer has: output,
    losses and rows to the bit, the gradients to float32's rounding (as
    ``test_dropless.py::test_the_layer_is_the_program_it_was`` says why)."""
    from deepspeed_tpu.moe import layer as L
    from tests.unit.moe.test_dropless import (close as near, layer_and_gradients, same_bits,
                                              sorted_as_it_was)
    E, k, h, f = 16, 3, 32, 16
    moe = MoE(h, f, num_experts=E, top_k=k, capacity_factor=None, balance_loss="topk_share",
              router="sigmoid_bias", routed_scale=2.5, experts_held=held)
    params = jax.tree.map(lambda a: a * 20, moe.init(jax.random.PRNGKey(0)))
    params["bias"] = jnp.zeros((E,)).at[0:2].set(bias)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1024, h))
    (got, got_aux), got_g = layer_and_gradients(moe, params, x)
    monkeypatch.setattr(L, "sigmoid_bias_router", sigmoid_router_as_it_was)
    monkeypatch.setattr(L, "_sorted_by", sorted_as_it_was)
    (want, want_aux), want_g = layer_and_gradients(moe, params, x)
    same_bits((got, got_aux), (want, want_aux))
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        near(a, b)
    assert (int(got_aux[2][0:2].sum()) > held_capacity(2048 * k, 2, E)) is (bias > 0)


def test_what_the_new_fields_refuse():
    moe = MoEConfig(num_experts=8, top_k=2, capacity_factor=None, router="sigmoid_bias")
    with pytest.raises(ValueError, match="sigmoid_bias"):
        MoEConfig(seq_balance_coef=1e-4)
    with pytest.raises(ValueError, match="no-drop"):
        MoE(8, 8, router="sigmoid_bias")
    with pytest.raises(ValueError, match="no range"):
        MoE(8, 8, capacity_factor=None, experts_held=(4, 12))
    with pytest.raises(ValueError, match="latent attention needs"):
        TransformerLM(TransformerConfig(attention="latent", position="rope", norm="rmsnorm"))
    with pytest.raises(ValueError, match="first_dense_layers"):
        TransformerLM(TransformerConfig(first_dense_layers=1))
    with pytest.raises(NotImplementedError, match="one multi-token"):
        TransformerLM(TransformerConfig(mtp_layers=2))
    model = instella_moe_model("instella-tiny", dtype=F32)
    assert model.config.moe.router == moe.router and model.has_router_bias
    x = jnp.zeros((1, 8, 64))
    # (shapes alone: the refusal comes before a value is read)
    block = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a[0], model.init(jax.random.PRNGKey(0))["blocks"]))
    with pytest.raises(NotImplementedError, match="one block at a time"):
        jax.eval_shape(lambda b, x: model.block_apply(b, x, jnp.arange(8)[None]), block, x)
    from deepspeed_tpu.models.registry import get_architecture
    spec = get_architecture("deepseek_v3")
    # (a compressed query is read since PR 55: ``q_latent_rank``)
    with pytest.raises(NotImplementedError, match="hidden_act"):
        spec.config_fn({"hidden_act": "gelu", "num_attention_heads": 4})
    with pytest.raises(ValueError, match="q_latent_rank"):
        TransformerLM(TransformerConfig(q_latent_rank=16))
    with pytest.raises(NotImplementedError, match="checkpoint"):
        spec.params_fn(None, {})


@pytest.mark.parametrize("preset", ["instella-tiny", "afmoe-tiny", "olmoe-tiny", "sdar-tiny"])
def test_the_rows_back_to_the_tokens_are_counted_from_static_shapes(preset):
    """``moe_totals["combine_route"]`` / ``["combine_rows_moved"]`` of a traced
    step: for a share (the Instella, Trinity and SDAR presets hold 8 of 16
    experts; under SDAR's block-diffusion objective a token is two rows) the
    route ``pallas_segment_sum.choose_route`` gives the sum's
    static shape and the buffer's rows a pass of it, with remat three passes
    a layer; with every expert held (OLMoE) None and no rows."""
    import deepspeed_tpu
    from deepspeed_tpu.ops.transformer import pallas_segment_sum
    cell = harness.Cell(os.path.join(DATA, f"BENCHMARK.{preset}.json"), f"{preset}.train")
    adapter = cell.load_module("adapters", cell.config["adapter"])
    model = adapter.model(cell.config, remat=True, dtype="float32")
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    assert engine.moe_totals["combine_route"] is None
    assert engine.moe_totals["combine_rows_moved"] == 0
    ids = np.random.default_rng(0).integers(0, cell.config["vocab_size"] - 1, (8, 32))
    engine.train_batch({"input_ids": ids})
    moe, layers = model._moe, engine.moe_expert_rows().shape[0]
    n = 8 * 32 * model.rows_per_token
    back = moe.rows_back(n)
    if preset == "olmoe-tiny":
        assert back is None and moe.experts_held is None
        assert engine.moe_totals["combine_route"] is None
        assert engine.moe_totals["combine_rows_moved"] == 0
        return
    rows, tokens, h = back
    assert (rows, tokens, h) == (held_capacity(n * moe.top_k, 8, 16), n,
                                 cell.config["hidden_size"])
    assert rows <= n * moe.top_k         # at most the slabs' tokens x k (512s: all, here)
    assert engine.moe_totals["combine_route"] == pallas_segment_sum.choose_route(
        rows, tokens, h, jnp.float32, "cpu", engine.mesh.size) == "xla"
    assert engine.moe_totals["combine_rows_moved"] == layers * 3 * rows
