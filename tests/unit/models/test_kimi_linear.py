"""Kimi Linear's stack on the CPU at the ``kimi-linear-tiny`` preset (the tests'
benchmark data: hidden 64, 2 KDA heads of 16 x 16 in chunks of 16, 4 latent heads
of 12 + 4 with values of 8, 16 experts of which this chip holds 8, five layers: a
dense KDA layer, then KDA, KDA, latent attention without positions, KDA with
experts; a vocabulary of 512): the program against the plain reference
(benchmark/reference/kimi_linear.py, whose recurrence runs a token at a time) in
float32 on seeded random weights for the loss, every gradient leaf and the first
step through ``initialize``, and in bfloat16 at a tolerance the fp8 control fails;
the shares' expert outputs add up to the uncut layer; a document's loss and
gradients unchanged by what is packed in front of it; a long row's slices are the
whole; the lists' kinds and runs at 5 and at 27 layers and the parameter counts;
and what refuses the stack by name."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from deepspeed_tpu.models import (kimi_linear_config, kimi_linear_model, mixers, phi4flash_model,
                                  transformer)
from deepspeed_tpu.models.kimi_linear import _FLAGS, _PRESETS, config_kwargs
from tests.benchmark.helpers import DATA

MANIFEST = os.path.join(DATA, "BENCHMARK.kimi-linear-tiny.json")
F32 = jnp.float32
SEP = 511


@pytest.fixture(scope="module")
def parts():
    """(reference module, adapter module, configuration, weights, ids): eight
    rows of 64 tokens (a row a device of the tests' mesh: four chunks of 16),
    three documents in the first (a border inside a chunk and a sub-block, one on
    a chunk's last row) and two in the second."""
    cell = harness.Cell(MANIFEST, "kimi-linear-tiny.train")
    ref = cell.load_module("reference", cell.config["reference"])
    adapter = cell.load_module("adapters", cell.config["adapter"])
    w = ref.make_weights(ref.key_of(7), cell.config, F32)
    ids = np.random.default_rng(0).integers(0, SEP, (8, 64))
    ids[0, 20] = ids[0, 47] = ids[1, 7] = SEP
    return ref, adapter, cell.config, w, jnp.asarray(ids, jnp.int32)


@pytest.fixture(scope="module")
def wanted(parts):
    ref, _, cfg, w, ids = parts
    return jax.jit(jax.value_and_grad(lambda p: ref.next_token_loss(p, ids, cfg)))(w)


def close(a, b, rel=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-12)


def cosines(got, want):
    """Each leaf's gradient against the reference's, as the cosine between them."""
    out = {}
    for name, g in want.items():
        a, b = np.asarray(got[name], np.float64).ravel(), np.asarray(g, np.float64).ravel()
        if np.abs(b).max() > 0:
            out[name] = float(a @ b / np.sqrt((a @ a) * (b @ b) + 1e-300))
    return out


def test_first_step_through_initialize_leaf_by_leaf(parts, wanted):
    """``initialize`` -> ``train_batch`` in float32, ONE compile of the program:
    the step's loss and gradient norm are the reference's; every gradient leaf is
    (read back from Adam's first moment, (1 - beta1) x the gradient after one
    step), the latent layer's without positions among them; the router's bias has
    no gradient and moved by the load alone; the engine's records say what ran."""
    import deepspeed_tpu
    ref, adapter, cfg, w, ids = parts
    want, want_g = wanted
    model = adapter.model(cfg, remat=True, dtype="float32")
    c = model.config
    assert (c.kda_heads, c.kda_head_dim, c.kda_inner, c.kda_conv,
            c.position, c.attention, c.document_separator, c.first_dense_layers) == (
                2, 16, 32, 4, "none", "latent", SEP, 1)
    assert (c.moe.num_experts, c.moe.experts_held, c.moe.top_k, c.moe.router,
            c.moe.routed_scale, c.moe.shared_width) == (16, (0, 8), 3, "sigmoid_bias", 2.446, 16)
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
    for name in ("r0.wq", "r0.conv_k", "r0.w_fa", "r0.w_fb", "r0.dt_b", "r0.A_log", "r0.w_beta",
                 "r0.w_ga", "r0.norm_o", "r1.e_gate", "r1.router", "r1.s_down", "r2.wq",
                 "r2.wkva", "r2.wkvb", "r2.kv_norm", "r3.wo", "r3.e_down", "embed", "head"):
        assert np.abs(np.asarray(want_g[name])).max() > 1e-9, name
    # the bias: no gradient, no moment; moved by bias_update against the load
    new = adapter.from_program(engine.state["opt"]["master"])
    for name in ("r1.router_bias", "r2.router_bias", "r3.router_bias"):
        assert float(jnp.abs(want_g[name]).max()) == float(jnp.abs(got_g[name]).max()) == 0.0
        moved = np.asarray(new[name], np.float64) - np.asarray(w[name], np.float64)
        assert np.allclose(np.abs(moved[moved != 0]), 1e-3, rtol=1e-3) and (moved != 0).any()
    assert engine.attn_totals["kda"] == {
        "heads": 2, "key_dim": 16, "value_dim": 16, "conv": 4, "gate_rank": 16,
        "layers": [0, 1, 2, 4], "route": "xla", "chunk": 16, "tile": None}
    assert engine.attn_totals["mla"]["qk_dim"] == 16 and engine.attn_totals["mla"]["v_dim"] == 8
    assert (engine.attn_totals["layers_window"], engine.attn_totals["layers_full"]) == (0, 1)
    assert engine.moe_totals["experts_published"] == 16 and engine.moe_totals["experts_held"] == 8
    assert engine.moe_expert_rows().shape == (4, 8)        # four expert layers, the held


def test_bfloat16_is_the_reference_where_the_fp8_control_is_not(parts, wanted):
    """The program in bfloat16 (weights, activations; the core's float32 inside)
    against the float32 reference: a loss within 2.5e-4 (read 1.1e-4), every leaf's
    gradient at a cosine of 0.95 at least (the routers read 0.978, the least) and
    0.99 on the mean (read 0.998); the reference with every matmul operand rounded
    to float8 fails all three (3.9e-4, 0.894, 0.965)."""
    ref, adapter, cfg, w, ids = parts
    want, want_g = wanted
    model = adapter.model(cfg, remat=True, dtype="bfloat16")
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), adapter.to_program(w))
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, {"input_ids": ids})))(params)
    low, low_g = jax.jit(jax.value_and_grad(
        lambda p: ref.next_token_loss(p, ids, cfg, control="fp8")))(w)
    sound = cosines(adapter.from_program(got_g), want_g)
    assert abs(float(got) - float(want)) < 2.5e-4
    assert min(sound.values()) > 0.95 and np.mean(list(sound.values())) > 0.99
    control = cosines(low_g, want_g)
    assert abs(float(low) - float(want)) > 2.5e-4
    assert min(control.values()) < 0.95 and np.mean(list(control.values())) < 0.99


def test_the_shares_expert_outputs_add_up_to_the_uncut_layer(parts):
    """The share tied to the model: two chips hold experts 0-7 and 8-15 of a router
    of 16. Each chip's expert layer (the PROGRAM's, on its held experts, what the
    absent ones would add left out) summed, the shared expert counted once, is the
    uncut reference's whole layer on the same rows."""
    ref, adapter, cfg, _, _ = parts
    uncut = {**cfg, "num_experts": 16, "share": None}
    w = ref.make_weights(ref.key_of(11), uncut, F32)
    lw = {name[3:]: v[0] for name, v in w.items() if name.startswith("r1.")}
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 64), F32)
    with jax.default_matmul_precision("highest"):
        whole = ref.ffn(h.reshape(128, 64), lw, "experts", ref.sizes(uncut))
        shared = ref.gated_mlp(h.reshape(128, 64), lw["s_gate"], lw["s_up"], lw["s_down"])
    total = 0.0
    for rank in (0, 1):
        mine = {**cfg, "assumed": {**cfg["assumed"], "share_rank": rank}}
        model = adapter.model(mine, remat=False, dtype="float32")
        assert model.config.moe.experts_held == (8 * rank, 8 * rank + 8)
        held = slice(8 * rank, 8 * rank + 8)
        moe = {"gate": lw["router"], "bias": lw["router_bias"], "wi_gate": lw["e_gate"][held],
               "wi_up": lw["e_up"][held], "wo": lw["e_down"][held],
               "shared": {"gate_proj": lw["s_gate"], "up_proj": lw["s_up"],
                          "down_proj": lw["s_down"]}}
        out, _, rows = jax.jit(lambda moe, h, model=model: model._mlp({"moe": moe}, h))(moe, h)
        assert rows.shape == (16,)
        total = total + out.reshape(128, 64)
        # the reference, given the same share, is that chip's layer
        theirs = ref.ffn(h.reshape(128, 64), {**lw, "e_gate": lw["e_gate"][held],
                                              "e_up": lw["e_up"][held],
                                              "e_down": lw["e_down"][held]},
                         "experts", ref.sizes(mine))
        np.testing.assert_allclose(out.reshape(128, 64), theirs, atol=2e-6)
    np.testing.assert_allclose(total - shared, whole, atol=3e-6)
    assert float(jnp.abs(whole - shared).max()) > 1e-3      # (the routed part is not nothing)


def test_a_document_does_not_see_what_is_packed_in_front_of_it(parts):
    """The second row's last document (positions 8-63) alone in a row, and behind
    other documents: the same logits there and the same gradient from a loss over
    them (latent attention, KDA's state and the convolutions' taps all cut)."""
    _, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=False, dtype="float32")
    params = adapter.to_program(w)
    tail = ids[1:2, 8:]
    alone = jnp.concatenate([tail, jnp.full((1, 8), SEP, jnp.int32)], axis=1)
    other = jnp.concatenate([ids[0:1, 30:37], jnp.full((1, 1), SEP, jnp.int32), tail], axis=1)
    pick = jax.random.normal(jax.random.PRNGKey(3), (56, 512))

    def probe(p, row, at):
        return jnp.sum(jax.lax.dynamic_slice_in_dim(model.apply(p, row)[0][0], at, 56) * pick)

    both = jax.jit(jax.value_and_grad(probe))          # one program for every row
    a, ga = both(params, alone, 0)
    b, gb = both(params, other, 8)
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        assert close(x, y, rel=2e-4)
    assert float(both(params, other.at[0, 3].set(11), 8)[0]) == pytest.approx(float(b), rel=1e-5)
    assert float(both(params, other.at[0, 9].set(11), 8)[0]) != pytest.approx(float(b), rel=1e-5)


def test_a_long_rows_slices_are_the_whole(parts, monkeypatch):
    """A row too long for a KDA layer's convolutions, gates and gated norm at once
    takes them a slice of the row at a time (a slice reads the taps' rows before
    it, inside its document): the same loss and gradients, and `kda_row_slices`
    leaves every row the benchmark had before whole."""
    assert mixers.kda_row_slices(64, 2 * 32) == 1
    assert mixers.kda_row_slices(16384, 4096) == 4 and mixers.kda_row_slices(8192, 4096) == 1
    assert mixers.kda_row_slices(32768, 4096) == 8
    _, adapter, cfg, w, ids = parts
    params = adapter.to_program(w)

    def run():
        model = adapter.model(cfg, remat=True, dtype="float32")
        return jax.jit(jax.value_and_grad(
            lambda p: model.loss(p, {"input_ids": ids[:2]})))(params)

    whole, whole_g = run()
    monkeypatch.setattr(mixers, "KDA_WHOLE_ELEMENTS", 2 ** 9)
    monkeypatch.setattr(mixers, "KDA_SLICE_ELEMENTS", 2 ** 9)
    assert mixers.kda_row_slices(64, 2 * 32) == 8
    sliced, sliced_g = run()
    assert float(sliced) == pytest.approx(float(whole), rel=1e-6)
    for a, b in zip(jax.tree.leaves(sliced_g), jax.tree.leaves(whole_g)):
        assert close(a, b, rel=1e-4)


def test_every_lone_block_takes_its_two_cotangents_together(parts, monkeypatch):
    """A block that runs by itself (a run of one) passes `_taken_together`, whatever
    its mixer or MLP: the dense KDA layer, the latent layer and the last KDA layer
    here; the scanned pair does not."""
    _, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype="float32")
    fenced = []
    plain = transformer._taken_together
    monkeypatch.setattr(transformer, "_taken_together",
                        lambda x, layer: fenced.append(x.shape) or plain(x, layer))
    jax.eval_shape(lambda p: model.loss(p, {"input_ids": ids[:1]}), adapter.to_program(w))
    assert [(len(unit), repeats) for unit, repeats in model.run_plan] == [(1, 1), (1, 2), (2, 1)]
    assert len(fenced) == 3


def kinds_of(model):
    return [mixer for mixer, _ in model._mixer_kinds]


def test_the_lists_at_5_and_at_27_layers():
    tiny = kimi_linear_model("kimi-linear-tiny", dtype=F32, experts_held=(0, 8))
    assert kinds_of(tiny) == ["kda", "kda", "kda", "latent", "kda"]
    assert [tuple((k[2], k[4]) for k in unit) + (n,) for unit, n in tiny.run_plan] == [
        (("kda", "dense"), 1), (("kda", "experts"), 2),
        (("latent", "experts"), ("kda", "experts"), 1)]
    c = kimi_linear_config()
    assert (c.hidden_size, c.num_layers, c.num_heads, c.head_dim, c.v_head_dim, c.kv_latent_rank,
            c.q_latent_rank, c.ffn_size, c.dense_intermediate_size, c.first_dense_layers,
            c.kda_heads, c.kda_head_dim, c.kda_inner, c.kda_conv, c.vocab_size,
            c.max_seq_len, c.norm_eps, c.position, c.tie_embeddings) == (
                2304, 27, 32, 192, 128, 512, 0, 1024, 9216, 1, 32, 128, 4096, 4, 163840,
                1048576, 1e-5, "none", False)
    assert (c.moe.num_experts, c.moe.top_k, c.moe.routed_scale, c.moe.shared_width,
            c.moe.normalize_weights, c.moe.router, c.moe.bias_update) == (
                256, 8, 2.446, 1024, True, "sigmoid_bias", 1e-3)
    # the published 48 B: 26 expert layers of 256 x 7.08 M and the rest
    assert abs(c.num_parameters() - 49.1e9) < 0.1e9
    full = transformer.TransformerLM(c)
    assert kinds_of(full) == [
        "latent" if l in (4, 8, 12, 16, 20, 24, 27) else "kda" for l in range(1, 28)]
    mixer = full._mixers
    assert mixer["kda"].parameters() == 39_514_272 and mixer["latent"].parameters() == 29_114_880
    # the cell's share: the lists' first five entries, 16 held, an eighth of the rows
    cell = kimi_linear_config(layers=5, experts_held=(0, 16), vocab_size=20480)
    assert cell.num_parameters() == 828_926_848
    assert kinds_of(transformer.TransformerLM(cell)) == ["kda", "kda", "kda", "latent", "kda"]
    params = jax.eval_shape(lambda: tiny.init(jax.random.PRNGKey(0)))
    assert sum(p.size for p in jax.tree.leaves(params)) == tiny.config.num_parameters()
    small = params["runs"]["1"]["0"]["kda"]
    assert (small["A_log"].shape, small["dt_bias"].shape, small["conv_q"].shape) == (
        (2, 2), (2, 32), (2, 4, 32))
    assert params["runs"]["1"]["0"]["moe"]["wi_gate"].shape == (2, 8, 64, 16)
    assert "moe" not in params["runs"]["0"]["0"] and "gate_proj" in params["runs"]["0"]["0"]


def test_what_does_not_run_the_stack_refuses_it_by_name():
    from deepspeed_tpu.inference.v2.model import RaggedInferenceModel
    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    model = kimi_linear_model("kimi-linear-tiny", dtype=F32)
    mechanisms = ("kda_heads", "layer_mixers", "attention='latent'", "first_dense_layers", "moe")
    assert set(mechanisms) <= set(model.mechanisms)
    for consumer in (lambda: PipelineModule(model.config, num_stages=1, num_microbatches=2),
                     lambda: RaggedInferenceModel(model, block_size=8, max_blocks_per_seq=1),
                     lambda: model.block_apply(None, None, None)):
        with pytest.raises(NotImplementedError) as refusal:
            consumer()
        for name in ("kda_heads", "layer_mixers"):
            assert name in str(refusal.value)


@pytest.mark.parametrize("keys,error,says", [
    (dict(indexer=transformer.IndexerConfig(heads=2, head_dim=4, topk=3)), ValueError,
     "indexer is not computed in a stack named by layer_mixers"),
    (dict(mtp_layers=1), ValueError, "mtp_layers is not computed"),
    (dict(farskip=True), ValueError, "farskip is not computed"),
    (dict(residual_streams=2), ValueError, "residual_streams is not computed"),
    (dict(qk_norm=True, qk_norm_per_head=True), ValueError, "qk_norm is not computed"),
    (dict(attn_gate=True), ValueError, "attn_gate is not computed"),
    (dict(seq_parallel="ring"), ValueError, "seq_parallel='ring' is not computed"),
    (dict(norm_style="sandwich"), ValueError, "sequential pre-norm"),
    (dict(differential_attention=True), ValueError, "ssm_period rule's stacks"),
    (dict(shared_from=0), ValueError, "ssm_period rule's stacks"),
    (dict(attention="mha"), ValueError, "all 'mha' or all 'latent'"),
    (dict(kda_heads=0), ValueError, "'kda' layers need kda_heads"),
    (dict(layer_mixers=("kda", "kda", "kda", "latent", "gmu")), ValueError,
     "each 'ssd' or 'mha', 'kda' or 'latent'"),
    (dict(layer_mixers=("latent",) * 5), ValueError, "leave layer_mixers None"),
    (dict(moe_layer_freq=2), NotImplementedError, "moe_layer_freq=1"),
    (dict(position="rope", rope_scaling=transformer.YarnScaling(4.0, 32)), None, None),
], ids=["indexer", "mtp", "farskip", "streams", "qk-norm", "attn-gate", "ring", "sandwich",
        "differential", "shared", "mha-beside-latent", "kda-without-heads", "unknown-kind",
        "attention-alone", "every-other-layer-experts", "latent-with-positions-stands"])
def test_what_a_listed_stack_still_cannot_have_is_refused_by_name(keys, error, says):
    """One cause a message, held where the model is built; a list may carry experts
    behind a leading dense layer and latent heads, with or without positions."""
    if error is None:       # (a rotary latent layer in a listed stack builds)
        assert kimi_linear_model("kimi-linear-tiny", dtype=F32, **keys).config.position == "rope"
        return
    with pytest.raises(error, match=says):
        kimi_linear_model("kimi-linear-tiny", dtype=F32, **keys)


def test_the_period_rules_stacks_still_refuse_experts_and_latent_heads():
    """What went for a LIST stays refused for the ``l % ssm_period`` rule's stacks."""
    moe = transformer.MoEConfig(num_experts=4, top_k=2, capacity_factor=None)
    with pytest.raises(ValueError, match="moe is not computed in a stack by the ssm_period"):
        phi4flash_model("phi4flash-tiny", moe=moe)
    with pytest.raises(ValueError, match="attention='latent' is not computed"):
        phi4flash_model("phi4flash-tiny", attention="latent", kv_latent_rank=8, qk_nope_dim=12,
                        qk_rope_dim=4, v_head_dim=16)


@pytest.mark.parametrize("key,value", [
    ("num_nextn_predict_layers", 1), ("mla_use_nope", False), ("q_lora_rank", 1536),
    ("rope_scaling", {"type": "yarn", "factor": 4}), ("num_expert_group", 8),
    ("tie_word_embeddings", True), ("moe_router_activation_func", "softmax"),
    ("moe_layer_freq", 2), ("num_key_value_heads", 8), ("hidden_act", "gelu"),
    ("num_hidden_layers", 28), ("attention_dropout", 0.1)])
def test_a_configuration_it_does_not_compute_is_refused_by_its_key(key, value):
    hf = {**_FLAGS, **_PRESETS["kimi-linear-48b-a3b"]}
    with pytest.raises(NotImplementedError, match=key):
        config_kwargs({**hf, key: value})


@pytest.mark.parametrize("lists", [
    {"kda_layers": [1, 2, 3, 4], "full_attn_layers": [4, 5]},       # a layer in both
    {"kda_layers": [1, 2, 3], "full_attn_layers": [5]}])            # one in neither
def test_a_layer_in_both_lists_or_in_neither_is_refused(lists):
    hf = {**_FLAGS, **_PRESETS["kimi-linear-tiny"]}
    linear = {**hf["linear_attn_config"], **lists}
    with pytest.raises(NotImplementedError, match="kda_layers / full_attn_layers"):
        config_kwargs({**hf, "linear_attn_config": linear})
    # a depth below the lists' length takes their entries up to it
    assert config_kwargs({**_FLAGS, **_PRESETS["kimi-linear-48b-a3b"],
                          "num_hidden_layers": 9})["layer_mixers"] == (
        "kda", "kda", "kda", "latent", "kda", "kda", "kda", "latent", "kda")
    with pytest.raises(NotImplementedError, match="linear_attn_config.gate_low_rank"):
        config_kwargs({**hf, "linear_attn_config": {**hf["linear_attn_config"],
                                                    "gate_low_rank": 64}})
