"""Granite 4.0-H's stack on the CPU at the ``granite-hybrid-tiny`` preset (the
tests' benchmark data: hidden 64, 4 query over 2 key heads of 16, 2 Mamba-2 heads
of 64 over 16 states in one group, chunks of 16, ten layers with the attention
layer at index 5, a vocabulary of 96): the program against the plain reference
(benchmark/reference/granite_hybrid.py, whose recurrence runs a token at a
time) in float32 on seeded random weights for the loss, every gradient leaf and
the first step through ``initialize``; the list's kinds and runs at 10 and at 40
layers and the published parameter count; a document's loss and gradients
unchanged by what is packed in front of it; each multiplier changes what it
should and 1.0 traces the standing program; the logits over the four row slices
of the tied matrix; and what refuses the stack by name."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from deepspeed_tpu.models import gpt2_model, granite_hybrid_config, granite_hybrid_model
from deepspeed_tpu.models import transformer
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import Budget
from tests.benchmark.helpers import DATA

MANIFEST = os.path.join(DATA, "BENCHMARK.granite-hybrid-tiny.json")
F32 = jnp.float32
SEP = 95


@pytest.fixture(scope="module")
def parts():
    """(reference module, adapter module, configuration, weights, ids): eight
    rows of 64 tokens (a row a device of the tests' mesh: four chunks of 16),
    three documents in the first (a border inside a chunk, one on a chunk's
    first row) and two in the second."""
    cell = harness.Cell(MANIFEST, "granite-hybrid-tiny.train")
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


def test_first_step_through_initialize_leaf_by_leaf(parts, wanted):
    """``initialize`` -> ``train_batch`` in float32, ONE compile of the program:
    the step's loss and gradient norm are the reference's; every gradient leaf is
    (read back from Adam's first moment, (1 - beta1) x the gradient after one
    step); every weight moves against the reference's gradient; the engine's
    records say what ran."""
    import deepspeed_tpu
    ref, adapter, cfg, w, ids = parts
    want, want_g = wanted
    model = adapter.model(cfg, remat=True, dtype="float32")
    c = model.config
    assert (c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups, c.ssm_inner, c.ssm_conv,
            c.ssm_chunk, c.position, c.document_separator) == (2, 64, 16, 1, 128, 4, 16,
                                                                "none", SEP)
    assert (c.attn_scale, c.embedding_scale, c.residual_scale, c.logits_divisor) == (
        0.0625, 12.0, 0.22, 8.0)
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
    for name in ("r0.w_in", "r0.A_log", "r0.conv", "r0.conv_b", "r0.dt_b", "r0.D", "r0.norm_g",
                 "r2.w_out", "r1.wq", "r1.wk", "r1.wv", "r1.wo", "embed", "norm_f"):
        assert np.abs(np.asarray(want_g[name])).max() > 1e-9, name
    new = adapter.from_program(engine.state["opt"]["master"])
    wrong = total = 0
    for name, g in want_g.items():
        s = np.where(np.abs(np.asarray(g)) > 1e-8, np.sign(np.asarray(g)), 0)
        moved = np.sign(np.asarray(new[name], np.float64) - np.asarray(w[name], np.float64))
        wrong += np.sum((moved + s != 0) & (s != 0))
        total += np.sum(s != 0)
    assert wrong / total < 2e-3
    assert engine.attn_totals["ssm"] == {
        "kind": "ssd", "heads": 2, "head_dim": 64, "groups": 1, "layers": 9,
        "memory_units": 0, "d_inner": 128, "d_state": 16, "conv": 4, "dt_rank": None,
        "route": "xla", "chunk": 16, "tile": None}
    assert (engine.attn_totals["layers_window"], engine.attn_totals["layers_full"],
            engine.attn_totals["group"]) == (0, 1, 2)
    # (eight rows' starts and the three documents that start inside a row)
    assert engine.attn_last_step()["ssm_resets"] == 8 + 3


def test_the_controls_round_what_they_say(parts):
    """``fp8`` rounds a matmul's operands; ``bf16_state`` the recurrence's carried
    state alone."""
    ref, _, cfg, w, _ = parts
    lw = {name[3:]: v[0] for name, v in w.items() if name.startswith("r0.")}
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64), F32)
    sound, low = ref.mlp(x, lw), ref.mlp(x, lw, control="fp8")
    assert 0 < float(jnp.abs(low - sound).max()) < 0.2 * float(jnp.abs(sound).max())
    np.testing.assert_array_equal(ref.mlp(x, lw, control="bf16_state"), sound)
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    a, dt = jax.random.normal(k[0], (16, 128)), jax.nn.softplus(jax.random.normal(k[1], (16, 2)))
    scan = lambda control: ref.recurrence(
        a, dt, -jnp.exp(jax.random.normal(k[2], (2,))), jax.random.normal(k[3], (16, 1, 16)),
        jax.random.normal(k[4], (16, 1, 16)), jnp.ones(2), jnp.arange(16) == 0, 64, control)
    assert 1e-4 < float(jnp.abs(scan("bf16_state") - scan(None)).max()) < 0.5
    with pytest.raises(ValueError, match="unknown control"):
        ref.rounded(a, "fp4")


def test_a_document_does_not_see_what_is_packed_in_front_of_it(parts):
    """The second row's last document (positions 8-63) alone in a row, and behind
    other documents: the same logits there and the same gradient from a loss over
    them (attention, the recurrence's state and the convolution's taps all cut)."""
    _, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=False, dtype="float32")
    params = adapter.to_program(w)
    tail = ids[1:2, 8:]
    alone = jnp.concatenate([tail, jnp.full((1, 8), SEP, jnp.int32)], axis=1)
    other = jnp.concatenate([ids[0:1, 30:37], jnp.full((1, 1), SEP, jnp.int32), tail], axis=1)
    pick = jax.random.normal(jax.random.PRNGKey(3), (56, 96))

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


def test_the_four_row_slices_of_the_tied_matrix_are_the_uncut_head(parts):
    """The share: four chips hold rows 0-23, 24-47, 48-71, 72-95 of the tied
    matrix; each chip's logits over its slice (the program's head on its rows,
    ids and loss over the slice), laid side by side, are the uncut reference's
    logits over the whole vocabulary on the same stream."""
    ref, adapter, cfg, w, ids = parts
    s = ref.sizes(cfg)
    with jax.default_matmul_precision("highest"):
        x = ref.stream(w, ids[:2], cfg)
        whole = ref.head_logits({"norm_f": w["norm_f"], "embed": w["embed"]}, x, s)
    quarter = {**cfg, "vocab_size": 24}
    model = adapter.model(quarter, remat=False, dtype="float32")
    assert model.config.vocab_size == 24 and model.config.logits_divisor == 8.0
    head = jax.jit(lambda rows: model.head(
        {"ln_f": {"scale": w["norm_f"]}, "wte": {"embedding": rows}}, x))
    side_by_side = jnp.concatenate(
        [head(w["embed"][24 * rank:24 * (rank + 1)]) for rank in range(4)], axis=-1)
    assert side_by_side.shape == whole.shape == (2, 64, 96)
    np.testing.assert_allclose(side_by_side, whole, atol=2e-6)


def lowered(model, ids):
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    return jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, {"input_ids": ids}))).lower(params).as_text()


def test_each_multiplier_changes_what_it_should_and_one_traces_nothing(parts):
    """On the tiny stack: the embedding's, the branches' and the logits'
    multipliers each move the loss, through the whole head and through the head
    in slices alike (the loss AND its gradient); at 1.0 (and ``embedding_scale``
    None) a plain model lowers to the program it lowered to before the fields
    were there: their values do not enter a trace."""
    _, adapter, cfg, w, ids = parts
    params = adapter.to_program(w)
    batch = {"input_ids": ids[:2]}

    whole = 2 * 4 * batch["input_ids"].size * 96

    def loss_and_grads(slices=1, **overrides):
        model = transformer.TransformerLM(granite_hybrid_config(
            "granite-hybrid-tiny", dtype=F32, document_separator=SEP, remat=False,
            layers=6, **overrides))     # (five scan layers and the attention layer)
        budget = Budget(None if slices == 1 else 2 * whole - 1)
        out = jax.jit(jax.value_and_grad(
            lambda p: model.loss(p, batch, remat_budget=budget)))(params)
        assert budget.totals.get("head_row_slices", 1) == slices
        return out

    base, base_g = loss_and_grads()
    sliced, sliced_g = loss_and_grads(slices=4)
    assert float(base) == pytest.approx(float(sliced), rel=1e-6)
    for a, b in zip(jax.tree.leaves(base_g), jax.tree.leaves(sliced_g)):
        assert close(a, b, rel=1e-4)
    for slices in (1, 4):       # the logits' divisor through the whole head and the sliced
        other, other_g = loss_and_grads(slices=slices, logits_divisor=2.0)
        assert abs(float(other) - float(base)) > 1e-5, slices
        assert not close(other_g["wte"]["embedding"], base_g["wte"]["embedding"]), slices
    for field, value in (("embedding_scale", 6.0), ("residual_scale", 0.5)):
        model = transformer.TransformerLM(granite_hybrid_config(
            "granite-hybrid-tiny", dtype=F32, document_separator=SEP, remat=False,
            layers=6, **{field: value}))
        other = jax.jit(lambda p: model.loss(p, batch))(params)      # (forward alone)
        assert abs(float(other) - float(base)) > 1e-5, field
    # the logits' divisor, by hand: the same logits times 4
    model = granite_hybrid_model("granite-hybrid-tiny", dtype=F32, remat=False)
    loud = granite_hybrid_model("granite-hybrid-tiny", dtype=F32, remat=False, logits_divisor=2.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 64), F32)
    head = {"ln_f": params["ln_f"], "wte": params["wte"]}
    np.testing.assert_allclose(loud.head(head, x), 4.0 * model.head(head, x), rtol=1e-6)
    # 1.0 traces the standing program
    plain = gpt2_model("gpt2-tiny", dtype=F32, max_seq_len=32, vocab_size=64, remat=False)
    same = gpt2_model("gpt2-tiny", dtype=F32, max_seq_len=32, vocab_size=64, remat=False,
                      residual_scale=1.0, logits_divisor=1.0)
    tokens = jnp.zeros((1, 16), jnp.int32)
    text = lowered(plain, tokens)
    assert lowered(same, tokens) == text
    scaled = gpt2_model("gpt2-tiny", dtype=F32, max_seq_len=32, vocab_size=64, remat=False,
                        residual_scale=0.5)
    assert lowered(scaled, tokens) != text


def kinds_of(model):
    return [mixer for mixer, _ in model._mixer_kinds]


def test_the_list_at_10_and_at_40_layers():
    tiny = granite_hybrid_model("granite-hybrid-tiny", dtype=F32)
    period = ["ssd"] * 5 + ["mha"] + ["ssd"] * 4
    assert kinds_of(tiny) == period
    assert [(len(unit), n, unit[0][2]) for unit, n in tiny.run_plan] == [
        (1, 5, "ssd"), (1, 1, "mha"), (1, 4, "ssd")]
    c = granite_hybrid_config()
    assert (c.hidden_size, c.num_layers, c.num_heads, c.kv_heads, c.head_dim, c.ffn_size,
            c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups, c.ssm_inner, c.ssm_conv,
            c.ssm_chunk, c.vocab_size, c.max_seq_len, c.norm_eps, c.attn_scale,
            c.embedding_scale, c.residual_scale, c.logits_divisor) == (
                2048, 40, 32, 8, 64, 8192, 64, 64, 128, 1, 4096, 4, 256, 100352, 131072, 1e-5,
                0.015625, 12.0, 0.22, 8.0)
    assert c.num_parameters() == 3_191_396_096
    full = transformer.TransformerLM(c)
    assert kinds_of(full) == period * 4
    assert [(len(unit), n) for unit, n in full.run_plan] == [(10, 4)]
    # the cell's share: the list's first ten entries, a quarter of the rows
    cell = granite_hybrid_config(layers=10, vocab_size=25088)
    assert cell.num_parameters() == 797_850_560
    assert kinds_of(transformer.TransformerLM(cell)) == period
    params = jax.eval_shape(lambda: tiny.init(jax.random.PRNGKey(0)))
    assert sum(p.size for p in jax.tree.leaves(params)) == tiny.config.num_parameters()
    ssm = params["runs"]["0"]["0"]["ssm"]
    assert (ssm["A_log"].shape, ssm["D"].shape, ssm["dt_bias"].shape, ssm["conv"].shape) == (
        (5, 2), (5, 2), (5, 2), (5, 4, 128 + 2 * 16))


def test_what_does_not_run_the_stack_refuses_it_by_name():
    from deepspeed_tpu.inference.v2.model import RaggedInferenceModel
    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    model = granite_hybrid_model("granite-hybrid-tiny", dtype=F32)
    mechanisms = ("ssm_heads", "layer_mixers", "residual_scale", "logits_divisor")
    assert set(mechanisms) <= set(model.mechanisms)
    for consumer in (lambda: PipelineModule(model.config, num_stages=1, num_microbatches=2),
                     lambda: RaggedInferenceModel(model, block_size=8, max_blocks_per_seq=1),
                     lambda: model.block_apply(None, None, None)):
        with pytest.raises(NotImplementedError) as refusal:
            consumer()
        for name in mechanisms:
            assert name in str(refusal.value)
    with pytest.raises(ValueError, match="ssm_groups"):
        granite_hybrid_model("granite-hybrid-tiny", ssm_groups=3)
    with pytest.raises(ValueError, match="sequential pre-norm"):
        granite_hybrid_model("granite-hybrid-tiny", norm_style="sandwich")


@pytest.mark.parametrize("keys,error,says", [
    (dict(layer_mixers=("ssd",) * 9), ValueError, "one name a layer"),
    (dict(layer_mixers=("ssd",) * 9 + ("gmu",)), ValueError, "each 'ssd' or 'mha'"),
    (dict(layer_mixers=("mha",) * 10), ValueError, "leave layer_mixers None"),
    (dict(layer_mixers=None), ValueError, "not by the ssm_period rule"),
    (dict(differential_attention=True), ValueError, "ssm_period rule's stacks"),
    (dict(shared_from=0), ValueError, "ssm_period rule's stacks"),
    (dict(layer_mixers=None, ssm_heads=0, ssm_state=0, parallel_block=True),
     NotImplementedError, "residual_scale"),
    (dict(layer_mixers=None, ssm_heads=0, ssm_state=0, norm_style="post"),
     NotImplementedError, "residual_scale"),
], ids=["short", "unknown-kind", "mha-alone", "heads-without-a-list", "differential",
        "shared", "scale-parallel", "scale-post-norm"])
def test_a_list_or_a_scale_the_block_does_not_apply_is_refused_when_built(keys, error, says):
    """One place picks a layer's mixer (the list, or the period's rule without
    one), and the list is held where the model is built; a block form that never
    reads ``residual_scale`` refuses it instead of computing without it."""
    with pytest.raises(error, match=says):
        granite_hybrid_model("granite-hybrid-tiny", dtype=F32, **keys)


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("position_embedding_type", "rope"), ("mamba_n_groups", 3),
    ("layer_types", ["mamba"] * 39), ("layer_types", ["mamba"] * 39 + ["conv"]),
    ("attention_bias", True), ("mamba_proj_bias", True), ("tie_word_embeddings", False),
    ("num_hidden_layers", 41), ("router_aux_loss_coef", 0.01)])
def test_a_configuration_it_does_not_compute_is_refused_by_its_key(key, value):
    from deepspeed_tpu.models.granite_hybrid import config_kwargs, _FLAGS, _PRESETS
    hf = {**_FLAGS, **_PRESETS["granite-4.0-h-micro"]}
    with pytest.raises(NotImplementedError, match=key):
        config_kwargs({**hf, key: value})
    assert config_kwargs({**hf, "num_hidden_layers": 12})["layer_mixers"] == tuple(
        ["ssd"] * 5 + ["mha"] + ["ssd"] * 6)
