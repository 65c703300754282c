"""Phi-4-mini-flash-reasoning's stack on the CPU at the ``phi4flash-tiny`` preset
(the tests' benchmark data: hidden 64, 4 query over 2 key heads of 16, 128 scan
channels of 4 states, a window of 8, 8 layers, a vocabulary of 96): the program
against the plain reference (benchmark/reference/phi4flash.py) in float32 on
seeded random weights for the loss, every gradient leaf and the first step
through ``initialize``; the released rule's kinds and runs at 8 and at 32
layers and the published parameter count; a document's loss and gradients
unchanged by what is packed in front of it; the head over slices of the rows;
and what refuses the three mechanisms by name."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from deepspeed_tpu.models import phi4flash_config, phi4flash_model
from deepspeed_tpu.models import transformer
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import Budget
from tests.benchmark.helpers import DATA

MANIFEST = os.path.join(DATA, "BENCHMARK.phi4flash-tiny.json")
F32 = jnp.float32
SEP = 95


@pytest.fixture(scope="module")
def parts():
    """(reference module, adapter module, configuration, weights, ids): eight
    rows of 64 tokens (a row a device of the tests' mesh), three documents in the
    first and two in the second."""
    cell = harness.Cell(MANIFEST, "phi4flash-tiny.train")
    ref = cell.load_module("reference", cell.config["reference"])
    adapter = cell.load_module("adapters", cell.config["adapter"])
    w = ref.make_weights(ref.key_of(7), cell.config, F32)
    ids = np.random.default_rng(0).integers(0, SEP, (8, 64))
    ids[0, 20] = ids[0, 41] = ids[1, 7] = SEP
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
    step), the layers that hand their tensors on among them: they collect from
    their readers; every weight moves against the reference's gradient; the
    engine's records say what ran. (A key's bias moves no softmax: its gradient
    is rounding.)"""
    import deepspeed_tpu
    ref, adapter, cfg, w, ids = parts
    want, want_g = wanted
    model = adapter.model(cfg, remat=True, dtype="float32")
    c = model.config
    assert (c.ssm_state, c.ssm_inner, c.ssm_rank, c.ssm_conv, c.shared_from, c.position,
            c.differential_attention, c.document_separator) == (4, 128, 4, 4, 4, "none", True, SEP)
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
    for name in ("ms.w_in", "ms.A_log", "ms.conv", "mf.wkv", "cg.w1", "cx.wq", "cx.lam",
                 "sw.subln", "ss.dt_b", "ss.D", "embed"):
        assert np.abs(np.asarray(want_g[name])).max() > 1e-9, name
    keys = np.asarray(want_g["mf.bkv"])[0, :32]
    assert np.abs(keys).max() < 1e-8 < np.abs(np.asarray(want_g["mf.bkv"])[0, 32:]).max()
    new = adapter.from_program(engine.state["opt"]["master"])
    wrong = total = 0
    for name, g in want_g.items():
        s = np.where(np.abs(np.asarray(g)) > 1e-8, np.sign(np.asarray(g)), 0)
        moved = np.sign(np.asarray(new[name], np.float64) - np.asarray(w[name], np.float64))
        wrong += np.sum((moved + s != 0) & (s != 0))
        total += np.sum(s != 0)
    assert wrong / total < 2e-3
    assert engine.attn_totals["ssm"] == {
        "kind": "selective", "heads": None, "head_dim": None, "groups": None,
        "layers": 3, "memory_units": 1, "d_inner": 128, "d_state": 4, "conv": 4, "dt_rank": 4,
        "route": "xla", "chunk": 64, "tile": None}
    assert engine.attn_totals["diff"] == {"qk_dim": 16, "v_dim": 32, "launches_a_layer": 2,
                                          "shared_readers": 1}
    assert (engine.attn_totals["layers_window"], engine.attn_totals["layers_full"],
            engine.attn_totals["window"]) == (2, 2, 8)
    # (eight rows' starts and the three documents that start inside a row)
    assert engine.attn_last_step()["ssm_resets"] == 8 + 3
    # (the record is the last kind of block's, a cross layer's)
    assert {"q_proj", "o_proj", "gate_proj"} <= set(engine.remat_totals["saved"])


def test_the_controls_round_what_they_say(parts):
    """(``loss_and_gradient`` against ``jax.grad``, and the fp8 control through
    the whole loss, are tests/benchmark/test_reference.py's, for every
    reference file.) ``fp8`` rounds a matmul's operands; ``bf16_state`` the scan's
    carried state alone."""
    ref, _, cfg, w, _ = parts
    lw = {name[3:]: v[0] for name, v in w.items() if name.startswith("ss.")}
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64), F32)
    sound, low = ref.mlp(x, lw), ref.mlp(x, lw, control="fp8")
    assert 0 < float(jnp.abs(low - sound).max()) < 0.2 * float(jnp.abs(sound).max())
    np.testing.assert_array_equal(ref.mlp(x, lw, control="bf16_state"), sound)
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    a, dt = jax.random.normal(k[0], (16, 8)), jax.nn.softplus(jax.random.normal(k[1], (16, 8)))
    scan = lambda control: ref.selective_scan(
        a, dt, -jnp.exp(jax.random.normal(k[2], (8, 4))), jax.random.normal(k[3], (16, 4)),
        jax.random.normal(k[4], (16, 4)), jnp.ones(8), jnp.arange(16) == 0, control)
    assert 1e-4 < float(jnp.abs(scan("bf16_state") - scan(None)).max()) < 0.1
    with pytest.raises(ValueError, match="unknown control"):
        ref.rounded(a, "fp4")


def test_a_document_does_not_see_what_is_packed_in_front_of_it(parts):
    """The second row's last document (positions 8-63) alone in a row, and behind
    other documents: the same logits there and the same gradient from a loss over
    them (attention, the scan's state and the convolution's taps all cut)."""
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


def test_the_head_over_slices_is_the_head(parts):
    """`head_row_slices` by hand; the head and its loss over 1 and over 4 slices
    of the rows, equal in loss and within float32 summation in every gradient
    (the tied matrix's and the final norm's summed over the slices, the
    stream's); and a step picks the count from its budget's room."""
    _, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype="float32")
    params = adapter.to_program(w)
    rows, whole = ids.size, 2 * 4 * ids.size * 96
    slices = transformer.head_row_slices
    assert slices(rows, 96, None) == 1 == slices(rows, 96, 0)
    assert slices(rows, 96, 2 * whole) == 1 and slices(rows, 96, 2 * whole - 1) == 4
    assert slices(rows, 96, whole // 4) == 16
    assert slices(16384, 25008, 5_010_000_000) == 4
    assert slices(16384, 25024, 7_650_000_000) == 1          # Trinity's cell
    x = jax.random.normal(jax.random.PRNGKey(5), ids.shape + (64,), F32)
    labels = model.derive_labels({"input_ids": ids})
    head = {k: params[k] for k in ("ln_f", "wte")}
    fn = lambda n: jax.jit(jax.value_and_grad(
        lambda h, x: model.head_loss(h, x, labels, slices=n), argnums=(0, 1)))(head, x)
    (l1, g1), (l4, g4) = fn(1), fn(4)
    assert float(l1) == pytest.approx(float(l4), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
        assert close(a, b, rel=1e-5)
    one, four = Budget(None), Budget(2 * whole - 1)
    for budget in (one, four):
        jax.eval_shape(lambda p: model.loss(p, {"input_ids": ids}, remat_budget=budget), params)
    assert "head_row_slices" not in one.totals and four.totals["head_row_slices"] == 4
    assert four.outside_bytes < one.outside_bytes


def kinds_of(model):
    return [(mixer, hands, bool(window)) for (window, _), (mixer, hands)
            in zip(model._kinds, model._mixer_kinds)]


def test_the_released_rule_at_8_and_at_32_layers():
    tiny = phi4flash_model("phi4flash-tiny", dtype=F32)
    s, w, g, x = ("ssm", None, False), ("attn", None, True), ("gmu", None, False), ("cross", None, False)
    assert kinds_of(tiny) == [s, w, s, w, ("ssm", "memory", False), ("attn", "kv", False), g, x]
    assert [(len(unit), n) for unit, n in tiny.run_plan] == [(2, 2), (4, 1)]
    c = phi4flash_config()
    assert (c.hidden_size, c.num_layers, c.num_heads, c.kv_heads, c.head_dim, c.ffn_size,
            c.ssm_inner, c.ssm_state, c.ssm_conv, c.ssm_rank, c.shared_from, c.vocab_size,
            c.max_seq_len, c.norm_eps) == (2560, 32, 40, 20, 64, 10240, 5120, 16, 4, 160, 16,
                                           200064, 262144, 1e-5)
    assert c.num_parameters() == 3_852_562_944
    full = transformer.TransformerLM(c)
    kinds = kinds_of(full)
    assert (kinds.count(s), kinds.count(w), kinds.count(g), kinds.count(x)) == (8, 8, 7, 7)
    assert kinds[16:18] == [("ssm", "memory", False), ("attn", "kv", False)]
    assert [(len(unit), n) for unit, n in full.run_plan] == [(2, 8), (2, 1), (2, 7)]
    assert {w for w, _ in full._kinds} == {0, 512}
    # seven readers: their cotangents are summed in float32
    attn = full._mixers["attn"]
    assert attn.shared_dtype("kv") == attn.shared_dtype("memory") == F32
    assert tiny._mixers["attn"].shared_dtype("kv") == tiny.config.dtype
    params = jax.eval_shape(lambda: tiny.init(jax.random.PRNGKey(0)))
    assert sum(p.size for p in jax.tree.leaves(params)) == tiny.config.num_parameters()


def test_what_does_not_run_the_stack_refuses_it_by_name():
    from deepspeed_tpu.inference.v2.model import RaggedInferenceModel
    from deepspeed_tpu.models.phi4flash import config_kwargs, _FLAGS, _PRESETS
    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    model = phi4flash_model("phi4flash-tiny", dtype=F32)
    assert {"ssm_state", "differential_attention", "shared_from"} <= set(model.mechanisms)
    for consumer in (lambda: PipelineModule(model.config, num_stages=1, num_microbatches=2),
                     lambda: RaggedInferenceModel(model, block_size=8, max_blocks_per_seq=1),
                     lambda: model.block_apply(None, None, None)):
        with pytest.raises(NotImplementedError) as refusal:
            consumer()
        for name in ("ssm_state", "differential_attention", "shared_from"):
            assert name in str(refusal.value)
    hf = {**_FLAGS, **_PRESETS["phi4-mini-flash"]}
    for key, value in (("resid_pdrop", 0.1), ("mlp_bias", True), ("hidden_act", "gelu"),
                       ("num_hidden_layers", 10), ("rope_theta", 1e4)):
        with pytest.raises(NotImplementedError, match=key):
            config_kwargs({**hf, key: value})
    with pytest.raises(ValueError, match="shared_from"):
        phi4flash_model("phi4flash-tiny", shared_from=3)
    with pytest.raises(ValueError, match="sequential pre-norm"):
        phi4flash_model("phi4flash-tiny", norm_style="sandwich")
