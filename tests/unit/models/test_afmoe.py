"""Trinity-Mini's block (``afmoe``) on the CPU at the ``afmoe-tiny`` preset
(the tests' benchmark data): the program against the plain reference
(benchmark/reference/afmoe.py) in float32 on seeded random weights, for the
loss, every gradient and the first step through ``initialize``, on a stack
with two dense layers, sliding and full expert layers and documents longer
than the window; what a window and a rotary position do to each kind of
layer; the shares of a layer that several chips divide add up to the whole
layer; the bias step; and the published stack's 32 kinds as the layer scan
lays them out."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from deepspeed_tpu.models import afmoe_model
from deepspeed_tpu.models.afmoe import KINDS, config_kwargs, layer_types
from deepspeed_tpu.models.registry import get_architecture
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
from deepspeed_tpu.moe.layer import MoE
from tests.benchmark.helpers import DATA

MANIFEST = os.path.join(DATA, "BENCHMARK.afmoe-tiny.json")
F32 = jnp.float32
S, F = (16, True), (0, False)         # a sliding and a full layer's kind at the preset


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(MANIFEST, "afmoe-tiny.train")


@pytest.fixture(scope="module")
def parts(cell):
    """(reference module, adapter module, configuration, weights, ids): rows
    of 64 under a window of 16, two of the eight rows cut into documents
    (some longer than the window, some shorter)."""
    ref = cell.load_module("reference", cell.config["reference"])
    adapter = cell.load_module("adapters", cell.config["adapter"])
    w = ref.make_weights(ref.key_of(7), cell.config, F32)
    ids = np.random.default_rng(0).integers(0, cell.config["vocab_size"] - 1, (8, 64))
    sep = cell.config["assumed"]["separator"]
    ids[1, [9, 40]] = sep
    ids[2, [30, 31, 63]] = sep
    return ref, adapter, cell.config, w, jnp.asarray(ids, jnp.int32)


@pytest.fixture(scope="module")
def blocked(parts):
    """The reference in blocks (its own ``loss_and_gradient``: what runs at
    16,384) over the module's rows, ONE jitted program for the two tests that
    read it."""
    ref, _, cfg, w, ids = parts
    return jax.jit(lambda p: ref.loss_and_gradient(p, ids, cfg))(w)


def close(a, b, rel=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-12)


def test_loss_and_gradient_match_the_reference(parts, blocked):
    """float32 against float32 at ``highest``: the loss to 1e-5 (one
    reduction order apart), every gradient to 2e-4 of its largest element
    (the sandwich norms divide by a branch's own RMS, which amplifies a
    last-bit difference of the branch)."""
    ref, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype="float32")
    assert model.scan_plan == ((S, F, S), 1, (S,)) and model._kinds[:2] == (S, S)
    # (each side ONE jitted program: op by op this test compiled 1,341 of them)
    want, want_g = jax.jit(jax.value_and_grad(lambda p: ref.next_token_loss(p, ids, cfg)))(w)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(
            lambda p: model.loss(p, {"input_ids": ids})))(adapter.to_program(w))
        logits, _ = jax.jit(lambda p: model.apply(p, ids))(adapter.to_program(w))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat = adapter.from_program(got_g)
    assert set(flat) == set(w)
    for name, g in want_g.items():
        assert close(flat[name], g), name
    assert not np.asarray(flat["router_bias"]).any()     # stop_gradient: exactly 0
    assert close(logits, jax.jit(lambda p: ref.forward(p, ids, cfg))(w), rel=1e-4)
    # the reference in blocks (what runs at 16,384) is the reference
    assert float(blocked[0]) == pytest.approx(float(want), rel=1e-6)


def test_first_step_through_initialize(parts, blocked):
    """``initialize`` -> ``train_batch`` in float32: the step's loss and
    gradient norm are the reference's, every weight moves against the
    reference's gradient, the router's bias moves by ``load_balance_coeff``
    against the load the reference counts, and the counters say what ran."""
    import deepspeed_tpu
    ref, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype="float32")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=adapter.to_program(w), config={
            "train_micro_batch_size_per_gpu": 1,
            "zero_optimization": {"stage": 1},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3, "weight_decay": 0.1}}})
    assert engine.attn_totals == {"layers_window": 5, "layers_full": 1, "window": 16,
                                  "kv_heads": 2, "group": 4, "documents": True,
                                  "route": {"window": None, "full": None},
                                  "dq": {"window": None, "full": None},
                                  "layout": {"window": None, "full": None}}
    assert engine.attn_last_step() is None
    loss = float(engine.train_batch({"input_ids": np.asarray(ids)}))
    want, gnorm, signs = blocked
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
    load = np.asarray(ref.router_load(w, ids, cfg))
    np.testing.assert_allclose(
        np.asarray(new["router_bias"]),
        np.asarray(ref.bias_after_step(w["router_bias"], load, cfg)), rtol=0, atol=1e-7)
    assert np.abs(np.asarray(new["router_bias"]) - np.asarray(w["router_bias"])).max() \
        == pytest.approx(cfg["load_balance_coeff"], rel=1e-3)
    rows = engine.moe_expert_rows()
    assert rows.shape == (4, 8)
    np.testing.assert_array_equal(rows, load[:, :8].astype(np.int32))
    assert engine.attn_totals["route"] == {"window": "xla", "full": "xla"}
    assert engine.attn_totals["dq"] == {"window": None, "full": None}   # no kernel, no mode
    assert {k: engine.moe_totals[k] for k in ("path", "experts_published", "experts_held")} \
        == {"path": "dropless", "experts_published": 16, "experts_held": 8}


def one_layer(kind):
    """A one-block model of ``kind`` at the preset's widths, its block and a
    normed input of 48 positions."""
    window, rope = kind
    model = TransformerLM(TransformerConfig(
        vocab_size=64, max_seq_len=256, num_layers=1, num_heads=8, num_kv_heads=2,
        hidden_size=64, head_size=16, intermediate_size=32, activation="silu_gated",
        norm="rmsnorm", position="rope", rope_layers="windowed", attn_windows=window,
        qk_norm=True, qk_norm_per_head=True, attn_gate=True, dtype=F32, remat=False))
    assert model._kinds == (kind,)
    block = jax.tree.map(lambda a: a[0], model.init(jax.random.PRNGKey(0))["blocks"])
    return model, block, jax.random.normal(jax.random.PRNGKey(1), (1, 48, 64), F32)


@pytest.mark.parametrize("kind", [S, F], ids=["sliding", "full"])
def test_a_key_outside_the_window_and_a_shift_of_all_positions(kind):
    """The last query and a key 16 back, the first outside a window of 16:
    changing that key's input changes a full layer's output there and not a
    sliding layer's; the key one nearer changes both. Positions: a full
    layer has no positional term and is blind to any change of them; a
    sliding layer's rope reads their differences, so a shift of every
    position cancels and a stretch does not."""
    model, block, h = one_layer(kind)
    window, rope = kind
    positions = jnp.arange(48)[None]
    out = lambda h, p=positions: model._mixer(block, h, p, None, (window, rope))[0][0, -1]
    base = out(h)
    moved = lambda at: float(jnp.abs(out(h.at[0, at].add(1.0)) - base).max())
    assert moved(47 - 15) > 1e-4                       # inside either kind's reach
    assert (moved(47 - 16) > 1e-4) is (kind == F)      # one further: the full layer's alone
    if kind == F:
        np.testing.assert_array_equal(np.asarray(out(h, positions * 3 + 5)), np.asarray(base))
    else:
        assert close(out(h, positions + 5), base, rel=1e-4)
        assert float(jnp.abs(out(h, positions * 3) - base).max()) > 1e-3


def test_the_shares_add_up_to_the_whole_layer(parts):
    """``chips_sharing_a_layer`` = 2: the two held ranges' routed parts
    (program, each on its own weight stacks) plus the shared expert counted
    once are the uncut reference's whole expert layer; and the reference
    given each rank's share adds up the same way."""
    ref, _, cfg, _, _ = parts
    chips = cfg["share"]["chips_sharing_a_layer"]
    whole = {k: v for k, v in cfg.items() if k != "share"}
    whole["num_experts"] = cfg["share"]["published"]["num_experts"]
    s = ref.sizes(whole)
    w = ref.make_weights(ref.key_of(3), whole, F32)
    lw = {k: w[k][1] for k in ("router", "router_bias", "w_gate", "w_up", "w_down",
                               "s_gate", "s_up", "s_down")}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, s["H"]), F32)
    h = x.reshape(-1, s["H"])
    with jax.default_matmul_precision("highest"):
        weight, load = ref.route(h, lw["router"], lw["router_bias"], s)
        routed = ref.held_experts(h, weight, lw, s)
        shared = ref.gated_mlp(h, lw["s_gate"], lw["s_up"], lw["s_down"])
    assert s["Eh"] == s["E"] == 16 and int(load.sum()) == 48 * s["k"]
    held = s["E"] // chips
    total, ref_total = shared, 0                                   # the shared expert: once
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
        sr = ref.sizes(dict(cfg, assumed=dict(cfg["assumed"], share_rank=rank)))
        assert (sr["lo"], sr["Eh"], sr["E"]) == (lo, held, 16)
        mine = {k: (v[lo:hi] if k.startswith("w_") else v) for k, v in lw.items()}
        ref_total = ref_total + ref.held_experts(h, weight, mine, sr)
    assert close(total, routed + shared, rel=1e-5)
    assert close(ref_total, routed, rel=1e-5)


def test_the_bias_step(parts):
    """Up by the coefficient under the mean load, down over it, still at it."""
    ref, _, cfg, _, _ = parts
    load = jnp.asarray([[4.0, 0, 2, 2], [1, 1, 1, 1]])
    np.testing.assert_allclose(
        np.asarray(ref.bias_after_step(jnp.zeros((2, 4)), load, cfg)),
        [[-0.001, 0.001, 0, 0], [0, 0, 0, 0]], atol=1e-9)


def published(**changes):
    return {"model_type": "afmoe", "vocab_size": 512, "hidden_size": 32,
            "num_hidden_layers": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "intermediate_size": 48, "moe_intermediate_size": 8,
            "num_dense_layers": 2, "num_experts": 8, "num_shared_experts": 1,
            "num_experts_per_tok": 2, "route_scale": 2.826, "route_norm": True,
            "score_func": "sigmoid", "load_balance_coeff": 0.001, "mup_enabled": True,
            "sliding_window": 8, "layer_types": list(layer_types(4, 32)),
            "max_position_embeddings": 64, "rms_norm_eps": 1e-5, "rope_theta": 10000,
            "rope_scaling": None, "hidden_act": "silu", **changes}


def test_the_published_depth_is_seven_periods_and_a_run_of_two(parts):
    """All 32 layers' kinds at tiny widths: two dense sliding layers outside
    the scan; the 30 expert layers (s F s s s F ...) as the unit (s, F, s, s)
    seven times and a tail of (s, F), each kind traced as itself; the loss is
    the reference's."""
    ref, adapter, _, _, _ = parts
    cfg = published(assumed={"separator": 511})
    kw = get_architecture("afmoe").config_fn(cfg)
    w8, f = (8, True), (0, False)
    model = TransformerLM(TransformerConfig(**kw, dtype=F32, remat=True))
    assert model._kinds == (w8, w8, w8, f) * 8
    assert model.scan_plan == ((w8, f, w8, w8), 7, (w8, f))
    assert [model._kinds[2 + i] for i in range(30)] == \
        list(model.scan_plan[0]) * 7 + list(model.scan_plan[2])
    # each kind once in the program, under its own scope, however deep
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 511, (1, 32)), jnp.int32)
    w = ref.make_weights(ref.key_of(5), cfg, F32)
    import re
    text = jax.jit(lambda p: model.loss(p, {"input_ids": ids})).lower(
        adapter.to_program(w)).as_text(debug_info=True)
    assert set(re.findall(r"attn/(core[a-z_]*)/", text)) == {"core", "core_window"}
    model_s = adapter.model(cfg, remat=False, dtype="float32")
    with jax.default_matmul_precision("highest"):
        got, rows = jax.jit(lambda p: (model_s.loss(p, {"input_ids": ids}),
                                       model_s.loss_and_stats(p, {"input_ids": ids})[1]))(
            adapter.to_program(w))
    want = jax.jit(lambda p: ref.next_token_loss(p, ids, cfg))(w)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    assert rows["moe_expert_rows"].shape == (30, 8)


def test_what_the_configuration_maps_to_and_refuses():
    kw = config_kwargs(published())
    assert (kw["head_size"], kw["num_kv_heads"], kw["norm_style"], kw["rope_layers"]) \
        == (16, 2, "sandwich", "windowed")
    assert kw["embedding_scale"] == 32 ** 0.5 and kw["first_dense_layers"] == 2
    assert kw["attn_windows"][:4] == (8, 8, 8, 0) and kw["moe"].bias_update == 0.001
    assert (kw["moe"].shared_width, kw["moe"].routed_scale, kw["moe"].seq_balance_coef) \
        == (8, 2.826, 0.0)
    # a cut in depth reads layer_types from its start
    assert config_kwargs(published(num_hidden_layers=6))["attn_windows"] == (8, 8, 8, 0, 8, 8)
    for bad in (dict(rope_scaling={"type": "yarn"}), dict(score_func="softmax"),
                dict(n_group=2), dict(layer_types=["linear_attention"] * 32),
                dict(num_dense_layers=0)):
        with pytest.raises(NotImplementedError, match=next(iter(bad)).split("_")[0]):
            config_kwargs(published(**bad))
    assert layer_types(4, 6) == (KINDS[0],) * 3 + (KINDS[1],) + (KINDS[0],) * 2
    model = afmoe_model("afmoe-tiny", dtype=F32)
    assert model.has_router_bias and model.config.head_dim == 16
    # the paths that take one kind of block say which kinds they refuse
    # (shapes alone: the refusal comes before a value is read)
    block = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a[0], model.init(jax.random.PRNGKey(0))["blocks"]))
    with pytest.raises(NotImplementedError, match="rope_layers='windowed'"):
        jax.eval_shape(lambda b, x: model.block_apply(b, x, jnp.arange(8)[None]),
                       block, jnp.zeros((1, 8, 64)))
    # a policy that is gone is refused by its name, with the names there are
    with pytest.raises(ValueError, match="'alternating' is none of .*nothing_saveable"):
        TransformerLM(TransformerConfig(
            num_layers=4, position="rope", norm="rmsnorm", rope_layers="windowed",
            attn_windows=(8, 0, 8, 0), max_seq_len=64, remat_policy="alternating"))
    with pytest.raises(ValueError, match="document_separator"):
        TransformerLM(TransformerConfig(causal=False, document_separator=3))


@pytest.mark.parametrize("separator", [63, None])
def test_attn_last_step_counts_the_tiles_packed_documents_leave(separator):
    """A dense two-layer model (a window of 256, then the whole row) over one
    row of 1,024 whose second half is another document: ``attn_last_step()``
    gives, for each kind of launch and kernel, the tiles the position test
    alone runs and the tiles the documents leave (the forward's 512 x 512:
    the tile under the diagonal goes; the full layer's backward is one
    tile), ``attn_totals`` says the launches carry the table; without a
    separator there is no count and the step returns no statistics."""
    import deepspeed_tpu
    model = TransformerLM(TransformerConfig(
        vocab_size=64, max_seq_len=1024, num_layers=2, num_heads=2, hidden_size=32,
        position="rope", norm="rmsnorm", attn_windows=(256, 0),
        document_separator=separator, dtype=F32, remat=False))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "zero_optimization": {"stage": 1},
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    assert engine.attn_totals["documents"] == (separator is not None)
    assert engine.attn_last_step() is None
    ids = np.random.default_rng(0).integers(0, 63, (8, 1024))
    ids[:, 511] = 63
    assert np.isfinite(float(engine.train_batch({"input_ids": ids})))
    if separator is None:
        assert engine.attn_last_step() is None and not engine._step_has_stats
        return
    assert model.attn_tile_kinds == (("window", 256), ("full", 0))
    rows = 8    # the count is a launch's, over its batch rows
    assert engine.attn_last_step() == {
        "window": {"forward": {"position": 3 * rows, "run": 2 * rows},
                   "backward": {"position": 3 * rows, "run": 2 * rows}},
        "full": {"forward": {"position": 3 * rows, "run": 2 * rows},
                 "backward": {"position": rows, "run": rows}}}
