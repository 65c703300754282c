"""SmallThinker's block on the CPU at the ``smallthinker-tiny`` preset (the
tests' benchmark data): the program against the plain reference
(benchmark/reference/smallthinker.py) in float32 on seeded random weights, for
the loss and every gradient, on a period that STARTS with its full layer, a
group of 7 query heads to a key head and documents longer than the window;
three wrong forms of the mathematics (a router fed the block's NORMED input;
SiLU in ReLU's place; rope on the full layer) that have to FAIL the same
tolerances; the shares of a layer that several chips divide add
up to the whole layer; and what the configuration maps to and refuses.
Cheap on purpose (PR 58's rule): one jitted program a side, rows of 64."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from deepspeed_tpu.models import smallthinker_model
from deepspeed_tpu.models.registry import get_architecture
from deepspeed_tpu.models.smallthinker import config_kwargs, layout
from deepspeed_tpu.models.transformer import MoEConfig, TransformerConfig, TransformerLM
from deepspeed_tpu.moe.layer import MoE
from tests.benchmark.helpers import DATA

MANIFEST = os.path.join(DATA, "BENCHMARK.smallthinker-tiny.json")
F32 = jnp.float32
W, F = (16, True), (0, False)         # a windowed and a full layer's kind at the preset


@pytest.fixture(scope="module")
def parts():
    """(reference module, adapter module, configuration, weights, ids): rows
    of 64 under a window of 16, two of the four rows cut into documents (some
    longer than the window, some shorter)."""
    cell = harness.Cell(MANIFEST, "smallthinker-tiny.train")
    ref = cell.load_module("reference", cell.config["reference"])
    adapter = cell.load_module("adapters", cell.config["adapter"])
    # (the preset is half a period deep, for the tests that pay by the layer
    # op by op; here the whole period runs: full, window, window, window)
    cfg = dict(cell.config, num_hidden_layers=4)
    w = ref.make_weights(ref.key_of(7), cfg, F32)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"] - 1, (4, 64))
    sep = cfg["assumed"]["separator"]
    ids[1, [9, 40]] = sep
    ids[2, [30, 31, 63]] = sep
    return ref, adapter, cfg, w, jnp.asarray(ids, jnp.int32)


@pytest.fixture(scope="module")
def wanted(parts):
    """The reference's loss and gradients, ONE jitted program."""
    ref, _, cfg, w, ids = parts
    return jax.jit(jax.value_and_grad(lambda p: ref.next_token_loss(p, ids, cfg)))(w)


def close(a, b, rel=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-12)


def loss_and_gradients(model, adapter, w, ids):
    with jax.default_matmul_precision("highest"):
        got, g = jax.jit(jax.value_and_grad(
            lambda p: model.loss(p, {"input_ids": ids})))(adapter.to_program(w))
    return float(got), adapter.from_program(g)


def agrees(got, flat, wanted) -> bool:
    """float32 against float32 at ``highest``: the loss to 1e-5 (one reduction
    order apart), every gradient to 2e-4 of its largest element (the router's
    softmax over a token's chosen logits and the norms' division amplify a
    last-bit difference of the stream by no more than that at this size)."""
    want, want_g = wanted
    return (abs(got - float(want)) <= 1e-5 * abs(float(want))
            and set(flat) == set(want_g)
            and all(close(flat[name], g) for name, g in want_g.items()))


def test_loss_and_gradient_match_the_reference(parts, wanted):
    ref, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype="float32")
    assert model.scan_plan == ((F, W, W, W), 1, ()) and model._routes_ahead
    assert model.config.num_heads // model.config.kv_heads == 7
    got, flat = loss_and_gradients(model, adapter, w, ids)
    assert agrees(got, flat, wanted)
    assert np.abs(np.asarray(flat["router"])).max() > 0      # the router learns
    # the reference in blocks (what runs at 16,384) is the reference
    blocked = jax.jit(lambda p: ref.loss_and_gradient(p, ids, cfg))(w)
    assert float(blocked[0]) == pytest.approx(float(wanted[0]), rel=1e-6)


def _normed_input_router(model):
    """The router ahead of the mixer, but fed the block's NORMED input."""
    wrong = TransformerLM(model.config)
    norm = wrong._block_layers["ln_1"]
    route_ahead = wrong._route_ahead
    wrong._route_ahead = lambda block, x: route_ahead(block, norm(block["ln_1"], x))
    return wrong


WRONG = {
    "router-fed-the-normed-input": _normed_input_router,
    "silu-in-relus-place": lambda m: TransformerLM(
        dataclasses.replace(m.config, activation="silu_gated")),
    "rope-on-the-full-layer": lambda m: TransformerLM(
        dataclasses.replace(m.config, rope_layers="all")),
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_a_wrong_form_of_the_mathematics_fails_the_tolerances(parts, wanted, wrong):
    """The comparison is tight enough to tell: each variant computes another
    function, and its loss or one of its gradients lies outside what the
    right program meets."""
    _, adapter, cfg, w, ids = parts
    model = WRONG[wrong](adapter.model(cfg, remat=False, dtype="float32"))
    got, flat = loss_and_gradients(model, adapter, w, ids)
    assert not agrees(got, flat, wanted)


def test_the_shares_add_up_to_the_whole_layer(parts):
    """Four ranks of 4 experts, as the cell's four chips divide 64 (the preset's
    own two divide its 16 the same way): the held ranges' parts (program, each on its
    own weight stacks, routed ahead from ANOTHER tensor than the experts
    multiply; no shared expert to count once) are the uncut reference's whole
    expert layer; and the reference given each rank's share adds up the same
    way."""
    ref, _, cfg, _, _ = parts
    whole = {k: v for k, v in cfg.items() if k != "share"}
    whole["moe_num_primary_experts"] = cfg["share"]["published"]["moe_num_primary_experts"]
    s = ref.sizes(whole)
    w = ref.make_weights(ref.key_of(3), whole, F32)
    lw = {k: w[k][1] for k in ("router", "w_gate", "w_up", "w_down")}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, s["H"]), F32)      # the block's input
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 24, s["H"]), F32)      # the experts'
    with jax.default_matmul_precision("highest"):
        weight, load = ref.route(x.reshape(-1, s["H"]), lw["router"], s)
        routed = ref.held_experts(u.reshape(-1, s["H"]), weight, lw, s)
    assert s["Eh"] == s["E"] == 16 and int(load.sum()) == 48 * s["k"]
    for chips in (4,):
        held = s["E"] // chips
        total, ref_total = 0, 0
        for rank in range(chips):
            lo, hi = rank * held, (rank + 1) * held
            layer = MoE(s["H"], s["I"], num_experts=s["E"], top_k=s["k"], capacity_factor=None,
                        balance_loss="topk_share", activation="relu_gated",
                        router_input="block_input", experts_held=(lo, hi))
            params = {"gate": lw["router"], "wi_gate": lw["w_gate"][lo:hi],
                      "wi_up": lw["w_up"][lo:hi], "wo": lw["w_down"][lo:hi]}
            out, _, rows = layer.dropless_forward(params, u, layer.route(params, x))
            np.testing.assert_array_equal(np.asarray(rows), np.asarray(load, np.int32))
            total = total + out.reshape(-1, s["H"])
            sr = ref.sizes(dict(whole, moe_num_primary_experts=held,
                                share={"published": {"moe_num_primary_experts": 16}},
                                assumed=dict(cfg["assumed"], share_rank=rank)))
            assert (sr["lo"], sr["Eh"], sr["E"]) == (lo, held, 16)
            mine = {k: (v[lo:hi] if k.startswith("w_") else v) for k, v in lw.items()}
            ref_total = ref_total + ref.held_experts(u.reshape(-1, s["H"]), weight, mine, sr)
        assert close(total, routed, rel=1e-5)
        assert close(ref_total, routed, rel=1e-5)


def published(**changes):
    from deepspeed_tpu.models.smallthinker import _FLAGS, _PRESETS
    return {**_FLAGS, **_PRESETS["smallthinker-21b-a3b"], **changes}


def test_what_the_configuration_maps_to_and_refuses():
    kw = config_kwargs(published())
    assert (kw["head_size"], kw["num_heads"], kw["num_kv_heads"], kw["activation"]) \
        == (128, 28, 4, "relu_gated")
    assert kw["attn_windows"][:5] == (0, 4096, 4096, 4096, 0) and kw["rope_layers"] == "windowed"
    assert (kw["moe"].num_experts, kw["moe"].top_k, kw["moe"].router_input,
            kw["moe"].capacity_factor, kw["moe"].aux_loss_coef) == (64, 6, "block_input", None, 0.0)
    assert get_architecture("smallthinker").config_fn is config_kwargs
    # a cut in depth reads both layouts from their start
    assert config_kwargs(published(num_hidden_layers=4))["attn_windows"] == (0, 4096, 4096, 4096)
    assert layout(4, 6) == (0, 1, 1, 1, 0, 1)
    for bad in (dict(rope_scaling={"type": "yarn"}), dict(rope_layout=(1,) * 52),
                dict(moe_primary_router_apply_softmax=False), dict(norm_topk_prob=False),
                dict(sliding_window_layout=(0, 2) * 26), dict(tie_word_embeddings=True)):
        with pytest.raises(NotImplementedError, match=next(iter(bad))):
            config_kwargs(published(**bad))
    # the published count: 52 x (20.97 M + 0.16 M + 64 x 5.898 M) + 2 x 388.96 M
    count = TransformerConfig(**kw).num_parameters()
    assert abs(count - 21.5e9) < 0.05e9


def test_the_router_ahead_is_refused_where_it_is_not_run():
    """The expert layer's capacity path, the four other forms of a block, an
    unknown activation and a dense ReLU-gated MLP say so by name; the three
    consumers that compute less than ``loss`` refuse through ``require``
    (tests/unit/models/test_consumer_seam.py has those three cases)."""
    from deepspeed_tpu.ops.transformer import pallas_moe
    with pytest.raises(ValueError, match="router_input='block_input'.*no-drop"):
        MoE(16, 32, router_input="block_input")
    with pytest.raises(ValueError, match="router_input 'attention'"):
        MoE(16, 32, capacity_factor=None, router_input="attention")
    with pytest.raises(ValueError, match="activation 'swish' is none of"):
        MoE(16, 32, activation="swish")
    ahead = MoEConfig(num_experts=4, top_k=2, capacity_factor=None, router_input="block_input")
    base = dict(vocab_size=64, max_seq_len=32, num_layers=2, num_heads=2, hidden_size=16,
                position="rope", norm="rmsnorm", activation="silu_gated", moe=ahead)
    for form in (dict(norm_style="post", position="learned"), dict(parallel_block=True),
                 dict(farskip=True), dict(residual_streams=2, hc_sinkhorn_iters=3)):
        with pytest.raises(NotImplementedError, match="moe.router_input='block_input'"):
            TransformerLM(TransformerConfig(**{**base, **form}))
    with pytest.raises(ValueError, match="relu_gated.*expert"):
        TransformerLM(TransformerConfig(**{**base, "moe": None, "activation": "relu_gated"}))
    # a layer that routes ahead has to be HANDED its routing, and one that
    # does not may not be
    layer = MoE(16, 32, num_experts=4, capacity_factor=None, router_input="block_input")
    x = jnp.zeros((1, 8, 16))
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="by the caller, before the token mixer"):
        jax.eval_shape(layer.dropless_forward, params, x)
    plain = dataclasses.replace(layer, router_input="ffn_input")
    with pytest.raises(ValueError, match="from the experts' own input"):
        jax.eval_shape(lambda p, x: plain.dropless_forward(p, x, plain.route(p, x)), params, x)
    assert not pallas_moe.moe_kernel_supported(
        top_k=2, activation="relu_gated", dtype=jnp.bfloat16, tokens=256, num_experts=8,
        hidden=128)
    assert pallas_moe.moe_kernel_supported(
        top_k=2, activation="silu_gated", dtype=jnp.bfloat16, tokens=256, num_experts=8,
        hidden=128)


def test_first_step_through_initialize_and_what_the_counters_say(parts, wanted):
    """``initialize`` -> ``train_batch`` in float32 (a row a device of the
    mesh): the step's loss is the reference's, and the counters carry the new
    keys (docs/OBSERVABILITY.md). The preset's bfloat16 step under its limits,
    and the fp8 control over them, is ``benchmark/limits.py``'s on one device
    (the readings are in the preset's file). Runs last: it owns the engine."""
    import deepspeed_tpu
    _, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype="float32")
    rows = jnp.concatenate([ids, ids])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=adapter.to_program(w), config={
            "train_micro_batch_size_per_gpu": 1, "zero_optimization": {"stage": 1},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    assert engine.attn_totals["group"] == 7
    assert (engine.attn_totals["layers_full"], engine.attn_totals["layers_window"]) == (1, 3)
    assert (engine.moe_totals["router_input"], engine.moe_totals["activation"]) \
        == ("block_input", "relu_gated")
    loss = float(engine.train_batch({"input_ids": np.asarray(rows)}))
    assert loss == pytest.approx(float(wanted[0]), rel=1e-4)
    counted = engine.moe_expert_rows()
    assert counted.shape == (4, 8) and (counted.sum(1) < 8 * 64 * 3).all()
    assert engine.moe_totals["path"] == "dropless" and engine.moe_totals["experts_held"] == 8
