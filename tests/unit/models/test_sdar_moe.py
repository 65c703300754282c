"""SDAR-30B-A3B-Chat's block (``model_type`` ``sdar_moe``) under the
block-diffusion objective at the tiny preset, on the CPU: loss and every
gradient against the plain reference (benchmark/reference/sdar_moe.py) for
rows of several documents and two block lengths; the first step through
``initialize`` -> ``train_batch``; the four properties of the mask on both
routes; what the loss counts and how it weights; the noise as a function of
the batch; the shares of a layer that several chips divide add up to the
whole layer under the softmax router; the published stack's 48 layers; and the
paths that refuse the objective by name."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from deepspeed_tpu.models import sdar_moe_model
from deepspeed_tpu.models.registry import get_architecture
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.ops.transformer import attention
from tests.benchmark.helpers import DATA

MANIFEST = os.path.join(DATA, "BENCHMARK.sdar-tiny.json")
F32 = jnp.float32


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(MANIFEST, "sdar-tiny.train")


@pytest.fixture(scope="module")
def parts(cell):
    """(reference module, adapter module, configuration, weights, ids): rows
    of 64, three of the eight cut into documents whose lengths are no
    multiple of a block (a block cut by a document's end, a document of one
    token, a document that starts a row's last block)."""
    ref = cell.load_module("reference", cell.config["reference"])
    adapter = cell.load_module("adapters", cell.config["adapter"])
    w = ref.make_weights(ref.key_of(7), cell.config, F32)
    ids = np.random.default_rng(0).integers(0, cell.config["vocab_size"] - 1, (8, 64))
    sep = cell.config["assumed"]["separator"]
    ids[1, [9, 41]] = sep
    ids[2, [30, 31, 62]] = sep
    ids[5, [0, 5, 22]] = sep
    return ref, adapter, cell.config, w, jnp.asarray(ids, jnp.int32)


def close(a, b, rel=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-12)


@pytest.mark.parametrize("block_length", [4, 16])
def test_loss_and_gradient_match_the_reference(parts, block_length):
    """float32 against float32 at ``highest``: the loss to 1e-5 (one
    reduction order apart, and a masked position's weight 1 / t reaches
    hundreds), every gradient to 2e-4 of its largest element (QK-norm divides
    by a head's own RMS, which amplifies a last-bit difference), the first
    denoising pass's logits to 1e-4. Both draw the same noise from the ids,
    each in its own code."""
    ref, adapter, cfg, w, ids = parts
    cfg = dict(cfg, assumed=dict(cfg["assumed"], block_length=block_length))
    model = adapter.model(cfg, remat=True, dtype="float32")
    assert model.config.block_length == block_length and model.rows_per_token == 2
    assert model.scan_plan == (((0, True),), 2, ())
    # (each side ONE jitted program: op by op this is a thousand compiles)
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
    assert close(logits, jax.jit(lambda p: ref.forward(p, ids, cfg))(w), rel=1e-4)
    noised, weights, masked = model.noise({"input_ids": ids})
    ref_noised, ref_weights = ref.noise(ids, ref.sizes(cfg))
    np.testing.assert_array_equal(np.asarray(noised), np.asarray(ref_noised))
    np.testing.assert_array_equal(np.asarray(weights), np.asarray(ref_weights))
    # the reference in blocks (what runs at 2 x 8,192 rows) is the reference
    blocked = jax.jit(lambda p: ref.loss_and_gradient(p, ids, cfg)[0])(w)
    assert float(blocked) == pytest.approx(float(want), rel=1e-6)


def test_the_reference_scores_queries_in_blocks(parts, monkeypatch):
    """At the cell's size the reference scores 256 queries at a time against
    the clean keys and their own positions' noised keys: the same numbers as
    the whole 2 L x 2 L mask at once (here blocks of 16 over rows of 64)."""
    ref, _, cfg, w, ids = parts
    whole, whole_g = jax.jit(jax.value_and_grad(
        lambda p: ref.next_token_loss(p, ids[:3], cfg, checkpoint=False)))(w)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    blocked, blocked_g = jax.jit(jax.value_and_grad(
        lambda p: ref.next_token_loss(p, ids[:3], cfg, checkpoint=True)))(w)
    assert float(blocked) == pytest.approx(float(whole), rel=1e-6)
    assert all(close(blocked_g[k], whole_g[k], rel=1e-5) for k in w)


def test_first_step_through_initialize(parts):
    """``initialize`` -> ``train_batch`` in float32: the step's loss and
    gradient norm are the reference's, every weight moves against the
    reference's gradient, the held experts' rows are the reference's count
    over BOTH copies' rows, and the counters say what ran."""
    import deepspeed_tpu
    ref, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype="float32")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=adapter.to_program(w), config={
            "train_micro_batch_size_per_gpu": 1,
            "zero_optimization": {"stage": 1},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3, "weight_decay": 0.1}}})
    assert engine.diffusion_totals == {"block_length": 4, "rows_per_token": 2,
                                       "steps": 0, "route": None, "dq": None,
                                       "layout": None}
    assert engine.diffusion_last_step() is None
    loss = float(engine.train_batch({"input_ids": np.asarray(ids)}))
    want, gnorm, signs = jax.jit(lambda p: ref.loss_and_gradient(p, ids, cfg))(w)
    assert loss == pytest.approx(float(want), rel=1e-5)
    assert float(engine.get_global_grad_norm()) == pytest.approx(float(gnorm), rel=1e-4)
    new = adapter.from_program(engine.state["opt"]["master"])
    # a fifth of the router's gradient is 0 but for rounding (1e-14 where the
    # median is 5e-9: the chosen probabilities over their own sum do not move
    # with a logit that was not chosen), with a sign of its own in either
    # program: an element counts from a millionth of its leaf's largest
    grads = jax.jit(jax.grad(lambda p: ref.next_token_loss(p, ids, cfg)))(w)
    wrong = total = 0
    for name, s in signs.items():
        g = np.abs(np.asarray(grads[name]))
        real = (np.asarray(s) != 0) & (g > 1e-6 * g.max())
        moved = np.sign(np.asarray(new[name], np.float64) - np.asarray(w[name], np.float64))
        wrong += np.sum((moved + np.asarray(s) != 0) & real)
        total += np.sum(real)
    assert wrong / total < 2e-3 and total > 0.75 * sum(v.size for v in w.values())
    load = np.asarray(ref.router_load(w, ids, cfg))
    rows = engine.moe_expert_rows()
    assert rows.shape == (2, 8) and int(load.sum()) == 2 * 2 * ids.size * 3
    np.testing.assert_array_equal(rows, load[:, :8].astype(np.int32))
    assert engine.diffusion_totals == {"block_length": 4, "rows_per_token": 2,
                                       "steps": 1, "route": "xla", "dq": None,
                                       "layout": None}
    _, weights, masked = model.noise({"input_ids": ids})
    last = engine.diffusion_last_step()
    assert last["masked_share"] == pytest.approx(float(jnp.mean(masked)), rel=1e-6)
    assert last["mean_weight"] == pytest.approx(
        float(jnp.sum(weights) / jnp.sum(masked)), rel=1e-5)
    assert {k: engine.moe_totals[k] for k in ("path", "experts_published", "experts_held")} \
        == {"path": "dropless", "experts_published": 16, "experts_held": 8}
    # the products are counted over both copies' rows
    assert engine.moe_totals["products_xla"]["forward"] == 2 * 3


def _attention_case(route, monkeypatch):
    """q, k, v of 2 x 32 rows (clean, then noised) over 4 query heads and 2
    key heads of 16, documents that end at 9 and 22, b 4; ``run`` on the
    given route."""
    monkeypatch.setenv("DSTPU_ATTN", route)
    L, b = 32, 4
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2 * L, h, 16)), F32) for h in (4, 2, 2))
    ends = np.zeros((1, L), np.int32)
    ends[0, [9, 22]] = 1
    doc = jnp.asarray(np.cumsum(ends, 1) - ends)
    run = lambda q, k, v: np.asarray(attention.blockdiff_attention(q, k, v, b, doc))[0]
    return L, q, k, v, run


def _changed(q, k, v, row):
    """The same operands with the token at ``row`` (of the 2 L) replaced."""
    bump = lambda a, by: a.at[0, row].add(by)
    return bump(q, 1.0), bump(k, -2.0), bump(v, 3.0)


#: (what changes: half and position; the rows that must NOT move; a row that
#: MUST move), positions of the first document (0..9), blocks of 4
MASK_PROPERTIES = {
    # a clean token of a LATER block leaves every earlier block's outputs alone
    # (nor the noised queries of its own block; the noised queries of the
    # block after it see it)
    "later-block": (("clean", 4), lambda L: list(range(0, 4)) + list(range(L, L + 8)),
                    lambda L: L + 8),
    # a later token of the SAME block moves the clean query before it:
    # block-causal, not causal (a causal mask would leave row 4 alone)
    "same-block": (("clean", 6), lambda L: list(range(0, 4)) + list(range(L, L + 8)),
                   lambda L: 4),
    # a noised token moves no clean output
    "noised-token": (("noised", 5), lambda L: list(range(0, L)), lambda L: L + 4),
    # the clean token AT a noised query's own position (and anywhere in its
    # block) leaves that query's output alone
    "own-position": (("clean", 5), lambda L: [L + 4, L + 5, L + 6, L + 7],
                     lambda L: 5),
}


@pytest.mark.parametrize("route", ["xla", "pallas"])
@pytest.mark.parametrize("name", sorted(MASK_PROPERTIES))
def test_the_masks_properties(name, route, monkeypatch):
    """Four properties of the block-diffusion mask, on the XLA route and on
    the kernel route (interpret mode): which outputs a changed token may
    move. An unmoved row is bit for bit the same."""
    (half, at), still, moves = MASK_PROPERTIES[name]
    L, q, k, v, run = _attention_case(route, monkeypatch)
    before = run(q, k, v)
    after = run(*_changed(q, k, v, at + (L if half == "noised" else 0)))
    rows = still(L)
    np.testing.assert_array_equal(after[rows], before[rows])
    assert np.abs(after[moves(L)] - before[moves(L)]).max() > 1e-3
    # and nothing crosses a document's end: position 8 is the first
    # document's, position 10 the second's
    other = run(*_changed(q, k, v, 8))
    np.testing.assert_array_equal(other[10:L], before[10:L])
    np.testing.assert_array_equal(other[L + 10:], before[L + 10:])


def test_the_loss_counts_masked_positions_and_weights_them(parts):
    """The objective by hand: t and the Bernoulli draws made here by the
    recipe, the logits from the program's own trunk and head: only a masked
    position counts, each 1 / t of its block, over rows x L."""
    _, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=False, dtype="float32")
    params = adapter.to_program(w)
    key = jax.random.PRNGKey(42)
    batch = {"input_ids": ids, "noise_key": key}
    noised, weights, masked = model.noise(batch)
    key_t, key_mask = jax.random.split(key)
    t = np.repeat(np.asarray(jax.random.uniform(key_t, (8, 16), F32, 1e-3, 1.0)), 4, axis=1)
    drawn = np.asarray(jax.random.uniform(key_mask, (8, 64), F32)) < t
    np.testing.assert_array_equal(np.asarray(masked), drawn)
    np.testing.assert_array_equal(np.asarray(noised), np.where(drawn, 256, np.asarray(ids)))
    np.testing.assert_allclose(np.asarray(weights), np.where(drawn, 1.0 / t, 0.0), rtol=1e-6)
    assert 0.3 < drawn.mean() < 0.7 and (t >= 1e-3).all() and (t < 1).all()
    # a block's positions share their t
    assert (t.reshape(8, 16, 4) == t.reshape(8, 16, 4)[:, :, :1]).all()
    with jax.default_matmul_precision("highest"):
        x = model._trunk(params, ids, None, None, None, None, with_mtp=False,
                         noised_ids=noised)[0]
        logp = np.asarray(jax.nn.log_softmax(model.head(params, x), axis=-1), np.float64)
        loss, stats = model.loss_and_stats(params, batch)
    nll = -np.take_along_axis(logp, np.asarray(ids)[..., None], axis=-1)[..., 0]
    by_hand = np.sum(np.where(drawn, nll / t, 0.0)) / ids.size
    assert float(loss) == pytest.approx(by_hand, rel=1e-5)
    assert float(stats["diffusion_masked_share"]) == pytest.approx(drawn.mean(), rel=1e-6)
    assert float(stats["diffusion_mean_weight"]) == pytest.approx(
        (1.0 / t)[drawn].mean(), rel=1e-5)
    # the head and the loss are vocab_size wide; the embedding has the mask row
    assert logp.shape == (8, 64, 256)
    assert params["wte"]["embedding"].shape == (257, 64)
    with pytest.raises(ValueError, match="no labels"):
        model.loss(params, dict(batch, labels=ids))


def test_the_noise_is_a_function_of_the_batch(parts):
    """The same batch gives the same noise, another batch (one id changed)
    another; ``batch["noise_key"]`` overrides the ids' key."""
    _, adapter, cfg, _, ids = parts
    model = adapter.model(cfg, remat=False, dtype="float32")
    same = [np.asarray(model.noise({"input_ids": ids})[1]) for _ in range(2)]
    np.testing.assert_array_equal(*same)
    other = np.asarray(model.noise({"input_ids": ids.at[7, 63].add(1)})[1])
    assert (other != same[0]).mean() > 0.2
    key = jax.random.PRNGKey(9)
    given = np.asarray(model.noise({"input_ids": ids, "noise_key": key})[1])
    again = np.asarray(model.noise({"input_ids": ids.at[0, 0].add(1), "noise_key": key})[1])
    assert (given != same[0]).mean() > 0.2
    np.testing.assert_array_equal(given, again)
    # f(ids): the sum of id x (2 x index + 1) in uint32, shifted right one bit
    flat = np.asarray(ids, np.uint64).reshape(-1)
    f = int((flat * (2 * np.arange(flat.size, dtype=np.uint64) + 1)).sum() % 2 ** 32) >> 1
    want = jax.random.fold_in(jax.random.PRNGKey(cfg["assumed"]["noise_seed"]), f)
    np.testing.assert_array_equal(np.asarray(model.noise_key({"input_ids": ids})),
                                  np.asarray(want))


def test_the_shares_add_up_to_the_whole_layer(parts):
    """Eight ranks of two experts each under the SOFTMAX router: the held
    ranges' parts (program, each on its own weight stacks) add up to the
    uncut reference's whole expert layer (no shared expert to count once);
    and the reference given each rank's share adds up the same way."""
    ref, _, cfg, _, _ = parts
    chips = 8
    whole = {k: v for k, v in cfg.items() if k != "share"}
    whole["num_experts"] = cfg["share"]["published"]["num_experts"]
    s = ref.sizes(whole)
    w = ref.make_weights(ref.key_of(3), whole, F32)
    lw = {k: w[k][1] for k in ("router", "w_gate", "w_up", "w_down")}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, s["H"]), F32)
    h = x.reshape(-1, s["H"])
    with jax.default_matmul_precision("highest"):
        weight, load = ref.route(h, lw["router"], s)
        routed = ref.held_experts(h, weight, lw, s)
    assert s["Eh"] == s["E"] == 16 and int(load.sum()) == 48 * s["k"]
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 1.0, rtol=1e-6)   # norm_topk_prob
    held = s["E"] // chips
    total = ref_total = 0
    for rank in range(chips):
        lo, hi = rank * held, (rank + 1) * held
        layer = MoE(s["H"], s["I"], num_experts=s["E"], top_k=s["k"], capacity_factor=None,
                    balance_loss="topk_share", router="softmax", normalize_weights=True,
                    experts_held=(lo, hi))
        params = {"gate": lw["router"], "wi_gate": lw["w_gate"][lo:hi],
                  "wi_up": lw["w_up"][lo:hi], "wo": lw["w_down"][lo:hi]}
        out, _, rows = layer.dropless_forward(params, x)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(load, np.int32))
        total = total + out.reshape(-1, s["H"])
        share = {"chips_sharing_a_layer": chips, "published": {"num_experts": 16}, "held": "x"}
        sr = ref.sizes(dict(whole, num_experts=held, share=share,
                            assumed=dict(cfg["assumed"], share_rank=rank)))
        assert (sr["lo"], sr["Eh"], sr["E"]) == (lo, held, 16)
        mine = {k: (v[lo:hi] if k.startswith("w_") else v) for k, v in lw.items()}
        ref_total = ref_total + ref.held_experts(h, weight, mine, sr)
    assert close(total, routed, rel=1e-5)
    assert close(ref_total, routed, rel=1e-5)


def test_the_published_depth_builds(cell):
    """All 48 layers at tiny widths through the registry: one kind of layer,
    one scan of 48; every layer an expert layer; the embedding one row more
    than the head."""
    hf = dict(cell.config, num_hidden_layers=48, num_experts=16, vocab_size=512)
    kw = get_architecture("sdar_moe").config_fn(hf)
    model = TransformerLM(TransformerConfig(**kw, dtype=F32))
    assert model.scan_plan == (((0, True),), 48, ()) and model.moe_path == "dropless"
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert shapes["blocks"]["moe"]["wi_gate"].shape == (48, 16, 64, 16)
    assert shapes["blocks"]["moe"]["gate"].shape == (48, 64, 16)
    assert shapes["blocks"]["q_norm"]["scale"].shape == (48, 16)
    assert shapes["wte"]["embedding"].shape == (513, 64)
    assert shapes["lm_head"]["kernel"].shape == (64, 512)
    assert "bias" not in shapes["blocks"]["q_proj"] and "dense_blocks" not in shapes
    c = model.config
    assert (c.objective, c.block_length, c.mask_token_id) == ("block_diffusion", 4, 512)
    assert c.moe.router == "softmax" and c.moe.normalize_weights and not c.moe.aux_loss_coef


def test_what_the_configuration_maps_to_and_refuses(cell):
    config_fn = get_architecture("sdar_moe").config_fn
    for key, value in (("use_sliding_window", True), ("rope_scaling", {"factor": 2}),
                       ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
                       ("attention_bias", True), ("tie_word_embeddings", True),
                       ("hidden_act", "gelu")):
        with pytest.raises(NotImplementedError, match=key):
            config_fn(dict(cell.config, **{key: value}))
    assert sdar_moe_model("sdar-tiny", experts_held=(8, 16)).config.moe.experts_held == (8, 16)
    base = sdar_moe_model("sdar-tiny").config
    for bad, why in ((dict(block_length=6), "power of two"),
                     (dict(mask_token_id=None), "mask_token_id"),
                     (dict(tie_embeddings=True), "block_diffusion"),
                     (dict(attn_windows=8), "block_diffusion"),
                     (dict(objective="denoise"), "objective")):
        with pytest.raises(ValueError, match=why):
            TransformerLM(dataclasses.replace(base, **bad))


def test_paths_that_cannot_take_the_objective_refuse_it_by_name():
    """One block at a time (the ZeRO-3 pipelined scan, parameter streaming),
    ``PipelineModule`` and the ragged serving engine."""
    from deepspeed_tpu.inference.v2.model import RaggedInferenceModel
    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    model = sdar_moe_model("sdar-tiny", dtype=F32)
    block = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a[0], model.init(jax.random.PRNGKey(0))["blocks"]))
    x = jax.ShapeDtypeStruct((1, 16, 64), F32)
    with pytest.raises(NotImplementedError, match="block_diffusion"):
        jax.eval_shape(lambda b, x: model.block_apply(b, x, jnp.arange(16)[None]), block, x)
    with pytest.raises(NotImplementedError, match="block_diffusion"):
        PipelineModule(model.config, num_stages=2)
    with pytest.raises(NotImplementedError, match="block_diffusion"):
        RaggedInferenceModel(model, 16, 4)
