"""Keye-VL-2.0-30B-A3B's language-model block (``model_type`` ``KeyeVL2``) at
the tiny preset, on the CPU: both terms of the loss and every gradient against
the plain reference (benchmark/reference/keye_vl2.py) on the XLA route and on
the kernel route in interpret mode; the program's selection equal to the
reference's, with rows of fewer than ``topk`` visible keys, several documents
and a tie; the gradients of the two terms kept apart, exactly; ``topk`` >= L
is plain ``mha``; rope by sections; the first step through ``initialize`` ->
``train_batch``; the shares of a layer that several chips divide add up to the
whole layer; the published stack's 48 layers; and the paths that refuse the
mechanism by name."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from deepspeed_tpu.models import keye_vl2_model
from deepspeed_tpu.models.registry import get_architecture
from deepspeed_tpu.models.transformer import (IndexerConfig, TransformerConfig,
                                              TransformerLM)
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.ops.transformer import attention
from tests.benchmark.helpers import DATA

MANIFEST = os.path.join(DATA, "BENCHMARK.keye-vl2-tiny.json")
F32 = jnp.float32
INDEXER = ("idx_wq", "idx_wk", "idx_ww", "idx_ln_g", "idx_ln_b")


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(MANIFEST, "keye-vl2-tiny.train")


@pytest.fixture(scope="module")
def parts(cell):
    """(reference module, adapter module, configuration, weights, ids): rows
    of 64 under ``topk`` 8: a row of one document, rows of two and three (a
    document of one token, a document shorter than ``topk``)."""
    ref = cell.load_module("reference", cell.config["reference"])
    adapter = cell.load_module("adapters", cell.config["adapter"])
    w = ref.make_weights(ref.key_of(7), cell.config, F32)
    ids = np.random.default_rng(0).integers(0, cell.config["vocab_size"] - 1, (4, 64))
    sep = cell.config["assumed"]["separator"]
    ids[1, [20]] = sep
    ids[2, [5, 6, 40]] = sep
    ids[3, [0, 59]] = sep
    return ref, adapter, cell.config, w, jnp.asarray(ids, jnp.int32)


@pytest.fixture(scope="module")
def wanted(parts):
    """The reference over the module's rows, each ONE jitted program for the
    routes and tests that read it: loss and gradient whole, the two terms, the
    logits, and in blocks a layer at a time (what runs at 16,384 rows)."""
    ref, _, cfg, w, ids = parts
    return {"whole": jax.jit(jax.value_and_grad(lambda p: ref.next_token_loss(p, ids, cfg)))(w),
            "terms": jax.jit(lambda p: ref.loss_terms(p, ids, cfg))(w),
            "logits": jax.jit(lambda p: ref.forward(p, ids, cfg))(w),
            "blocked": jax.jit(lambda p: ref.loss_and_gradient(p, ids, cfg))(w)}


@pytest.fixture(scope="module")
def step_wanted(parts):
    """The batch the two engines step on (a row a device of the test mesh) and
    the reference over it: (batch, (loss, norm, signs), (L_LM, L_I))."""
    ref, _, cfg, w, ids = parts
    batch = {"input_ids": np.concatenate([np.asarray(ids), np.asarray(ids)[:, ::-1]]
                                         )[:jax.device_count()]}
    rows = jnp.asarray(batch["input_ids"])
    return (batch, jax.jit(lambda p: ref.loss_and_gradient(p, rows, cfg))(w),
            jax.jit(lambda p: ref.loss_terms(p, rows, cfg))(w))


def close(a, b, rel=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-12)


@pytest.mark.parametrize("route", ["xla", "pallas"])
def test_loss_and_gradient_match_the_reference(parts, wanted, route, monkeypatch):
    """float32 against float32 at ``highest``: L_LM and L_I each to 1e-5, every
    leaf's gradient (the indexer's five among them) to 2e-4 of its largest
    element, the logits to 1e-4; on the XLA route and with the flash pair
    reading the selection's operand (interpret mode)."""
    ref, adapter, cfg, w, ids = parts
    monkeypatch.setenv("DSTPU_ATTN", route)
    model = adapter.model(cfg, remat=True, dtype="float32")
    assert model.config.indexer == IndexerConfig(heads=2, head_dim=8, topk=8)
    assert model.config.rope_sections == (2, 3, 3) and model.scan_plan == (((0, True),), 2, ())
    plan = model._mixer.plan(*ids.shape)
    assert plan.route == ("kernel" if route == "pallas" else "xla")
    # the KL beside it: through the Pallas pair where the flash pair is a kernel
    assert model.attention_records(*ids.shape)[0]["dsa"]["kl"] == plan.route
    (want, want_g), (lm, kl) = wanted["whole"], wanted["terms"]
    with jax.default_matmul_precision("highest"):
        (got, stats), got_g = jax.jit(jax.value_and_grad(
            lambda p: model.loss_and_stats(p, {"input_ids": ids}), has_aux=True))(
                adapter.to_program(w))
        (logits, aux), plain = jax.jit(lambda p: (
            model.apply(p, ids), model.loss(p, {"input_ids": ids})))(adapter.to_program(w))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(plain) == pytest.approx(float(want), rel=1e-5)
    assert float(stats["attn_lm_loss"]) == pytest.approx(float(lm), rel=1e-5)
    assert float(stats["attn_indexer_kl"]) == pytest.approx(float(kl), rel=1e-5)
    assert float(aux[1]) == pytest.approx(float(kl), rel=1e-5) and float(kl) > 0.01
    flat = adapter.from_program(got_g)
    assert set(flat) == set(w) and set(INDEXER) <= set(w)
    for name, g in want_g.items():
        assert close(flat[name], g), name
        assert float(jnp.max(jnp.abs(g))) > 0, name
    assert close(logits, wanted["logits"], rel=1e-4)
    # the reference in blocks, a layer at a time (what runs at 16,384 rows)
    blocked = wanted["blocked"]
    assert float(blocked[0]) == pytest.approx(float(want), rel=1e-6)
    norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in want_g.values())))
    assert float(blocked[1]) == pytest.approx(norm, rel=1e-5)
    # selected over visible pairs: a query with v visible keys picks min(v, 8)
    sep = cfg["assumed"]["separator"]
    lengths = [n for row in np.asarray(ids) for n in np.diff(np.r_[
        -1, np.flatnonzero(row == sep), 63 if row[-1] != sep else []]) if n]
    visible = sum(n * (n + 1) // 2 for n in lengths)
    assert sum(lengths) == ids.size
    assert float(stats["attn_selected_share"]) == pytest.approx(
        ref.dsa_pairs(lengths, 8) / visible, rel=1e-6)


def test_the_reference_scores_queries_in_blocks(parts, monkeypatch):
    ref, _, cfg, w, ids = parts
    whole, whole_g = jax.jit(jax.value_and_grad(
        lambda p: ref.next_token_loss(p, ids[:3], cfg, checkpoint=False)))(w)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    blocked, blocked_g = jax.jit(jax.value_and_grad(
        lambda p: ref.next_token_loss(p, ids[:3], cfg, checkpoint=True)))(w)
    assert float(blocked) == pytest.approx(float(whole), rel=1e-6)
    assert all(close(blocked_g[k], whole_g[k], rel=1e-5) for k in w)


def test_the_selection_is_the_references(parts):
    """Layer 0's selected set, pair for pair: rows of one, two and three
    documents; every query with fewer than ``topk`` visible keys picks them
    all, every other exactly ``topk``, none outside its document or ahead."""
    ref, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=False, dtype="float32")
    params = adapter.to_program(w)
    def layer_0(params):
        block = jax.tree.map(lambda a: a[0], params["blocks"])
        x, _ = model.embed(params, ids)
        h = model._layer("ln_1")(block["ln_1"], x)
        return model._mixer.selection(block, h, jnp.arange(64)[None], model._documents(ids))[3]
    with jax.default_matmul_precision("highest"):
        want, want1 = (np.asarray(a) for a in jax.jit(lambda p: (
            ref.selection(p, ids, cfg, 0), ref.selection(p, ids, cfg, 1)))(w))
        packed = jax.jit(layer_0)(params)
        got = np.asarray(attention.unpack_selection(packed, 64))
    assert packed.dtype == jnp.int8 and packed.shape == (ids.shape[0], 8, 64)
    np.testing.assert_array_equal(got, want)
    doc = np.asarray(model._documents(ids))
    seen = (np.arange(64)[None, :] <= np.arange(64)[:, None])[None] & (
        doc[:, :, None] == doc[:, None, :])
    assert not (got & ~seen).any()
    np.testing.assert_array_equal(got.sum(-1), np.minimum(seen.sum(-1), 8))
    assert (seen.sum(-1) < 8).any() and (seen.sum(-1) > 8).any()
    # layer 1's too, behind a whole layer
    assert want1.shape == want.shape and (want1 != want).any()


@pytest.mark.parametrize("how", sorted(attention.THRESHOLDS))
def test_select_topk_is_exact_with_ties_and_short_rows(how):
    """`select_topk` against a sort by (score descending, s ascending): ties AT
    the threshold go to the lower s (here 0.0 four times over, a -0.0 among
    them, and a score repeated), a row with fewer visible keys than k picks
    them all, an invisible key of the largest score is never picked."""
    rng = np.random.default_rng(3)
    scores = rng.normal(size=(2, 24, 24)).astype(np.float32)
    scores[0, 12, [1, 4, 6, 9]] = [0.0, -0.0, 0.0, 0.0]
    scores[0, 12, [0, 2, 3, 5, 7, 8, 10, 11, 12]] = [3, 2, -1, -2, 1, -3, -4, -5, -6]
    scores[1, 20, :8] = 0.5
    scores[1, 23, 22] = scores[1, 23, 3]
    docs = jnp.asarray(np.r_[np.zeros(15), np.ones(9)][None].repeat(2, 0), jnp.int32)
    seen = attention.causal_in_document(jnp.arange(24), docs, docs)
    scores[:, :, -1] = np.where(np.asarray(seen)[:, :, -1], scores[:, :, -1], 99.0)
    got = np.asarray(attention.select_topk(jnp.asarray(scores), seen, 5, how))
    want = np.zeros_like(got)
    for b, t in np.ndindex(2, 24):
        order = sorted((s for s in range(24) if seen[b, t, s]),
                       key=lambda s: (-scores[b, t, s], s))[:5]
        want[b, t, order] = True
    np.testing.assert_array_equal(got, want)
    assert list(np.flatnonzero(got[0, 12])) == [0, 1, 2, 4, 7]     # 3, 2, 1, then 0.0 twice
    assert list(np.flatnonzero(got[1, 20])) == [15, 16, 17, 18, 19] or got[1, 20].sum() == 5
    # k at or over the row's length: what is visible
    np.testing.assert_array_equal(
        np.asarray(attention.select_topk(jnp.asarray(scores), seen, 24, how)), np.asarray(seen))


def test_the_two_losses_reach_disjoint_leaves_exactly(parts):
    """d L_LM / d (indexer leaves) = 0 and d L_I / d (every other leaf) = 0,
    exactly: the selection has no gradient, the indexer reads
    ``stop_gradient(h)``, and the KL's target is data."""
    ref, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype="float32")
    term = lambda name: lambda p: model.loss_and_stats(p, {"input_ids": ids})[1][name]
    params = adapter.to_program(w)
    lm, kl = (adapter.from_program(g) for g in jax.jit(lambda p: (
        jax.grad(term("attn_lm_loss"))(p), jax.grad(term("attn_indexer_kl"))(p)))(params))
    for name in w:
        mine, other = (kl, lm) if name in INDEXER else (lm, kl)
        assert float(jnp.max(jnp.abs(other[name]))) == 0.0, name
        assert float(jnp.max(jnp.abs(mine[name]))) > 0.0, name
    # and the reference says the same of its own two terms
    ref_lm, ref_kl = jax.jit(lambda p: tuple(
        jax.grad(lambda p: ref.loss_terms(p, ids, cfg)[i])(p) for i in (0, 1)))(w)
    for name in w:
        other = ref_lm if name in INDEXER else ref_kl
        assert float(jnp.max(jnp.abs(other[name]))) == 0.0, name


def test_topk_at_the_rows_length_is_plain_mha(parts):
    """With every visible key picked the language-model loss is the plain
    grouped-head model's over the same weights, and the selected share is 1."""
    _, adapter, cfg, w, ids = parts
    wide = dict(cfg, sa_config=dict(cfg["sa_config"], topk=64))
    model = adapter.model(wide, remat=False, dtype="float32")
    params = adapter.to_program(w)
    plain = TransformerLM(dataclasses.replace(model.config, indexer=None))
    bare = dict(params, blocks={k: v for k, v in params["blocks"].items()
                                if not k.startswith("indexer")})
    _, stats = model.loss_and_stats(params, {"input_ids": ids})
    assert float(stats["attn_lm_loss"]) == pytest.approx(
        float(plain.loss(bare, {"input_ids": ids})), rel=1e-6)
    assert float(stats["attn_selected_share"]) == 1.0


def test_rope_by_sections(parts):
    """Unequal streams against the reference's rotation; equal streams are
    plain rope, bit for bit; and the loss under unequal streams is the
    reference's under the same."""
    ref, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=False, dtype="float32")
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 64, 4, 16)).astype(np.float32))
    # (streams that differ by more than an offset: rope is relative)
    at = np.arange(64)
    streams = jnp.asarray(np.stack([at, at // 3, at % 5])[:, None].repeat(2, 1), jnp.int32)
    got = model._rotate(x, streams)
    want = ref.rotate(x, ref.by_sections(streams, (2, 3, 3)), 1e7)
    assert close(got, want, rel=1e-6)
    equal = jnp.broadcast_to(jnp.arange(64), (3, 2, 64))
    np.testing.assert_array_equal(np.asarray(model._rotate(x, equal)),
                                  np.asarray(model._rotate(x, jnp.arange(64)[None])))
    assert not close(got, model._rotate(x, equal), rel=1e-3)
    positions = jnp.broadcast_to(streams[:, :1], (3,) + ids.shape)
    with jax.default_matmul_precision("highest"):
        mine, text = jax.jit(lambda p: (
            model.loss(p, {"input_ids": ids, "position_ids": positions}),
            model.loss(p, {"input_ids": ids})))(adapter.to_program(w))
    theirs = jax.jit(lambda p: ref.next_token_loss(p, ids, cfg, positions=positions))(w)
    assert float(mine) == pytest.approx(float(theirs), rel=1e-5)
    assert abs(float(mine) - float(text)) > 1e-4
    with pytest.raises(ValueError, match="position_ids"):
        model.loss(adapter.to_program(w), {"input_ids": ids, "position_ids": positions[:2]})


def test_first_step_through_initialize(parts, step_wanted):
    """``initialize`` -> ``train_batch`` in float32: the step's loss and
    gradient norm are the reference's, every weight moves against the
    reference's gradient, and the counters say what ran."""
    import deepspeed_tpu
    ref, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype="float32")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=adapter.to_program(w), config={
            "train_micro_batch_size_per_gpu": 1,
            "zero_optimization": {"stage": 1},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3, "weight_decay": 0.1}}})
    assert engine.attn_totals["dsa"] == {
        "topk": 8, "indexer_heads": 2, "indexer_head_dim": 8, "route": None,
        "select": None, "select_tiles": None, "select_rows": None, "dq": None,
        "layout": None, "kl": None, "kl_tiles": None, "operand": "bits",
        "operand_bytes": None}
    assert engine.attn_last_step() is None
    batch, (want, _, signs), (lm, kl) = step_wanted
    rows = batch["input_ids"]
    before = adapter.from_program(jax.tree.map(np.asarray, engine.state["opt"]["master"]))
    loss = engine.train_batch(batch)
    assert float(loss) == pytest.approx(float(want), rel=1e-4)
    after = adapter.from_program(engine.state["opt"]["master"])
    wrong = total = 0
    for name, s in signs.items():
        moved = np.sign(np.asarray(after[name], np.float64) - before[name])
        s = np.asarray(s)
        wrong += int(np.sum((moved + s != 0) & (s != 0)))
        total += int(np.sum(s != 0))
    assert wrong / total < 0.01
    assert engine.attn_totals["dsa"]["route"] == "xla" and engine.attn_totals["dsa"]["dq"] is None
    assert engine.attn_totals["dsa"]["kl"] == "xla" and engine.attn_totals["dsa"]["kl_tiles"] is None
    # off the chip the selection is the XLA loop's: no launch, no counts of one
    assert engine.attn_totals["dsa"]["select"] == attention.SELECT_THRESHOLD
    assert engine.attn_totals["dsa"]["select_tiles"] is None
    assert engine.attn_totals["dsa"]["select_rows"] is None
    # a layer's operand over the step's rows: a bit a (query, key) pair
    assert engine.attn_totals["dsa"]["operand_bytes"] == len(rows) * 64 * 64 // 8
    last = engine.attn_last_step()
    assert last["indexer_kl"] == pytest.approx(float(kl), rel=1e-4)
    assert last["lm_loss"] == pytest.approx(float(lm), rel=1e-4)
    assert 0 < last["selected_share"] < 1
    assert engine.moe_totals["experts_published"] == 16 and engine.moe_totals["experts_held"] == 8
    from deepspeed_tpu.telemetry import setup_spans
    flat = setup_spans.flat_totals(attn=engine.attn_totals)
    assert flat["attn.dsa.topk"] == 8 and flat["attn.dsa.select"] == attention.SELECT_THRESHOLD
    assert flat["attn.dsa.operand"] == "bits"
    assert flat["attn.dsa.operand_bytes"] == len(rows) * 64 * 64 // 8


def test_the_kl_counters_on_the_kernel_route(parts, step_wanted, monkeypatch):
    """``DSTPU_ATTN=pallas`` through ``initialize`` -> ``train_batch``: the KL
    takes the Pallas pair, and the first step leaves the tiles one layer's
    forward launch ran of its grid (``[run, of]``; a row of 64 is one tile
    here), which ride in ``engine_totals`` as ``attn.dsa.kl_tiles``."""
    import deepspeed_tpu
    from deepspeed_tpu.telemetry import setup_spans
    ref, adapter, cfg, w, ids = parts
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=adapter.model(cfg, remat=True, dtype="float32"),
        model_parameters=adapter.to_program(w), config={
            "train_micro_batch_size_per_gpu": 1, "zero_optimization": {"stage": 1},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    batch, (want, _, _), _ = step_wanted
    rows = batch["input_ids"]
    loss = engine.train_batch(batch)
    assert float(loss) == pytest.approx(float(want), rel=1e-4)
    dsa = engine.attn_totals["dsa"]
    assert dsa["route"] == dsa["kl"] == "kernel"
    assert dsa["kl_tiles"] == [len(rows), len(rows)]
    flat = setup_spans.flat_totals(attn=engine.attn_totals)
    assert flat["attn.dsa.kl"] == "kernel"
    assert flat["attn.dsa.kl_tiles"] == f"{len(rows)}+{len(rows)}"
    engine.train_batch(batch)       # asked once: a later step moves nothing
    assert engine.attn_totals["dsa"]["kl_tiles"] == [len(rows), len(rows)]


def test_the_shares_add_up_to_the_whole_layer(parts):
    """Two chips' held experts (0-7 and 8-15) on the same rows under the same
    router add up to all sixteen's output, in the program's layer and in the
    reference's: the indexer and the attention are every chip's own, whole."""
    ref, _, cfg, w, ids = parts
    s = ref.sizes(cfg)
    whole = dict(cfg, num_experts=16, num_local_experts=16)
    whole.pop("share")
    sw = ref.sizes(whole)
    assert (sw["Eh"], sw["E"], s["Eh"], s["E"]) == (16, 16, 8, 16)
    ww = ref.make_weights(ref.key_of(7), whole, F32)
    lw = {k: ww[k][0] for k in ("router", "w_gate", "w_up", "w_down")}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, s["H"]), F32)
    h = x.reshape(-1, s["H"])
    weight, load = ref.route(h, lw["router"], sw)
    routed = ref.held_experts(h, weight, lw, sw)
    total = ref_total = 0.0
    for rank, (lo, hi) in enumerate(((0, 8), (8, 16))):
        layer = MoE(s["H"], s["I"], num_experts=16, top_k=s["k"], capacity_factor=None,
                    balance_loss="topk_share", router="softmax", normalize_weights=True,
                    experts_held=(lo, hi))
        params = {"gate": lw["router"], "wi_gate": lw["w_gate"][lo:hi],
                  "wi_up": lw["w_up"][lo:hi], "wo": lw["w_down"][lo:hi]}
        out, _, rows = layer.dropless_forward(params, x)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(load, np.int32))
        total = total + out.reshape(-1, s["H"])
        sr = ref.sizes(dict(cfg, assumed=dict(cfg["assumed"], share_rank=rank)))
        assert (sr["lo"], sr["Eh"], sr["E"]) == (lo, 8, 16)
        mine = {k: (v[lo:hi] if k.startswith("w_") else v) for k, v in lw.items()}
        ref_total = ref_total + ref.held_experts(h, weight, mine, sr)
    assert close(total, routed, rel=1e-5)
    assert close(ref_total, routed, rel=1e-5)


def test_the_published_depth_builds(cell):
    """All 48 layers at tiny widths through the registry: one kind of layer,
    one scan of 48; every layer an expert layer with an indexer's four leaves."""
    hf = dict(cell.config, num_hidden_layers=48, num_experts=16, vocab_size=512)
    kw = get_architecture("KeyeVL2").config_fn(hf)
    model = TransformerLM(TransformerConfig(**kw, dtype=F32))
    assert model.scan_plan == (((0, True),), 48, ()) and model.moe_path == "dropless"
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    blocks = shapes["blocks"]
    assert blocks["moe"]["wi_gate"].shape == (48, 16, 64, 16)
    assert blocks["indexer_q"]["kernel"].shape == (48, 64, 16)
    assert blocks["indexer_k"]["kernel"].shape == (48, 64, 8)
    assert blocks["indexer_w"]["kernel"].shape == (48, 64, 2)
    assert blocks["indexer_k_norm"]["scale"].shape == blocks["indexer_k_norm"]["bias"].shape == (48, 8)
    assert shapes["wte"]["embedding"].shape == (512, 64)
    assert "bias" not in blocks["indexer_q"] and "dense_blocks" not in shapes
    # the count an engine logs holds the indexer's leaves
    assert model.config.num_parameters() - dataclasses.replace(
        model.config, indexer=None).num_parameters() == 48 * (64 * (16 + 8 + 2) + 16)
    c = model.config
    assert c.rope_theta == 1e7 and c.moe.router == "softmax" and not c.moe.aux_loss_coef
    # the published sizes, as the preset holds them
    big = keye_vl2_model("keye-vl2-30b-a3b", experts_held=(0, 16)).config
    assert (big.indexer, big.rope_sections, big.max_seq_len) == (
        IndexerConfig(16, 64, 2048), (16, 24, 24), 262144)
    assert (big.num_heads, big.kv_heads, big.head_dim, big.moe.num_experts,
            big.moe.top_k, big.ffn_size) == (32, 4, 128, 128, 8, 768)


def test_what_the_configuration_maps_to_and_refuses(cell):
    config_fn = get_architecture("KeyeVL2").config_fn
    scaling, sa = cell.config["rope_scaling"], cell.config["sa_config"]
    for key, value in (
            ("use_sliding_window", True), ("decoder_sparse_step", 2),
            ("mlp_only_layers", [0]), ("attention_bias", True),
            ("tie_word_embeddings", True), ("hidden_act", "gelu"),
            ("rope_scaling", dict(scaling, mrope_section=[2, 3, 4])),
            ("rope_scaling", dict(scaling, rope_type="yarn")),
            ("sa_config", {k: v for k, v in sa.items() if k != "topk"}),
            ("sa_config", dict(sa, indexer_num_kv_heads=2))):
        with pytest.raises(NotImplementedError, match=key):
            config_fn(dict(cell.config, **{key: value}))
    assert keye_vl2_model("keye-vl2-tiny", experts_held=(4, 8)).config.moe.experts_held == (4, 8)
    base = keye_vl2_model("keye-vl2-tiny").config
    for bad, why in ((dict(attn_windows=8), "indexer"), (dict(attn_gate=True), "indexer"),
                     (dict(objective="block_diffusion", mask_token_id=3), "indexer"),
                     (dict(indexer=IndexerConfig(2, 7, 8)), "indexer"),
                     (dict(rope_sections=(2, 3, 4)), "rope_sections"),
                     (dict(rope_sections=(4, 4)), "rope_sections")):
        with pytest.raises(ValueError, match=why):
            TransformerLM(dataclasses.replace(base, **bad))


@pytest.mark.parametrize("consumer", ["block_apply", "PipelineModule", "inference/v2"])
def test_paths_that_cannot_take_the_selection_refuse_it_by_name(consumer):
    """One block at a time (the ZeRO-3 pipelined scan, parameter streaming),
    ``PipelineModule`` and the ragged serving engine."""
    from deepspeed_tpu.inference.v2.model import RaggedInferenceModel
    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    model = keye_vl2_model("keye-vl2-tiny", dtype=F32)
    assert {"indexer", "rope_sections"} <= set(model.mechanisms)
    with pytest.raises(NotImplementedError, match="indexer.*rope_sections"):
        if consumer == "block_apply":
            block = jax.eval_shape(lambda: jax.tree.map(
                lambda a: a[0], model.init(jax.random.PRNGKey(0))["blocks"]))
            x = jax.ShapeDtypeStruct((1, 16, 64), F32)
            jax.eval_shape(lambda b, x: model.block_apply(b, x, jnp.arange(16)[None]), block, x)
        elif consumer == "PipelineModule":
            PipelineModule(model.config, num_stages=2)
        else:
            RaggedInferenceModel(model, 16, 4)
