"""OLMoE through ``TransformerLM``: the benchmark's tiny preset
(tests/benchmark/data/benchmark/configs/olmoe-tiny.json: QK-norm, 8 experts,
3 a token, no capacity, weights not renormalised, both router losses)
against the plain float32 reference, ``benchmark/reference/olmoe.py``, on
seeded weights. Tolerances are float32's over this depth (logits of order
1: 2e-5; the loss: 1e-5; gradients: 2e-5 of the largest element); the same
model in bfloat16 misses them by orders of magnitude (a test holds it)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
MANIFEST = os.path.join(REPO, "tests", "benchmark", "data", "BENCHMARK.olmoe-tiny.json")


@pytest.fixture(scope="module")
def tiny():
    """(reference module, adapter module, configuration dict, float32
    weights under the reference's names, token ids)."""
    from benchmark import harness
    cell = harness.Cell(MANIFEST, "olmoe-tiny.train")
    ref = cell.load_module("reference", cell.config["reference"])
    adapter = cell.load_module("adapters", cell.config["adapter"])
    w = ref.make_weights(ref.key_of(5), cell.config, jnp.float32)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cell.config["vocab_size"], (3, 40)), jnp.int32)
    return ref, adapter, cell.config, w, ids


def program_loss(model, params, ids):
    with jax.default_matmul_precision("highest"):
        return model.loss(params, {"input_ids": ids})


def test_logits_match_the_reference(tiny):
    ref, adapter, cfg, w, ids = tiny
    model = adapter.model(cfg, remat=False, dtype="float32")
    assert model.moe_path == "dropless" and model.config.qk_norm
    with jax.default_matmul_precision("highest"):
        logits, aux, stats = model.apply(adapter.to_program(w), ids, return_stats=True)
    np.testing.assert_allclose(logits, ref.forward(w, ids, cfg), atol=2e-5)
    _, balance, z = ref.forward_with_router_losses(w, ids, cfg)
    np.testing.assert_allclose(aux, [balance.sum(), z.sum()], rtol=1e-5)
    rows = np.asarray(stats["moe_expert_rows"])
    assert rows.shape == (2, 8) and (rows.sum(1) == ids.size * 3).all()


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_gradients_match_the_reference(tiny, remat):
    """The objective with both router losses, and its gradient for every
    weight, under the reference's names."""
    ref, adapter, cfg, w, ids = tiny
    model = adapter.model(cfg, remat=remat, dtype="float32")
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: program_loss(model, p, ids)))(adapter.to_program(w))
    want, want_g = jax.jit(jax.value_and_grad(lambda p: ref.next_token_loss(p, ids, cfg)))(w)
    # the program shifts the labels and masks the last position; the
    # reference predicts ids[:, 1:]: the same mean
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    got_g = adapter.from_program(grads)
    assert set(got_g) == set(want_g)
    for k in want_g:
        scale = float(jnp.abs(want_g[k]).max())
        np.testing.assert_allclose(got_g[k], want_g[k], atol=2e-5 * max(scale, 1.0),
                                   err_msg=k)
    assert float(jnp.abs(got_g["router"]).max()) > 0


def test_router_losses_are_in_the_objective(tiny):
    """Each router loss under its own coefficient, averaged over layers."""
    import dataclasses
    ref, adapter, cfg, w, ids = tiny
    model = adapter.model(cfg, remat=False, dtype="float32")
    params = adapter.to_program(w)
    _, balance, z = ref.forward_with_router_losses(w, ids, cfg)

    def with_coefs(b, zc):
        moe = dataclasses.replace(model.config.moe, aux_loss_coef=b, z_loss_coef=zc)
        m = type(model)(dataclasses.replace(model.config, moe=moe))
        return float(program_loss(m, params, ids))

    base = with_coefs(0.0, 0.0)
    assert with_coefs(0.5, 0.0) - base == pytest.approx(0.5 * float(balance.mean()), rel=1e-3)
    assert with_coefs(0.0, 0.25) - base == pytest.approx(0.25 * float(z.mean()), rel=1e-3)


def test_qk_norm_alone_is_its_equation():
    """q <- q / sqrt(mean(q^2) + eps) x g over the WHOLE projected vector,
    before the head split and rope: against a dense model with the norm
    written out here, and not a per-head norm."""
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
    kw = dict(vocab_size=64, max_seq_len=16, num_layers=1, num_heads=4, num_kv_heads=2,
              hidden_size=32, intermediate_size=48, activation="silu_gated",
              norm="rmsnorm", norm_eps=1e-5, position="rope", tie_embeddings=False,
              dtype=jnp.float32, remat=False)
    plain, normed = TransformerLM(TransformerConfig(**kw)), TransformerLM(
        TransformerConfig(qk_norm=True, **kw))
    assert (normed.config.num_parameters() - plain.config.num_parameters()
            == 32 + 16)
    params = normed.init(jax.random.PRNGKey(0))
    g_q = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (1, 32))
    g_k = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (1, 16))
    params["blocks"]["q_norm"]["scale"], params["blocks"]["k_norm"]["scale"] = g_q, g_k
    assert params["blocks"]["q_norm"]["scale"].shape == (1, 32)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 12)), jnp.int32)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 32))
    block = jax.tree.map(lambda a: a[0], params["blocks"])
    pos = jnp.arange(12)[None]

    def by_hand(vec, gain):
        return vec / jnp.sqrt(jnp.mean(vec * vec, -1, keepdims=True) + 1e-5) * gain

    # the same attention with the projections' outputs normalised by hand:
    # fold the norm into a model without it by normalising q and k outside
    q = by_hand(h @ block["q_proj"]["kernel"], g_q[0])
    k = by_hand(h @ block["k_proj"]["kernel"], g_k[0])
    v = h @ block["v_proj"]["kernel"]
    qh = plain._rotate(q.reshape(2, 12, 4, 8), pos)
    kh = plain._rotate(k.reshape(2, 12, 2, 8), pos)
    want = plain._mixer._attn_core(qh, kh, v.reshape(2, 12, 2, 8), None, None)
    want = want.reshape(2, 12, 32) @ block["o_proj"]["kernel"]
    np.testing.assert_allclose(normed._mixer(block, h, pos, None)[0], want, atol=1e-5)
    assert float(jnp.abs(plain._mixer(block, h, pos, None)[0] - want).max()) > 1e-2
    logits, _ = normed.apply(params, ids)
    assert bool(jnp.isfinite(logits).all())


def test_bfloat16_fails_the_float32_tolerance(tiny):
    ref, adapter, cfg, w, ids = tiny
    model = adapter.model(cfg, remat=False, dtype="bfloat16")
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), adapter.to_program(w))
    logits, _ = model.apply(low, ids)
    assert float(jnp.abs(logits - ref.forward(w, ids, cfg)).max()) > 10 * 2e-5


def test_trains_through_initialize_and_counts_every_assignment(tiny):
    """``initialize`` -> ``train_batch`` on the fused step: the loss falls,
    the engine's counters name the path, and every one of the ``tokens x
    top_k`` assignments reached an expert in every layer."""
    import deepspeed_tpu
    from deepspeed_tpu.models import olmoe_model
    model = olmoe_model("olmoe-tiny", dtype=jnp.float32, remat=True)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "zero_optimization": {"stage": 1},
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    assert engine.moe_expert_rows() is None
    batch = {"input_ids": np.random.default_rng(0).integers(0, 512, size=(8, 32))}
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    assert losses[-1] < losses[0]
    k, layers = model.config.moe.top_k, model.config.num_layers
    # three grouped matmuls a layer, each forward, as a row gradient and as
    # a weight gradient; remat keeps every name here, so none runs again; on
    # the CPU the route is ``ragged_dot``
    each = dict.fromkeys(("forward", "row_gradient", "weight_gradient"), 3 * layers)
    assert engine.remat_totals["saved"]
    assert engine.moe_totals == {"path": "dropless", "steps": 3,
                                 "experts_published": 8, "experts_held": 8,
                                 "router_input": "ffn_input", "activation": "silu_gated",
                                 "grouped_matmul_route": "xla",
                                 "products_kernel": dict.fromkeys(each, 0),
                                 "products_xla": each,
                                 "combine_route": None, "combine_rows_moved": 0}
    rows = engine.moe_expert_rows()
    assert rows.shape == (layers, 8) and (rows.sum(1) == 8 * 32 * k).all()


def test_capacity_models_count_too_and_keep_their_program():
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2_model, mixtral_model
    assert gpt2_model("gpt2-tiny").moe_path is None
    model = mixtral_model("mixtral-tiny", dtype=jnp.float32, remat=False,
                          max_seq_len=32, vocab_size=256)
    assert model.moe_path == "capacity"
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    engine.train_batch({"input_ids": np.random.default_rng(0).integers(0, 256, (8, 16))})
    assert engine.moe_totals == {"path": "capacity", "steps": 1,
                                 "experts_published": 4, "experts_held": 4,
                                 "router_input": "ffn_input", "activation": "silu_gated",
                                 "grouped_matmul_route": None,
                                 "products_kernel": None, "products_xla": None,
                                 "combine_route": None, "combine_rows_moved": 0}
    assert engine.moe_expert_rows() is None


def test_serving_refuses_the_configuration():
    from deepspeed_tpu.inference.v2.model import RaggedInferenceModel
    from deepspeed_tpu.models import olmoe_model
    with pytest.raises(NotImplementedError, match="serving engine.*qk_norm.*capacity_factor=None"):
        RaggedInferenceModel(olmoe_model("olmoe-tiny"), block_size=16,
                             max_blocks_per_seq=8)
