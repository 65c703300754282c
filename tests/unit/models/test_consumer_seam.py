"""The seam an architecture goes through (``transformer.MECHANISMS``): for
every mechanism and each consumer that computes less than
``TransformerLM.loss`` (one block at a time, ``PipelineModule``, the ragged
serving model), the consumer either gives ``TransformerLM.apply``'s logits on
a two-layer model or refuses with ``NotImplementedError`` naming the
mechanism; and no configuration field is outside the seam."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.model import RaggedInferenceModel
from deepspeed_tpu.models.transformer import (MECHANISMS, IndexerConfig, MoEConfig,
                                              TransformerConfig, TransformerLM)
from deepspeed_tpu.runtime.pipe.module import PipelineModule

BASE = dict(vocab_size=64, max_seq_len=32, num_layers=2, num_heads=2, hidden_size=16,
            intermediate_size=32, position="rope", norm="rmsnorm",
            activation="silu_gated", tie_embeddings=False, dtype=jnp.float32, remat=False)
ENCODER = dict(causal=False, position="learned")
# (capacity for every assignment: training's capacity path then drops nothing,
# as serving's never does)
EXPERTS = MoEConfig(num_experts=4, top_k=2, capacity_factor=4.0)
# a mechanism -> the keys of a configuration that uses it
USES = {
    "causal=False": ENCODER,
    "norm_style='post'": dict(norm_style="post"),
    "norm_style='sandwich'": dict(norm_style="sandwich"),
    "mlm_head": dict(ENCODER, mlm_head=True, tie_embeddings=True, norm="layernorm",
                     activation="gelu"),
    "type_vocab_size": dict(ENCODER, type_vocab_size=2),
    "pad_based_positions": dict(position="learned", pad_based_positions=True,
                                pad_token_id=1, position_offset=2),
    "attn_windows": dict(attn_windows=(4, 0)),
    "rope_layers='windowed'": dict(attn_windows=(4, 0), rope_layers="windowed"),
    "document_separator": dict(document_separator=1),
    "embedding_scale": dict(embedding_scale=4.0),
    "residual_fp32": dict(residual_fp32=True),
    "qk_norm": dict(qk_norm=True),
    "attn_gate": dict(attn_gate=True),
    "attention='latent'": dict(attention="latent", kv_latent_rank=8, qk_nope_dim=6,
                               qk_rope_dim=2, v_head_dim=8),
    "attention='eva'": dict(attention="eva", eva_window=4, eva_chunk=2, attn_bias=False),
    "indexer": dict(indexer=IndexerConfig(heads=2, head_dim=4, topk=3)),
    "rope_sections": dict(rope_sections=(1, 2, 1)),
    "pred_heads": dict(pred_heads=2),
    "farskip": dict(farskip=True),
    "q_latent_rank": dict(attention="latent", kv_latent_rank=8, q_latent_rank=4, qk_nope_dim=6,
                          qk_rope_dim=2, v_head_dim=8),
    "residual_streams": dict(residual_streams=2, hc_sinkhorn_iters=3),
    "ssm_state": dict(ssm_state=4, ssm_dt_rank=2),
    "ssm_heads": dict(ssm_state=4, ssm_heads=2, ssm_head_dim=8, layer_mixers=("ssd", "ssd")),
    "layer_mixers": dict(ssm_state=4, ssm_heads=2, ssm_head_dim=8, layer_mixers=("ssd", "mha")),
    "kda_heads": dict(kda_heads=2, kda_head_dim=8, layer_mixers=("kda", "mha")),
    "residual_scale": dict(residual_scale=0.5),
    "logits_divisor": dict(logits_divisor=2.0),
    "differential_attention": dict(differential_attention=True),
    "shared_from": dict(num_layers=4, ssm_state=4, ssm_dt_rank=2, shared_from=0),
    "first_dense_layers": dict(first_dense_layers=1, dense_intermediate_size=32, moe=EXPERTS),
    "mtp_layers": dict(mtp_layers=1),
    "objective='block_diffusion'": dict(objective="block_diffusion", block_length=4,
                                        mask_token_id=63),
    "moe": dict(moe=EXPERTS),
    "moe.capacity_factor=None": dict(moe=dataclasses.replace(EXPERTS, capacity_factor=None)),
    "moe.bias_update": dict(moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=None,
                                          router="sigmoid_bias", bias_update=1e-3)),
    "moe.router_input='block_input'": dict(moe=dataclasses.replace(
        EXPERTS, capacity_factor=None, router_input="block_input")),
}
IDS = np.asarray([[5, 9, 1, 7, 3, 1, 8, 2], [4, 4, 6, 1, 9, 2, 2, 7]], np.int32)


def _one_block_at_a_time(model, params):
    x, positions = model.embed(params, IDS)
    for i in range(model.config.num_layers):
        window = None if model._windows is None else jnp.asarray(model._windows[i])
        # (a mixed stack has no ``blocks``: the consumer refuses before it reads one)
        x, _ = model.block_apply(jax.tree.map(lambda a: a[i], params.get("blocks")),
                                 x, positions, window=window)
    return model.head(params, x)


def _pipeline(model, params):
    staged = PipelineModule(model.config, num_stages=1, num_microbatches=2)
    blocks = jax.tree.map(lambda a: a[None], params["blocks"])
    return staged.apply({**params, "blocks": blocks}, IDS)[0]


def _ragged(model, params):
    """Each row prefilled alone into a page of its own: its last position's
    logits (stacked with ``apply``'s other positions, which it never gives)."""
    served = RaggedInferenceModel(model, block_size=8, max_blocks_per_seq=1)
    c = model.config
    pages = jnp.zeros((c.num_layers, c.kv_heads, 2, 8, c.head_dim), c.dtype)
    want = model.apply(params, IDS)[0]
    last = [served.prefill_chunk(params, pages, pages, jnp.asarray(row), jnp.arange(8),
                                 jnp.asarray([1]), 0, 8)[0] for row in IDS]
    return want.at[:, -1].set(jnp.stack(last))


CONSUMERS = {"one_block_at_a_time": _one_block_at_a_time, "pipeline": _pipeline,
             "ragged": _ragged}


@pytest.fixture(scope="module")
def built():
    """A mechanism's two-layer model, its parameters and ``apply``'s logits,
    built once for the three consumers."""
    @functools.lru_cache(None)
    def build(mechanism):
        model = TransformerLM(TransformerConfig(**{**BASE, **USES[mechanism]}))
        params = model.init(jax.random.PRNGKey(0))
        return model, params, model.apply(params, IDS)[0]
    return build


@pytest.mark.parametrize("consumer", sorted(CONSUMERS))
@pytest.mark.parametrize("mechanism", sorted(MECHANISMS))
def test_a_consumer_runs_a_mechanism_or_refuses_it_by_name(built, mechanism, consumer):
    model, params, want = built(mechanism)
    assert mechanism in model.mechanisms
    try:
        got = CONSUMERS[consumer](model, params)
    except NotImplementedError as refusal:
        assert mechanism in str(refusal) and MECHANISMS[mechanism][1] in str(refusal)
        return
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_a_plain_decoder_uses_no_mechanism_and_every_consumer_runs_it():
    model = TransformerLM(TransformerConfig(**BASE))
    assert model.mechanisms == ()
    params = model.init(jax.random.PRNGKey(0))
    want = model.apply(params, IDS)[0]
    for run in CONSUMERS.values():
        np.testing.assert_allclose(np.asarray(run(model, params)), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    with pytest.raises(KeyError, match="no MECHANISMS"):
        model.require("a consumer with a typo", {"farskipp"})


# what every consumer computes through the model's own layers (a width, a
# norm's kind, a bias, a rotary detail), or what belongs to a mechanism named
# by another field: no consumer has to know it
PLAIN = {
    "vocab_size", "max_seq_len", "num_layers", "num_heads", "num_kv_heads", "hidden_size",
    "head_size", "intermediate_size", "activation", "norm", "norm_eps", "position",
    "position_offset", "rope_theta", "rope_dim", "rope_style", "rope_scaling", "attn_scale",
    "embedding_norm", "parallel_block", "parallel_norms", "linear_bias", "attn_bias",
    "attn_out_bias", "lm_head_bias", "tie_embeddings", "norm_unit_offset", "seq_parallel",
    "dtype", "remat", "remat_policy", "pad_token_id", "qk_norm_per_head", "moe_layer_freq",
    "eva_window", "eva_chunk", "kv_latent_rank", "qk_nope_dim", "qk_rope_dim", "v_head_dim",
    "dense_intermediate_size", "mtp_loss_coef", "block_length", "mask_token_id", "noise_seed",
    "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp",
    "ssm_conv", "ssm_expand", "ssm_dt_rank", "ssm_period",
    "ssm_head_dim", "ssm_groups", "ssm_chunk",
    "kda_head_dim", "kda_conv",
}


def test_every_configuration_field_is_plain_or_behind_a_mechanism():
    """The seam fails closed: a field added to ``TransformerConfig`` is
    refused here until it is called plain or given a mechanism (which every
    consumer then refuses until it lists it)."""
    behind = {name.split("=")[0].split(".")[0] for name in MECHANISMS}
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    assert behind <= fields and not behind & PLAIN
    assert fields == behind | PLAIN
