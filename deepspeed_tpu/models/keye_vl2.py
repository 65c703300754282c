"""Kwai-Keye's Keye-VL-2.0 family (``model_type`` ``KeyeVL2``;
Kwai-Keye/Keye-VL-2.0-30B-A3B) from Hugging Face's configuration keys onto
``TransformerLM``: the LANGUAGE MODEL of the checkpoint, a rotary MoE decoder
whose every layer attends a learned selection of keys. The SigLIP-class vision
tower and its projector are not built: a batch is token ids (and, optionally,
the three position streams a vision front end would hand over).

- the layer is ``sdar_moe``'s autoregressive parent's (the Qwen3-MoE family's):
  ``num_attention_heads`` query heads over ``num_key_value_heads`` key heads of
  ``head_dim``, no bias, RMSNorm over each head's query and key vector before
  rope, pre-norm; every layer an expert layer (``decoder_sparse_step`` 1,
  ``mlp_only_layers`` empty): ``num_experts`` gated-SiLU experts of
  ``moe_intermediate_size``, ``num_experts_per_tok`` a token by a float32
  softmax over all of them (``norm_topk_prob``), no shared expert, no
  capacity, no drops, no auxiliary loss;
- rope by sections (``rope_scaling.mrope_section``, Qwen2-VL's layout): the
  head's frequency pairs are turned by the temporal, height and width streams
  of ``batch["position_ids"]`` [3, B, L]; text alone (no ``position_ids``) has
  all three at a token's index: plain rope;
- ``sa_config``: DeepSeek Sparse Attention's lightning indexer
  (``TransformerConfig.indexer``): ``indexer_num_heads`` query heads and ONE key
  head (``indexer_num_kv_heads`` 1) of ``indexer_head_dim`` score a query's
  visible keys, the ``topk`` largest are what its main heads attend, and the
  indexer learns from their own distribution (the report's sparse-training
  stage: L_LM + L_I, no gradient between the two). ``q_chunk_size`` /
  ``kv_chunk_size`` are read as the tiles the released code scores in: they
  have no effect on which keys are picked, and none here.

Packed documents (a separator id) are the caller's: ``document_separator``.
A chip that holds a share of each layer's experts passes ``experts_held``
(``MoEConfig``)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from .registry import register_architecture
from .transformer import IndexerConfig, MoEConfig, TransformerConfig, TransformerLM

_SA = dict(indexer_head_dim=64, indexer_num_heads=16, indexer_num_kv_heads=1,
           kv_chunk_size=512, q_chunk_size=512, topk=2048)
#: Kwai-Keye/Keye-VL-2.0-30B-A3B config.json (the language model's keys), and
#: a toy of the same block
_PRESETS = {
    "keye-vl2-30b-a3b": dict(
        vocab_size=151936, hidden_size=2048, num_hidden_layers=48,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        intermediate_size=6144, moe_intermediate_size=768, num_experts=128,
        num_experts_per_tok=8, max_position_embeddings=262144,
        rope_scaling=dict(mrope_section=[16, 24, 24], rope_type="default",
                          type="default"), sa_config=_SA),
    "keye-vl2-tiny": dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=96, moe_intermediate_size=16, num_experts=8,
        num_experts_per_tok=2, max_position_embeddings=256,
        rope_scaling=dict(mrope_section=[2, 3, 3], rope_type="default",
                          type="default"),
        sa_config=dict(_SA, indexer_head_dim=8, indexer_num_heads=2, topk=8)),
}
_FLAGS = dict(model_type="KeyeVL2", hidden_act="silu", rms_norm_eps=1e-6,
              rope_theta=10000000, norm_topk_prob=True, decoder_sparse_step=1,
              mlp_only_layers=[], attention_bias=False, use_sliding_window=False,
              sliding_window=None, tie_word_embeddings=False)


def config_kwargs(hf: Dict[str, Any]) -> Dict[str, Any]:
    """``TransformerConfig`` arguments from a ``KeyeVL2`` configuration dict;
    what this program does not compute is refused by name."""
    scaling = hf.get("rope_scaling") or {}
    sections = tuple(scaling.get("mrope_section") or ())
    sa = hf.get("sa_config") or {}
    refused = {
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "rope_scaling.rope_type": scaling.get("rope_type", "default") != "default",
        "rope_scaling.mrope_section": (len(sections) != 3
                                       or sum(sections) != hf["head_dim"] // 2),
        "use_sliding_window": bool(hf.get("use_sliding_window")),
        "decoder_sparse_step": hf.get("decoder_sparse_step", 1) != 1,
        "mlp_only_layers": bool(hf.get("mlp_only_layers")),
        "attention_bias": bool(hf.get("attention_bias")),
        "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
        "sa_config": not {"indexer_num_heads", "indexer_head_dim", "topk"} <= set(sa),
        "sa_config.indexer_num_kv_heads": sa.get("indexer_num_kv_heads", 1) != 1,
    }
    if any(refused.values()):
        raise NotImplementedError(
            "KeyeVL2 configuration keys this program does not compute: "
            + ", ".join(k for k, bad in refused.items() if bad))
    moe = MoEConfig(
        num_experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        capacity_factor=None, normalize_weights=bool(hf.get("norm_topk_prob", True)),
        balance_loss="topk_share", aux_loss_coef=0.0, z_loss_coef=0.0)
    return dict(
        vocab_size=hf["vocab_size"], max_seq_len=hf["max_position_embeddings"],
        num_layers=hf["num_hidden_layers"], num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], hidden_size=hf["hidden_size"],
        head_size=hf["head_dim"], intermediate_size=hf["moe_intermediate_size"],
        activation="silu_gated", norm="rmsnorm", norm_eps=hf.get("rms_norm_eps", 1e-6),
        position="rope", rope_theta=float(hf["rope_theta"]), rope_sections=sections,
        qk_norm=True, qk_norm_per_head=True, linear_bias=False,
        tie_embeddings=False, moe=moe, attention="mha",
        indexer=IndexerConfig(heads=int(sa["indexer_num_heads"]),
                              head_dim=int(sa["indexer_head_dim"]),
                              topk=int(sa["topk"])))


def checkpoint_params(cfg, state_dict):
    """No checkpoint loader: the published tensors' names are the modelling
    code's, and its vision tower is not built here."""
    raise NotImplementedError(
        "loading a KeyeVL2 checkpoint is not written; build the model from its "
        "configuration (keye_vl2_model) and hand initialize() its parameters")


register_architecture("KeyeVL2", config_kwargs, checkpoint_params)


def keye_vl2_config(preset: str = "keye-vl2-30b-a3b", dtype=jnp.bfloat16,
                    experts_held: Optional[Tuple[int, int]] = None,
                    **overrides) -> TransformerConfig:
    """A preset's ``TransformerConfig``; ``experts_held``: the range of each
    layer's experts this chip holds (None: all)."""
    kw = config_kwargs({**_FLAGS, **_PRESETS[preset]})
    kw["moe"] = dataclasses.replace(kw["moe"], experts_held=experts_held)
    kw.update(dtype=dtype, **overrides)
    return TransformerConfig(**kw)


def keye_vl2_model(preset: str = "keye-vl2-30b-a3b", **overrides) -> TransformerLM:
    return TransformerLM(keye_vl2_config(preset, **overrides))
