"""JetLM's SDAR family (``model_type`` ``sdar_moe``; JetLM/SDAR-30B-A3B-Chat)
from Hugging Face's configuration keys onto ``TransformerLM``: an
autoregressive rotary MoE decoder turned into a block-diffusion model.

- the layer is the family's autoregressive parent's: ``num_attention_heads``
  query heads over ``num_key_value_heads`` key heads of ``head_dim`` (heads x
  head is not the hidden size), no bias, RMSNorm over each head's query and
  key vector (one gain of ``head_dim`` for all heads), rotary positions over
  the whole head (``rope_theta``), pre-norm;
- every layer an expert layer (``decoder_sparse_step`` 1, ``mlp_only_layers``
  empty): ``num_experts`` gated-SiLU experts of ``moe_intermediate_size``,
  ``num_experts_per_tok`` a token by a float32 softmax over all of them, the
  chosen probabilities over their sum (``norm_topk_prob``), no shared expert,
  no capacity and no drops, no auxiliary loss (the configuration has no
  coefficient);
- the objective is BD3-LM's block diffusion (``TransformerConfig.objective``):
  a clean and a noised copy of every row under one mask, the head over the
  noised copy, a masked position's cross-entropy weighted 1 / t.

The configuration has no key for the QK-norm, the block length, the noise
schedule or the mask token: ``block_length``, ``mask_token_id`` and
``noise_seed`` are the caller's (defaults: 4, one row past the vocabulary,
0). Packed documents (a separator id) are the caller's too:
``document_separator``. A chip that holds a share of each layer's experts
passes ``experts_held`` (``MoEConfig``)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from .registry import register_architecture
from .transformer import MoEConfig, TransformerConfig, TransformerLM

#: JetLM/SDAR-30B-A3B-Chat config.json, and a toy of the same block
_PRESETS = {
    "sdar-30b-a3b": dict(
        vocab_size=151936, hidden_size=2048, num_hidden_layers=48,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        intermediate_size=6144, moe_intermediate_size=768, num_experts=128,
        num_experts_per_tok=8, max_position_embeddings=32768),
    "sdar-tiny": dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        intermediate_size=96, moe_intermediate_size=16, num_experts=16,
        num_experts_per_tok=3, max_position_embeddings=256),
}
_FLAGS = dict(model_type="sdar_moe", hidden_act="silu", rms_norm_eps=1e-6,
              rope_theta=1000000, rope_scaling=None, norm_topk_prob=True,
              decoder_sparse_step=1, mlp_only_layers=[], attention_bias=False,
              use_sliding_window=False, sliding_window=None,
              tie_word_embeddings=False)


def config_kwargs(hf: Dict[str, Any]) -> Dict[str, Any]:
    """``TransformerConfig`` arguments from an ``sdar_moe`` configuration
    dict; what this program does not compute is refused by name."""
    refused = {
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "rope_scaling": hf.get("rope_scaling") is not None,
        "use_sliding_window": bool(hf.get("use_sliding_window")),
        "decoder_sparse_step": hf.get("decoder_sparse_step", 1) != 1,
        "mlp_only_layers": bool(hf.get("mlp_only_layers")),
        "attention_bias": bool(hf.get("attention_bias")),
        "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
    }
    if any(refused.values()):
        raise NotImplementedError(
            "sdar_moe configuration keys this program does not compute: "
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
        position="rope", rope_theta=float(hf["rope_theta"]),
        qk_norm=True, qk_norm_per_head=True, linear_bias=False,
        tie_embeddings=False, moe=moe,
        objective="block_diffusion", block_length=4,
        mask_token_id=hf["vocab_size"])


def checkpoint_params(cfg, state_dict):
    """No checkpoint loader: a published checkpoint's mask token lies inside
    its own vocabulary and its tensors' names are the modelling code's."""
    raise NotImplementedError(
        "loading an sdar_moe checkpoint is not written; build the model from "
        "its configuration (sdar_moe_model) and hand initialize() its "
        "parameters")


register_architecture("sdar_moe", config_kwargs, checkpoint_params)


def sdar_moe_config(preset: str = "sdar-30b-a3b", dtype=jnp.bfloat16,
                    experts_held: Optional[Tuple[int, int]] = None,
                    **overrides) -> TransformerConfig:
    """A preset's ``TransformerConfig``; ``experts_held``: the range of each
    layer's experts this chip holds (None: all)."""
    kw = config_kwargs({**_FLAGS, **_PRESETS[preset]})
    kw["moe"] = dataclasses.replace(kw["moe"], experts_held=experts_held)
    kw.update(dtype=dtype, **overrides)
    return TransformerConfig(**kw)


def sdar_moe_model(preset: str = "sdar-30b-a3b", **overrides) -> TransformerLM:
    return TransformerLM(sdar_moe_config(preset, **overrides))
