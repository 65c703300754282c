"""Granite 4.0-H (``model_type`` ``granitemoehybrid``; IBM's Mamba-2 / attention
hybrid, dense: no routed experts) from Hugging Face's configuration keys onto
``TransformerLM``:

- every layer: pre-norm RMSNorm (``rms_norm_eps``), a gated SiLU MLP of
  ``shared_intermediate_size`` without bias, each branch's output times
  ``residual_multiplier`` before it is added (``residual_scale``), no positional
  term at all (``position_embedding_type`` ``nope``);
- layer l's token mixer is ``layer_types[l]`` (``TransformerConfig.
  layer_mixers``): ``mamba`` a Mamba-2 layer (``mixers.Ssd``: ``mamba_n_heads``
  heads of ``mamba_d_head`` over ``mamba_d_state`` states in ``mamba_n_groups``
  groups, a convolution of ``mamba_d_conv`` taps with bias, the gate before the
  norm), ``attention`` grouped-query attention (``mixers.Mha``) whose softmax is
  of ``q k^T x attention_multiplier`` (``attn_scale``);
- the embedding times ``embedding_multiplier`` (``embedding_scale``), a final
  RMSNorm, a tied head whose logits are divided by ``logits_scaling``
  (``logits_divisor``).

``num_hidden_layers`` below the list's length takes the list's first entries.
What this program does not compute is refused by name: routed experts
(``num_local_experts`` > 0), a positional term, groups that do not divide the
heads, a list of another length than the published depth or with a name it does
not know, biases it does not have, an untied head. No checkpoint loader."""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp

from .registry import register_architecture
from .transformer import TransformerConfig, TransformerLM

#: the published stack's length (one period is ten layers: five scan layers,
#: one attention layer, four scan layers) and its mixers by the list's names
PUBLISHED_LAYERS = 40
_MIXER = {"mamba": "ssd", "attention": "mha"}
_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

#: ibm-granite/granite-4.0-h-micro config.json, and a toy of the same stack: a
#: period with its attention layer inside it, two heads to a lane tile, one group
_PRESETS = {
    "granite-4.0-h-micro": dict(
        vocab_size=100352, hidden_size=2048, num_hidden_layers=40,
        num_attention_heads=32, num_key_value_heads=8, intermediate_size=8192,
        shared_intermediate_size=8192, max_position_embeddings=131072,
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128, mamba_n_groups=1,
        mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=256,
        attention_multiplier=0.015625, embedding_multiplier=12,
        residual_multiplier=0.22, logits_scaling=8),
    "granite-hybrid-tiny": dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=10,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        shared_intermediate_size=128, max_position_embeddings=64,
        mamba_n_heads=2, mamba_d_head=64, mamba_d_state=16, mamba_n_groups=1,
        mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=16,
        attention_multiplier=0.0625, embedding_multiplier=12,
        residual_multiplier=0.22, logits_scaling=8),
}
_FLAGS = dict(
    model_type="granitemoehybrid", hidden_act="silu", rms_norm_eps=1e-5,
    normalization_function="rmsnorm", position_embedding_type="nope",
    layer_types=list(_PERIOD * 4), tie_word_embeddings=True, attention_bias=False,
    mamba_conv_bias=True, mamba_proj_bias=False, num_local_experts=0,
    num_experts_per_tok=0, rope_theta=10000, rope_scaling=None)
#: the keys read; any other key of a configuration is refused by name
_READ = frozenset(_FLAGS) | frozenset(_PRESETS["granite-4.0-h-micro"])


def config_kwargs(hf: Dict[str, Any]) -> Dict[str, Any]:
    """``TransformerConfig`` arguments from a ``granitemoehybrid`` configuration
    dict; what this program does not read or compute is refused by name."""
    unread = sorted(set(hf) - _READ)
    kinds = list(hf.get("layer_types") or [])
    heads, groups = hf["mamba_n_heads"], hf.get("mamba_n_groups", 1)
    layers = hf["num_hidden_layers"]
    inner = heads * hf["mamba_d_head"]
    refused = {
        "num_local_experts": bool(hf.get("num_local_experts")),
        "num_experts_per_tok": bool(hf.get("num_experts_per_tok")),
        "position_embedding_type": hf.get("position_embedding_type", "nope") != "nope",
        "mamba_n_groups": groups < 1 or heads % groups != 0,
        "mamba_expand": hf.get("mamba_expand", 2) * hf["hidden_size"] != inner,
        "layer_types": (len(kinds) != PUBLISHED_LAYERS
                        or any(kind not in _MIXER for kind in kinds)),
        "num_hidden_layers": not 0 < layers <= PUBLISHED_LAYERS,
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "normalization_function": hf.get("normalization_function", "rmsnorm") != "rmsnorm",
        "attention_bias": bool(hf.get("attention_bias")),
        "mamba_proj_bias": bool(hf.get("mamba_proj_bias")),
        "mamba_conv_bias": not hf.get("mamba_conv_bias", True),
        "tie_word_embeddings": not hf.get("tie_word_embeddings", True),
        "shared_intermediate_size": (hf.get("shared_intermediate_size")
                                     != hf.get("intermediate_size")),
    }
    if unread or any(refused.values()):
        raise NotImplementedError(
            "granitemoehybrid configuration keys this program does not compute: "
            + ", ".join(unread + [k for k, bad in refused.items() if bad]))
    return dict(
        vocab_size=hf["vocab_size"], max_seq_len=hf["max_position_embeddings"],
        num_layers=layers, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["shared_intermediate_size"], activation="silu_gated",
        norm="rmsnorm", norm_eps=hf.get("rms_norm_eps", 1e-5), position="none",
        linear_bias=False, attn_bias=False, tie_embeddings=True,
        attn_scale=hf["attention_multiplier"],
        embedding_scale=float(hf["embedding_multiplier"]),
        residual_scale=float(hf["residual_multiplier"]),
        logits_divisor=float(hf["logits_scaling"]),
        # (a depth under the published one takes the list's first entries)
        layer_mixers=tuple(_MIXER[kind] for kind in kinds[:layers]),
        ssm_heads=heads, ssm_head_dim=hf["mamba_d_head"], ssm_state=hf["mamba_d_state"],
        ssm_groups=groups, ssm_conv=hf.get("mamba_d_conv", 4),
        ssm_chunk=hf.get("mamba_chunk_size", 256))


def checkpoint_params(cfg, state_dict):
    """No checkpoint loader: the released tensors' names are the modelling
    code's."""
    raise NotImplementedError(
        "loading a granitemoehybrid checkpoint is not written; build the model from "
        "its configuration (granite_hybrid_model) and hand initialize() its parameters")


register_architecture("granitemoehybrid", config_kwargs, checkpoint_params)


def granite_hybrid_config(preset: str = "granite-4.0-h-micro", dtype=jnp.bfloat16,
                          layers: Optional[int] = None, **overrides) -> TransformerConfig:
    """``layers``: another depth than the preset's, the list's first entries."""
    depth = {} if layers is None else {"num_hidden_layers": layers}
    kw = config_kwargs({**_FLAGS, **_PRESETS[preset], **depth})
    kw.update(dtype=dtype, **overrides)
    return TransformerConfig(**kw)


def granite_hybrid_model(preset: str = "granite-4.0-h-micro", **overrides) -> TransformerLM:
    return TransformerLM(granite_hybrid_config(preset, **overrides))
