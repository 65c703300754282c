"""EvaByte (``model_type`` ``evabyte``; EvaByte/EvaByte, 6.5 B, byte-level)
from Hugging Face's configuration keys onto ``TransformerLM``: a dense rotary
decoder over a vocabulary of 320 byte ids whose attention is EVA
(arXiv:2302.04542).

- the layer: ``num_attention_heads`` heads of ``hidden_size / heads`` with as
  many key heads, no bias, rotary positions over the whole head
  (``rope_theta``), pre-norm RMSNorm whose gain is ``1 + g``
  (``norm_add_unit_offset``), a gated-SiLU MLP of ``intermediate_size``, each
  branch added to the stream in float32 (``fp32_skip_add``);
- ``attention_class`` ``eva``: a query sees the exact keys of its own window
  of ``window_size`` positions and one learned summary a chunk of
  ``chunk_size`` for every window before it, under one softmax
  (``TransformerConfig.attention='eva'``); a layer holds two vectors a head
  for the summaries (``eva_phi``, ``eva_mu``: the released model's
  ``adaptive_phi`` and ``adaptive_mu_k``);
- ``num_pred_heads`` next-byte heads on the one trunk, an untied head of
  ``num_pred_heads x vocab_size`` outputs, float32 logits (``fp32_logits``).

What this program does not compute is refused by name: ``num_chunks`` (a fixed
number of chunks instead of a fixed chunk size), fewer key heads than query
heads, a scaled rope, biases, a tied head, another activation. ``fp32_ln``
false is taken as given and NOT followed: this engine's norms take their
statistics in float32 whatever the flag says. No checkpoint loader."""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from .registry import register_architecture
from .transformer import TransformerConfig, TransformerLM

#: EvaByte/EvaByte config.json, and a toy of the same block
_PRESETS = {
    "evabyte-6.5b": dict(
        vocab_size=320, hidden_size=4096, num_hidden_layers=32,
        num_attention_heads=32, num_key_value_heads=32, intermediate_size=11008,
        max_position_embeddings=32768, window_size=2048, chunk_size=16,
        num_pred_heads=8),
    "evabyte-tiny": dict(
        vocab_size=320, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
        max_position_embeddings=128, window_size=32, chunk_size=4,
        num_pred_heads=8),
}
_FLAGS = dict(model_type="evabyte", attention_class="eva", attention_bias=False,
              hidden_act="silu", rms_norm_eps=1e-5, rope_theta=100000,
              rope_scaling=None, num_chunks=None, norm_add_unit_offset=True,
              fp32_skip_add=True, fp32_logits=True, fp32_ln=False,
              mixedp_attn=True, tie_word_embeddings=False)


def config_kwargs(hf: Dict[str, Any]) -> Dict[str, Any]:
    """``TransformerConfig`` arguments from an ``evabyte`` configuration
    dict; what this program does not compute is refused by name."""
    heads = hf["num_attention_heads"]
    refused = {
        "attention_class": hf.get("attention_class", "eva") != "eva",
        "num_chunks": hf.get("num_chunks") is not None,
        "num_key_value_heads": hf.get("num_key_value_heads", heads) != heads,
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "rope_scaling": hf.get("rope_scaling") is not None,
        "attention_bias": bool(hf.get("attention_bias")),
        "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
        "fp32_logits": not hf.get("fp32_logits", True),
    }
    if any(refused.values()):
        raise NotImplementedError(
            "evabyte configuration keys this program does not compute: "
            + ", ".join(k for k, bad in refused.items() if bad))
    return dict(
        vocab_size=hf["vocab_size"], max_seq_len=hf["max_position_embeddings"],
        num_layers=hf["num_hidden_layers"], num_heads=heads,
        hidden_size=hf["hidden_size"], intermediate_size=hf["intermediate_size"],
        activation="silu_gated", norm="rmsnorm", norm_eps=hf.get("rms_norm_eps", 1e-5),
        norm_unit_offset=bool(hf.get("norm_add_unit_offset", True)),
        residual_fp32=bool(hf.get("fp32_skip_add", True)),
        position="rope", rope_theta=float(hf["rope_theta"]), linear_bias=False,
        tie_embeddings=False, attention="eva", eva_window=hf["window_size"],
        eva_chunk=hf["chunk_size"], pred_heads=hf.get("num_pred_heads", 1))


def checkpoint_params(cfg, state_dict):
    """No checkpoint loader: the released tensors' names and the layout of
    its eight heads are the modelling code's."""
    raise NotImplementedError(
        "loading an evabyte checkpoint is not written; build the model from "
        "its configuration (evabyte_model) and hand initialize() its "
        "parameters")


register_architecture("evabyte", config_kwargs, checkpoint_params)


def evabyte_config(preset: str = "evabyte-6.5b", dtype=jnp.bfloat16,
                   **overrides) -> TransformerConfig:
    kw = config_kwargs({**_FLAGS, **_PRESETS[preset]})
    kw.update(dtype=dtype, **overrides)
    return TransformerConfig(**kw)


def evabyte_model(preset: str = "evabyte-6.5b", **overrides) -> TransformerLM:
    return TransformerLM(evabyte_config(preset, **overrides))
