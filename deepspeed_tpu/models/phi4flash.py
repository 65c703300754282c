"""Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``; the SambaY
decoder-hybrid-decoder, arXiv:2507.06607) from Hugging Face's configuration
keys onto ``TransformerLM``:

- every layer: pre-norm LayerNorm (gain and bias, ``layer_norm_eps``), a gated
  SiLU MLP of ``intermediate_size`` without bias, no positional term at all, a
  tied head;
- layer l is a selective-scan layer iff ``l % mb_per_layer == 0``
  (``TransformerConfig.ssm_state``; Mamba-1's defaults, which the configuration
  does not carry: 2 x hidden channels, 16 states, 4 taps, a rank of hidden /
  16), else differential attention (``differential_attention``) over
  ``num_attention_heads`` query and ``num_key_value_heads`` key heads with
  biases, under ``sliding_window`` in the first half of the stack and full from
  there;
- from layer ``L / 2 + 2`` on, the cross-decoder (``shared_from = L / 2``): its
  attention layers attend layer ``L / 2 + 1``'s keys and values, its scan-slot
  layers are gated memory units over layer ``L / 2``'s scan output.

What this program does not compute is refused by name: dropouts above 0, a
bias on the MLP or the head, an untied head, another activation, a depth at
which the rule leaves no cross-decoder. No checkpoint loader."""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp

from .registry import register_architecture
from .transformer import TransformerConfig, TransformerLM

#: microsoft/Phi-4-mini-flash-reasoning config.json, and a toy of the same stack
_PRESETS = {
    "phi4-mini-flash": dict(
        vocab_size=200064, hidden_size=2560, num_hidden_layers=32,
        num_attention_heads=40, num_key_value_heads=20, intermediate_size=10240,
        max_position_embeddings=262144, sliding_window=512),
    "phi4flash-tiny": dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        max_position_embeddings=64, sliding_window=8,
        ssm_state=4, ssm_dt_rank=4),
}
_FLAGS = dict(model_type="phi4flash", hidden_act="silu", layer_norm_eps=1e-5,
              mb_per_layer=2, tie_word_embeddings=True, mlp_bias=False,
              lm_head_bias=False, embd_pdrop=0, resid_pdrop=0)
#: the keys read; any other key of a configuration is refused by name
_READ = frozenset(_FLAGS) | frozenset(_PRESETS["phi4flash-tiny"]) | {
    "ssm_conv", "ssm_expand"}


def config_kwargs(hf: Dict[str, Any]) -> Dict[str, Any]:
    """``TransformerConfig`` arguments from a ``phi4flash`` configuration dict
    (``ssm_state``, ``ssm_conv``, ``ssm_expand``, ``ssm_dt_rank``: Mamba-1's sizes
    where a caller states them; the published file has none); what this program
    does not read or compute is refused by name."""
    unread = sorted(set(hf) - _READ)
    layers, period = hf["num_hidden_layers"], hf.get("mb_per_layer", 2)
    half = layers // 2
    refused = {
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "tie_word_embeddings": not hf.get("tie_word_embeddings", True),
        "mlp_bias": bool(hf.get("mlp_bias")),
        "lm_head_bias": bool(hf.get("lm_head_bias")),
        "embd_pdrop": bool(hf.get("embd_pdrop")),
        "resid_pdrop": bool(hf.get("resid_pdrop")),
        # the rule pairs the scan layer at L / 2 with the full layer after it
        "num_hidden_layers": half % period != 0 or layers < half + 4,
    }
    if unread or any(refused.values()):
        raise NotImplementedError(
            "phi4flash configuration keys this program does not compute: "
            + ", ".join(unread + [k for k, bad in refused.items() if bad]))
    window = hf["sliding_window"]
    return dict(
        vocab_size=hf["vocab_size"], max_seq_len=hf["max_position_embeddings"],
        num_layers=layers, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"], activation="silu_gated",
        norm="layernorm", norm_eps=hf.get("layer_norm_eps", 1e-5), position="none",
        linear_bias=False, attn_bias=True, tie_embeddings=True,
        attn_windows=tuple(window if l % period and l < half else 0
                           for l in range(layers)),
        ssm_state=hf.get("ssm_state", 16), ssm_conv=hf.get("ssm_conv", 4),
        ssm_expand=hf.get("ssm_expand", 2), ssm_dt_rank=hf.get("ssm_dt_rank"),
        ssm_period=period, differential_attention=True, shared_from=half)


def checkpoint_params(cfg, state_dict):
    """No checkpoint loader: the released tensors' names are the modelling
    code's."""
    raise NotImplementedError(
        "loading a phi4flash checkpoint is not written; build the model from its "
        "configuration (phi4flash_model) and hand initialize() its parameters")


register_architecture("phi4flash", config_kwargs, checkpoint_params)


def phi4flash_config(preset: str = "phi4-mini-flash", dtype=jnp.bfloat16,
                     layers: Optional[int] = None, **overrides) -> TransformerConfig:
    """``layers``: another depth than the preset's, under the same rule."""
    depth = {} if layers is None else {"num_hidden_layers": layers}
    kw = config_kwargs({**_FLAGS, **_PRESETS[preset], **depth})
    kw.update(dtype=dtype, **overrides)
    return TransformerConfig(**kw)


def phi4flash_model(preset: str = "phi4-mini-flash", **overrides) -> TransformerLM:
    return TransformerLM(phi4flash_config(preset, **overrides))
