"""PowerInfer's SmallThinker family (``model_type`` ``smallthinker``;
PowerInfer/SmallThinker-21BA3B-Instruct, arXiv:2507.20984) from Hugging Face's
configuration keys onto ``TransformerLM``.

- every layer is an expert layer (no dense layer, no shared expert):
  ``moe_num_primary_experts`` experts of ``moe_ffn_hidden_size``,
  ``moe_num_active_primary_experts`` a token, gated by ReLU
  (``activation='relu_gated'``: ``relu(u W_gate) * (u W_up)``), no capacity
  and no drops, no auxiliary loss (the configuration has no coefficient);
- the ROUTER reads the block's un-normed input, before attention
  (``MoEConfig.router_input='block_input'``): ``r = x W_r`` in float32, the
  ``k`` largest logits chosen, their softmax the weights
  (``moe_primary_router_apply_softmax``; with ``norm_topk_prob`` the softmax
  over all experts renormalised over the chosen gives the same numbers). The
  experts multiply the stream after attention, normed;
- ``sliding_window_layout``: 1 for a layer that attends a causal window of
  ``sliding_window_size``, 0 for one that attends the whole row (every fourth,
  from layer 0); ``rope_layout`` is 1 exactly on the windowed layers: a full
  layer has no positional term (``rope_layers='windowed'``). The kinds are
  static: the layer scan's unit is one period (``TransformerLM.scan_plan``),
  which here STARTS with its full layer;
- ``num_attention_heads`` query heads over ``num_key_value_heads`` key heads
  of ``head_dim`` (28 over 4: a group of 7; heads x head is not the hidden
  size), no bias, no QK-norm, no gate; pre-norm blocks, RMSNorm, an untied
  head.

The configuration has no key for where the router reads, for the lack of
bias and QK-norm or for the rotary form (rotate-half over the whole head):
they are the published modelling code's, and ``TransformerConfig`` says how
each is computed. Packed documents (a separator id) are the caller's:
``document_separator``. A chip that holds a share of each layer's experts
passes ``experts_held`` (``MoEConfig``)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from .registry import register_architecture
from .transformer import MoEConfig, TransformerConfig, TransformerLM


def layout(period: int, layers: int) -> Tuple[int, ...]:
    """A full layer (0), then ``period - 1`` windowed ones (1), over ``layers``:
    ``sliding_window_layout`` and ``rope_layout`` alike."""
    return tuple(int(i % period != 0) for i in range(layers))


#: PowerInfer/SmallThinker-21BA3B-Instruct config.json, and a toy of the same
#: block (a group of 7 query heads to a key head, a period that starts full)
_PRESETS = {
    "smallthinker-21b-a3b": dict(
        vocab_size=151936, hidden_size=2560, num_hidden_layers=52,
        num_attention_heads=28, num_key_value_heads=4, head_dim=128,
        moe_ffn_hidden_size=768, moe_num_primary_experts=64,
        moe_num_active_primary_experts=6, sliding_window_size=4096,
        sliding_window_layout=layout(4, 52), rope_layout=layout(4, 52),
        rope_theta=1500000, max_position_embeddings=16384),
    "smallthinker-tiny": dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=14, num_key_value_heads=2, head_dim=16,
        moe_ffn_hidden_size=32, moe_num_primary_experts=16,
        moe_num_active_primary_experts=3, sliding_window_size=16,
        sliding_window_layout=layout(4, 4), rope_layout=layout(4, 4),
        rope_theta=10000, max_position_embeddings=256),
}
_FLAGS = dict(model_type="smallthinker", rms_norm_eps=1e-6, rope_scaling=None,
              moe_primary_router_apply_softmax=True, norm_topk_prob=True,
              tie_word_embeddings=False)


def config_kwargs(hf: Dict[str, Any]) -> Dict[str, Any]:
    """``TransformerConfig`` arguments from a ``smallthinker`` configuration
    dict; what this program does not compute is refused by name. Layouts
    longer than ``num_hidden_layers`` (a cut in depth) are read from their
    start."""
    layers = hf["num_hidden_layers"]
    windowed = tuple(hf["sliding_window_layout"])[:layers]
    rope = tuple(hf.get("rope_layout", windowed))[:layers]
    refused = {
        "rope_scaling": hf.get("rope_scaling") is not None,
        "moe_primary_router_apply_softmax": not hf.get("moe_primary_router_apply_softmax", True),
        # (what the weights are without the renormalisation is not written down)
        "norm_topk_prob": not hf.get("norm_topk_prob", True),
        "sliding_window_layout": len(windowed) != layers or not set(windowed) <= {0, 1},
        # rope on the windowed layers alone is the one mix TransformerLM has
        "rope_layout": rope != windowed,
        "tie_word_embeddings": bool(hf.get("tie_word_embeddings", False)),
    }
    if any(refused.values()):
        raise NotImplementedError(
            "smallthinker configuration keys this program does not compute: "
            + ", ".join(k for k, bad in refused.items() if bad))
    # (the softmax over the chosen logits IS the softmax over all experts
    # renormalised over the chosen: ``softmax_topk_router`` with ``normalize``)
    moe = MoEConfig(
        num_experts=hf["moe_num_primary_experts"],
        top_k=hf["moe_num_active_primary_experts"], capacity_factor=None,
        normalize_weights=True,
        balance_loss="topk_share", aux_loss_coef=0.0, router_input="block_input")
    return dict(
        vocab_size=hf["vocab_size"], max_seq_len=hf["max_position_embeddings"],
        num_layers=layers, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], hidden_size=hf["hidden_size"],
        head_size=hf["head_dim"], intermediate_size=hf["moe_ffn_hidden_size"],
        activation="relu_gated", norm="rmsnorm", norm_eps=hf.get("rms_norm_eps", 1e-6),
        position="rope", rope_theta=float(hf["rope_theta"]), rope_layers="windowed",
        attn_windows=tuple(hf["sliding_window_size"] if w else 0 for w in windowed),
        tie_embeddings=False, moe=moe)


def checkpoint_params(cfg, state_dict):
    """No checkpoint loader: how a ``smallthinker`` checkpoint names its
    router and its expert stacks is the modelling code's and not the
    configuration's."""
    raise NotImplementedError(
        "loading a smallthinker checkpoint is not written; build the model from "
        "its configuration (smallthinker_model) and hand initialize() its parameters")


register_architecture("smallthinker", config_kwargs, checkpoint_params)


def smallthinker_config(preset: str = "smallthinker-21b-a3b", dtype=jnp.bfloat16,
                        experts_held: Optional[Tuple[int, int]] = None,
                        **overrides) -> TransformerConfig:
    """A preset's ``TransformerConfig``; ``experts_held``: the range of each
    layer's experts this chip holds (None: all)."""
    kw = config_kwargs({**_FLAGS, **_PRESETS[preset]})
    kw["moe"] = dataclasses.replace(kw["moe"], experts_held=experts_held)
    kw.update(dtype=dtype, **overrides)
    return TransformerConfig(**kw)


def smallthinker_model(preset: str = "smallthinker-21b-a3b", **overrides) -> TransformerLM:
    return TransformerLM(smallthinker_config(preset, **overrides))
