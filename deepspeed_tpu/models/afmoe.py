"""Arcee's Trinity family (``model_type`` ``afmoe``; arcee-ai/Trinity-Mini)
from Hugging Face's configuration keys onto ``TransformerLM``.

- ``layer_types``: ``sliding_attention`` layers (``sliding_window`` keys) and
  ``full_attention`` layers mixed in one stack, three to one; the rotary
  position turns a sliding layer's queries and keys and a full layer has no
  positional term at all (``rope_layers='windowed'``). The kinds are static:
  the layer scan's unit is one period of them (``TransformerLM.scan_plan``);
- ``num_attention_heads`` query heads over ``num_key_value_heads`` key heads of
  ``head_dim`` (heads x head is not the hidden size), RMSNorm over each head's
  query and key vector, a sigmoid gate on the attention output;
- sandwich norms: each branch's input AND its output are normed;
- ``mup_enabled``: the embedding's output times ``hidden_size ** 0.5``;
- ``num_dense_layers`` leading dense layers of ``intermediate_size``, then
  expert layers: ``num_experts`` experts of ``moe_intermediate_size``,
  ``num_experts_per_tok`` a token by sigmoid scores (``score_func``) under a
  bias that load moves by ``load_balance_coeff`` a step, the chosen scores
  over their sum (``route_norm``) times ``route_scale``,
  ``num_shared_experts`` shared experts as one MLP, no auxiliary loss, no
  capacity and no drops.

The configuration has no key for the QK-norm, the gate, the place of the
norms, the full layers' lack of a position or what ``mup_enabled`` scales:
they are the published ``afmoe`` modelling code's, and ``TransformerConfig``
says how each is computed. Packed documents (a separator id) are the
caller's: ``document_separator``. A chip that holds a share of each layer's
experts passes ``experts_held`` (``MoEConfig``)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from .registry import register_architecture
from .transformer import MoEConfig, TransformerConfig, TransformerLM

KINDS = ("sliding_attention", "full_attention")


def layer_types(period: int, layers: int) -> Tuple[str, ...]:
    """``period - 1`` sliding layers, then a full one, over ``layers``."""
    return tuple(KINDS[(i + 1) % period == 0] for i in range(layers))


#: arcee-ai/Trinity-Mini config.json, and a toy of the same block
_PRESETS = {
    "trinity-mini": dict(
        vocab_size=200192, hidden_size=2048, num_hidden_layers=32,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        intermediate_size=6144, moe_intermediate_size=1024, num_dense_layers=2,
        num_experts=128, num_shared_experts=1, num_experts_per_tok=8,
        route_scale=2.826, sliding_window=2048, layer_types=layer_types(4, 32),
        max_position_embeddings=131072),
    "afmoe-tiny": dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=6,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        intermediate_size=96, moe_intermediate_size=16, num_dense_layers=2,
        num_experts=16, num_shared_experts=1, num_experts_per_tok=3,
        route_scale=2.826, sliding_window=16, layer_types=layer_types(4, 6),
        max_position_embeddings=256),
}
_FLAGS = dict(model_type="afmoe", hidden_act="silu", rms_norm_eps=1e-5,
              rope_theta=10000, rope_scaling=None, score_func="sigmoid",
              route_norm=True, load_balance_coeff=0.001, mup_enabled=True,
              n_group=1, topk_group=1, num_expert_groups=1, num_limited_groups=1,
              tie_word_embeddings=False)


def config_kwargs(hf: Dict[str, Any]) -> Dict[str, Any]:
    """``TransformerConfig`` arguments from an ``afmoe`` configuration dict;
    what this program does not compute is refused by name. A ``layer_types``
    longer than ``num_hidden_layers`` (a cut in depth) is read from its
    start."""
    layers = hf["num_hidden_layers"]
    kinds = tuple(hf["layer_types"])[:layers]
    groups = ("n_group", "topk_group", "num_expert_groups", "num_limited_groups")
    refused = {
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "score_func": hf.get("score_func", "sigmoid") != "sigmoid",
        "rope_scaling": hf.get("rope_scaling") is not None,
        " / ".join(groups): any(hf.get(k, 1) != 1 for k in groups),
        "layer_types": len(kinds) != layers or not set(kinds) <= set(KINDS),
        "num_dense_layers": not 0 < hf.get("num_dense_layers", 0) < layers,
    }
    if any(refused.values()):
        raise NotImplementedError(
            "afmoe configuration keys this program does not compute: "
            + ", ".join(k for k, bad in refused.items() if bad))
    moe = MoEConfig(
        num_experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        capacity_factor=None, normalize_weights=bool(hf.get("route_norm", True)),
        balance_loss="topk_share", aux_loss_coef=0.0, router="sigmoid_bias",
        routed_scale=float(hf.get("route_scale", 1.0)),
        shared_width=hf.get("num_shared_experts", 0) * hf["moe_intermediate_size"],
        bias_update=float(hf.get("load_balance_coeff", 1e-3)))
    return dict(
        vocab_size=hf["vocab_size"], max_seq_len=hf["max_position_embeddings"],
        num_layers=layers, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], hidden_size=hf["hidden_size"],
        head_size=hf["head_dim"], intermediate_size=hf["moe_intermediate_size"],
        dense_intermediate_size=hf["intermediate_size"],
        first_dense_layers=hf["num_dense_layers"],
        activation="silu_gated", norm="rmsnorm", norm_eps=hf.get("rms_norm_eps", 1e-5),
        norm_style="sandwich", position="rope", rope_theta=float(hf["rope_theta"]),
        rope_layers="windowed",
        attn_windows=tuple(hf["sliding_window"] if k == KINDS[0] else 0 for k in kinds),
        qk_norm=True, qk_norm_per_head=True, attn_gate=True,
        embedding_scale=(hf["hidden_size"] ** 0.5 if hf.get("mup_enabled") else None),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)), moe=moe)


def checkpoint_params(cfg, state_dict):
    """No checkpoint loader: which tensors of an ``afmoe`` checkpoint carry
    the gate, the four norms and the router's bias is the modelling code's
    and not the configuration's."""
    raise NotImplementedError(
        "loading an afmoe / Trinity checkpoint is not written; build the "
        "model from its configuration (afmoe_model) and hand initialize() "
        "its parameters")


register_architecture("afmoe", config_kwargs, checkpoint_params)


def afmoe_config(preset: str = "trinity-mini", dtype=jnp.bfloat16,
                 experts_held: Optional[Tuple[int, int]] = None,
                 **overrides) -> TransformerConfig:
    """A preset's ``TransformerConfig``; ``experts_held``: the range of each
    layer's experts this chip holds (None: all)."""
    kw = config_kwargs({**_FLAGS, **_PRESETS[preset]})
    kw["moe"] = dataclasses.replace(kw["moe"], experts_held=experts_held)
    kw.update(dtype=dtype, **overrides)
    return TransformerConfig(**kw)


def afmoe_model(preset: str = "trinity-mini", **overrides) -> TransformerLM:
    return TransformerLM(afmoe_config(preset, **overrides))
