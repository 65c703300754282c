"""Kimi Linear (``model_type`` ``kimi_linear``; moonshotai/Kimi-Linear-48B-A3B,
arXiv:2510.26692) from Hugging Face's configuration keys onto ``TransformerLM``:

- every layer ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``
  (``rms_norm_eps``), a final RMSNorm, an untied head;
- layer l's token mixer by the two 1-indexed lists of ``linear_attn_config``
  (``TransformerConfig.layer_mixers``): ``kda_layers`` Kimi Delta Attention
  (``mixers.Kda``: ``num_heads`` heads with keys and values of ``head_dim``, a
  convolution of ``short_conv_kernel_size`` taps), ``full_attn_layers`` multi-head
  latent attention (``mixers.Latent``: ``kv_lora_rank``, ``qk_nope_head_dim`` +
  ``qk_rope_head_dim``, ``v_head_dim``) with NO positional term (``mla_use_nope``:
  the shared ``qk_rope_head_dim`` stay in the products, unturned; ``rope_theta``
  is read and unused);
- ``first_k_dense_replace`` leading layers with a dense gated-SiLU MLP of
  ``intermediate_size``, then expert layers: ``num_experts`` experts of
  ``moe_intermediate_size``, ``num_experts_per_token`` a token by sigmoid scores
  under a bias that load moves (one group: a plain top-k), ``moe_renormalize``,
  ``routed_scaling_factor``, ``num_shared_experts`` shared experts as one MLP, no
  capacity and no drops.

``num_hidden_layers`` below the lists' length takes the lists' entries up to
it. Not in the configuration, hence read from the dict where it gives them:
``bias_update_speed`` (the DeepSeek-V3 report's 1e-3); the low-rank gates' rank
is a linear head's width (no key gives it). What this program does not compute
is refused by name: a prediction module, ``mla_use_nope`` false, a compressed
query, ``rope_scaling``, grouped routing, a tied head, a layer in both lists or
in neither. A chip that holds a share of each layer's experts passes
``experts_held`` (``MoEConfig``). No checkpoint loader."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from .registry import register_architecture
from .transformer import MoEConfig, TransformerConfig, TransformerLM


def _lists(layers: int) -> Dict[str, Any]:
    """The published period over ``layers`` layers: three KDA layers to one of
    latent attention, the last layer latent."""
    full = [l for l in range(1, layers + 1) if l % 4 == 0 or l == layers]
    return {"full_attn_layers": full,
            "kda_layers": [l for l in range(1, layers + 1) if l not in full]}


#: moonshotai/Kimi-Linear-48B-A3B-Instruct config.json, and a toy of the same
#: stack: a dense KDA layer, then KDA, KDA, MLA, KDA with experts
_PRESETS = {
    "kimi-linear-48b-a3b": dict(
        vocab_size=163840, hidden_size=2304, num_hidden_layers=27, head_dim=72,
        num_attention_heads=32, num_key_value_heads=32, intermediate_size=9216,
        moe_intermediate_size=1024, first_k_dense_replace=1, num_experts=256,
        num_shared_experts=1, num_experts_per_token=8, routed_scaling_factor=2.446,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        model_max_length=1048576,
        linear_attn_config={**_lists(27), "head_dim": 128, "num_heads": 32,
                            "short_conv_kernel_size": 4}),
    "kimi-linear-tiny": dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=5, head_dim=16,
        num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
        moe_intermediate_size=16, first_k_dense_replace=1, num_experts=16,
        num_shared_experts=1, num_experts_per_token=3, routed_scaling_factor=2.446,
        kv_lora_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=8,
        model_max_length=128,
        linear_attn_config={"full_attn_layers": [4], "kda_layers": [1, 2, 3, 5],
                            "head_dim": 16, "num_heads": 2, "short_conv_kernel_size": 4}),
}
_FLAGS = dict(
    model_type="kimi_linear", hidden_act="silu", rms_norm_eps=1e-5, mla_use_nope=True,
    moe_layer_freq=1, moe_renormalize=True, moe_router_activation_func="sigmoid",
    num_expert_group=1, topk_group=1, use_grouped_topk=True, num_nextn_predict_layers=0,
    q_lora_rank=None, rope_scaling=None, rope_theta=10000, tie_word_embeddings=False)
#: the keys read; any other key of a configuration is refused by name
_READ = (frozenset(_FLAGS) | frozenset(_PRESETS["kimi-linear-48b-a3b"])
         | {"bias_update_speed"})
_LINEAR_READ = frozenset(_PRESETS["kimi-linear-48b-a3b"]["linear_attn_config"])


def _listed(linear: Dict[str, Any]) -> Tuple[list, list, int]:
    kda, full = list(linear.get("kda_layers") or []), list(linear.get("full_attn_layers") or [])
    return kda, full, max(kda + full + [0])


def layer_mixers(linear: Dict[str, Any], layers: int) -> Optional[Tuple[str, ...]]:
    """Each of the first ``layers`` layers' mixer from the two 1-indexed lists;
    None where a layer of the lists' range is in both or in neither."""
    kda, full, depth = _listed(linear)
    if sorted(kda + full) != list(range(1, depth + 1)):
        return None
    return tuple("kda" if l in kda else "latent" for l in range(1, min(layers, depth) + 1))


def config_kwargs(hf: Dict[str, Any]) -> Dict[str, Any]:
    """``TransformerConfig`` arguments from a ``kimi_linear`` configuration dict;
    what this program does not read or compute is refused by name."""
    linear = dict(hf.get("linear_attn_config") or {})
    unread = sorted(set(hf) - _READ) + sorted(
        f"linear_attn_config.{key}" for key in set(linear) - _LINEAR_READ)
    layers = hf["num_hidden_layers"]
    mixers = layer_mixers(linear, layers)
    refused = {
        "num_nextn_predict_layers": bool(hf.get("num_nextn_predict_layers")),
        "mla_use_nope": not hf.get("mla_use_nope", False),
        "q_lora_rank": hf.get("q_lora_rank") is not None,
        "rope_scaling": hf.get("rope_scaling") is not None,
        "num_expert_group": (hf.get("num_expert_group", 1), hf.get("topk_group", 1)) != (1, 1),
        "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
        "num_hidden_layers": not 0 < layers <= _listed(linear)[2],
        "linear_attn_config.kda_layers / full_attn_layers": mixers is None,
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "moe_router_activation_func": hf.get("moe_router_activation_func") != "sigmoid",
        "moe_layer_freq": hf.get("moe_layer_freq", 1) != 1,
        "num_key_value_heads": hf.get("num_key_value_heads", hf["num_attention_heads"])
        != hf["num_attention_heads"],
        "first_k_dense_replace": not 0 < hf.get("first_k_dense_replace", 0) < layers,
    }
    if unread or any(refused.values()):
        raise NotImplementedError(
            "kimi_linear configuration keys this program does not compute: "
            + ", ".join(unread + [k for k, bad in refused.items() if bad]))
    moe = MoEConfig(
        num_experts=hf["num_experts"], top_k=hf["num_experts_per_token"],
        capacity_factor=None, normalize_weights=bool(hf.get("moe_renormalize", True)),
        balance_loss="topk_share", aux_loss_coef=0.0, router="sigmoid_bias",
        routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        shared_width=hf.get("num_shared_experts", 0) * hf["moe_intermediate_size"],
        bias_update=float(hf.get("bias_update_speed", 1e-3)))
    return dict(
        vocab_size=hf["vocab_size"], max_seq_len=hf["model_max_length"],
        num_layers=layers, num_heads=hf["num_attention_heads"],
        hidden_size=hf["hidden_size"], intermediate_size=hf["moe_intermediate_size"],
        dense_intermediate_size=hf["intermediate_size"],
        first_dense_layers=hf["first_k_dense_replace"],
        activation="silu_gated", norm="rmsnorm", norm_eps=hf.get("rms_norm_eps", 1e-5),
        position="none", linear_bias=False, attn_bias=False, tie_embeddings=False,
        # (a depth without a latent layer has no latent sizes to give)
        attention="latent" if "latent" in mixers else "mha",
        kv_latent_rank=hf["kv_lora_rank"], qk_nope_dim=hf["qk_nope_head_dim"],
        qk_rope_dim=hf["qk_rope_head_dim"], v_head_dim=hf["v_head_dim"],
        layer_mixers=mixers, kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        kda_conv=linear.get("short_conv_kernel_size", 4), moe=moe)


def checkpoint_params(cfg, state_dict):
    """No checkpoint loader: the released tensors' names are the modelling
    code's."""
    raise NotImplementedError(
        "loading a kimi_linear checkpoint is not written; build the model from its "
        "configuration (kimi_linear_model) and hand initialize() its parameters")


register_architecture("kimi_linear", config_kwargs, checkpoint_params)


def kimi_linear_config(preset: str = "kimi-linear-48b-a3b", dtype=jnp.bfloat16,
                       layers: Optional[int] = None,
                       experts_held: Optional[Tuple[int, int]] = None,
                       **overrides) -> TransformerConfig:
    """``layers``: another depth than the preset's, the lists' entries up to it;
    ``experts_held``: the range of each layer's experts this chip holds (None:
    all)."""
    depth = {} if layers is None else {"num_hidden_layers": layers}
    kw = config_kwargs({**_FLAGS, **_PRESETS[preset], **depth})
    kw["moe"] = dataclasses.replace(kw["moe"], experts_held=experts_held)
    kw.update(dtype=dtype, **overrides)
    return TransformerConfig(**kw)


def kimi_linear_model(preset: str = "kimi-linear-48b-a3b", **overrides) -> TransformerLM:
    return TransformerLM(kimi_linear_config(preset, **overrides))
