"""Instella-MoE (``model_type`` ``deepseek_v3``; amd/Instella-MoE-16B-A3B-Base)
from Hugging Face's configuration keys onto ``TransformerLM``: DeepSeek-V3's
block with three flags of AMD's own.

- multi-head latent attention (``kv_lora_rank``, ``qk_nope_head_dim`` +
  ``qk_rope_head_dim``, ``v_head_dim``; ``q_lora_rank``: the compressed
  query, null in Instella's own file), interleaved
  rotary pairs on the rope part, YaRN frequencies (``rope_scaling``);
- ``qk_layernorm``: RMSNorm over each head's query and key vector before
  rope; ``gated_attention``: a sigmoid gate on the attention output from the
  sub-block's input; ``farskip``: each sub-block reads the stream as it stood
  before the sub-block in front of it (the configuration carries the three
  flags and no formula: ``TransformerConfig`` says how each is read);
- ``first_k_dense_replace`` leading dense layers, then expert layers:
  ``n_routed_experts`` experts of ``moe_intermediate_size``,
  ``num_experts_per_tok`` a token by sigmoid scores under a correction bias
  (``topk_method`` ``noaux_tc``, one group), ``norm_topk_prob``,
  ``routed_scaling_factor``, ``n_shared_experts`` shared experts as one MLP,
  the sequence-wise balance loss (``seq_aux``), no capacity and no drops;
- ``num_nextn_predict_layers`` multi-token-prediction modules (one).

The balance coefficient, the bias update rate and the module's loss weight
are not in the configuration: the DeepSeek-V3 report's 1e-4, 1e-3 and 0.3
unless the dict gives ``aux_loss_alpha``, ``bias_update_speed``,
``mtp_loss_lambda``. A chip that holds a share of each layer's experts
passes ``experts_held`` (``MoEConfig``)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from .registry import register_architecture
from .transformer import MoEConfig, TransformerConfig, TransformerLM, YarnScaling

#: amd/Instella-MoE-16B-A3B-Base config.json, and a toy of the same block
_PRESETS = {
    "instella-moe-16b-a3b": dict(
        vocab_size=128896, hidden_size=2048, num_hidden_layers=27,
        num_attention_heads=16, num_key_value_heads=16, intermediate_size=10944,
        moe_intermediate_size=1408, first_k_dense_replace=1, n_routed_experts=64,
        n_shared_experts=2, num_experts_per_tok=6, routed_scaling_factor=2.5,
        kv_lora_rank=512, qk_nope_head_dim=96, qk_rope_head_dim=32, v_head_dim=128,
        max_position_embeddings=65536, rope_theta=8000000,
        rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
                      "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096},
        num_nextn_predict_layers=1),
    "instella-tiny": dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
        moe_intermediate_size=16, first_k_dense_replace=1, n_routed_experts=16,
        n_shared_experts=2, num_experts_per_tok=3, routed_scaling_factor=2.5,
        kv_lora_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
        max_position_embeddings=128, rope_theta=10000,
        rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
                      "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 32},
        num_nextn_predict_layers=1),
}
_FLAGS = dict(model_type="deepseek_v3", hidden_act="silu", attention_bias=False,
              q_lora_rank=None, rope_interleave=True, rms_norm_eps=1e-6,
              qk_layernorm=True, gated_attention=True, farskip=True,
              scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
              topk_group=1, norm_topk_prob=True, seq_aux=True, moe_layer_freq=1,
              tie_word_embeddings=False)


def config_kwargs(hf: Dict[str, Any]) -> Dict[str, Any]:
    """``TransformerConfig`` arguments from a ``deepseek_v3`` configuration
    dict; what this program does not compute is refused by name."""
    refused = {
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "attention_bias": bool(hf.get("attention_bias")),
        "n_group / topk_group": (hf.get("n_group", 1), hf.get("topk_group", 1)) != (1, 1),
        "moe_layer_freq": hf.get("moe_layer_freq", 1) != 1,
        "scoring_func / topk_method": (hf.get("scoring_func"), hf.get("topk_method"))
        != ("sigmoid", "noaux_tc"),
        "num_key_value_heads": hf.get("num_key_value_heads", hf["num_attention_heads"])
        != hf["num_attention_heads"],
    }
    if any(refused.values()):
        raise NotImplementedError(
            "deepseek_v3 configuration keys this program does not compute: "
            + ", ".join(k for k, bad in refused.items() if bad))
    scaling = hf.get("rope_scaling")
    if scaling is not None:
        if scaling.get("type", scaling.get("rope_type")) != "yarn":
            raise NotImplementedError(f"rope_scaling {scaling!r}: yarn alone")
        scaling = YarnScaling(
            factor=float(scaling["factor"]),
            original_max_position=int(scaling["original_max_position_embeddings"]),
            beta_fast=float(scaling.get("beta_fast", 32)),
            beta_slow=float(scaling.get("beta_slow", 1)),
            mscale=float(scaling.get("mscale", 1)),
            mscale_all_dim=float(scaling.get("mscale_all_dim", 0)))
    moe = MoEConfig(
        num_experts=hf["n_routed_experts"], top_k=hf["num_experts_per_tok"],
        capacity_factor=None, normalize_weights=bool(hf.get("norm_topk_prob", True)),
        balance_loss="topk_share", aux_loss_coef=0.0, router="sigmoid_bias",
        routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        shared_width=hf.get("n_shared_experts", 0) * hf["moe_intermediate_size"],
        seq_balance_coef=(float(hf.get("aux_loss_alpha", 1e-4))
                          if hf.get("seq_aux", True) else 0.0),
        bias_update=float(hf.get("bias_update_speed", 1e-3)))
    return dict(
        vocab_size=hf["vocab_size"], max_seq_len=hf["max_position_embeddings"],
        num_layers=hf["num_hidden_layers"], num_heads=hf["num_attention_heads"],
        hidden_size=hf["hidden_size"], intermediate_size=hf["moe_intermediate_size"],
        dense_intermediate_size=hf["intermediate_size"],
        first_dense_layers=hf.get("first_k_dense_replace", 0),
        activation="silu_gated", norm="rmsnorm", norm_eps=hf.get("rms_norm_eps", 1e-6),
        position="rope", rope_theta=float(hf["rope_theta"]),
        rope_style="interleaved" if hf.get("rope_interleave", True) else "half",
        rope_scaling=scaling, attention="latent", kv_latent_rank=hf["kv_lora_rank"],
        q_latent_rank=hf.get("q_lora_rank") or 0,
        qk_nope_dim=hf["qk_nope_head_dim"], qk_rope_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], qk_norm=bool(hf.get("qk_layernorm")),
        qk_norm_per_head=bool(hf.get("qk_layernorm")),
        attn_gate=bool(hf.get("gated_attention")), farskip=bool(hf.get("farskip")),
        mtp_layers=hf.get("num_nextn_predict_layers", 0),
        mtp_loss_coef=float(hf.get("mtp_loss_lambda", 0.3)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)), moe=moe)


def checkpoint_params(cfg, state_dict):
    """No checkpoint loader: the configuration names the gate, the QK-norm
    and the FarSkip residual by flag alone, and which tensors of a
    checkpoint carry them is not in it."""
    raise NotImplementedError(
        "loading a deepseek_v3 / Instella-MoE checkpoint is not written; "
        "build the model from its configuration (instella_moe_model) and "
        "hand initialize() its parameters")


register_architecture("deepseek_v3", config_kwargs, checkpoint_params)


def instella_moe_config(preset: str = "instella-moe-16b-a3b", dtype=jnp.bfloat16,
                        experts_held: Optional[Tuple[int, int]] = None,
                        **overrides) -> TransformerConfig:
    """A preset's ``TransformerConfig``; ``experts_held``: the range of each
    layer's experts this chip holds (None: all)."""
    kw = config_kwargs({**_FLAGS, **_PRESETS[preset]})
    kw["moe"] = dataclasses.replace(kw["moe"], experts_held=experts_held)
    kw.update(dtype=dtype, **overrides)
    return TransformerConfig(**kw)


def instella_moe_model(preset: str = "instella-moe-16b-a3b", **overrides) -> TransformerLM:
    return TransformerLM(instella_moe_config(preset, **overrides))
