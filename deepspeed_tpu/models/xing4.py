"""Xing4.0 (``model_type`` ``xing4_0``; XingChen-AGI/Xing4.0-29B-A4B) from
Hugging Face's configuration keys onto ``TransformerLM``: DeepSeek-V3's block
(``models/instella_moe.py`` reads those keys: latent attention, here with the
compressed query ``q_lora_rank`` and value heads of ``v_head_dim`` beside key
heads of ``qk_nope_head_dim + qk_rope_head_dim``; YaRN; leading dense layers;
the sigmoid ``noaux_tc`` router, shared experts, one prediction module) under
manifold-constrained hyper-connections: ``hc_mult`` residual streams mixed, a
sub-layer at a time, by a matrix that ``hc_sinkhorn_iters`` rounds of Sinkhorn
make doubly stochastic (``hc_eps``, ``mhc_h_res_clamp_min`` / ``_max``;
``TransformerConfig.residual_streams`` has the equations).

Every key this file does not read is refused by name. The configuration has
no rope pairing, balance coefficient, bias update rate or module loss weight:
``rope_interleave`` (True), ``aux_loss_alpha``, ``bias_update_speed`` and
``mtp_loss_lambda`` are read where the dict gives them, else Instella's."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from . import instella_moe
from .registry import register_architecture
from .transformer import TransformerConfig, TransformerLM

#: the keys of the published config.json, and a toy of the same block
_PRESETS = {
    "xing4-29b-a4b": dict(
        vocab_size=131072, hidden_size=3584, num_hidden_layers=40,
        num_attention_heads=32, num_key_value_heads=32, intermediate_size=9216,
        moe_intermediate_size=1024, first_k_dense_replace=2, n_routed_experts=64,
        n_shared_experts=1, num_experts_per_tok=4, routed_scaling_factor=2,
        kv_lora_rank=512, q_lora_rank=768, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, max_position_embeddings=262144,
        rope_theta=10000,
        rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
                      "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096},
        num_nextn_predict_layers=1),
    "xing4-tiny": dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=6,
        num_attention_heads=2, num_key_value_heads=2, intermediate_size=96,
        moe_intermediate_size=16, first_k_dense_replace=2, n_routed_experts=8,
        n_shared_experts=1, num_experts_per_tok=2, routed_scaling_factor=2,
        kv_lora_rank=24, q_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
        v_head_dim=16, max_position_embeddings=128, rope_theta=10000,
        rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
                      "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 32},
        num_nextn_predict_layers=1, hc_sinkhorn_iters=6),
}
_FLAGS = dict(model_type="xing4_0", hidden_act="silu", attention_bias=False,
              rms_norm_eps=1e-6, scoring_func="sigmoid", topk_method="noaux_tc",
              n_group=1, topk_group=1, norm_topk_prob=True, moe_layer_freq=1,
              ep_size=1, tie_word_embeddings=False, hc_mult=4, hc_sinkhorn_iters=20,
              hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)

#: the keys the DeepSeek-V3 reader takes of this file's, and the streams' own
_HC_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max")
_READ = frozenset(_PRESETS["xing4-29b-a4b"]) | frozenset(_FLAGS) | {
    "rope_interleave", "aux_loss_alpha", "bias_update_speed", "mtp_loss_lambda"}


def config_kwargs(hf: Dict[str, Any]) -> Dict[str, Any]:
    """``TransformerConfig`` arguments from a ``xing4_0`` configuration dict;
    a key this file does not read, or a value this program does not compute,
    is refused by name."""
    unread = sorted(set(hf) - _READ)
    if unread or hf.get("ep_size", 1) != 1:
        raise NotImplementedError(
            "xing4_0 configuration keys this program does not read: "
            + ", ".join(unread or ["ep_size"]))
    kw = instella_moe.config_kwargs(
        {**{k: v for k, v in hf.items() if k not in _HC_KEYS and k != "ep_size"},
         "seq_aux": True, "model_type": "deepseek_v3"})
    return dict(
        kw, residual_streams=int(hf.get("hc_mult", 1)),
        hc_sinkhorn_iters=int(hf.get("hc_sinkhorn_iters", 20)),
        hc_eps=float(hf.get("hc_eps", 1e-6)),
        hc_res_clamp=(float(hf.get("mhc_h_res_clamp_min", -30.0)),
                      float(hf.get("mhc_h_res_clamp_max", 30.0))))


def checkpoint_params(cfg, state_dict):
    """No checkpoint loader: the released tensors' names are not in the
    configuration."""
    raise NotImplementedError(
        "loading a xing4_0 checkpoint is not written; build the model from its "
        "configuration (xing4_model) and hand initialize() its parameters")


register_architecture("xing4_0", config_kwargs, checkpoint_params)


def xing4_config(preset: str = "xing4-29b-a4b", dtype=jnp.bfloat16,
                 experts_held: Optional[Tuple[int, int]] = None,
                 **overrides) -> TransformerConfig:
    """A preset's ``TransformerConfig``; ``experts_held``: the range of each
    layer's experts this chip holds (None: all)."""
    kw = config_kwargs({**_FLAGS, **_PRESETS[preset]})
    kw["moe"] = dataclasses.replace(kw["moe"], experts_held=experts_held)
    kw.update(dtype=dtype, **overrides)
    return TransformerConfig(**kw)


def xing4_model(preset: str = "xing4-29b-a4b", **overrides) -> TransformerLM:
    return TransformerLM(xing4_config(preset, **overrides))
