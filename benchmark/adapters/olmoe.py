"""OLMoE (benchmark/reference/olmoe.py) onto ``deepspeed_tpu``'s
``TransformerLM``: Hugging Face's key names onto the program's settings
(QK-norm on; the expert layer with no capacity, the top-k probabilities as
they are unless ``norm_topk_prob``, the balance loss over all ``tokens x
top_k`` assignments and the router z-loss, under the configuration file's
two ``assumed`` coefficients), and the reference's flat weight names under
the program's parameter paths. What an adapter is:
benchmark/adapters/gpt2.py."""

from __future__ import annotations

from typing import Any, Dict

from benchmark import program


def model(config: dict, *, remat: bool, dtype: str):
    """``TransformerLM`` at the configuration file's published sizes."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.transformer import (MoEConfig, TransformerConfig,
                                                  TransformerLM)
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the adapter maps the gated SiLU experts only")
    if (config.get("attention_bias") or config.get("tie_word_embeddings")
            or config.get("clip_qkv") is not None
            or config.get("rope_scaling") is not None):
        raise ValueError("the adapter maps bias-free attention, an untied "
                         "head, no clip_qkv and no rope scaling only")
    assumed = config["assumed"]
    moe = MoEConfig(
        num_experts=config["num_experts"], top_k=config["num_experts_per_tok"],
        capacity_factor=None, normalize_weights=bool(config["norm_topk_prob"]),
        balance_loss="topk_share",
        aux_loss_coef=assumed["router_aux_loss_coef"],
        z_loss_coef=assumed["router_z_loss_coef"])
    return TransformerLM(TransformerConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        activation="silu_gated", norm="rmsnorm", norm_eps=config["rms_norm_eps"],
        position="rope", rope_theta=float(config["rope_theta"]), qk_norm=True,
        tie_embeddings=False, moe=moe, dtype=jnp.dtype(dtype), remat=remat))


#: the reference's flat weight names -> the program's parameter paths
_PATHS = {
    "embed": ("wte", "embedding"), "head": ("lm_head", "kernel"),
    "norm_f": ("ln_f", "scale"),
    "norm1": ("blocks", "ln_1", "scale"), "norm2": ("blocks", "ln_2", "scale"),
    "wq": ("blocks", "q_proj", "kernel"), "wk": ("blocks", "k_proj", "kernel"),
    "wv": ("blocks", "v_proj", "kernel"), "wo": ("blocks", "o_proj", "kernel"),
    "q_norm": ("blocks", "q_norm", "scale"), "k_norm": ("blocks", "k_norm", "scale"),
    "router": ("blocks", "moe", "gate"),
    "w_gate": ("blocks", "moe", "wi_gate"), "w_up": ("blocks", "moe", "wi_up"),
    "w_down": ("blocks", "moe", "wo"),
}


def to_program(weights: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's flat weights under the program's parameter names."""
    return program.tree_of(_PATHS, weights)


def from_program(tree: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's flat names."""
    return program.flat_of(_PATHS, tree)
