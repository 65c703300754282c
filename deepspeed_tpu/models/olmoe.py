"""OLMoE presets (Muennighoff et al. 2024, arXiv:2409.02060; HF
``modeling_olmoe.py``): a Llama-style rotary decoder whose every MLP is a
fine-grained sparse mixture of experts routed WITHOUT capacity (no token is
dropped), the top-k router probabilities used as they are
(``norm_topk_prob`` false), QK-norm over the whole projected query and key
vectors, and the paper's two router losses (load balancing 0.01, z-loss
0.001). The expert layers take ``moe/layer.py``'s no-drop path."""

from __future__ import annotations

import jax.numpy as jnp

from .transformer import MoEConfig, TransformerConfig, TransformerLM


def olmoe_moe(num_experts: int, top_k: int) -> MoEConfig:
    """OLMoE's expert layer: dropless, weights not renormalised, balance
    loss over all ``tokens x top_k`` assignments (0.01), router z-loss
    (0.001)."""
    return MoEConfig(num_experts=num_experts, top_k=top_k, capacity_factor=None,
                     normalize_weights=False, balance_loss="topk_share",
                     aux_loss_coef=0.01, z_loss_coef=0.001)


_PRESETS = {
    "olmoe-tiny": dict(num_layers=2, num_heads=4, hidden_size=64,
                       intermediate_size=32, max_seq_len=128, vocab_size=512,
                       moe=olmoe_moe(8, 3)),
    # allenai/OLMoE-1B-7B-0125-Instruct config.json
    "olmoe-1b-7b": dict(num_layers=16, num_heads=16, hidden_size=2048,
                        intermediate_size=1024, max_seq_len=4096,
                        vocab_size=50304, moe=olmoe_moe(64, 8)),
}


def olmoe_config(preset: str = "olmoe-1b-7b", dtype=jnp.bfloat16, **overrides) -> TransformerConfig:
    base = dict(
        activation="silu_gated",
        norm="rmsnorm",
        norm_eps=1e-5,
        position="rope",
        rope_theta=10000.0,
        qk_norm=True,
        tie_embeddings=False,
        dtype=dtype,
    )
    base.update(_PRESETS[preset])
    base.update(overrides)
    return TransformerConfig(**base)


def olmoe_model(preset: str = "olmoe-1b-7b", **overrides) -> TransformerLM:
    return TransformerLM(olmoe_config(preset, **overrides))
