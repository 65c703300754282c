"""Xing4.0-29B-A4B (benchmark/reference/xing4.py) onto ``deepspeed_tpu``: the
configuration file's Hugging Face keys go through the program's own table of
architectures (``models/registry.py``, ``model_type`` ``xing4_0``), with the
published expert count in the router's place, the range of experts this chip
holds from the file's ``share`` block, the separator of packed documents, the
rope pairing and the three coefficients from ``assumed``; and the reference's
flat weight names under the program's parameter paths (``from_program`` hands
out the engine's own arrays, no copy). What an adapter is:
benchmark/adapters/gpt2.py."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchmark import program

#: what a configuration file holds beside the model's own keys
_FILE_KEYS = frozenset({
    "name", "source", "reduced", "share", "reduced_why", "assumed", "deployment",
    "reference", "adapter", "stated_precision", "params_note", "engine", "limits",
    "cpu_test_preset"})


def model(config: dict, *, remat: bool, dtype: str):
    """``TransformerLM`` at the configuration file's published widths, as
    the chip of its ``share`` block (every expert held without one)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.registry import get_architecture
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
    assumed, share = config["assumed"], config.get("share")
    held = config["n_routed_experts"]
    published = share["published"].get("n_routed_experts", held) if share else held
    kw = get_architecture(config["model_type"]).config_fn({
        **{k: v for k, v in config.items() if k not in _FILE_KEYS},
        "n_routed_experts": published,
        "rope_interleave": bool(assumed["rope_interleave"]),
        "aux_loss_alpha": assumed["seq_aux_alpha"],
        "bias_update_speed": assumed["bias_update_gamma"],
        "mtp_loss_lambda": assumed["mtp_loss_lambda"]})
    if held != published:
        rank = int(assumed.get("share_rank", 0))
        kw["moe"] = dataclasses.replace(
            kw["moe"], experts_held=(rank * held, (rank + 1) * held))
    return TransformerLM(TransformerConfig(
        **kw, document_separator=assumed.get("separator"),
        dtype=jnp.dtype(dtype), remat=remat))


_ATTENTION = {
    "norm1": ("ln_1", "scale"), "norm2": ("ln_2", "scale"),
    "wqa": ("q_a_proj", "kernel"), "q_norm": ("q_a_norm", "scale"),
    "wqb": ("q_b_proj", "kernel"), "wkva": ("kv_a_proj", "kernel"),
    "kv_norm": ("kv_a_norm", "scale"), "wkvb": ("kv_b_proj", "kernel"),
    "wo": ("o_proj", "kernel"),
    **{sub + name: (layer, leaf)
       for sub, layer in (("a_", "hc_attn"), ("f_", "hc_mlp"))
       for name, leaf in (("phi", "phi"), ("b", "bias"), ("alpha", "alpha"))},
}
_DENSE = {"gate": ("gate_proj", "kernel"), "up": ("up_proj", "kernel"),
          "down": ("down_proj", "kernel")}
_EXPERTS = {
    "router": ("moe", "gate"), "router_bias": ("moe", "bias"),
    "w_gate": ("moe", "wi_gate"), "w_up": ("moe", "wi_up"), "w_down": ("moe", "wo"),
    "s_gate": ("moe", "shared", "gate_proj"), "s_up": ("moe", "shared", "up_proj"),
    "s_down": ("moe", "shared", "down_proj"),
}
#: the reference's flat weight names -> the program's parameter paths
_PATHS = {
    "embed": ("wte", "embedding"), "head": ("lm_head", "kernel"),
    "norm_f": ("ln_f", "scale"),
    "m_norm_h": ("mtp", "norm_h", "scale"), "m_norm_e": ("mtp", "norm_e", "scale"),
    "m_norm_f": ("mtp", "ln_f", "scale"), "m_merge": ("mtp", "merge", "kernel"),
    **{prefix + name: root + path
       for prefix, root, mlp in (("d_", ("dense_blocks",), _DENSE),
                                 ("", ("blocks",), _EXPERTS),
                                 ("m_", ("mtp", "blocks"), _EXPERTS))
       for name, path in {**_ATTENTION, **mlp}.items()},
}


def to_program(weights: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's flat weights under the program's parameter names."""
    return program.tree_of({k: v for k, v in _PATHS.items() if k in weights}, weights)


def from_program(tree: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's flat names (the
    tree's own arrays)."""
    return program.flat_of({k: p for k, p in _PATHS.items() if p[0] in tree}, tree)
