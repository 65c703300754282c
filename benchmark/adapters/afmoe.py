"""Trinity-Mini (benchmark/reference/afmoe.py) onto ``deepspeed_tpu``: the
configuration file's Hugging Face keys go through the program's own table of
architectures (``models/registry.py``, ``model_type`` ``afmoe``), with the
published expert count in the router's place, the range of experts this chip
holds from the file's ``share`` block, and the separator of packed documents
from ``assumed``; and the reference's flat weight names under the program's
parameter paths. What an adapter is: benchmark/adapters/gpt2.py."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchmark import program


def model(config: dict, *, remat: bool, dtype: str):
    """``TransformerLM`` at the configuration file's published widths, as
    the chip of its ``share`` block (every expert held without one)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.registry import get_architecture
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
    assumed, share = config["assumed"], config.get("share")
    held = config["num_experts"]
    published = share["published"].get("num_experts", held) if share else held
    kw = get_architecture(config["model_type"]).config_fn(
        {**config, "num_experts": published})
    if held != published:
        rank = int(assumed.get("share_rank", 0))
        kw["moe"] = dataclasses.replace(
            kw["moe"], experts_held=(rank * held, (rank + 1) * held))
    return TransformerLM(TransformerConfig(
        **kw, document_separator=assumed.get("separator"),
        dtype=jnp.dtype(dtype), remat=remat))


_ATTENTION = {
    "norm1": ("ln_1", "scale"), "norm1_post": ("post_ln_1", "scale"),
    "norm2": ("ln_2", "scale"), "norm2_post": ("post_ln_2", "scale"),
    "wq": ("q_proj", "kernel"), "wk": ("k_proj", "kernel"), "wv": ("v_proj", "kernel"),
    "wg": ("attn_gate", "kernel"), "wo": ("o_proj", "kernel"),
    "q_norm": ("q_norm", "scale"), "k_norm": ("k_norm", "scale"),
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
    **{prefix + name: root + path
       for prefix, root, mlp in (("d_", ("dense_blocks",), _DENSE),
                                 ("", ("blocks",), _EXPERTS))
       for name, path in {**_ATTENTION, **mlp}.items()},
}


def to_program(weights: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's flat weights under the program's parameter names."""
    return program.tree_of(_PATHS, weights)


def from_program(tree: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's flat names."""
    return program.flat_of(_PATHS, tree)
