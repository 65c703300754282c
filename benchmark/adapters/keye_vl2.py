"""Keye-VL-2.0-30B-A3B's language model (benchmark/reference/keye_vl2.py) onto
``deepspeed_tpu``: the configuration file's Hugging Face keys go through the
program's own table of architectures (``models/registry.py``, ``model_type``
``KeyeVL2``), with the published expert count in the router's place, the range
of experts this chip holds from the file's ``share`` block and the separator of
packed documents from ``assumed``; and the reference's flat weight names under
the program's parameter paths. What an adapter is: benchmark/adapters/gpt2.py."""

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


_LAYER = {
    "norm1": ("ln_1", "scale"), "norm2": ("ln_2", "scale"),
    "wq": ("q_proj", "kernel"), "wk": ("k_proj", "kernel"), "wv": ("v_proj", "kernel"),
    "wo": ("o_proj", "kernel"),
    "q_norm": ("q_norm", "scale"), "k_norm": ("k_norm", "scale"),
    "idx_wq": ("indexer_q", "kernel"), "idx_wk": ("indexer_k", "kernel"),
    "idx_ww": ("indexer_w", "kernel"),
    "idx_ln_g": ("indexer_k_norm", "scale"), "idx_ln_b": ("indexer_k_norm", "bias"),
    "router": ("moe", "gate"),
    "w_gate": ("moe", "wi_gate"), "w_up": ("moe", "wi_up"), "w_down": ("moe", "wo"),
}
#: the reference's flat weight names -> the program's parameter paths
_PATHS = {
    "embed": ("wte", "embedding"), "head": ("lm_head", "kernel"),
    "norm_f": ("ln_f", "scale"),
    **{name: ("blocks",) + path for name, path in _LAYER.items()},
}


def to_program(weights: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's flat weights under the program's parameter names."""
    return program.tree_of(_PATHS, weights)


def from_program(tree: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's flat names."""
    return program.flat_of(_PATHS, tree)
