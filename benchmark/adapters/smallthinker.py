"""SmallThinker-21B-A3B (benchmark/reference/smallthinker.py) onto
``deepspeed_tpu``: the configuration file's Hugging Face keys go through the
program's own table of architectures (``models/registry.py``, ``model_type``
``smallthinker``, which the file states under ``assumed``), with the published
expert count in the router's place, the range of experts this chip holds from
the file's ``share`` block, and the separator of packed documents from
``assumed``; and the reference's flat weight names under the program's
parameter paths. What an adapter is: benchmark/adapters/gpt2.py."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchmark import program

EXPERTS = "moe_num_primary_experts"


def model(config: dict, *, remat: bool, dtype: str):
    """``TransformerLM`` at the configuration file's published widths, as
    the chip of its ``share`` block (every expert held without one)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.registry import get_architecture
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
    assumed, share = config["assumed"], config.get("share")
    held = config[EXPERTS]
    published = share["published"].get(EXPERTS, held) if share else held
    kw = get_architecture(assumed["model_type"]).config_fn({**config, EXPERTS: published})
    if held != published:
        rank = int(assumed.get("share_rank", 0))
        kw["moe"] = dataclasses.replace(
            kw["moe"], experts_held=(rank * held, (rank + 1) * held))
    return TransformerLM(TransformerConfig(
        **kw, document_separator=assumed.get("separator"),
        dtype=jnp.dtype(dtype), remat=remat))


#: the reference's flat weight names -> the program's parameter paths
_PATHS = {
    "embed": ("wte", "embedding"), "head": ("lm_head", "kernel"),
    "norm_f": ("ln_f", "scale"),
    "norm1": ("blocks", "ln_1", "scale"), "norm2": ("blocks", "ln_2", "scale"),
    "wq": ("blocks", "q_proj", "kernel"), "wk": ("blocks", "k_proj", "kernel"),
    "wv": ("blocks", "v_proj", "kernel"), "wo": ("blocks", "o_proj", "kernel"),
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
