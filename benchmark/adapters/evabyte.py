"""EvaByte (benchmark/reference/evabyte.py) onto ``deepspeed_tpu``: the
configuration file's Hugging Face keys go through the program's own table of
architectures (``models/registry.py``, ``model_type`` ``evabyte``; a program
without the entry fails here, at the first call, before a weight is made),
and the reference's flat weight names under the program's parameter paths.
What an adapter is: benchmark/adapters/gpt2.py."""

from __future__ import annotations

from typing import Any, Dict

from benchmark import program


def model(config: dict, *, remat: bool, dtype: str):
    """``TransformerLM`` at the configuration file's published widths."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.registry import get_architecture
    kw = get_architecture(config["model_type"]).config_fn(config)
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
    return TransformerLM(TransformerConfig(**kw, dtype=jnp.dtype(dtype), remat=remat))


_LAYER = {
    "norm1": ("ln_1", "scale"), "norm2": ("ln_2", "scale"),
    "wq": ("q_proj", "kernel"), "wk": ("k_proj", "kernel"), "wv": ("v_proj", "kernel"),
    "wo": ("o_proj", "kernel"),
    "phi": ("eva_phi", "value"), "mu": ("eva_mu", "value"),
    "w_gate": ("gate_proj", "kernel"), "w_up": ("up_proj", "kernel"),
    "w_down": ("down_proj", "kernel"),
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
