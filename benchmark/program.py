"""The only place the benchmark touches ``deepspeed_tpu``: it builds the
system under test through the entry points a user calls and hands it the
benchmark's own weights. No measurement and no yardstick lives here."""

from __future__ import annotations

from typing import Any, Dict


def transformer_lm(config: dict, *, remat: bool, dtype: str):
    """``TransformerLM`` at the configuration file's published sizes."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
    if config.get("activation_function", "gelu_new") != "gelu_new":
        raise ValueError("the adapter maps GPT-2's gelu_new only")
    return TransformerLM(TransformerConfig(
        vocab_size=config["vocab_size"], max_seq_len=config["n_positions"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        hidden_size=config["n_embd"], activation="gelu", norm="layernorm",
        norm_eps=config["layer_norm_epsilon"], position="learned",
        tie_embeddings=True, dtype=jnp.dtype(dtype), remat=remat))


#: the reference's flat weight names -> the program's parameter paths
_PATHS = {
    "wte": ("wte", "embedding"), "wpe": ("wpe", "embedding"),
    "lnf_g": ("ln_f", "scale"), "lnf_b": ("ln_f", "bias"),
    "ln1_g": ("blocks", "ln_1", "scale"), "ln1_b": ("blocks", "ln_1", "bias"),
    "ln2_g": ("blocks", "ln_2", "scale"), "ln2_b": ("blocks", "ln_2", "bias"),
    "wq": ("blocks", "q_proj", "kernel"), "bq": ("blocks", "q_proj", "bias"),
    "wk": ("blocks", "k_proj", "kernel"), "bk": ("blocks", "k_proj", "bias"),
    "wv": ("blocks", "v_proj", "kernel"), "bv": ("blocks", "v_proj", "bias"),
    "wo": ("blocks", "o_proj", "kernel"), "bo": ("blocks", "o_proj", "bias"),
    "w_in": ("blocks", "fc_in", "kernel"), "b_in": ("blocks", "fc_in", "bias"),
    "w_out": ("blocks", "fc_out", "kernel"), "b_out": ("blocks", "fc_out", "bias"),
}


def program_tree(w: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's flat weights under the program's parameter names."""
    tree: Dict[str, Any] = {}
    for name, path in _PATHS.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = w[name]
    return tree


def flat_weights(tree: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's flat names."""
    out = {}
    for name, path in _PATHS.items():
        node = tree
        for part in path:
            node = node[part]
        out[name] = node
    return out


def release() -> None:
    """Drop the process-global topology and telemetry between engines."""
    import gc
    from deepspeed_tpu.runtime import topology as topo_mod
    from deepspeed_tpu.telemetry import reset_telemetry
    topo_mod.reset()
    reset_telemetry()
    gc.collect()


def train_engine(model, ds_config: dict, weights: Dict[str, Any], seed: int):
    import deepspeed_tpu
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=ds_config, model_parameters=program_tree(weights),
        seed=seed % (2 ** 31))
    return engine


def master_weights(engine) -> Dict[str, Any]:
    """The float32 master weights the optimizer steps (the engine's own
    arrays, not copies), as the program's parameter tree."""
    return engine.state["opt"]["master"]
