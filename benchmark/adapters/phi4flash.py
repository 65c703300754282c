"""Phi-4-mini-flash-reasoning (benchmark/reference/phi4flash.py) onto
``deepspeed_tpu``: the configuration file's Hugging Face keys go through the
program's own table of architectures (``models/registry.py``, ``model_type``
``phi4flash``; a program without the entry fails here, at the first call, before
a weight is made), with Mamba-1's four sizes and the separator of packed
documents from ``assumed``; and the reference's flat weight names, a kind of
layer a stack, under the program's parameter paths, a run of its layer scan
and a place in the run's unit a stack (``TransformerLM.run_plan``): a kind of
the reference IS one such stack, so both directions hand out the arrays they
were given, no copy. What an adapter is: benchmark/adapters/gpt2.py."""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

from benchmark import program

#: what a configuration file holds beside the model's own keys
_FILE_KEYS = frozenset({
    "name", "source", "reduced", "share", "reduced_why", "assumed", "deployment",
    "reference", "adapter", "stated_precision", "params_note", "engine", "limits",
    "cpu_test_preset"})


def _hf(config: dict) -> dict:
    """The model's own keys, with Mamba-1's sizes from ``assumed``."""
    assumed = config["assumed"]
    hidden = config["hidden_size"]
    return {**{k: v for k, v in config.items() if k not in _FILE_KEYS},
            "ssm_state": assumed["d_state"], "ssm_conv": assumed["d_conv"],
            "ssm_expand": assumed["d_inner"] // hidden, "ssm_dt_rank": assumed["dt_rank"]}


def model(config: dict, *, remat: bool, dtype: str):
    """``TransformerLM`` at the configuration file's published widths."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.registry import get_architecture
    kw = get_architecture(config["model_type"]).config_fn(_hf(config))
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
    return TransformerLM(TransformerConfig(
        **kw, document_separator=config["assumed"].get("separator"),
        dtype=jnp.dtype(dtype), remat=remat))


_EVERY = {
    "norm1_g": ("ln_1", "scale"), "norm1_b": ("ln_1", "bias"),
    "norm2_g": ("ln_2", "scale"), "norm2_b": ("ln_2", "bias"),
    "w_gate": ("gate_proj", "kernel"), "w_up": ("up_proj", "kernel"),
    "w_down": ("down_proj", "kernel")}
_SCAN = {
    "w_in": ("in_proj", "kernel"), "conv": ("ssm", "conv"), "conv_b": ("ssm", "conv_bias"),
    "w_x": ("x_proj", "kernel"), "w_dt": ("dt_proj", "kernel"), "dt_b": ("ssm", "dt_bias"),
    "A_log": ("ssm", "A_log"), "D": ("ssm", "D"), "w_out": ("out_proj", "kernel")}
_CROSS = {
    "wq": ("q_proj", "kernel"), "bq": ("q_proj", "bias"),
    "wo": ("o_proj", "kernel"), "bo": ("o_proj", "bias"),
    "lam": ("diff_lambda", "value"), "subln": ("diff_norm", "scale")}
_ATTN = {**_CROSS, "wkv": ("kv_proj", "kernel"), "bkv": ("kv_proj", "bias")}
_UNIT = {"w1": ("gmu_in", "kernel"), "w2": ("gmu_out", "kernel")}
#: the reference's kinds of layer: its names, and the program's kind of each
#: (mixer, what it hands on, whether under a window)
_KINDS = {
    "ss": (_SCAN, ("ssm", None, False)), "sw": (_ATTN, ("attn", None, True)),
    "ms": (_SCAN, ("ssm", "memory", False)), "mf": (_ATTN, ("attn", "kv", False)),
    "cg": (_UNIT, ("gmu", None, False)), "cx": (_CROSS, ("cross", None, False))}
_TOP = {"embed": ("wte", "embedding"), "norm_f_g": ("ln_f", "scale"),
        "norm_f_b": ("ln_f", "bias")}


@functools.lru_cache(maxsize=None)
def _paths(layers: int) -> Dict[str, Tuple[str, ...]]:
    """The reference's flat weight names -> the program's parameter paths at a
    depth of ``layers``: each kind of the reference is the stack of ONE run and
    place of the program's plan, found by the kind alone."""
    from deepspeed_tpu.models.phi4flash import phi4flash_model
    plan = phi4flash_model("phi4flash-tiny", layers=layers).run_plan
    place = {}
    for i, (unit, _) in enumerate(plan):
        for j, (window, _, mixer, hands) in enumerate(unit):
            if place.setdefault((mixer, hands, bool(window)), (str(i), str(j))) != (str(i), str(j)):
                raise ValueError(f"layers of kind {(mixer, hands, bool(window))} lie in "
                                 f"several stacks of the program's plan {plan}")
    paths = dict(_TOP)
    for kind, (names, found) in _KINDS.items():
        if found in place:
            paths.update({f"{kind}.{name}": ("runs",) + place[found] + path
                          for name, path in {**_EVERY, **names}.items()})
    return paths


def to_program(weights: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's flat weights under the program's parameter names."""
    return program.tree_of(_paths(4 * weights["ss.w_in"].shape[0]), weights)


def from_program(tree: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's flat names (the
    tree's own arrays)."""
    self_scans = tree["runs"]["0"]["0"]["in_proj"]["kernel"].shape[0]
    return program.flat_of(_paths(4 * self_scans), tree)
