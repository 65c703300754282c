"""Granite 4.0-H (benchmark/reference/granite_hybrid.py) onto ``deepspeed_tpu``:
the configuration file's Hugging Face keys go through the program's own table of
architectures (``models/registry.py``, ``model_type`` ``granitemoehybrid``; a
program without the entry fails here, at the first call, before a weight is
made), with the separator of packed documents from ``assumed``; and the
reference's flat weight names, a stretch of consecutive layers of one kind a
stack, under the program's parameter paths, a run of its layer scan a stack
(``TransformerLM.run_plan``): at the depths this benchmark runs a stretch of
the reference IS one run of one kind, so both directions hand out the arrays
they were given, no copy. What an adapter is: benchmark/adapters/gpt2.py."""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import program

#: what a configuration file holds beside the model's own keys
_FILE_KEYS = frozenset({
    "name", "source", "reduced", "share", "reduced_why", "assumed", "deployment",
    "reference", "adapter", "stated_precision", "params_note", "engine", "limits",
    "cpu_test_preset"})


def model(config: dict, *, remat: bool, dtype: str):
    """``TransformerLM`` at the configuration file's published widths."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.registry import get_architecture
    kw = get_architecture(config["model_type"]).config_fn(
        {k: v for k, v in config.items() if k not in _FILE_KEYS})
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
    return TransformerLM(TransformerConfig(
        **kw, document_separator=config["assumed"].get("separator"),
        dtype=jnp.dtype(dtype), remat=remat))


_EVERY = {
    "norm1": ("ln_1", "scale"), "norm2": ("ln_2", "scale"),
    "w_gate": ("gate_proj", "kernel"), "w_up": ("up_proj", "kernel"),
    "w_down": ("down_proj", "kernel")}
_OF_KIND = {
    "ssd": {"w_in": ("in_proj", "kernel"), "conv": ("ssm", "conv"),
            "conv_b": ("ssm", "conv_bias"), "dt_b": ("ssm", "dt_bias"),
            "A_log": ("ssm", "A_log"), "D": ("ssm", "D"),
            "norm_g": ("ssd_norm", "scale"), "w_out": ("out_proj", "kernel")},
    "mha": {"wq": ("q_proj", "kernel"), "wk": ("k_proj", "kernel"),
            "wv": ("v_proj", "kernel"), "wo": ("o_proj", "kernel")}}
_TOP = {"embed": ("wte", "embedding"), "norm_f": ("ln_f", "scale")}


def _paths(mixers: Tuple[str, ...]) -> Dict[str, Tuple[str, ...]]:
    """The reference's flat weight names -> the program's parameter paths for
    the runs' mixers in order: stretch k of the reference is run k of the
    program's plan, a unit of ONE kind (a plan that repeats a longer unit, as
    the whole published depth's does, lays a stretch out over several stacks,
    which this adapter does not copy together)."""
    paths = dict(_TOP)
    for k, mixer in enumerate(mixers):
        paths.update({f"r{k}.{name}": ("runs", str(k), "0") + path
                      for name, path in {**_EVERY, **_OF_KIND[mixer]}.items()})
    return paths


def _mixer_of(leaves) -> str:
    """A run's mixer, read off which leaves it has."""
    return "ssd" if any(leaf in leaves for leaf in ("w_in", "in_proj")) else "mha"


def to_program(weights: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's flat weights under the program's parameter names."""
    stretches = sorted({name.split(".")[0] for name in weights if "." in name},
                       key=lambda name: int(name[1:]))
    mixers = tuple(_mixer_of({n.split(".")[1] for n in weights if n.startswith(r + ".")})
                   for r in stretches)
    return program.tree_of(_paths(mixers), weights)


def from_program(tree: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's flat names (the
    tree's own arrays)."""
    runs = tree["runs"]
    if any(len(run) != 1 for run in runs.values()):
        raise ValueError("the program's plan repeats a unit of several kinds: a stretch "
                         "of the reference then lies in several stacks")
    mixers = tuple(_mixer_of(runs[str(k)]["0"]) for k in range(len(runs)))
    return program.flat_of(_paths(mixers), tree)
