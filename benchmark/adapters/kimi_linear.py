"""Kimi Linear (benchmark/reference/kimi_linear.py) onto ``deepspeed_tpu``: the
configuration file's Hugging Face keys go through the program's own table of
architectures (``models/registry.py``, ``model_type`` ``kimi_linear``; a program
without the entry fails here, at the first call, before a weight is made), as
the chip of the file's ``share`` block (the router keeps its published outputs,
the experts held are ``share_rank``'s), with the separator of packed documents
and the bias's update speed from ``assumed``; and the reference's flat weight
names, a stretch of consecutive layers of one mixer and one FFN a stack, under
the program's parameter paths, where a stack is a place of a run of the layer
scan (``TransformerLM.run_plan``): stretch k of the reference is the k-th stack
of the program's runs in order, so both directions hand out the arrays they
were given, no copy. What an adapter is: benchmark/adapters/gpt2.py."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

from benchmark import program

#: what a configuration file holds beside the model's own keys
_FILE_KEYS = frozenset({
    "name", "source", "reduced", "share", "reduced_why", "assumed", "deployment",
    "reference", "adapter", "stated_precision", "params_note", "engine", "limits",
    "cpu_test_preset"})


def model(config: dict, *, remat: bool, dtype: str):
    """``TransformerLM`` at the configuration file's published widths, as the
    chip of its ``share`` block (every expert held without one)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.registry import get_architecture
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
    assumed, share = config["assumed"], config.get("share")
    held = config["num_experts"]
    published = share["published"].get("num_experts", held) if share else held
    kw = get_architecture(config["model_type"]).config_fn({
        **{k: v for k, v in config.items() if k not in _FILE_KEYS},
        "num_experts": published, "bias_update_speed": assumed["bias_update_speed"]})
    if held != published:
        rank = int(assumed.get("share_rank", 0))
        kw["moe"] = dataclasses.replace(
            kw["moe"], experts_held=(rank * held, (rank + 1) * held))
    return TransformerLM(TransformerConfig(
        **kw, document_separator=assumed.get("separator"),
        dtype=jnp.dtype(dtype), remat=remat))


_EVERY = {"norm1": ("ln_1", "scale"), "norm2": ("ln_2", "scale")}
_MIXER = {
    "kda": {**{f"w{a}": (f"{a}_proj", "kernel") for a in "qkv"},
            **{f"conv_{a}": ("kda", f"conv_{a}") for a in "qkv"},
            "w_fa": ("kda_fa", "kernel"), "w_fb": ("kda_fb", "kernel"),
            "w_ga": ("kda_ga", "kernel"), "w_gb": ("kda_gb", "kernel"),
            "dt_b": ("kda", "dt_bias"), "A_log": ("kda", "A_log"),
            "w_beta": ("kda_beta", "kernel"), "norm_o": ("kda_norm", "scale"),
            "wo": ("o_proj", "kernel")},
    "latent": {"wq": ("q_proj", "kernel"), "wkva": ("kv_a_proj", "kernel"),
               "kv_norm": ("kv_a_norm", "scale"), "wkvb": ("kv_b_proj", "kernel"),
               "wo": ("o_proj", "kernel")}}
_FFN = {
    "dense": {"w_gate": ("gate_proj", "kernel"), "w_up": ("up_proj", "kernel"),
              "w_down": ("down_proj", "kernel")},
    "experts": {"router": ("moe", "gate"), "router_bias": ("moe", "bias"),
                "e_gate": ("moe", "wi_gate"), "e_up": ("moe", "wi_up"),
                "e_down": ("moe", "wo"), "s_gate": ("moe", "shared", "gate_proj"),
                "s_up": ("moe", "shared", "up_proj"),
                "s_down": ("moe", "shared", "down_proj")}}
_TOP = {"embed": ("wte", "embedding"), "head": ("lm_head", "kernel"),
        "norm_f": ("ln_f", "scale")}

Kind = Tuple[str, str]


def _paths(stacks: List[Tuple[Tuple[str, str], Kind]]) -> Dict[str, Tuple[str, ...]]:
    """The reference's flat weight names -> the program's parameter paths:
    stretch k of the reference is ``stacks[k]``, ((run, place), (mixer, ffn))."""
    paths = dict(_TOP)
    for k, (at, (mixer, ffn)) in enumerate(stacks):
        paths.update({f"r{k}.{name}": ("runs",) + at + path
                      for name, path in {**_EVERY, **_MIXER[mixer], **_FFN[ffn]}.items()})
    return paths


def _kind_of(leaves) -> Kind:
    """A stack's (mixer, ffn), read off which leaves it has."""
    return ("latent" if any(leaf in leaves for leaf in ("wkva", "kv_a_proj")) else "kda",
            "experts" if any(leaf in leaves for leaf in ("router", "moe")) else "dense")


def _places(layers: List[int]) -> List[Tuple[str, str]]:
    """Where the program's plan lays stretches of ``layers`` layers each, in order:
    a stretch of several layers is a run of its own (a unit of one kind,
    repeated), single layers that repeat nothing join one run, a place each (a
    plan that repeats a longer unit, as the whole published depth's does, lays a
    stretch out over several stacks, which this adapter does not copy together)."""
    at: List[Tuple[str, str]] = []
    run, place, joins = -1, 0, False
    for n in layers:
        run, place = (run, place + 1) if n == 1 and joins else (run + 1, 0)
        joins = n == 1
        at.append((str(run), str(place)))
    return at


def to_program(weights: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's flat weights under the program's parameter names."""
    stretches = sorted({name.split(".")[0] for name in weights if "." in name},
                       key=lambda name: int(name[1:]))
    kinds = [_kind_of({n.split(".")[1] for n in weights if n.startswith(r + ".")})
             for r in stretches]
    layers = [weights[r + ".norm1"].shape[0] for r in stretches]
    return program.tree_of(_paths(list(zip(_places(layers), kinds))), weights)


def from_program(tree: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's flat names (the
    tree's own arrays)."""
    runs = tree["runs"]
    at = [(i, j) for i in sorted(runs, key=int) for j in sorted(runs[i], key=int)]
    kinds = [_kind_of(runs[i][j]) for i, j in at]
    if _places([runs[i][j]["ln_1"]["scale"].shape[0] for i, j in at]) != at:
        raise ValueError("the program's plan lays its layers out otherwise than the "
                         "reference's stretches: a stretch then lies in several stacks")
    return program.flat_of(_paths(list(zip(at, kinds))), tree)
