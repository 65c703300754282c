"""Model-architecture registry.

Counterpart of the reference's per-architecture model implementations and
their registration (``inference/v2/model_implementations/*`` registered via
``inference/v2/engine_factory.py``, and the kernel-injection policy map in
``module_inject/replace_policy.py``): one table mapping an HF
``model_type`` to the pair of functions that adapt it onto the shared
:class:`~deepspeed_tpu.models.transformer.TransformerLM` —

- ``config_fn(hf_config_dict) -> kwargs for TransformerConfig``
- ``params_fn(cfg, state_dict) -> TransformerLM param pytree``

An architecture registers where it is defined: a ``models/<arch>.py`` with
``config_kwargs`` and ``checkpoint_params`` calls `register_architecture`
itself (the package imports it), ``runtime/state_dict_factory.py`` registers
the families whose adapters it holds (gpt2, the llama family, opt, phi, falcon,
bloom, gpt_neo, gpt_neox, gptj, the bert encoders) at import, and user code
can register more without touching the loader.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict


@dataclasses.dataclass(frozen=True)
class ArchitectureSpec:
    model_type: str
    config_fn: Callable[[Dict[str, Any]], Dict[str, Any]]
    params_fn: Callable[[Any, Dict[str, Any]], Dict[str, Any]]


_ARCHITECTURES: Dict[str, ArchitectureSpec] = {}


def register_architecture(model_type: str,
                          config_fn: Callable,
                          params_fn: Callable) -> ArchitectureSpec:
    spec = ArchitectureSpec(model_type, config_fn, params_fn)
    _ARCHITECTURES[model_type] = spec
    return spec


def get_architecture(model_type: str) -> ArchitectureSpec:
    # the built-ins register when the loader module imports
    from ..runtime import state_dict_factory  # noqa: F401
    if model_type not in _ARCHITECTURES:
        raise ValueError(f"unsupported model_type {model_type!r} "
                         f"(supported: {supported_architectures()})")
    return _ARCHITECTURES[model_type]


def supported_architectures() -> list:
    from ..runtime import state_dict_factory  # noqa: F401
    return sorted(_ARCHITECTURES)
