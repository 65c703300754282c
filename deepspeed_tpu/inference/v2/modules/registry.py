"""Pluggable implementation registry for inference-v2 modules.

Counterpart of the reference's ``inference/v2/modules/module_registry.py``
(``DSModuleRegistryBase``) + the per-module registries under
``modules/implementations/``: each module slot (decode attention, prefill
attention, linear, MoE dispatch) holds named implementations with a
``supports(context)`` predicate; heuristics (``heuristics.py``) pick the
best supported one for the attached hardware.

The TPU redesign needs far fewer slots than the reference's CUDA zoo — XLA
fusion covers norms/embeds/unembeds — so the registry covers exactly the
choices that exist on TPU: Pallas kernel vs XLA fallback per attention
form, and dense vs weight-only-quantized linears.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class ModuleImplementation:
    name: str
    supports: Callable[[Dict[str, Any]], bool]
    priority: int = 0           # higher wins among supported
    make: Optional[Callable[..., Any]] = None


class DSModuleRegistry:
    """One module slot: named implementations, priority-ordered choice."""

    def __init__(self, slot: str):
        self.slot = slot
        self._impls: Dict[str, ModuleImplementation] = {}

    def register(self, impl: ModuleImplementation) -> ModuleImplementation:
        if impl.name in self._impls:
            raise ValueError(f"{self.slot}: duplicate implementation {impl.name!r}")
        self._impls[impl.name] = impl
        return impl

    def get(self, name: str) -> ModuleImplementation:
        return self._impls[name]

    def implementations(self) -> List[ModuleImplementation]:
        return sorted(self._impls.values(), key=lambda i: -i.priority)

    def choose(self, context: Dict[str, Any],
               preference: Optional[str] = None) -> ModuleImplementation:
        """Highest-priority supported implementation (reference
        ``heuristics.py`` instantiate_* selection), or the named one if a
        preference is given and supported."""
        if preference is not None:
            impl = self._impls[preference]
            if not impl.supports(context):
                raise ValueError(
                    f"{self.slot}: preferred implementation {preference!r} "
                    f"does not support this configuration")
            return impl
        for impl in self.implementations():
            if impl.supports(context):
                return impl
        raise ValueError(f"{self.slot}: no implementation supports {context}")


def _pallas_paged_supported(ctx: Dict[str, Any]) -> bool:
    """Opt-in (DSTPU_PALLAS_PAGED=1) + TPU backend —
    ONE policy shared with the kernel layer (paged_attention.py helpers)
    so the registry never selects an implementation the kernel dispatch
    would not take; the ctx may override the backend for planning."""
    import jax

    from ..kernels.paged_attention import _paged_kernel_opted_in
    if not _paged_kernel_opted_in():
        return False
    if ctx.get("backend", jax.default_backend()) != "tpu":
        return False
    # stock kernel has no bias input (bloom → XLA path)
    return ctx.get("position") != "alibi"


ATTENTION_DECODE_REGISTRY = DSModuleRegistry("attention_decode")
ATTENTION_DECODE_REGISTRY.register(ModuleImplementation(
    name="pallas_paged", priority=10, supports=_pallas_paged_supported))
ATTENTION_DECODE_REGISTRY.register(ModuleImplementation(
    name="xla_gather", priority=0, supports=lambda ctx: True))

ATTENTION_PREFILL_REGISTRY = DSModuleRegistry("attention_prefill")
ATTENTION_PREFILL_REGISTRY.register(ModuleImplementation(
    name="ragged_chunk", priority=10, supports=lambda ctx: True))


def _ragged_wave_pallas_supported(ctx: Dict[str, Any]) -> bool:
    """The in-repo ragged paged attention kernel (ISSUE 6,
    kernels/ragged_paged_attention.py): default on TPU, env-gated like the
    kernel's own dispatch (DSTPU_RAGGED_ATTN: ''=auto, 'pallas' force,
    'xla' escape). ALiBi models route the bias through the XLA atom path."""
    import jax

    from ..kernels.ragged_paged_attention import _ragged_backend
    mode = _ragged_backend()
    if mode == "xla":
        return False
    if ctx.get("position") == "alibi":
        return False
    if mode == "pallas":
        return True
    return ctx.get("backend", jax.default_backend()) == "tpu"


#: the unified wave program's attention slot (ISSUE 6): ONE atom class for
#: any prefill/decode composition, vs the decode/prefill split above that
#: the legacy two-class dispatch still uses
ATTENTION_WAVE_REGISTRY = DSModuleRegistry("attention_wave")
ATTENTION_WAVE_REGISTRY.register(ModuleImplementation(
    name="ragged_pallas", priority=10,
    supports=_ragged_wave_pallas_supported))
ATTENTION_WAVE_REGISTRY.register(ModuleImplementation(
    name="ragged_xla", priority=0, supports=lambda ctx: True))

LINEAR_REGISTRY = DSModuleRegistry("linear")
LINEAR_REGISTRY.register(ModuleImplementation(
    name="dense", priority=0, supports=lambda ctx: True))
LINEAR_REGISTRY.register(ModuleImplementation(
    name="woq_int8", priority=5,
    supports=lambda ctx: ctx.get("quantization_mode") in ("int8", "wint8")))
LINEAR_REGISTRY.register(ModuleImplementation(
    name="woq_int4", priority=6,
    supports=lambda ctx: ctx.get("quantization_mode") in ("int4", "wint4")))
