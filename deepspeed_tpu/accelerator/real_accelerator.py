"""Accelerator auto-detection.

Counterpart of ``accelerator/real_accelerator.py:51-186`` (``get_accelerator``
with env override ``DS_ACCELERATOR``). Detection order: tpu → cpu. The env
override here is ``DSTPU_ACCELERATOR``.
"""

from __future__ import annotations

import os
from typing import Optional

from .abstract_accelerator import DeepSpeedAccelerator

_ACCELERATOR: Optional[DeepSpeedAccelerator] = None

ACCELERATOR_ENV = "DSTPU_ACCELERATOR"


def _make(name: str) -> DeepSpeedAccelerator:
    if name == "tpu":
        from .tpu_accelerator import TPU_Accelerator
        return TPU_Accelerator()
    if name == "cpu":
        from .cpu_accelerator import CPU_Accelerator
        return CPU_Accelerator()
    raise ValueError(f"Unknown accelerator '{name}' (expected 'tpu' or 'cpu')")


def get_accelerator() -> DeepSpeedAccelerator:
    global _ACCELERATOR
    if _ACCELERATOR is not None:
        return _ACCELERATOR

    override = os.environ.get(ACCELERATOR_ENV)
    if override:
        _ACCELERATOR = _make(override)
        return _ACCELERATOR

    import jax
    _ACCELERATOR = _make(jax.default_backend())
    return _ACCELERATOR


def set_accelerator(accel: DeepSpeedAccelerator) -> None:
    global _ACCELERATOR
    _ACCELERATOR = accel


def is_current_accelerator_supported() -> bool:
    return get_accelerator().is_available()
