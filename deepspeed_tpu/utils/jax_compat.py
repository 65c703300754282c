"""The few JAX spellings every in-repo user shares, as the installed JAX
(0.9.0) has them: top-level ``jax.shard_map`` with ``check_vma``, and
``jax.lax.axis_size``."""

from __future__ import annotations

import jax
from jax import shard_map  # noqa: F401  (re-exported)


def axis_size(axis_name) -> int:
    """Size of a bound mesh axis (product over a tuple of axes). Static at
    trace time: no collective reaches the graph."""
    if isinstance(axis_name, list):
        axis_name = tuple(axis_name)
    return int(jax.lax.axis_size(axis_name))


def in_manual_axes() -> bool:
    """True while tracing inside a shard_map/pmap body (mesh axes bound as
    manual). Sharding constraints are illegal there — XLA already sees the
    per-device view."""
    return bool(jax.core.nonempty_axis_env_DO_NOT_USE())


def with_sharding_constraint(x, spec):
    """``jax.lax.with_sharding_constraint`` that degrades to identity where
    the constraint cannot apply: inside shard_map/pmap bodies (manual axes —
    the primitive binds at trace time but fails at lowering, so a call-site
    try/except cannot catch it) and outside any mesh context."""
    if in_manual_axes():
        return x
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, TypeError, RuntimeError):  # no mesh context
        return x
