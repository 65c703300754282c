"""Names on the device's work.

``jax.named_scope`` puts a component on the name stack of every operation
traced inside it; XLA keeps the stack as each HLO instruction's ``op_name``
and the profiler records it per executed operation. Scopes are metadata
only: the lowered program is the same without them
(``tests/unit/telemetry/test_named_work.py``). The vocabulary is listed in
docs/OBSERVABILITY.md; ``benchmark/trace/scopes.py`` sums device time by it.
"""

from __future__ import annotations

import functools

import jax


def scoped(name: str):
    """Decorator: trace the function's body under ``jax.named_scope(name)``."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco
