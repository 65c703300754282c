"""Device time under the no-drop expert layer's own scopes, and the FLOPs
its grouped matmuls execute.

The program names four scopes under ``mlp`` (``deepspeed_tpu/moe/layer.py``,
``MoE.dropless_forward``): ``moe/route`` (router matmul, softmax, top-k,
both router losses, the sort by expert and the rows per expert),
``moe/dispatch`` (the one row gather), ``moe/experts`` (the three grouped
matmuls and the activation) and ``moe/combine`` (the rows back in token
order, weighted and summed). An operation belongs to a scope when its
``op_name`` (benchmark/trace/scopes.py) has the components ``moe`` and
that scope's name one after the other; forward, recomputed forward and
backward alike. A fusion goes whole to the scope its ``op_name`` carries,
as in ``scopes.py``. The grouped matmuls themselves are the exception,
found on the chip (my chip run, PR 27): XLA's TPU backend lowers
``jax.lax.ragged_dot`` to a custom call named ``ragged-dot-none.N`` whose
``op_name`` is ``ragged-dot-none:`` and nothing else, so the name stack is
lost. They are recognised by that instruction name and counted under
``moe/experts``, where the program traced them (``scopes.py`` has them as
unscoped: PERF.md section 7). A program without these scopes (every configuration
that is not a no-drop mixture of experts, and the parent of PR 27) has
nothing to read: every function here then returns None.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from benchmark.trace import reduce, scopes

SCOPES = ("route", "dispatch", "experts", "combine")
#: the instruction XLA's TPU backend makes of ``jax.lax.ragged_dot``
GROUPED_MATMUL = re.compile(r"^ragged-dot")


def scope_of(name: str, op_name: Optional[str]) -> Optional[str]:
    """``route`` / ``dispatch`` / ``experts`` / ``combine`` or None, of one
    operation by its instruction's short name and its ``op_name``."""
    if GROUPED_MATMUL.match(name):
        return "experts"
    if not op_name:
        return None
    parts = scopes.components(op_name)
    for a, b in zip(parts, parts[1:]):
        if a == "moe" and b in SCOPES:
            return b
    return None


def seconds_by_scope(trace: reduce.Trace, names: Dict[str, str]) -> Dict[str, float]:
    """Summed leaf-operation seconds on the first chip by MoE scope."""
    first = sorted(trace["devices"])[0]
    out = dict.fromkeys(SCOPES, 0.0)
    for e in reduce.leaf_events(trace["devices"][first]):
        s = scope_of(e[0], names.get(e[0]))
        if s is not None:
            out[s] += e[2] / 1e9
    return out


def of_run(ctx: dict) -> Optional[dict]:
    """The run's seconds by MoE scope and its ``steps``, computed once and
    kept on ``ctx``; None without a trace, without the program's step
    annotations, or where nothing ran under any of the four scopes."""
    if "moe_scopes" not in ctx:
        ctx["moe_scopes"] = None
        sums = scopes.of_run(ctx)
        if sums is not None:
            secs = seconds_by_scope(
                ctx["trace"], scopes.op_names(ctx["trace_out"]["trace_file"]))
            if any(secs.values()):
                ctx["moe_scopes"] = dict(secs, steps=sums["steps"])
    return ctx["moe_scopes"]


def ms_per_step(ctx: dict, *names: str) -> Optional[float]:
    """Device ms a step under the named MoE scopes together."""
    sums = of_run(ctx)
    if sums is None:
        return None
    return 1e3 * sum(sums[n] for n in names) / sums["steps"]


def expert_matmul_flops_a_step(*, tokens: int, experts_per_token: int, hidden: int,
                               width: int, layers: int, remat: bool) -> float:
    """FLOPs the grouped matmuls under ``moe/experts`` EXECUTE in one
    training step, from the rows that exist: ``tokens x experts_per_token``
    rows (never a padded tile, never the experts a token was not routed to),
    each through the gated MLP's 3 products of ``hidden x width`` (gate, up,
    down) at 2 FLOPs a multiply-add. Each product runs once
    forward, once more when the block is rematerialised, and twice backward
    (the gradient of its rows and the gradient of its weights)."""
    rows = tokens * experts_per_token
    passes = 4 if remat else 3
    return 2.0 * rows * hidden * width * 3 * passes * layers
