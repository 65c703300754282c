#!/usr/bin/env python3
"""The remat plan of ONE benchmark cell from shapes alone, on the CPU: the
cell's model through its adapter at the configuration file's own size, the
gradient of its loss traced over ``jax.eval_shape``'s parameters and the
traffic mix's first batch (never compiled, never run), under the routes the
chip takes (``DSTPU_ATTN=pallas`` and a backend that answers ``"tpu"``), with
a ``checkpointing.Budget`` of the room given.

    python3 tools/remat_plan.py --workload <cell> [--manifest BENCHMARK.json]
        [--room-gb X]

prints one JSON line: the kinds of block with their layers, bytes and names;
the step's candidates in ``SAVE_ORDER`` with running totals; the working
set's parts (a block's, outside the blocks, handed on, the gradients, every
block's input); the budget and what is kept; and ``reckoned_step_bytes`` =
gradients + inputs + working set + what the kept values cost (``STACK_COST``
a byte), the number that stands beside the chip's ``train_step_temp_gb``.
``--room-gb`` is what the engine logged as ``a room of`` on the chip (free
less gradients); without it there is no budget and every name is kept.

It BOUNDS a plan and does not predict it: the names, their bytes, the kinds'
counts and the head's bytes are the chip's to the byte, the blocks' walked
bytes lie 70-270 MB from the engine's own trace there (tile and ``dq``
choices that ask the real device), which is enough to move a choice that sits
near a group's edge (PERF.md, PR 60: both of the builder's predictions from
it missed by one group). The engine's ``remat keeps`` line on the chip is the
plan; the tests' table of the nine cells holds the chip's bytes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def plan(manifest: str, workload: str, room_bytes=None) -> dict:
    """The cell's plan as a dict (the module's docstring says of what)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import harness, traffic
    from deepspeed_tpu.runtime import engine
    from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ck

    cell = harness.Cell(manifest, workload)
    cfg, mix = cell.config, cell.traffic
    settings = cfg["engine"]["train"]
    adapter = cell.load_module("adapters", cfg["adapter"])
    model = adapter.model(cfg, remat=settings["remat"], dtype=settings["param_dtype"])
    rows = int(settings["ds_config"]["train_micro_batch_size_per_gpu"])
    batch = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
             for k, v in next(traffic.train_batches(mix, 0, cfg["vocab_size"], rows)).items()}
    dtype = jnp.dtype(settings["param_dtype"])
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), dtype))
    grads = engine.gradients_bytes(params, engine.grad_accum_dtype(
        settings["ds_config"].get("data_types", {}).get("grad_accum_dtype")))
    budget = ck.Budget(room_bytes, grads_bytes=grads)
    loss = (lambda p, b: model.loss_and_stats(p, b, remat_budget=budget)[0]) \
        if getattr(model, "returns_step_stats", False) \
        else (lambda p, b: model.loss(p, b, remat_budget=budget))
    backend, mode, t0 = jax.default_backend, os.environ.get("DSTPU_ATTN"), time.perf_counter()
    jax.default_backend = lambda: "tpu"     # the routes the chip takes
    os.environ["DSTPU_ATTN"] = "pallas"
    try:
        jax.make_jaxpr(jax.grad(loss))(params, batch)
    finally:
        jax.default_backend = backend
        os.environ.pop("DSTPU_ATTN")
        if mode is not None:
            os.environ["DSTPU_ATTN"] = mode
    totals = budget.totals
    candidates, costs = ck.step_candidates(budget.kinds), ck.step_costs(budget.kinds)
    order = [n for group in ck.SAVE_ORDER for n in group if n in candidates]
    saved_cost = totals["saved_cost_bytes"]
    running, table = 0, []
    for name in order:      # name, its bytes, what keeping it costs, the costs so far
        running += costs[name]
        table.append([name, candidates[name], costs[name], running])
    return {
        "workload": workload, "trace_s": round(time.perf_counter() - t0, 2),
        "kinds": {label: {"layers": kind.layers, "block_bytes": kind.block_bytes,
                          "carry_bytes": kind.carry_bytes, "named": kind.named}
                  for label, kind in budget.kinds.items()},
        "candidates": table,
        "room_bytes": room_bytes, "grads_bytes": grads,
        "carries_bytes": totals["carries_bytes"],
        "block_bytes": totals["block_bytes"], "outside_bytes": totals["outside_bytes"],
        "handed_bytes": totals["handed_bytes"],
        "working_bytes": budget.working_bytes,
        "stack_cost": ck.STACK_COST, "budget_bytes": totals["budget_bytes"],
        "saved": list(totals["saved"]), "saved_bytes": totals["saved_bytes"],
        "saved_cost_bytes": saved_cost,
        "saved_by_kind": {label: {**kind, "saved": list(kind["saved"])}
                          for label, kind in totals["saved_by_kind"].items()},
        "reckoned_step_bytes": (grads + totals["carries_bytes"] + saved_cost
                                + budget.working_bytes),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--room-gb", type=float, default=None)
    args = ap.parse_args()
    room = None if args.room_gb is None else int(args.room_gb * 1e9)
    print(json.dumps(plan(args.manifest, args.workload, room)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
