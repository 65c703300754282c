#!/usr/bin/env python3
"""The fused train step of ONE benchmark cell as lowered text, for comparing
two trees' programs: the cell's model through its adapter at the
configuration file's own size, ``deepspeed_tpu.initialize`` under the cell's
``ds_config`` (the engine's own random weights: a step's program reads their
shapes alone), the traffic mix's first batch, ``engine._jit_train_step.lower``
and never a compile or a step.

    python3 tools/step_program.py --workload <cell> [--manifest BENCHMARK.json] --out <file>

writes the text with no debug information (a Mosaic kernel's serialized body,
which carries its own source locations, parsed and printed without them) and
prints one JSON line ``{"workload", "sha256", "bytes", "kernels", "lower_s"}``.
Run from the root of the tree whose program is wanted (a parent unpacked by
``git archive`` has its own copy of this file's imports: copy the file there).
Two trees whose lines carry the same ``sha256`` lower the same program.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import re
import sys
import time

sys.path.insert(0, os.getcwd())

# (a lowered module's text escapes a quote inside an attribute as \22)
_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def _plain_bodies(text: str):
    """``text`` with every ``tpu_custom_call``'s body (base64 of a serialized
    MLIR module) replaced by the module printed without debug information."""
    from jax._src.lib.mlir import ir
    count = [0]

    def plain(match):
        count[0] += 1
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(match.group(1)), ctx)
        return 'body: <<' + module.operation.get_asm(enable_debug_info=False) + '>>'

    return _BODY.sub(plain, text), count[0]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    import jax.numpy as jnp
    import deepspeed_tpu
    from benchmark import harness, traffic
    cell = harness.Cell(args.manifest, args.workload)
    cfg, mix = cell.config, cell.traffic
    settings = cfg["engine"]["train"]
    adapter = cell.load_module("adapters", cfg["adapter"])
    model = adapter.model(cfg, remat=settings["remat"], dtype=settings["param_dtype"])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=settings["ds_config"], seed=args.seed)
    rows = int(settings["ds_config"]["train_micro_batch_size_per_gpu"])
    batch = engine._prepare_batch(next(traffic.train_batches(
        mix, args.seed, cfg["vocab_size"], rows)))
    t0 = time.perf_counter()
    engine._build_fused_jit()
    with engine.mesh:
        lowered = engine._jit_train_step.lower(
            engine.state, batch, jnp.asarray(1e-3, jnp.float32))
    text, kernels = _plain_bodies(lowered.as_text())
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        f.write(text)
    print(json.dumps({
        "workload": args.workload, "sha256": hashlib.sha256(text.encode()).hexdigest(),
        "bytes": len(text), "kernels": kernels,
        "saved": list(engine.remat_totals.get("saved") or ()),
        "lower_s": round(time.perf_counter() - t0, 2)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
