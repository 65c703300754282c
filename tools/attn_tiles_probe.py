#!/usr/bin/env python3
"""What a cell's packed documents leave of its flash launches' tiles, step
by step, beside the step's time: build a benchmark cell's engine as
``benchmark/jobs/train.py`` does (no reference), run ``--steps`` synced steps
of its own traffic and ask ``engine.attn_last_step()`` after each.

    chiprun -- python tools/attn_tiles_probe.py --workload <cell> [--seed n] [--steps 12]

One JSON line a step (``step_ms`` by the host's clock around a synced step;
``tiles``: ``{kind: {"forward" | "backward": {"position", "run"}}}``, a
launch's tiles a head by the position test alone and as run), then one with
the means and ``engine.attn_totals``; also in
``chiprun_out/attn_tiles_probe.jsonl``. A cell without ``document_separator``
reads ``tiles: null``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    args = ap.parse_args()

    from benchmark import harness, program, traffic
    from benchmark.jobs import train
    cell = harness.Cell(args.manifest, args.workload)
    harness.place_compile_cache()
    device = harness.require_device(cell)
    import jax
    import jax.numpy as jnp

    cfg, settings = cell.config, cell.config["engine"]["train"]
    rows = int(settings["ds_config"]["train_micro_batch_size_per_gpu"])
    ref = cell.load_module("reference", cfg["reference"])
    adapter = cell.load_module("adapters", cfg["adapter"])
    model = adapter.model(cfg, remat=settings["remat"], dtype=settings["param_dtype"])
    weights = train.make_weights(ref, cfg, args.seed, settings["param_dtype"])
    # what the job keeps on the device while the first step is traced (the
    # reference's sign of every gradient element, one byte each): the remat
    # budget then reads the cell's room and keeps the cell's names
    signs = jax.jit(lambda w: {k: jnp.zeros(v.shape, jnp.int8)
                               for k, v in w.items()})(weights)
    engine = program.train_engine(model, settings["ds_config"],
                                  adapter.to_program(weights), args.seed)
    del weights
    stream = traffic.train_batches(dict(cell.traffic), args.seed, cfg["vocab_size"], rows)
    float(engine.train_batch(next(stream)))      # the compile
    del signs
    lines = []
    for step in range(args.steps):
        batch = next(stream)
        t0 = time.perf_counter()
        float(engine.train_batch(batch))
        jax.block_until_ready(engine.state)
        lines.append({"workload": cell.name, "seed": args.seed, "step": step,
                      "step_ms": 1e3 * (time.perf_counter() - t0),
                      "tiles": engine.attn_last_step()})
        print(json.dumps(lines[-1]), flush=True)
    share = {}
    if lines[-1]["tiles"]:
        for kind, kernels in lines[-1]["tiles"].items():
            for kernel in kernels:
                counts = [l["tiles"][kind][kernel] for l in lines]
                share[f"{kind}.{kernel}"] = {
                    "position": statistics.fmean(c["position"] for c in counts),
                    "run": statistics.fmean(c["run"] for c in counts)}
    lines.append({"workload": cell.name, "seed": args.seed, "steps": args.steps,
                  "device": device["kind"], "mean_tiles": share or None,
                  "median_step_ms": statistics.median(l["step_ms"] for l in lines),
                  "attn_totals": engine.attn_totals})
    print(json.dumps(lines[-1]), flush=True)
    out = os.path.join(harness.CHECKOUT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "attn_tiles_probe.jsonl"), "a") as f:
        f.writelines(json.dumps(l) + "\n" for l in lines)


if __name__ == "__main__":
    main()
