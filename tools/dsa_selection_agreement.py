#!/usr/bin/env python3
"""How far the program's selection (bfloat16 operands, float32 scores) and the
float32 reference's agree at a benchmark cell's own size, on the chip.

    chiprun -- python tools/dsa_selection_agreement.py --workload <cell> [--seed n]

The cell's weights and its first batch from ``--seed`` as ``jobs/train.py``
makes them; layer 0's selection by the reference (``reference.selection``:
float32 at ``highest``, ``lax.top_k`` a query) and by the program
(``TransformerLM.selection`` in the cell's dtype, the threshold by
``attention.SELECT_THRESHOLD``). One JSON line: the picked pairs of each, the
pairs in one set and not the other as a share of the reference's
(``differ_share``), and the rows whose two sets are equal; also in
``chiprun_out/dsa_selection_agreement.json``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from benchmark import harness, traffic
    from benchmark.jobs import train
    cell = harness.Cell(args.manifest, args.workload)
    device = harness.require_device(cell)
    cfg, settings = cell.config, cell.config["engine"]["train"]
    ref = cell.load_module("reference", cfg["reference"])
    adapter = cell.load_module("adapters", cfg["adapter"])
    rows = int(settings["ds_config"]["train_micro_batch_size_per_gpu"])
    ids = jnp.asarray(next(traffic.train_batches(
        cell.traffic, args.seed, cfg["vocab_size"], rows))["input_ids"])
    weights = train.make_weights(ref, cfg, args.seed, settings["param_dtype"])
    want = jax.jit(lambda w, ids: ref.selection(w, ids, cfg, 0))(weights, ids)
    model = adapter.model(cfg, remat=False, dtype=settings["param_dtype"])

    def mine(params, ids):
        block = jax.tree.map(lambda a: a[0], params["blocks"])
        x, positions = model.embed(params, ids)
        h = model._layer("ln_1")(block["ln_1"], x)
        from deepspeed_tpu.ops.transformer import attention
        return attention.unpack_selection(
            model.selection(block, h, positions, model._documents(ids))[3], ids.shape[1])
    got = jax.jit(mine)(adapter.to_program(weights), ids)
    count = lambda a: int(jnp.sum(a, dtype=jnp.int32))
    out = {"workload": args.workload, "seed": args.seed, "layer": 0,
           "picked_reference": count(want), "picked_program": count(got),
           "differ_pairs": count(want != got),
           "rows": int(want.shape[0] * want.shape[1]),
           "rows_equal": count(jnp.all(want == got, axis=-1)),
           "device": device["kind"]}
    out["differ_share"] = out["differ_pairs"] / out["picked_reference"]
    print(json.dumps(out), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/dsa_selection_agreement.json", "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
