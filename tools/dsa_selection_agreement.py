#!/usr/bin/env python3
"""How far the program's selection (bfloat16 operands, float32 scores) and the
float32 reference's agree at a benchmark cell's own size, on the chip.

    chiprun -- python tools/dsa_selection_agreement.py --workload <cell> [--seed n]

The cell's weights and its first batch from ``--seed`` as ``jobs/train.py``
makes them; layer 0's selection by the reference (``reference.selection``:
float32 at ``highest``, ``lax.top_k`` a query) and by the program
(``mixers.Selected.selection`` in the cell's dtype, in the form
``attention.select_launch`` names for the row and the back end: on the chip the
one launch of ``pallas_select``, since PR 52; ``select`` says which) and, where
that is the launch, by the XLA loop in its place as well
(``attention.dsa_select_xla``, the threshold by ``attention.SELECT_THRESHOLD``).
One JSON line: the picked pairs of each, the pairs in one set and not the other
as a share of the reference's (``differ_share``; ``xla_differ_share`` the XLA
loop's), the rows whose two sets are equal, and the pairs the program's two
forms pick apart (``forms_differ_pairs``: a score's last bit, the sixteen
terms' order); also in ``chiprun_out/dsa_selection_agreement.json``.
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
        return attention.unpack_selection(
            model._mixer.selection(block, h, positions, model._documents(ids))[3], ids.shape[1])
    from deepspeed_tpu.ops.transformer import attention
    params = adapter.to_program(weights)
    form = attention.select_launch(ids.shape[1], jax.default_backend(), attention.attn_mode())[0]
    got = jax.jit(mine)(params, ids)
    count = lambda a: int(jnp.sum(a, dtype=jnp.int32))
    out = {"workload": args.workload, "seed": args.seed, "layer": 0, "select": form,
           "picked_reference": count(want), "picked_program": count(got),
           "differ_pairs": count(want != got),
           "rows": int(want.shape[0] * want.shape[1]),
           "rows_equal": count(jnp.all(want == got, axis=-1)),
           "device": device["kind"]}
    out["differ_share"] = out["differ_pairs"] / out["picked_reference"]
    if form == "kernel":
        os.environ["DSTPU_ATTN"] = "xla"        # `select_launch` then names the loop
        loop = jax.jit(mine)(params, ids)
        del os.environ["DSTPU_ATTN"]
        out.update(xla_differ_share=count(want != loop) / out["picked_reference"],
                   xla_rows_equal=count(jnp.all(want == loop, axis=-1)),
                   forms_differ_pairs=count(got != loop))
    print(json.dumps(out), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/dsa_selection_agreement.json", "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
