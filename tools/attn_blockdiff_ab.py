#!/usr/bin/env python3
"""The block-diffusion attention core on the chip, beside causal launches.

    chiprun -- python tools/attn_blockdiff_ab.py [--seq 8192] [--block 4]

At the ``sdar-30b-a3b.train.bd8k`` cell's shape (32 query heads over 4 key
heads of 128, bf16, one row of documents as the cell's traffic packs them):
``attention.blockdiff_attention`` over the 2 L rows (a clean and a noised copy),
one causal flash call over the 2 L concatenation with the same documents
repeated (what running the pair causally would multiply), one causal call
over L (a next-token layer of the same stack), and the own-block einsum
alone; forward, and forward + backward, median of ``--iters`` timed calls
each. One JSON line a row, also in ``chiprun_out/attn_blockdiff_ab.jsonl``.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--block", type=int, default=4)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic
    from deepspeed_tpu.ops.transformer import attention
    from deepspeed_tpu.ops.transformer.pallas_flash import flash_attention_kernel

    L, b, H, kvH, D = args.seq, args.block, 32, 4, 128
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "traffic",
                           "train.bd8k.json")) as f:
        mix = dict(json.load(f), seq_len=L)
    ids = next(traffic.train_batches(mix, args.seed, 18992, 1))["input_ids"]
    ends = (ids == 18991).astype(np.int32)
    doc = jnp.asarray(np.cumsum(ends, axis=1) - ends, jnp.int32)
    key = jax.random.PRNGKey(args.seed)
    draw = lambda i, rows, heads: jax.random.normal(
        jax.random.fold_in(key, i), (1, rows, heads, D), jnp.bfloat16)
    q2, k2, v2 = draw(0, 2 * L, H), draw(1, 2 * L, kvH), draw(2, 2 * L, kvH)
    doc2 = jnp.concatenate([doc, doc + doc.max() + 1], axis=1)

    cases = {
        "blockdiff_2L": (lambda q, k, v: attention.blockdiff_attention(q, k, v, b, doc),
                         (q2, k2, v2)),
        "causal_2L": (lambda q, k, v: flash_attention_kernel(
            q, k, v, causal=True, segment_ids=doc2), (q2, k2, v2)),
        "causal_L": (lambda q, k, v: flash_attention_kernel(
            q, k, v, causal=True, segment_ids=doc), (q2[:, :L], k2[:, :L], v2[:, :L])),
        "own_block_einsum": (lambda q, k, v: attention._own_block_attention(
            q, k, v, doc, b, None)[0], (q2[:, L:], k2[:, L:], v2[:, L:])),
    }
    out = []
    for name, (fn, operands) in cases.items():
        fwd = jax.jit(fn)
        both = jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                                argnums=(0, 1, 2)))
        row = {"case": name, "seq": L, "block": b, "device": jax.devices()[0].device_kind}
        for kind, f in (("forward_ms", fwd), ("forward_backward_ms", both)):
            jax.block_until_ready(f(*operands))
            times = []
            for _ in range(args.iters):
                t0 = time.perf_counter()
                jax.block_until_ready(f(*operands))
                times.append(1e3 * (time.perf_counter() - t0))
            row[kind] = statistics.median(times)
        print(json.dumps(row), flush=True)
        out.append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_blockdiff_ab.jsonl", "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
