#!/usr/bin/env python3
"""The block-diffusion attention core on the chip, beside causal launches,
over the cells' packed documents and over one document a row.

    chiprun -- python tools/attn_blockdiff_ab.py [--seq 8192] [--block 4] [--rows 8]

At the ``sdar-30b-a3b.train.bd8k`` cell's shape (32 query heads over 4 key
heads of 128, bf16, one row of documents as the cell's traffic packs them):
``attention.blockdiff_attention`` over the 2 L rows (a clean and a noised copy),
one causal flash call over the 2 L concatenation with the same documents
repeated (what running the pair causally would multiply), one causal call
over L (a next-token layer of the same stack), and the own-block einsum
alone; then the ``trinity-mini.train.seq16k`` cell's two kinds of layer at
2 L = 16,384 over ITS documents (the whole row, and a static window of
2048). Every call with documents has a twin ``.. / one document`` (the same
launch, ids all 0: the table rides along and skips nothing), so the pair
shows what the table of documents (``pallas_flash.block_ranges``) spares.
Forward, and forward + backward, median of ``--iters`` timed calls on each
of ``--rows`` successive rows of the traffic, averaged over the rows; beside
them the tiles the position test alone runs and the tiles run, a head
(``pallas_flash.tiles_run``; left out where the package has no such
function). Before the timings, the smallest grids on which a dq that is added
to where it lies could race (``pallas_flash.dq_mode`` ``in_place``, five
k-blocks or more: a q-block's tile is read again one grid step after it was
written where there is ONE q-block and one head a key head) are compared with
``_xla_attention`` in float32, gradient by gradient: interpret mode copies in
order and cannot show a race, the chip can. One JSON line a row, also in
``chiprun_out/attn_blockdiff_ab.jsonl``.
"""

import argparse
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--block", type=int, default=4)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--cases", default="", help="a regular expression: the timed calls to run")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic
    from deepspeed_tpu.ops.transformer import attention, pallas_flash
    from deepspeed_tpu.ops.transformer.pallas_flash import flash_attention_kernel

    L, b, H, kvH, D = args.seq, args.block, 32, 4, 128

    def documents(mix_name, seq, vocab):
        """``--rows`` successive rows of a cell's traffic -> their documents."""
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "traffic",
                               mix_name + ".json")) as f:
            mix = dict(json.load(f), seq_len=seq)
        stream = traffic.train_batches(mix, args.seed, vocab, 1)
        out = []
        for _ in range(args.rows):
            ends = (next(stream)["input_ids"] == mix["separator"] % vocab).astype(np.int32)
            out.append(jnp.asarray(np.cumsum(ends, axis=1) - ends, jnp.int32))
        return out

    docs = documents("train.bd8k", L, 18992)
    docs_2l = documents("train.seq16k", 2 * L, 25024)
    one = lambda rows: [jnp.zeros_like(d) for d in rows[:1]]
    key = jax.random.PRNGKey(args.seed)
    draw = lambda i, rows, heads: jax.random.normal(
        jax.random.fold_in(key, i), (1, rows, heads, D), jnp.bfloat16)
    q2, k2, v2 = draw(0, 2 * L, H), draw(1, 2 * L, kvH), draw(2, 2 * L, kvH)
    twice = lambda doc: jnp.concatenate([doc, doc + doc.max() + 1], axis=1)
    first = lambda a: a[:, :L]
    tiles_run = getattr(pallas_flash, "tiles_run", None)

    def blockdiff_tiles(doc):
        return pallas_flash.launch_tiles(2 * L, L, D, blockdiff=b), dict(
            q_ids=jnp.concatenate([doc, doc], axis=1), k_ids=doc, blockdiff=b)

    def causal_tiles(window=None):
        return lambda doc: (pallas_flash.choose_tiles(
            doc.shape[1], doc.shape[1], D, causal=True, window=window),
            dict(q_ids=doc, k_ids=doc, window=window))

    blockdiff = lambda q, k, v, doc: attention.blockdiff_attention(q, k, v, b, doc)
    causal = lambda q, k, v, doc: flash_attention_kernel(
        q, k, v, causal=True, segment_ids=doc)
    window = lambda q, k, v, doc: flash_attention_kernel(
        q, k, v, causal=True, segment_ids=doc, window=2048)
    # name: (the call, its operands, the rows' documents, the tiles' arguments)
    cases = {
        "blockdiff_2L": (blockdiff, (q2, k2, v2), docs, blockdiff_tiles),
        "blockdiff_2L / one document": (blockdiff, (q2, k2, v2), one(docs), blockdiff_tiles),
        "causal_2L": (causal, (q2, k2, v2), [twice(d) for d in docs], causal_tiles()),
        "causal_L": (causal, (first(q2), first(k2), first(v2)), docs, causal_tiles()),
        "causal_L / one document": (causal, (first(q2), first(k2), first(v2)), one(docs),
                                    causal_tiles()),
        "own_block_einsum": (lambda q, k, v, doc: attention._own_block_attention(
            q, k, v, doc, b, None)[0], (q2[:, L:], k2[:, L:], v2[:, L:]), docs[:1], None),
        "causal_16k": (causal, (q2, k2, v2), docs_2l, causal_tiles()),
        "causal_16k / one document": (causal, (q2, k2, v2), one(docs_2l), causal_tiles()),
        "window_16k": (window, (q2, k2, v2), docs_2l, causal_tiles(2048)),
        "window_16k / one document": (window, (q2, k2, v2), one(docs_2l), causal_tiles(2048)),
    }
    out = []
    # (q rows, keys, query heads, key heads) -> (q-blocks, k-blocks, G) of
    # the backward's 1024 x 1024 tiles
    for sq, sk, heads, kv_heads in ((1024, 5120, 2, 2), (2048, 6144, 2, 2),
                                    (1024, 5120, 8, 2)):
        shape = lambda rows, h: (2, rows, h, D)
        q, k, v = (jax.random.normal(jax.random.fold_in(key, 10 + n), shape(rows, h),
                                     jnp.bfloat16)
                   for n, (rows, h) in enumerate(((sq, heads), (sk, kv_heads), (sk, kv_heads))))
        ids = jnp.asarray(np.sort(np.random.default_rng(args.seed).integers(
            0, 3, (2, sk)), axis=1), jnp.int32)
        q_ids = ids[:, sk - sq:]
        grads = lambda attend, *x: jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2)))(*x)
        got = grads(lambda q, k, v: flash_attention_kernel(
            q, k, v, causal=True, segment_ids=ids, q_segment_ids=q_ids), q, k, v)
        want = grads(lambda q, k, v: attention._xla_attention(
            q, k, v, True, None, ids, q_segment_ids=q_ids),
            *(x.astype(jnp.float32) for x in (q, k, v)))
        tiles = pallas_flash.choose_tiles(sq, sk, D, causal=True)
        row = {"case": f"grid {sq // tiles.bwd[0]} x {sk // tiles.bwd[1]} x {heads // kv_heads}",
               "dq_mode": getattr(pallas_flash, "dq_mode", lambda *a: None)(sq, sk, tiles),
               "device": jax.devices()[0].device_kind}
        for name, ours, theirs in zip(("dq", "dk", "dv"), got, want):
            # the kernel's operands are bfloat16: its gradient is rounded once
            row[name + "_error"] = float(jnp.max(jnp.abs(ours.astype(jnp.float32) - theirs))
                                         / jnp.max(jnp.abs(theirs)))
        row["matches"] = all(row[n + "_error"] < 2e-2 for n in ("dq", "dk", "dv"))
        print(json.dumps(row), flush=True)
        out.append(row)
    for name, (fn, operands, rows, tiles_of) in cases.items():
        if not re.search(args.cases, name):
            continue
        fwd = jax.jit(fn)
        both = jax.jit(jax.grad(lambda q, k, v, doc: jnp.sum(fn(q, k, v, doc).astype(
            jnp.float32)), argnums=(0, 1, 2)))
        row = {"case": name, "seq": L, "block": b, "rows": len(rows),
               "device": jax.devices()[0].device_kind}
        for kind, f in (("forward_ms", fwd), ("forward_backward_ms", both)):
            medians = []
            for doc in rows:
                jax.block_until_ready(f(*operands, doc))
                times = []
                for _ in range(args.iters):
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(*operands, doc))
                    times.append(1e3 * (time.perf_counter() - t0))
                medians.append(statistics.median(times))
            row[kind] = statistics.fmean(medians)
        if tiles_run is not None and tiles_of is not None:
            counts = np.zeros((2, 2))
            for doc in rows:
                tiles, mask = tiles_of(doc)
                counts += [[int(n) for n in tiles_run(tile=tile, **mask)]
                           for tile in (tiles.fwd, tiles.bwd)]
            counts /= len(rows)
            row.update(forward_tiles_by_position=counts[0, 0], forward_tiles_run=counts[0, 1],
                       backward_tiles_by_position=counts[1, 0], backward_tiles_run=counts[1, 1])
        print(json.dumps(row), flush=True)
        out.append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_blockdiff_ab.jsonl", "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in out)
    return 0 if all(r.get("matches", True) for r in out) else 1


if __name__ == "__main__":
    sys.exit(main())
