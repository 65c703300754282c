#!/usr/bin/env python3
"""EVA's attention core on the chip: the two launches and the merge beside
the other forms of the same mask.

    chiprun -- python tools/attn_eva_ab.py [--seq 32768] [--heads 4] [--iters 10]

At the ``evabyte-6.5b.train.seq32k`` cell's shape (heads of 128, bf16, one row
of 32,768, a window of 2048, chunks of 16; ``--heads`` 4 is one of the cell's
eight head groups, which is what one launch of the cell holds):

- ``eva``: ``attention.eva_attention`` on the kernel route (``local`` + ``far``
  + the merge), with the summaries made outside the timed call;
- ``local``: the exact keys as one causal launch a WINDOW, the row folded to
  ``seq / 2048`` batch rows (``flash_*_eva_local``: what the program runs);
- ``local_by_ids``: the same keys as ONE causal launch over the row whose
  segment ids are ``position // 2048`` (ISSUE 42's form: the table of documents
  skips every tile off the block diagonal, and every one of them is a grid
  step);
- ``far``: the launch over the ``seq / 16`` summaries under a q-block's limit
  (``flash_*_eva_far``);
- ``summaries``: ``attention.eva_summaries`` alone;
- ``xla``: the XLA form of ``eva_visible``, its queries in parts of 1024
  (``DSTPU_ATTN=xla``'s route on a device);
- ``causal``: one plain causal launch over the row (what full attention
  would cost the same heads).

Forward, and forward + backward, median of ``--iters`` timed calls; for the
launches the tiles ``launch_tiles`` gives and, for
``local_by_ids``, the tiles run of the tiles the position test alone runs
(``pallas_flash.tiles_run``). One JSON line a case, also in
``chiprun_out/attn_eva_ab.jsonl``.
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
    ap.add_argument("--seq", type=int, default=32768)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--window", type=int, default=2048)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--skip", default="", help="cases to leave out, comma-separated")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.transformer import attention, pallas_flash
    from deepspeed_tpu.ops.transformer.pallas_flash import flash_attention_with_lse

    L, H, D, W, c = args.seq, args.heads, 128, args.window, args.chunk
    key = jax.random.PRNGKey(args.seed)
    draw = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)
    q, k, v = (draw(i, (1, L, H, D)) for i in range(3))
    phi, mu = draw(3, (H, D)) * 0.1, draw(4, (H, D))
    kbar, vbar = jax.jit(lambda k, v: attention.eva_summaries(k, v, phi, mu, c))(k, v)
    windows = L // W
    fold = lambda a: a.reshape((windows, W) + a.shape[2:])
    ids = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32) // W, (1, L))
    out_of = lambda pair: pair[0]

    cases = {
        "eva": (lambda q, k, v: attention.eva_attention(q, k, v, kbar, vbar, W, c), None),
        "local": (lambda q, k, v: out_of(flash_attention_with_lse(
            fold(q), fold(k), fold(v), causal=True, tag="eva_local")),
            pallas_flash.choose_tiles(W, W, D, causal=True)),
        "local_by_ids": (lambda q, k, v: out_of(flash_attention_with_lse(
            q, k, v, causal=True, segment_ids=ids)),
            pallas_flash.choose_tiles(L, L, D, causal=True)),
        "far": (lambda q, k, v: out_of(flash_attention_with_lse(
            q, kbar, vbar, causal=True, summaries=(W, W // c), tag="eva_far")),
            pallas_flash.launch_tiles(L, L // c, D, summaries=(W, W // c))),
        "summaries": (lambda q, k, v: attention.eva_summaries(k, v, phi, mu, c)[0], None),
        "xla": (lambda q, k, v: attention._xla_eva_attention(
            q, k, v, kbar, vbar, W, c, None, 1024), None),
        "causal": (lambda q, k, v: out_of(flash_attention_with_lse(q, k, v, causal=True)),
                   pallas_flash.choose_tiles(L, L, D, causal=True)),
    }
    out = []
    for name, (fn, tiles) in cases.items():
        if name in args.skip.split(","):
            continue
        both = jax.jit(jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)),
                                argnums=(0, 1, 2)))
        row = {"case": name, "seq": L, "heads": H, "window": W, "chunk": c,
               "device": jax.devices()[0].device_kind}
        for kind, f in (("forward_ms", jax.jit(fn)), ("forward_backward_ms", both)):
            jax.block_until_ready(f(q, k, v))
            times = []
            for _ in range(args.iters):
                t0 = time.perf_counter()
                jax.block_until_ready(f(q, k, v))
                times.append(1e3 * (time.perf_counter() - t0))
            row[kind] = statistics.median(times)
        if tiles is not None:
            row.update(forward_tile=list(tiles.fwd), backward_tile=list(tiles.bwd))
        if name == "local_by_ids":
            for kind, tile in (("forward", tiles.fwd), ("backward", tiles.bwd)):
                by_position, run = pallas_flash.tiles_run(ids, ids, tile)
                row[f"{kind}_tiles_by_position"] = int(by_position)
                row[f"{kind}_tiles_run"] = int(run)
                row[f"{kind}_grid_steps"] = (L // tile[0]) * (L // tile[1])
        print(json.dumps(row), flush=True)
        out.append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_eva_ab.jsonl", "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
