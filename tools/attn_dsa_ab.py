#!/usr/bin/env python3
"""A learned selection's parts on the chip, each beside its other forms.

    chiprun -- python tools/attn_dsa_ab.py [--seq 16384] [--iters 5]

At the ``keye-vl2-30b-a3b.train.dsa16k`` cell's shape (one row of 16,384, 32
query heads over 4 key heads of 128, an indexer of 16 heads of 64, topk 2048,
bf16 operands), one layer:

- ``scores``: ``attention.index_scores`` for a block of 512 queries against
  every key, x 32 blocks a layer (forward only: the selection has no gradient);
- ``threshold_<form>``: ``attention.select_topk`` on such a block's float32
  scores under each exact form of finding a row's k-th largest score
  (``attention.THRESHOLDS``: a counting bisection over the float's bits,
  ``lax.top_k``), and ``threshold_sort``, a whole ``jnp.sort`` of the block's
  rows for scale; x 32 a layer. Every form's picks are compared with the
  first's: ``same_as_first``;
- ``select``: ``attention.dsa_select``, the layer's whole pass (scores and
  selection over the 32 blocks, the int8 operand out);
- ``core_dsa`` against ``core_causal``: the flash pair whose tiles read the
  selection's operand against the plain causal pair over the same heads and
  documents (forward, and forward + backward);
- ``kl``: ``attention.indexer_kl``'s differentiated forward (value and the
  three gradients in one pass) and its primal alone.

Median of ``--iters`` timed calls. One JSON line a case, also in
``chiprun_out/attn_dsa_ab.jsonl``.
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
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--skip", default="", help="cases to leave out, comma-separated")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.transformer import attention
    from deepspeed_tpu.ops.transformer.pallas_flash import flash_attention_with_lse

    L, K, H, kvH, D, J, d = args.seq, args.topk, 32, 4, 128, 16, 64
    n = attention.SELECT_QUERY_BLOCK
    key = jax.random.PRNGKey(args.seed)
    draw = lambda i, shape, dtype=jnp.bfloat16: jax.random.normal(
        jax.random.fold_in(key, i), shape, dtype)
    q, k, v = draw(0, (1, L, H, D)), draw(1, (1, L, kvH, D)), draw(2, (1, L, kvH, D))
    q_idx, k_idx = draw(3, (1, L, J, d)), draw(4, (1, L, d))
    w = draw(5, (1, L, J), jnp.float32) * (J ** -0.5 * d ** -0.5)
    # four documents a row, as the cell's rows have a few
    cuts = np.asarray([0, L // 8, L // 2, L - L // 16])
    docs = jnp.asarray((np.arange(L)[None, :] >= cuts[:, None]).sum(0) - 1, jnp.int32)[None]
    block = slice(L - n, L)      # the row's last block: the most visible keys
    scores = jax.jit(attention.index_scores)(q_idx[:, block], k_idx, w[:, block])
    seen = attention.causal_in_document(jnp.arange(L - n, L), docs[:, block], docs)
    picked = jax.jit(lambda: attention.dsa_select(q_idx, k_idx, w, docs, K))()
    scale = D ** -0.5
    lse = jax.jit(lambda: flash_attention_with_lse(
        q, k, v, causal=True, segment_ids=docs, selected=picked)[1])()
    timed = {}

    def timing(name, fn, *xs, times=1):
        if name in args.skip.split(","):
            return None
        f = jax.jit(fn)
        out = jax.block_until_ready(f(*xs))
        laps = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*xs))
            laps.append(1e3 * (time.perf_counter() - t0))
        timed[name] = {"case": name, "seq": L, "topk": K, "ms": statistics.median(laps),
                       "times_a_layer": times,
                       "ms_a_layer": times * statistics.median(laps),
                       "device": jax.devices()[0].device_kind}
        return out

    blocks = L // n
    timing("scores", attention.index_scores, q_idx[:, block], k_idx, w[:, block], times=blocks)
    first = None
    for how in attention.THRESHOLDS:
        got = timing(f"threshold_{how}", lambda s, m, how=how: attention.select_topk(
            s, m, K, how), scores, seen, times=blocks)
        if got is not None:
            first = got if first is None else first
            timed[f"threshold_{how}"]["same_as_first"] = bool(jnp.all(got == first))
            timed[f"threshold_{how}"]["picked_a_row"] = float(jnp.mean(jnp.sum(got, -1)))
    timing("threshold_sort", lambda s: jnp.sort(s, axis=-1)[..., -K], scores, times=blocks)
    timing("select", lambda: attention.dsa_select(q_idx, k_idx, w, docs, K))
    total = lambda pair: jnp.sum(pair[0].astype(jnp.float32))
    dsa = lambda q, k, v: total(flash_attention_with_lse(
        q, k, v, causal=True, segment_ids=docs, selected=picked))
    causal = lambda q, k, v: total(flash_attention_with_lse(
        q, k, v, causal=True, segment_ids=docs))
    for name, fn in (("core_dsa", dsa), ("core_causal", causal)):
        timing(name + "_forward", fn, q, k, v)
        timing(name + "_forward_backward", jax.grad(fn, argnums=(0, 1, 2)), q, k, v)
    kl = lambda a, b, c: attention.indexer_kl(a, b, c, q, k, lse, picked, scale)
    timing("kl_primal", kl, q_idx, k_idx, w)
    timing("kl_value_and_gradients", jax.value_and_grad(kl, argnums=(0, 1, 2)), q_idx, k_idx, w)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_dsa_ab.jsonl", "w") as f:
        for row in timed.values():
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
