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
- ``select``: ``attention.dsa_select_xla``, the layer's whole pass in XLA
  (scores and selection over the 32 blocks, the operand out: bits since PR 50,
  with its ``operand_bytes``), and ``operand_transpose``, the copy the
  transposed readers (``flash_bwd_dsa``, the KL pair) are handed;
- ``select_kernel``: the same operand by the one launch of ``pallas_select``
  (since PR 52; ``dsa_select`` on the chip), with the tiles it ran of its grid,
  the queries it found a threshold for and the pairs whose bit differs from
  the XLA form's (a score's last bit: the sixteen terms' order), the launch
  with ``topk`` the row's length (``select_kernel_no_threshold``: no query has
  a threshold, so what is left is the scores, one counting pass and the bits)
  and under each of ``--select-blocks`` (``select_kernel_<block_k>``); then both
  forms on each of the cell's own first ``--cell-rows`` rows' documents
  (``benchmark/traffic/train.dsa16k.json``: ``select_row<i>``,
  ``select_kernel_row<i>``);
- ``core_dsa`` against ``core_causal``: the flash pair whose tiles unpack the
  selection's operand against the plain causal pair over the same heads and
  documents (forward, and forward + backward);
- ``kl``: ``attention.indexer_kl``'s differentiated forward (value and the
  three gradients in one pass) and its primal alone, as the Pallas pair
  (``pallas_indexer_kl``; ``kl_kernel_*``, with the tiles it ran of its grid)
  beside the XLA form (``kl_xla_*``: a scan of 128 queries at a time), and the
  pair under each of ``--kl-tiles`` (``kl_kernel_<bq>x<bk>``).

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
    ap.add_argument("--only", default="", help="run the cases that start with this alone")
    ap.add_argument("--kl-tiles", default="", help="the KL pair's tiles to try beside its "
                    "own choice, comma-separated <block_q>x<block_k>")
    ap.add_argument("--select-blocks", default="", help="the selection launch's key "
                    "blocks to try beside its own choice, comma-separated")
    ap.add_argument("--cell-rows", type=int, default=3, help="rows of the Keye cell's "
                    "own documents to time both forms of the selection on")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.transformer import attention, pallas_flash
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
    picked = jax.jit(lambda: attention.dsa_select_xla(q_idx, k_idx, w, docs, K))()
    scale = D ** -0.5
    lse = jax.jit(lambda q, k, v, picked: flash_attention_with_lse(
        q, k, v, causal=True, segment_ids=docs, selected=picked)[1])(q, k, v, picked)
    timed = {}

    def timing(name, fn, *xs, times=1):
        if name in args.skip.split(",") or not name.startswith(args.only):
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
        print("timed", name, "%.3f ms" % timed[name]["ms"], file=sys.stderr, flush=True)
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
    timing("select", lambda: attention.dsa_select_xla(q_idx, k_idx, w, docs, K))
    timing("operand_transpose", lambda p: jnp.swapaxes(p, 1, 2), picked)
    for name in {"select", "operand_transpose"} & set(timed):
        timed[name]["operand_bytes"] = picked.size * picked.dtype.itemsize
    from deepspeed_tpu.ops.transformer import pallas_indexer_kl, pallas_select
    compiled = jax.default_backend() != "cpu"

    def select_pair(suffix, docs, want, blocks=()):
        """Both forms of the selection under ``docs``: the XLA loop (unless
        ``want``, its operand, is at hand) and the launch at its own tile and
        at each of ``blocks``."""
        if want is None:
            want = timing("select" + suffix, lambda docs: attention.dsa_select_xla(
                q_idx, k_idx, w, docs, K), docs)
        visible = attention.visible_counts(docs)
        for bk in (None,) + tuple(blocks):
            tile = pallas_select.choose_tile(L, compiled, bk)
            name = "select_kernel" + suffix + ("_%d" % bk if bk else "")
            got = timing(name, lambda docs, tile=tile: pallas_select.select(
                q_idx, k_idx, w, docs, K, tile), docs)
            if got is None:
                continue
            timed[name].update(
                tile=list(tile), tiles_run=int(pallas_flash.tiles_run(docs, docs, tile)[1]),
                tiles_of=pallas_indexer_kl.tiles_of(1, L, tile),
                rows_thresholded=int(jnp.sum(visible > K)),
                visible_pairs=int(jnp.sum(visible)))
            if bk is None:
                # the same launch where no query has a threshold (topk the row's
                # length): the scores, the visible count and the bits alone
                timing(name + "_no_threshold", lambda docs, tile=tile: pallas_select.select(
                    q_idx, k_idx, w, docs, L, tile), docs)
            if want is not None:
                differ = attention.unpack_selection(got, L) != attention.unpack_selection(want, L)
                timed[name].update(
                    pairs_differ_from_xla=int(jnp.sum(differ)),
                    rows_differ_from_xla=int(jnp.sum(jnp.any(differ, axis=-1))))

    select_pair("", docs, picked, [int(b) for b in args.select_blocks.split(",") if b])
    if args.cell_rows:
        from benchmark import traffic
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "traffic",
                               "train.dsa16k.json")) as f:
            mix = json.load(f)
        vocab = mix["separator"] + 1         # the cell's slice: the separator its last id
        batches = traffic.train_batches(dict(mix, seq_len=L), 0, vocab, 1)
        for i in range(args.cell_rows):
            ends = (next(batches)["input_ids"] == mix["separator"]).astype(np.int32)
            select_pair("_row%d" % i, jnp.asarray(np.cumsum(ends, axis=1) - ends), None)
    total = lambda pair: jnp.sum(pair[0].astype(jnp.float32))
    dsa = lambda q, k, v: total(flash_attention_with_lse(
        q, k, v, causal=True, segment_ids=docs, selected=picked))
    causal = lambda q, k, v: total(flash_attention_with_lse(
        q, k, v, causal=True, segment_ids=docs))
    for name, fn in (("core_dsa", dsa), ("core_causal", causal)):
        timing(name + "_forward", fn, q, k, v)
        timing(name + "_forward_backward", jax.grad(fn, argnums=(0, 1, 2)), q, k, v)
    # (the target and the operand as arguments: a closed-over constant of tens
    # of MB is compiled into every case's program)
    target = (q, k, lse, picked, docs)
    kl = lambda a, b, c, *target: attention.indexer_kl(a, b, c, *target, scale)
    launch, outs = attention.kl_launch, {}
    for form in ("kernel", "xla"):
        if form == "xla":       # the route a shape without a tile takes
            attention.kl_launch = lambda made, length: ("xla_chunked", None)
        timing(f"kl_{form}_primal", kl, q_idx, k_idx, w, *target)
        outs[form] = timing(f"kl_{form}_value_and_gradients",
                            jax.value_and_grad(kl, argnums=(0, 1, 2)), q_idx, k_idx, w, *target)
    attention.kl_launch = launch
    if outs.get("kernel") is not None and outs.get("xla") is not None:
        # the pair beside the XLA form: each against the largest element
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        flat = lambda out: [out[0], *out[1]]
        timed["kl_kernel_value_and_gradients"]["against_xla"] = {
            name: float(jnp.max(jnp.abs(f32(a) - f32(b))) / jnp.max(jnp.abs(f32(b))))
            for name, a, b in zip(("value", "dq_idx", "dk_idx", "dw"),
                                  flat(outs["kernel"]), flat(outs["xla"]))}
    tiles = [pallas_indexer_kl.choose_tile(L, compiled=jax.default_backend() != "cpu")]
    tiles += [tuple(map(int, t.split("x"))) for t in args.kl_tiles.split(",") if t]
    for at, tile in enumerate(tiles):
        name = "kl_kernel_value_and_gradients" if at == 0 else "kl_kernel_%dx%d" % tile
        if at:
            pair = lambda a, b, c, *target, tile=tile: pallas_indexer_kl.value_and_gradients(
                a, b, c, *target, scale, tile)
            fwd = lambda a, b, c, *target, tile=tile: pallas_indexer_kl.value(
                a, b, c, *target, scale, tile)
            timing(name, pair, q_idx, k_idx, w, *target)
            timing(name + "_primal", fwd, q_idx, k_idx, w, *target)
        if name in timed:
            timed[name].update(
                tile=list(tile), tiles_run=int(pallas_flash.tiles_run(docs, docs, tile)[1]),
                tiles_of=pallas_indexer_kl.tiles_of(1, L, tile))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_dsa_ab.jsonl", "w") as f:
        for row in timed.values():
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
