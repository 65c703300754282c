#!/usr/bin/env python3
"""The flash pair's two layouts of the query side on the chip, a layer at a time.

    chiprun -- python tools/attn_layout_ab.py [--iters 20] [--cases REGEX]

``pallas_flash.launch_layout`` sends a launch whose head dim is whole lane
tiles its QUERY side (q, ``o``, ``do``, dq) as the projections leave it
(``"rows"``: ``[B, S, heads x D]``, a head picked by the index maps) and any
other with the heads transposed to lead (``"heads"``, what every launch did
before PR 51); the keys and values lead with their heads in both. Here each of
the benchmark cells' launches runs both ways through
``flash_attention_with_lse``, from ``[B, S, H, D]`` operands to a ``[B, S, H,
D]`` result and three gradients: one layer, bf16, over packed documents where
the cell has them. In ``"heads"`` that is the PARENT's wrapper (the launch and
the transposes around it), in ``"rows"`` the CHANGE.

For each: the host's clock around the forward and around forward + backward
(``jax.vjp`` with a given cotangent of ``o``; the median of ``--iters`` calls),
and from a profiler trace of three forward + backward calls the device's own
time a call, APART: the ``flash_fwd*`` launch, the ``flash_bwd*`` launch, and
everything else (the copies: transposes and relayouts, dq's sum or cast, ``dO
x O``'s row sum or the ``flash_delta`` launch). All in ms. What the copies
read HERE is not what a model pays: the operands arrive as ``[B, S, H, D]``,
which on the chip is tiled over ``(H, D)`` and so is a relayout away from
``[B, S, H x D]`` too; inside a step XLA lays q out for its neighbours (the
projection, the norm, the rotary embedding), and only a cell's trace says what
is left (docs/KERNELS.md). The launches' own times carry over. Before the
timings both layouts' ``o``, ``lse``, ``dq``, ``dk`` and ``dv`` are compared on
the chip (``differs``: the largest difference of each as a share of its largest
value; the kernels' tiles and their order are the same, so ``o`` and ``lse``
agree to the bit and the gradients to the rounding of ``dO x O``'s row sum,
which XLA makes one way and ``flash_delta`` another). One JSON line a case,
also in ``chiprun_out/attn_layout_ab.jsonl``, and the table of docs/KERNELS.md
in ``chiprun_out/attn_layout_ab.md``. Exit 1 where a case's layouts differ by
more than bf16's rounding.
"""

import argparse
import glob
import json
import os
import re
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

D = 128
TRACED_CALLS = 3
# name: (batch rows, query rows, key rows, query heads, key heads, packed
# documents, the launch's keywords)
CASES = {
    "trinity window 2048 (16,384 x 32q/4kv)": (1, 16384, 16384, 32, 4, True, dict(window=2048)),
    "trinity full (16,384 x 32q/4kv)": (1, 16384, 16384, 32, 4, True, {}),
    "sdar blockdiff (2 x 8,192 x 32q/4kv)": (1, 16384, 8192, 32, 4, True, dict(blockdiff=4)),
    "instella full (2 x 8,192 x 16)": (2, 8192, 8192, 16, 16, True, {}),
    "olmoe full (1 x 4,096 x 16)": (1, 4096, 4096, 16, 16, False, {}),
    "keye dsa (16,384 x 32q/4kv)": (1, 16384, 16384, 32, 4, True, dict(selected=True)),
    "evabyte local (16 x 2,048 x 4)": (16, 2048, 2048, 4, 4, False, dict(tag="eva_local")),
    "evabyte far (32,768 over 2,048 x 4)": (1, 32768, 2048, 4, 4, False,
                                           dict(summaries=(2048, 128), tag="eva_far")),
    # (PR 62: a group of 7, 28 column blocks by rows; packed, and each row one
    # document, where a window of 4,096 binds for three quarters of the positions)
    "smallthinker window 4096 (2 x 16,384 x 28q/4kv)": (2, 16384, 16384, 28, 4, True,
                                                        dict(window=4096)),
    "smallthinker full (2 x 16,384 x 28q/4kv)": (2, 16384, 16384, 28, 4, True, {}),
    "smallthinker window 4096, whole rows (2 x 16,384 x 28q/4kv)": (
        2, 16384, 16384, 28, 4, False, dict(window=4096)),
    "smallthinker full, whole rows (2 x 16,384 x 28q/4kv)": (2, 16384, 16384, 28, 4, False, {}),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--cases", default="", help="a regular expression: the cases to run")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.trace import reduce
    from deepspeed_tpu.ops.transformer import attention
    from deepspeed_tpu.ops.transformer import pallas_flash as pf

    key = jax.random.PRNGKey(args.seed)
    rng = np.random.default_rng(args.seed)
    trace_dir = os.path.join(ROOT, ".bench_trace", "attn_layout_ab")

    def documents(B, S):
        """Packed documents a row: lengths drawn about 2,048 tokens long."""
        ids = np.zeros((B, S), np.int32)
        for b in range(B):
            ends = np.cumsum(rng.integers(256, 4096, size=S // 256))
            ids[b] = np.searchsorted(ends, np.arange(S), side="right")
        return jnp.asarray(ids)

    def timed(fn, *operands):
        jax.block_until_ready(fn(*operands))
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            times.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(times)

    def device_ms(fn, *operands):
        """(``flash_fwd*``, ``flash_bwd*``, every other operation) of one call
        of ``fn``, device ms, from a trace of ``TRACED_CALLS`` calls."""
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        jax.profiler.start_trace(trace_dir)
        try:
            for _ in range(TRACED_CALLS):
                jax.block_until_ready(fn(*operands))
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        trace = reduce.load(path)
        # (a launch's event carries its name inside the transform's: ``jvp_flash_fwd_.1``)
        sums = {"flash_fwd": 0.0, "flash_bwd": 0.0, "": 0.0}
        for name, _, ns, *_ in reduce.leaf_events(trace["devices"][sorted(trace["devices"])[0]]):
            sums[next(k for k in sums if k in name)] += ns / 1e6 / TRACED_CALLS
        shutil.rmtree(trace_dir, ignore_errors=True)
        return sums["flash_fwd"], sums["flash_bwd"], sums[""]

    out = []
    for n, (name, (B, sq, sk, H, kvH, packed, kw)) in enumerate(CASES.items()):
        if not re.search(args.cases, name):
            continue
        kw = dict(kw)
        draw = lambda i, rows, heads: jax.random.normal(
            jax.random.fold_in(key, 10 * n + i), (B, rows, heads, D), jnp.bfloat16)
        q, k, v, do = draw(0, sq, H), draw(1, sk, kvH), draw(2, sk, kvH), draw(3, sq, H)
        if packed:
            kw["segment_ids"] = documents(B, sk)
            if "blockdiff" in kw:
                kw["q_segment_ids"] = jnp.concatenate([kw["segment_ids"]] * 2, axis=1)
        if kw.pop("selected", False):
            # a selection that holds every visible key: the launch's own work
            kw["selected"] = attention.pack_selection(attention.causal_in_document(
                jnp.arange(sq), kw["segment_ids"], kw["segment_ids"]))
        tiles = pf.launch_tiles(sq, sk, D, 2, **{a: kw[a] for a in (
            "window", "blockdiff", "summaries") if a in kw}, selected="selected" in kw,
            compiled=jax.default_backend() != "cpu")
        row = {"case": name, "device": jax.devices()[0].device_kind,
               "dq_mode": pf.dq_mode(sq, sk, tiles, kw.get("window"))}
        results = {}
        for layout in pf.LAYOUTS:
            forward = jax.jit(lambda q, k, v: pf.flash_attention_with_lse(
                q, k, v, causal=True, layout=layout, **kw))
            both = jax.jit(lambda q, k, v, do: jax.vjp(lambda *a: pf.flash_attention_with_lse(
                *a, causal=True, layout=layout, **kw)[0], q, k, v)[1](do))
            results[layout] = forward(q, k, v) + both(q, k, v, do)
            row[layout] = dict(zip(
                ("forward_ms", "forward_backward_ms", "flash_fwd_ms", "flash_bwd_ms", "copies_ms"),
                (timed(forward, q, k, v), timed(both, q, k, v, do))
                + device_ms(both, q, k, v, do)))
        f32 = lambda a: a.astype(jnp.float32)
        row["differs"] = {what: float(jnp.max(jnp.abs(f32(a) - f32(b))) / jnp.max(jnp.abs(f32(b))))
                          for what, a, b in zip(("o", "lse", "dq", "dk", "dv"),
                                                results["rows"], results["heads"])}
        row["matches"] = (row["differs"]["o"] == row["differs"]["lse"] == 0.0
                          and max(row["differs"].values()) < 2 ** -7)
        print(json.dumps(row), flush=True)
        out.append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_layout_ab.jsonl", "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in out)
    columns = ("flash_fwd_ms", "flash_bwd_ms", "copies_ms", "forward_ms", "forward_backward_ms")
    with open("chiprun_out/attn_layout_ab.md", "w") as f:
        f.write("| launch, one layer (dq) | layout | `flash_fwd*` | `flash_bwd*` | copies | "
                "forward, host | forward + backward, host |\n|---|---|---|---|---|---|---|\n")
        for r in out:
            for layout, side in (("heads", "parent"), ("rows", "change")):
                f.write(f"| {r['case']} ({r['dq_mode']}) | {layout} ({side}) | " + " | ".join(
                    f"{r[layout][c]:.2f}" for c in columns) + " |\n")
    return 0 if all(r["matches"] for r in out) else 1


if __name__ == "__main__":
    sys.exit(main())
