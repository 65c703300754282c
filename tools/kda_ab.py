#!/usr/bin/env python
"""A/B microbenchmark of the Kimi Delta Attention core on the attached chip (PR
68), at the ``kimi-linear-48b-a3b.train.kda32k`` cell's shape: 32,768 rows of 32
heads with a ``[128, 128]`` state each, bfloat16 operands, documents of 4,096.

Rungs, forward alone and forward + backward (every gradient of ``sum(w o)``):

- ``kernel``: the Pallas pair ``kda_fwd`` / ``kda_bwd``
  (``ops/transformer/pallas_kda.py``) at each ``--chunk``, ``--sub`` (rows of a
  sub-block) and ``--span`` (rows of a grid step);
- ``xla``: `kda_xla`, the same chunked form under a scan over chunks (with
  ``--xla``).

One JSON line a reading on stdout and in ``chiprun_out/kda_ab.jsonl``: ``ms`` a
pass (the best of ``--windows`` windows of ``--calls`` calls, host clock around
``block_until_ready``), ``floor_ms`` the larger of the bytes a pass must move over
the chip's 819 GB/s and the products it must make over 197 TFLOP/s
(``benchmark/reference/kimi_linear.py::kda_bytes_per_row`` / ``kda_flops_per_row``'s
counts) and ``err``: the largest distance of ``o`` and of each gradient from the
recurrence a token at a time at ``--check-rows`` rows over its largest element.
``--tiny`` rehearses the script at a small shape (the CPU, interpret mode). No
cell runs this file."""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.ops.transformer import pallas_kda as kda  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16
HBM_BYTES_PER_S, FLOPS_PER_S = 819e9, 197e12    # one v5e chip (Google Cloud, "TPU v5e")
NAMES = ("dq", "dk", "dv", "dg", "dbeta")


def operands(rows, heads, head, dtype, document):
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    first = (jnp.arange(rows) % document == 0).astype(jnp.int32)

    def unit(key, scale=1.0):
        x = jax.random.normal(key, (rows, heads, head), F32)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6) * scale
        return x.reshape(rows, heads * head).astype(dtype)
    g = -jnp.exp(jax.random.uniform(k[3], (rows, heads * head), F32, jnp.log(1e-3), jnp.log(2.0)))
    return (unit(k[0], 3.0), unit(k[1], 5.0),
            jax.random.normal(k[2], (rows, heads * head), dtype), g,
            jax.nn.sigmoid(jax.random.normal(k[4], (rows, heads), F32)),
            first), jax.random.normal(k[7], (rows, heads * head), dtype)


def timed(fn, args, calls, windows):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return 1e3 * best


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=32768)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--head", type=int, default=128)
    ap.add_argument("--document", type=int, default=4096)
    ap.add_argument("--chunk", default="64")
    ap.add_argument("--sub", default="16")
    ap.add_argument("--span", default="256")
    ap.add_argument("--xla", action="store_true")
    ap.add_argument("--solve", default="highest",
                    choices=("highest", "default"),
                    help="the block inverse's products: float32 at full precision, or one "
                         "bfloat16 pass")
    ap.add_argument("--check-rows", type=int, default=1024)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--windows", type=int, default=2)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.tiny:
        args.rows, args.heads, args.head, args.document = 96, 2, 16, 40
        args.check_rows, args.calls, args.windows = 96, 1, 1
        args.chunk, args.sub, args.span = "16", "8", "32"
    dtype = F32 if args.tiny else BF16
    kda._EXACT = jax.lax.Precision.HIGHEST if args.solve == "highest" else None
    ops, w = operands(args.rows, args.heads, args.head, dtype, args.document)
    item, wide = jnp.dtype(dtype).itemsize, args.heads * args.head
    cells = args.heads * args.head * args.head
    # q, k, v, o once each, g in float32, beta; the decay, k^T S, the write, the read-out
    fwd_floor = args.rows * max((item * 4 * wide + 4 * wide + 4 * args.heads) / HBM_BYTES_PER_S,
                                7.0 * cells / FLOPS_PER_S)
    bwd_floor = args.rows * max((item * 8 * wide + 8 * wide + 8 * args.heads) / HBM_BYTES_PER_S,
                                14.0 * cells / FLOPS_PER_S)
    os.makedirs("chiprun_out", exist_ok=True)
    out = open(os.path.join("chiprun_out", "kda_ab.jsonl"), "a")

    def both(fn):
        # (the big operands are ARGUMENTS of the jitted programs, never closed over)
        grad = lambda *a: jax.value_and_grad(
            lambda *x: jnp.sum((fn(*x, a[5]) * a[6]).astype(F32)), argnums=tuple(range(5)))(*a[:5])
        return jax.jit(fn), jax.jit(grad)

    def report(**line):
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()

    n = args.check_rows
    small = tuple(x[:n] for x in ops)
    ref_fwd, ref_grad = both(kda.kda_by_token)
    ref_o, (_, ref_g) = ref_fwd(*small), ref_grad(*small, w[:n])
    dist = lambda x, y: float(jnp.max(jnp.abs(x.astype(F32) - y.astype(F32)))
                              / (jnp.max(jnp.abs(y.astype(F32))) + 1e-30))

    def errors(fwd, grad):
        got_o, (_, got_g) = fwd(*small), grad(*small, w[:n])
        return {"o": dist(got_o, ref_o),
                **{name: dist(g, h) for name, g, h in zip(NAMES, got_g, ref_g)}}

    floors = dict(fwd_floor_ms=1e3 * fwd_floor, fwd_bwd_floor_ms=1e3 * (fwd_floor + bwd_floor))
    for chunk in map(int, args.chunk.split(",")):
        for sub in map(int, args.sub.split(",")):
            for span in map(int, args.span.split(",")):
                fwd, grad = both(lambda *a: kda.kda_kernel(*a, chunk=chunk, sub=sub, span=span))
                try:
                    report(rung="kernel", chunk=chunk, sub=sub, span=span, rows=args.rows,
                           solve=args.solve,
                           fwd_ms=timed(fwd, ops, args.calls, args.windows),
                           fwd_bwd_ms=timed(grad, ops + (w,), args.calls, args.windows),
                           **floors, err=errors(fwd, grad))
                except Exception as e:       # (a variant the chip's compiler refuses)
                    report(rung="kernel", chunk=chunk, sub=sub, span=span,
                           refused=f"{type(e).__name__}: {str(e)[:300]}")
    if args.xla:
        fwd, grad = both(kda.kda_xla)
        report(rung="xla", chunk=kda.XLA_CHUNK, rows=args.rows,
               fwd_ms=timed(fwd, ops, 1, 1), fwd_bwd_ms=timed(grad, ops + (w,), 1, 1),
               **floors, err=errors(fwd, grad))


if __name__ == "__main__":
    main()
