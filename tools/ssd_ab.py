#!/usr/bin/env python
"""A/B microbenchmark of the state-space-duality core on the attached chip (PR
65), at the ``granite-4.0-h-micro.train.ssd32k`` cell's shape: 32,768 rows of 64
heads of 64 over 128 states in one group, bfloat16 operands, documents of 4,096.

Rungs, forward alone and forward + backward (every gradient of ``sum(w m)``):

- ``kernel``: the Pallas pair ``ssd_fwd`` / ``ssd_bwd``
  (``ops/transformer/pallas_ssd.py``) at each ``--chunk`` and ``--tile`` (heads a
  grid step);
- ``xla``: `ssd_xla`, the same chunked form in ``jax.numpy`` under a scan over
  chunks (with ``--xla``).

One JSON line a reading on stdout and in ``chiprun_out/ssd_ab.jsonl``: ``ms`` a
pass (the best of ``--windows`` windows of ``--calls`` calls, host clock around
``block_until_ready``), ``floor_ms`` the larger of the bytes a pass must move over
the chip's 819 GB/s and the products it must make over 197 TFLOP/s
(``benchmark/reference/granite_hybrid.py::ssd_bytes_per_row`` /
``ssd_flops_per_row``'s counts) and ``err``: the largest distance of ``m`` and of
each gradient from the recurrence a token at a time at ``--check-rows`` rows over
its largest element. ``--tiny`` rehearses the script at a small shape (the CPU,
interpret mode). No cell runs this file."""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.ops.transformer import pallas_ssd as ssd  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16
HBM_BYTES_PER_S, FLOPS_PER_S = 819e9, 197e12    # one v5e chip (Google Cloud, "TPU v5e")
NAMES = ("da", "ddt", "dA", "dB", "dC", "dD")


def operands(rows, heads, head, states, dtype, document):
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    first = (jnp.arange(rows) % document == 0).astype(jnp.int32)
    dt = jnp.exp(jax.random.uniform(k[1], (rows, heads), F32, jnp.log(1e-3), jnp.log(1e-1)))
    return (jax.random.normal(k[0], (rows, heads * head), dtype), dt,
            -jax.random.uniform(k[2], (heads,), F32, 1.0, 16.0),
            jax.random.normal(k[3], (rows, states), dtype),
            jax.random.normal(k[4], (rows, states), dtype),
            jnp.ones((heads,), F32), first), jax.random.normal(k[7], (rows, heads * head), dtype)


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
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--head", type=int, default=64)
    ap.add_argument("--states", type=int, default=128)
    ap.add_argument("--document", type=int, default=4096)
    ap.add_argument("--chunk", default="256")
    ap.add_argument("--tile", default="16")
    ap.add_argument("--xla", action="store_true")
    ap.add_argument("--check-rows", type=int, default=1024)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.tiny:
        args.rows, args.heads, args.states, args.document = 96, 4, 16, 40
        args.check_rows, args.calls, args.windows, args.chunk, args.tile = 96, 1, 1, "32", "2"
    dtype = F32 if args.tiny else BF16
    ops, w = operands(args.rows, args.heads, args.head, args.states, dtype, args.document)
    item, cells = jnp.dtype(dtype).itemsize, args.heads * args.head * args.states
    di = args.heads * args.head
    fwd_floor = args.rows * max((item * (2 * di + 2 * args.states) + 4 * args.heads)
                                / HBM_BYTES_PER_S, 4.0 * cells / FLOPS_PER_S)
    bwd_floor = args.rows * max(
        (item * (3 * di + 2 * args.states) + 8 * args.states + 12 * args.heads)
        / HBM_BYTES_PER_S, 10.0 * cells / FLOPS_PER_S)
    os.makedirs("chiprun_out", exist_ok=True)
    out = open(os.path.join("chiprun_out", "ssd_ab.jsonl"), "a")

    def both(fn):
        # (the big operands are ARGUMENTS of the jitted programs, never closed over)
        grad = lambda *a: jax.value_and_grad(
            lambda *x: jnp.sum((fn(*x, a[6]) * a[7]).astype(F32)), argnums=tuple(range(6)))(*a[:6])
        return jax.jit(fn), jax.jit(grad)

    def report(**line):
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()

    n = args.check_rows
    small = tuple(x[:n] if x.shape[0] == args.rows else x for x in ops)
    ref_fwd, ref_grad = both(ssd.ssd_by_token)
    ref_m, (_, ref_g) = ref_fwd(*small), ref_grad(*small, w[:n])
    dist = lambda x, y: float(jnp.max(jnp.abs(x.astype(F32) - y.astype(F32)))
                              / (jnp.max(jnp.abs(y.astype(F32))) + 1e-30))

    def errors(fwd, grad):
        got_m, (_, got_g) = fwd(*small), grad(*small, w[:n])
        return {"m": dist(got_m, ref_m),
                **{name: dist(g, h) for name, g, h in zip(NAMES, got_g, ref_g)}}

    floors = dict(fwd_floor_ms=1e3 * fwd_floor, fwd_bwd_floor_ms=1e3 * (fwd_floor + bwd_floor))
    for chunk in map(int, args.chunk.split(",")):
        for tile in map(int, args.tile.split(",")):
            fwd, grad = both(lambda *a: ssd.ssd_kernel(*a, chunk=chunk, tile=tile))
            report(rung="kernel", chunk=chunk, tile=tile, rows=args.rows,
                   fwd_ms=timed(fwd, ops, args.calls, args.windows),
                   fwd_bwd_ms=timed(grad, ops + (w,), args.calls, args.windows),
                   **floors, err=errors(fwd, grad))
    if args.xla:
        fwd, grad = both(ssd.ssd_xla)
        report(rung="xla", chunk=ssd.XLA_CHUNK, rows=args.rows,
               fwd_ms=timed(fwd, ops, 1, 1), fwd_bwd_ms=timed(grad, ops + (w,), 1, 1),
               **floors, err=errors(fwd, grad))


if __name__ == "__main__":
    main()
