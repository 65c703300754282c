#!/usr/bin/env python
"""A/B microbenchmark of the selective scan on the attached chip (PR 57), at the
``phi4-mini-flash-reasoning.train.sambay`` cell's shape: 16,384 rows of 5120
channels of 16 states, bfloat16 operands, documents of 4,096.

Rungs, forward alone and forward + backward (every gradient of ``sum(w m)``):

- ``kernel``: the Pallas pair ``ssm_scan_fwd`` / ``ssm_scan_bwd``
  (``ops/transformer/pallas_scan.py``) at each ``--unroll`` and ``--tile``;
- ``xla``: `scan_xla`, the scan over chunks of an associative scan (with
  ``--xla``; at the cell's shape a pass is seconds).

One JSON line a reading on stdout and in ``chiprun_out/ssm_scan_ab.jsonl``: ``ms``
a pass (the best of ``--windows`` windows of ``--calls`` calls, host clock around
``block_until_ready``), ``floor_ms`` the bytes a pass must move over the chip's
819 GB/s (forward ``a``, ``dt`` read and ``m`` written, ``B`` and ``C``; backward
those, ``dm``, and the four gradients: ``benchmark/reference/phi4flash.py::
scan_bytes_per_row``'s count) and ``err``: the largest distance of ``m`` and of
each gradient from the XLA route's at ``--check-rows`` rows over its largest
element. ``--tiny`` rehearses the script at a small shape (the CPU, interpret
mode). No cell runs this file."""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.ops.transformer import pallas_scan as ps  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16
HBM_BYTES_PER_S = 819e9      # one v5e chip (Google Cloud documentation, "TPU v5e")


def operands(rows, channels, states, dtype, document):
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    first = (jnp.arange(rows) % document == 0).astype(jnp.int32)
    dt_bias = jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
        k[6], (channels,), F32, jnp.log(1e-3), jnp.log(1e-1)))))
    return (jax.random.normal(k[0], (rows, channels), dtype),
            jax.random.normal(k[1], (rows, channels), dtype),
            -jnp.broadcast_to(jnp.arange(1, states + 1, dtype=F32), (channels, states)),
            jax.random.normal(k[3], (rows, states), dtype),
            jax.random.normal(k[4], (rows, states), dtype),
            jnp.ones((channels,), F32), dt_bias, first), jax.random.normal(
                k[7], (rows, channels), dtype)


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
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--channels", type=int, default=5120)
    ap.add_argument("--states", type=int, default=16)
    ap.add_argument("--document", type=int, default=4096)
    ap.add_argument("--unroll", default="1,2,4,8")
    ap.add_argument("--tile", default="512")
    ap.add_argument("--xla", action="store_true")
    ap.add_argument("--check-rows", type=int, default=1024)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.tiny:
        args.rows, args.channels, args.states, args.document = 256, 256, 8, 64
        args.check_rows, args.calls, args.windows, args.tile = 256, 1, 1, "128"
    dtype = F32 if args.tiny else BF16
    ops, w = operands(args.rows, args.channels, args.states, dtype, args.document)
    item = jnp.dtype(dtype).itemsize
    fwd_bytes = args.rows * item * (3 * args.channels + 2 * args.states)
    bwd_bytes = args.rows * (item * (5 * args.channels + 2 * args.states) + 8 * args.states)
    os.makedirs("chiprun_out", exist_ok=True)
    out = open(os.path.join("chiprun_out", "ssm_scan_ab.jsonl"), "a")

    def both(fn):
        fwd = jax.jit(fn)
        grad = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum((fn(*a) * w).astype(F32)), argnums=tuple(range(7))))
        return fwd, grad

    def report(**line):
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()

    n = args.check_rows
    small = tuple(x[:n] if x.shape[0] == args.rows else x for x in ops)
    ref_m = jax.jit(ps.scan_xla)(*small)
    ref_g = jax.jit(jax.grad(lambda *a: jnp.sum((ps.scan_xla(*a) * w[:n]).astype(F32)),
                             argnums=tuple(range(7))))(*small)
    dist = lambda x, y: float(jnp.max(jnp.abs(x.astype(F32) - y.astype(F32)))
                              / (jnp.max(jnp.abs(y.astype(F32))) + 1e-30))
    for tile in map(int, args.tile.split(",")):
        for unroll in map(int, args.unroll.split(",")):
            fn = lambda *a: ps.scan_kernel(*a, tile=tile, unroll=unroll)
            fwd, grad = both(fn)
            got_m = jax.jit(fn)(*small)
            got_g = jax.jit(jax.grad(lambda *a: jnp.sum((fn(*a) * w[:n]).astype(F32)),
                                     argnums=tuple(range(7))))(*small)
            err = {"m": dist(got_m, ref_m), **{
                name: dist(g, h) for name, g, h in zip(
                    ("da", "ddt", "dA", "dB", "dC", "dD", "dbias"), got_g, ref_g)}}
            ms_fwd = timed(fwd, ops, args.calls, args.windows)
            ms_both = timed(grad, ops, args.calls, args.windows)
            report(rung="kernel", tile=tile, unroll=unroll, rows=args.rows, fwd_ms=ms_fwd,
                   fwd_bwd_ms=ms_both, fwd_floor_ms=1e3 * fwd_bytes / HBM_BYTES_PER_S,
                   fwd_bwd_floor_ms=1e3 * (fwd_bytes + bwd_bytes) / HBM_BYTES_PER_S, err=err)
    if args.xla:
        fwd, grad = both(ps.scan_xla)
        report(rung="xla", rows=args.rows, fwd_ms=timed(fwd, ops, 1, 1),
               fwd_bwd_ms=timed(grad, ops, 1, 1))


if __name__ == "__main__":
    main()
