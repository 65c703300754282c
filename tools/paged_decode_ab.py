#!/usr/bin/env python
"""Pallas paged-decode kernel vs XLA gather: the context-length crossover
(VERDICT r2 next #4 — the recorded numbers ARE the deliverable; if XLA
wins everywhere the measurement justifies the default permanently).

Interleaved best-of-4 windows per the repo noise protocol; sync by scalar
fetch. Covers the llama2-7b decode shape (kvH=32, D=128, MHA) and the
TinyLlama/GQA shape (kvH=4, D=64) at context 2k-32k (the 16k/32k points
are the round-4 long-context serving evidence: KV for B=8 at 32k is
4 GiB in the 7B shape — the regime the paged kernel exists for).

Run: python tools/paged_decode_ab.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.kernels.paged_attention import \
    _xla_paged_decode
from deepspeed_tpu.inference.v2.kernels.pallas_paged_decode import \
    paged_gqa_decode

B = 8
PS = 16
STEPS = 30


def sync(x):
    return float(jax.device_get(jnp.ravel(x)[0]))


def bench_pair(fa, fb, *args):
    """INTERLEAVED best-of-4 windows: A and B alternate within the same
    run so run-to-run drift hits both."""
    sync(fa(*args))  # compile
    sync(fb(*args))
    best_a = best_b = float("inf")
    for _ in range(4):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = fa(*args)
        sync(out)
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = fb(*args)
        sync(out)
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a, best_b


def main():
    rng = np.random.default_rng(0)
    for kvH, H, D in [(32, 32, 128), (4, 32, 64)]:
        for ctx in (2048, 4096, 8192, 16384, 32768):
            mp = ctx // PS
            P = B * mp + 1
            q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
            kp = jnp.asarray(rng.normal(size=(kvH, P, PS, D)), jnp.bfloat16)
            vp = jnp.asarray(rng.normal(size=(kvH, P, PS, D)), jnp.bfloat16)
            tables = jnp.asarray(
                1 + np.arange(B * mp).reshape(B, mp), jnp.int32)
            lens = jnp.full((B,), ctx, jnp.int32)
            scale = 1.0 / D ** 0.5

            fx = jax.jit(lambda q, k, v, l, t: _xla_paged_decode(
                q, k, v, l, t, scale=scale))
            fp = jax.jit(lambda q, k, v, l, t: paged_gqa_decode(
                q, k, v, l, t, scale=scale))
            row = {"kvH": kvH, "H": H, "D": D, "ctx": ctx,
                   "kv_bytes_mb": round(2 * B * ctx * kvH * D * 2 / 2**20, 1)}
            try:
                tx, tp = bench_pair(fx, fp, q, kp, vp, lens, tables)
                row["xla_ms_step"] = round(tx / STEPS * 1e3, 3)
                row["pallas_ms_step"] = round(tp / STEPS * 1e3, 3)
                row["pallas_speedup"] = round(tx / tp, 3)
            except Exception as e:  # noqa: BLE001
                # the pallas trace may reject shapes (e.g. MHA g=1 sublane
                # rule); record the XLA side alone in that case
                row["pallas_error"] = str(e)[:120]
                try:
                    sync(fx(q, kp, vp, lens, tables))
                    import time as _t
                    best = float("inf")
                    for _ in range(4):
                        t0 = _t.perf_counter()
                        for _ in range(STEPS):
                            out = fx(q, kp, vp, lens, tables)
                        sync(out)
                        best = min(best, _t.perf_counter() - t0)
                    row["xla_ms_step"] = round(best / STEPS * 1e3, 3)
                except Exception as e2:  # noqa: BLE001
                    row["xla_error"] = str(e2)[:120]
            print(json.dumps(row), flush=True)
            del q, kp, vp
            jax.clear_caches()


if __name__ == "__main__":
    main()
