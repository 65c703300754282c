#!/usr/bin/env python
"""Is the int8 WOQ matmul actually weight-bandwidth-efficient, or does
XLA materialize a bf16 copy of the weights (2.5x the traffic of dense)?

Single dispatches sit at the host's dispatch-latency floor, so the
probe chains N dependent decode-shaped MLP steps (x -> W1 -> W2 -> x)
inside ONE program via lax.scan — weights are loop-invariant, so if XLA
hoists the int8->bf16 convert out of the loop the cost vanishes (the
decode-burst regime); a fori-style re-convert per step would show as
~2.5x dense time. Compares:

  dense_bf16   : bf16 weights, the baseline traffic
  woq_int8     : quantized_matmul on int8 weights
  woq_prederef : dequantize once outside the scan (upper bound)

Also prints XLA cost-analysis bytes for the int8 program.

Run:  python tools/woq_matmul_ab.py [batch]
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.quantization.quantization import (
    QuantizationConfig, dequantize_kernel, quantize_kernel, quantized_matmul)

H, F = 4096, 11008   # llama2-7b MLP dims
N_STEPS = 64         # chained matmul pairs per program
WINDOWS = 4


def sync(x):
    return float(jax.device_get(jnp.ravel(x)[0]))


def main():
    b = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    rng = np.random.default_rng(0)
    w1 = jnp.asarray(rng.normal(size=(H, F)) * 0.02, jnp.bfloat16)
    w2 = jnp.asarray(rng.normal(size=(F, H)) * 0.02, jnp.bfloat16)
    cfg = QuantizationConfig(bits=8, group_size=128)
    q1 = quantize_kernel(w1, cfg)
    q2 = quantize_kernel(w2, cfg)
    x0 = jnp.asarray(rng.normal(size=(b, H)), jnp.bfloat16)

    def chain(matmul1, matmul2):
        def prog(x):
            def step(carry, _):
                y = jax.nn.silu(matmul1(carry))
                return jnp.tanh(matmul2(y)), None
            out, _ = jax.lax.scan(step, x, None, length=N_STEPS)
            return out
        return jax.jit(prog)

    from deepspeed_tpu.ops.quantizer.pallas_woq_matmul import woq_matmul

    progs = {
        "dense_bf16": chain(lambda v: v @ w1, lambda v: v @ w2),
        "woq_int8": chain(lambda v: quantized_matmul(v, q1),
                          lambda v: quantized_matmul(v, q2)),
        "woq_prederef": chain(
            lambda v: v @ dequantize_kernel(q1, jnp.bfloat16),
            lambda v: v @ dequantize_kernel(q2, jnp.bfloat16)),
        "woq_pallas": chain(
            lambda v: woq_matmul(v, q1["q"], q1["scale"]),
            lambda v: woq_matmul(v, q2["q"], q2["scale"])),
    }

    results = {k: [] for k in progs}
    for name, f in progs.items():
        sync(f(x0))  # compile
    for _ in range(WINDOWS):
        for name, f in progs.items():  # interleaved
            t0 = time.perf_counter()
            sync(f(x0))
            results[name].append(time.perf_counter() - t0)

    weight_bytes = {"dense_bf16": 2 * (H * F * 2),
                    "woq_int8": 2 * (H * F),
                    "woq_prederef": 2 * (H * F),
                    "woq_pallas": 2 * (H * F)}
    for name, times in results.items():
        best = min(times)
        print(json.dumps({
            "variant": name, "batch": b,
            "best_s_per_program": round(best, 4),
            "ms_per_step": round(best / N_STEPS * 1e3, 4),
            # steady-state GB/s if each step re-reads the weights
            "implied_gbps": round(
                weight_bytes[name] * N_STEPS / best / 1e9, 1),
        }), flush=True)

    cost = progs["woq_int8"].lower(
        jax.ShapeDtypeStruct(x0.shape, x0.dtype)).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    print(json.dumps({"woq_int8_cost_bytes": cost.get("bytes accessed"),
                      "flops": cost.get("flops")}), flush=True)


if __name__ == "__main__":
    main()
