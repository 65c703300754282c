#!/usr/bin/env python
"""A/B: capacity-dense batched einsum vs jax.lax.ragged_dot for the MoE
expert FFN, at the smoke's MoE dims, on the attached chip (VERDICT r2 next
#5 — record the grouped-matmul decision with numbers).

Interleaved timed windows per the repo's noise protocol (A and B
alternate within one process and the BEST window of each is compared).
Sync is by scalar fetch.

Run:  python tools/moe_ab.py        (writes one JSON line per variant)
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

# the smoke's MoE dims (chip_smoke.moe_train_model): h=1024, f=3584, 8 experts
# top-2, tokens = micro(8) x seq(1024), capacity_factor 1.25
E, H, F = 8, 1024, 3584
TOKENS = 8 * 1024
TOPK = 2
CAP = int(1.25 * TOKENS * TOPK / E)
STEPS = 30


def capacity_dense(expert_in, wi, wo):
    """[e, cap, h] batched einsum — pays cap padding (25% at cf=1.25)."""
    mid = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", expert_in, wi))
    return jnp.einsum("ecf,efh->ech", mid, wo)


def ragged(tokens_sorted, group_sizes, wi, wo):
    """jax.lax.ragged_dot over expert-sorted rows — no padding FLOPs."""
    mid = jax.nn.gelu(jax.lax.ragged_dot(tokens_sorted, wi, group_sizes))
    return jax.lax.ragged_dot(mid, wo, group_sizes)


def sync(x):
    return float(jax.device_get(jnp.ravel(x)[0]))


def main():
    rng = np.random.default_rng(0)
    dt = jnp.bfloat16
    expert_in = jnp.asarray(rng.normal(size=(E, CAP, H)), dt)
    wi = jnp.asarray(rng.normal(size=(E, H, F)) * 0.02, dt)
    wo = jnp.asarray(rng.normal(size=(E, F, H)) * 0.02, dt)
    # ragged layout: same real token count (topk*TOKENS), expert-sorted,
    # slightly imbalanced groups like real routing
    n_real = TOPK * TOKENS
    split = rng.multinomial(n_real, [1 / E] * E)
    tokens_sorted = jnp.asarray(rng.normal(size=(n_real, H)), dt)
    group_sizes = jnp.asarray(split, jnp.int32)

    f_dense = jax.jit(capacity_dense)
    f_ragged = jax.jit(ragged)

    # compile + settle
    sync(f_dense(expert_in, wi, wo))
    try:
        sync(f_ragged(tokens_sorted, group_sizes, wi, wo))
        ragged_ok = True
    except Exception as e:  # noqa: BLE001
        print(json.dumps({"variant": "ragged_dot",
                          "error": str(e)[:200]}), flush=True)
        ragged_ok = False

    results = {"dense": [], "ragged": []}
    for _ in range(4):  # interleaved windows
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = f_dense(expert_in, wi, wo)
        sync(out)
        results["dense"].append(time.perf_counter() - t0)
        if ragged_ok:
            t0 = time.perf_counter()
            for _ in range(STEPS):
                out = f_ragged(tokens_sorted, group_sizes, wi, wo)
            sync(out)
            results["ragged"].append(time.perf_counter() - t0)

    flops_real = 2 * n_real * H * F * 2  # two matmuls on real tokens
    for name, times in results.items():
        if not times:
            continue
        best = min(times)
        print(json.dumps({
            "variant": name,
            "dims": {"e": E, "h": H, "f": F, "cap": CAP, "real": n_real},
            "best_window_s": round(best, 4),
            "real_tflops": round(flops_real * STEPS / best / 1e12, 2),
            "padding_flops_frac": round(1 - n_real / (E * CAP), 3)
                if name == "dense" else 0.0,
        }), flush=True)


if __name__ == "__main__":
    main()
