#!/usr/bin/env python
"""Step-level MoE A/B at the bench dims: FULL engine.train_batch timing
(the standalone-einsum A/B in moe_ab.py is dispatch-latency-dominated;
the training step is one program, so knob effects show up honestly
here).

Variants: micro batch 8 (bench config) vs 10/12 (amortize fixed cost;
16 is a compile-time OOM), capacity_factor 1.25 vs 1.0. Interleaved
process-level runs like tools/remat_ab.py — two MoE engines do not fit
HBM together.

Run:  python tools/moe_step_ab.py                (driver, A/B/A/B)
      python tools/moe_step_ab.py --single m8    (one variant)
"""

import gc
import json
import os
import sys
import time

VARIANTS = {
    "m8": dict(micro=8, cf=1.25),
    "m10": dict(micro=10, cf=1.25),
    "m12": dict(micro=12, cf=1.25),
    "m8cf1": dict(micro=8, cf=1.0),
}
STEPS = 30
SEQ = 1024


def sync(x):
    import jax
    import jax.numpy as jnp
    return float(jax.device_get(jnp.ravel(jax.tree.leaves(x)[0])[0]))


def run_single(name):
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import mixtral_model
    from deepspeed_tpu.models.transformer import MoEConfig
    from deepspeed_tpu.runtime import topology as topo_mod

    v = VARIANTS[name]
    topo_mod.reset()
    model = mixtral_model(
        "mixtral-8x7b", dtype=jnp.bfloat16, remat=False,
        num_layers=4, hidden_size=1024, intermediate_size=3584,
        num_heads=16, num_kv_heads=8, max_seq_len=SEQ,
        moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=v["cf"]))
    cfg = {
        "train_micro_batch_size_per_gpu": v["micro"],
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "zero_optimization": {"stage": 2},
        "bf16": {"enabled": True},
        "data_types": {"grad_accum_dtype": "bf16"},
        "gradient_clipping": 1.0,
    }
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(
            0, model.config.vocab_size, size=(v["micro"], SEQ))}
        sync(engine.train_batch(batch))
        sync(engine.train_batch(batch))
    except Exception as e:  # noqa: BLE001 — OOM is a result, not a crash
        print(json.dumps({"variant": name, "error": str(e)[:300]}),
              flush=True)
        return
    windows = []
    for _ in range(4):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            loss = engine.train_batch(batch)
        sync(loss)
        windows.append(time.perf_counter() - t0)
    best = min(windows)
    toks = v["micro"] * SEQ * STEPS
    print(json.dumps({"variant": name, **v,
                      "best_window_s": round(best, 4),
                      "tokens_per_sec": round(toks / best, 1)}), flush=True)
    del engine
    gc.collect()


def main():
    if "--single" in sys.argv:
        run_single(sys.argv[sys.argv.index("--single") + 1])
        return
    from ab_common import run_interleaved
    names = sys.argv[1:] or list(VARIANTS)
    me = os.path.abspath(__file__)
    run_interleaved(names,
                    lambda n: [sys.executable, me, "--single", n])


if __name__ == "__main__":
    main()
