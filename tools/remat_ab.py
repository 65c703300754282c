#!/usr/bin/env python
"""A/B: full rematerialization (nothing_saveable) vs selective remat
policies for the two bench lines that train rematerialized (bert-large
seq128, gpt2-large 36L).

A selective policy saves matmul outputs and recomputes only the cheap
elementwise chain in the backward: the 8/6 recompute overhead mostly
disappears without the no-remat memory footprint.

Two bert-large ZeRO-1 engines do NOT fit HBM together (measured:
RESOURCE_EXHAUSTED at the second build), so interleaving is at PROCESS
granularity: `--single` runs one variant (build + warmup + 4 best-of
windows) and prints a JSON line; the driver mode alternates
baseline/candidate subprocesses twice each and compares the overall best
window per variant. Sync by scalar fetch per the repo noise protocol.

Run:  python tools/remat_ab.py [bert|gpt2] [policy]
      python tools/remat_ab.py [bert|gpt2] [policy] --single <policy>
"""

import gc
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import bert_model, gpt2_model
from deepspeed_tpu.runtime import topology as topo_mod

STEPS = 30


def sync(x):
    return float(jax.device_get(jnp.ravel(x)[0]))


def build(which, policy):
    topo_mod.reset()
    if which == "bert":
        model = bert_model("bert-large", dtype=jnp.bfloat16, remat=True,
                           remat_policy=policy, max_seq_len=512)
        micro, seq = 64, 128
    else:
        model = gpt2_model("gpt2-large", dtype=jnp.bfloat16, remat=True,
                           remat_policy=policy)
        micro, seq = 4, 1024
    cfg = {
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "zero_optimization": {"stage": 1},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.config.vocab_size, size=(micro, seq))
    batch = {"input_ids": ids}
    if not getattr(model.config, "causal", True):
        labels = np.full_like(ids, -100)
        mask = rng.random(ids.shape) < 0.15
        labels[mask] = ids[mask]
        batch["labels"] = labels
    return engine, batch, micro * seq


def run_single(which, policy):
    try:
        engine, batch, tok = build(which, policy)
        sync(engine.train_batch(batch))  # compile + settle
        sync(engine.train_batch(batch))
    except Exception as e:  # noqa: BLE001 — helper crash is a result
        print(json.dumps({"variant": policy, "model": which,
                          "error": str(e)[:300]}), flush=True)
        return
    windows = []
    for _ in range(4):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            loss = engine.train_batch(batch)
        sync(loss)
        leaf = jax.tree.leaves(engine.state["params"])[0]
        sync(jnp.ravel(leaf)[0])
        windows.append(time.perf_counter() - t0)
    best = min(windows)
    print(json.dumps({
        "variant": policy, "model": which,
        "best_window_s": round(best, 4),
        "tokens_per_sec": round(tok * STEPS / best, 1),
    }), flush=True)
    del engine
    gc.collect()


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "bert"
    candidate = sys.argv[2] if len(sys.argv) > 2 \
        else "dots_with_no_batch_dims_saveable"
    if "--single" in sys.argv:
        run_single(which, sys.argv[sys.argv.index("--single") + 1])
        return

    import os
    from ab_common import run_interleaved
    me = os.path.abspath(__file__)
    run_interleaved(
        ("nothing_saveable", candidate),
        lambda p: [sys.executable, me, which, candidate, "--single", p],
        timeout=900)


if __name__ == "__main__":
    main()
