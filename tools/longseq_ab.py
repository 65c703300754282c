#!/usr/bin/env python
"""Long-context training A/B: XLA attention vs the Pallas flash kernel,
seq 2k -> 16k, FULL-DEPTH TinyLlama-1.1B on one chip.

The question (round-3 verdict, missing #2): which attention path makes
long-sequence training possible, and at what length does the O(L^2)
materialized-scores XLA path stop fitting? At seq 8192 the XLA path's
per-layer scores buffer is 1*32*8192^2*2B = 4.3 GiB — expected to OOM
next to the 11 GiB train state; the flash kernel never materializes it.
Reference anchor: DeepSpeed-Ulysses sustains >54% peak at long seq
(reference blogs/deepspeed-ulysses/README.md:82).

Variants are "<seq>/<path>"; each runs in its own subprocess (two engines
never share HBM; the flash flag is trace-time). A variant that OOMs
reports the error as data — that IS the result.

Run:  python tools/longseq_ab.py            # driver, interleaved
      python tools/longseq_ab.py --single 8192 flash [--offload]
"""

import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SEQS = (2048, 4096, 8192)
# r6: "inrepo" = the in-repo Pallas flash kernel pair (the r6 default
# long-seq path, pallas_flash.py); r5: "flash" = GQA-native splash kernel;
# "repeat" = old broadcast-K/V stock kernel; "chunked" = query-chunked XLA
# (the r5 default long-seq path)
PATHS = ("xla", "flash", "repeat", "chunked", "inrepo")


def run_single(seq: int, path: str, offload: bool, micro: int = 1,
               remat: str = "full") -> None:
    if path == "inrepo":
        os.environ["DSTPU_ATTN"] = "pallas"
    elif path == "chunked":
        # a DSTPU_ATTN inherited from the caller's shell would silently
        # reroute every legacy arm — each arm owns the full env
        os.environ.pop("DSTPU_ATTN", None)
        os.environ.pop("DSTPU_PALLAS_FLASH", None)
        os.environ["DSTPU_LONGSEQ_ATTN"] = "chunked"
    else:
        os.environ.pop("DSTPU_ATTN", None)
        os.environ["DSTPU_PALLAS_FLASH"] = "0" if path == "xla" else "1"
        # 'xla' must measure the PLAIN one-shot path (its compile-OOM at
        # 4k+ is a documented datapoint) — without this the router's
        # chunked default would silently substitute at seq >= 4096
        os.environ["DSTPU_LONGSEQ_ATTN"] = "off"
    if path == "repeat":
        os.environ["DSTPU_SPLASH"] = "0"
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from bench import _flops_per_token, peak_tflops
    from deepspeed_tpu.models import llama_model
    from deepspeed_tpu.runtime import topology as topo_mod

    def sync(x):
        return float(jax.device_get(jnp.ravel(x)[0]))

    name = f"{seq}/{path}" + ("/offload" if offload else "") + \
        (f"/micro{micro}" if micro != 1 else "") + \
        (f"/{remat}" if remat != "full" else "")
    try:
        topo_mod.reset()
        model = llama_model(
            "tinyllama-1.1b", dtype=jnp.bfloat16, remat=True,
            max_seq_len=seq,
            **({"remat_policy": remat} if remat != "full" else {}))
        cfg = {
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "adamw",
                          "params": {"lr": 1e-4, "weight_decay": 0.01}},
            "bf16": {"enabled": True},
            "gradient_clipping": 1.0,
            "data_types": {"grad_accum_dtype": "bf16"},
            "zero_optimization": {"stage": 1},
        }
        if offload:
            # 16k residuals (5.9 GiB) don't fit beside the 8.8 GiB
            # on-chip optimizer state: page the optimizer to the host
            cfg["zero_optimization"] = {
                "stage": 3, "offload_optimizer": {"device": "cpu"}}
        else:
            cfg["data_types"]["optimizer_moment_dtype"] = "bf16"
            cfg["data_types"]["optimizer_moment_sq_dtype"] = "bf16"
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
        batch = {"input_ids": np.random.default_rng(0).integers(
            0, model.config.vocab_size, size=(micro, seq))}
        first = sync(engine.train_batch(batch))  # compile + settle
        sync(engine.train_batch(batch))
    except Exception as e:  # noqa: BLE001 — an OOM here is the datapoint
        print(json.dumps({"variant": name, "error": str(e)[:400]}),
              flush=True)
        return

    steps = max(3, 30 * 2048 // seq)  # ~constant tokens per window
    best = float("inf")
    windows = 2 if offload else 3
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(batch)
        sync(loss)
        sync(jax.tree.leaves(engine.state["params"])[0])
        best = min(best, time.perf_counter() - t0)
    peak = peak_tflops(jax.devices()[0].device_kind)
    tok_s = micro * seq * steps / best
    ach = tok_s * _flops_per_token(model.config, seq) / 1e12
    print(json.dumps({
        "variant": name, "best_window_s": round(best, 3),
        "ms_per_step": round(best / steps * 1e3, 1),
        "tokens_per_sec": round(tok_s, 1),
        "achieved_tflops": round(ach, 2),
        "mfu": round(ach / peak, 4) if peak else None,
        "loss_first": round(first, 3), "loss_last": round(sync(loss), 5),
        "steps_per_window": steps}), flush=True)
    del engine
    gc.collect()


def main():
    if "--single" in sys.argv:
        i = sys.argv.index("--single")
        micro = 1
        if "--micro" in sys.argv:
            micro = int(sys.argv[sys.argv.index("--micro") + 1])
        remat = "full"
        if "--remat" in sys.argv:
            remat = sys.argv[sys.argv.index("--remat") + 1]
        run_single(int(sys.argv[i + 1]), sys.argv[i + 2],
                   "--offload" in sys.argv, micro=micro, remat=remat)
        return
    from ab_common import run_interleaved
    # "chunked" only routes at seq >= 4096 (attention.XLA_CHUNK_MIN_SEQ,
    # the XLA route's memory bound; the kernel's own default starts at
    # FLASH_MIN_SEQ = 384); below that it would silently duplicate the
    # plain-xla datapoint
    variants = [f"{s}/{p}" for s in SEQS for p in PATHS
                if not (p == "chunked" and s < 4096)]

    def mk_cmd(name):
        seq, path = name.split("/")
        return [sys.executable, os.path.abspath(__file__),
                "--single", seq, path]

    run_interleaved(variants, mk_cmd, rounds=2, timeout=2400)


if __name__ == "__main__":
    main()
