"""On-chip ZeRO-Infinity param-streaming demo (VERDICT r4 item 1c).

Trains a model with offload_param.paged_training=true and reports the
honest record: loss trajectory, peak device param residency vs total param
bytes, per-step wall, fetch-stall seconds. Usage:

    python tools/param_stream_demo.py \
        [preset] [--steps N] [--batch B] [--seq S] [--layers L]

Presets: gpt2-tiny (smoke), gpt2-125m, gpt2-large, llama7b-dims (the
stretch goal: 7B dims full depth — params+grads 27 GB, far beyond one
16 GB chip; only possible paged).
"""

import argparse
import json
import sys
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("preset", nargs="?", default="gpt2-125m")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--cpu", action="store_true", help="force CPU mesh")
    ap.add_argument("--narrow-state", action="store_true",
                    help="bf16 moments (SR) + bf16 grad accumulators")
    args = ap.parse_args()

    if args.cpu:
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        jax.config.update("jax_platforms", "cpu")
        os.environ["DSTPU_ACCELERATOR"] = "cpu"
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2_model, llama_model

    over = {"max_seq_len": max(args.seq, 32), "remat": False}
    if args.layers:
        over["num_layers"] = args.layers
    if args.preset == "llama7b-dims":
        model = llama_model("llama2-7b", **over)
    else:
        model = gpt2_model(args.preset, **over)
    n_params = model.config.num_parameters()
    print(f"model {args.preset}: {n_params / 1e6:.1f}M params "
          f"({model.config.num_layers} layers)", flush=True)

    cfg = {
        "train_micro_batch_size_per_gpu": args.batch,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "zero_optimization": {
            "stage": 3,
            "offload_param": {"device": "cpu", "paged_training": True}},
    }
    if args.preset == "llama7b-dims" or args.narrow_state:
        # 7B-dims host state: fp32 master 27 GB + bf16 SR moments 27 +
        # bf16 grad acc 13.5 + bf16 store 13.5 ≈ 81 GB — fits 125 GB RAM
        # (fp32 everything would need ~121 GB plus temporaries)
        cfg["data_types"] = {"optimizer_moment_dtype": "bf16",
                             "grad_accum_dtype": "bf16"}
    eng, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    rs = eng._param_stream
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, model.config.vocab_size, size=(args.batch, args.seq))}

    losses, walls = [], []
    for i in range(args.steps):
        t0 = time.perf_counter()
        loss = float(eng.train_batch(batch))  # float() = sync by fetch
        walls.append(time.perf_counter() - t0)
        losses.append(loss)
        print(f"step {i}: loss {loss:.4f} wall {walls[-1]:.1f}s "
              f"fetch-stall {rs.last_fetch_wait_s:.2f}s", flush=True)

    rec = {
        "metric": f"param-stream {args.preset} paged training",
        "value": round(losses[-1], 4),
        "unit": "loss",
        "losses": [round(x, 4) for x in losses],
        "wall_s": [round(w, 2) for w in walls],
        "peak_param_hbm_bytes": rs.peak_param_bytes,
        "total_param_bytes": rs.total_param_bytes,
        "residency_ratio": round(rs.peak_param_bytes
                                 / max(rs.total_param_bytes, 1), 4),
        "fetch_stall_s_last": round(rs.last_fetch_wait_s, 3),
    }
    print(json.dumps(rec), flush=True)
    ok = losses[-1] < losses[0] and rs.peak_param_bytes < rs.total_param_bytes
    print(f"{'OK' if ok else 'FAIL'}: loss descending={losses[-1] < losses[0]}"
          f" residency<params={rs.peak_param_bytes < rs.total_param_bytes}",
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
