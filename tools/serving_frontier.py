#!/usr/bin/env python
"""7B serving frontier under the staggered-arrival protocol + 16-req bisect.

Serve with per-request prompt-SLA frac 1.0 at 4 AND 6 concurrent
requests, and name the variable behind the 16-request RESOURCE_EXHAUSTED.

Sweeps n_requests in (4, 6, 8) through bench_serving with arrival
stagger DSTPU_STAGGER_S (default 0.6 s ~ one 512-token prefill wave),
then attempts 16 requests at three knob settings to bisect the ceiling:
full KV pool, halved KV pool (max_context trimmed), halved token budget.

Each sweep point is its own subprocess (fresh HBM; a 16-req death cannot
take the sweep down). Run: python tools/serving_frontier.py
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def child(n_requests: int, budget: int, max_new: int = 64,
          kv_dtype=None) -> None:
    from bench import _child_setup, bench_serving
    from deepspeed_tpu.utils.synth_checkpoint import synthesize_hf_checkpoint
    _, _, peak = _child_setup()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = synthesize_hf_checkpoint(
        "llama2-7b", os.path.join(root, ".synth_ckpts", "llama2-7b"))
    stagger = float(os.environ.get("DSTPU_STAGGER_S", "0.6"))
    kd = f" kv={kv_dtype}" if kv_dtype else ""
    line = bench_serving(
        None, n_requests=n_requests, prompt_len=512, max_new=max_new,
        token_budget=budget, peak_tflops=peak, model_path=path,
        quantization="int4",
        label=f"frontier n={n_requests} b={budget}{kd}, ",
        stagger_s=stagger, decode_burst=8 if stagger > 0 else None,
        kv_dtype=kv_dtype)
    print(json.dumps(line), flush=True)


def main():
    if "--child" in sys.argv:
        i = sys.argv.index("--child")
        kd = sys.argv[i + 3] if len(sys.argv) > i + 3 else ""
        child(int(sys.argv[i + 1]), int(sys.argv[i + 2]),
              kv_dtype=kd or None)
        return

    # r5: fp8 KV halves the pool vs bf16 — the r4 24-request wall was a
    # KV-pool compile OOM at ~7.3 GiB, so the fp8 points probe PAST it
    points = [(int(n), 1024, {}, kd) for n, kd in (
        (16, ""), (16, "fp8"), (24, "fp8"), (32, "fp8"), (24, ""),
    )] if os.environ.get("DSTPU_FRONTIER_R5", "1") == "1" else [
        (4, 1024, {}, ""),
        (6, 1024, {}, ""),
        (8, 1024, {}, ""),
        (16, 1024, {}, ""),
        (16, 1024, {"DSTPU_PUT_CHUNK_BYTES": str(1 << 29)}, ""),
        (16, 512, {}, ""),
    ]
    for n, budget, env_extra, kd in points:
        env = dict(os.environ, **env_extra)
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--child", str(n), str(budget), kd],
                capture_output=True, text=True, timeout=2400, env=env)
        except subprocess.TimeoutExpired as e:
            print(json.dumps({"point": [n, budget, kd or "bf16", env_extra],
                              "error": f"timeout; tail: {str(e.stdout)[-200:]}"}),
                  flush=True)
            continue
        got = None
        for ln in (r.stdout or "").strip().splitlines():
            try:
                d = json.loads(ln)
                if "metric" in d:
                    got = d
            except json.JSONDecodeError:
                continue
        if got is None:
            print(json.dumps({"point": [n, budget, kd or "bf16", env_extra],
                              "error": (r.stderr or r.stdout or "")[-400:]}),
                  flush=True)
        else:
            got["point"] = [n, budget, kd or "bf16", env_extra]
            print(json.dumps(got), flush=True)


if __name__ == "__main__":
    main()
