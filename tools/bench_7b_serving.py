#!/usr/bin/env python
"""Full-depth serving bench (bench.py's dispatcher runs this as its own
process with a hard timeout: a stalled weight stream or 32-layer compile
must not be able to hang the whole bench).

Tries llama2-7b (32 layers, real dims, int4 WOQ ≈ 3.5 GB HBM, packed
uint8 storage, chunked weight upload) with fp8 KV pages at 16 concurrent
requests under the 0.6 s arrival protocol — prompt-SLA frac 1.0 with the
halved pool (r5 frontier, tools/serving_frontier.py; the sweep peaks at
32 reqs / 74.1 tok/s, committed at 16 where SLA holds with margin).
Falls back to tinyllama-1.1b int8, ALSO a real published architecture at
full depth (22 layers, GQA 32h/4kv), so the bench always produces a
no-scaling serving line.

Prints one JSON line per attempt; the LAST line is the result bench.py
keeps.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(arch: str, n_requests: int, token_budget: int):
    from bench import _child_setup, bench_serving
    from deepspeed_tpu.utils.synth_checkpoint import synthesize_hf_checkpoint
    _, _, peak = _child_setup()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = synthesize_hf_checkpoint(
        arch, os.path.join(root, ".synth_ckpts", arch))
    quant = {"llama2-7b": "int4", "tinyllama-1.1b": "int8"}[arch]
    label = {"llama2-7b": "llama2-7b FULL 32L int4 WOQ, ",
             "tinyllama-1.1b": "tinyllama-1.1b FULL 22L int8 WOQ, "}[arch]
    # fp8 KV applies to the 7B line only (the frontier-measured config);
    # the fallback keeps bf16 KV so its line stays comparable to earlier
    # rounds. Any env value other than "fp8" means bf16.
    kv = None
    if arch == "llama2-7b" and os.environ.get("DSTPU_7B_KV", "fp8") == "fp8":
        kv = "fp8"
    # request ARRIVAL spacing (FastGen benches an arrival process, not a
    # burst): ~ one 512-token prefill wave, so each arrival's prefill runs
    # in its own wave and every request's own-clock TTFT meets the SLA.
    # Long-context runs (DSTPU_7B_PROMPT=4096) stretch the stagger with
    # the prompt so each longer prefill still fits its arrival gap.
    prompt_len = int(os.environ.get("DSTPU_7B_PROMPT", "512"))
    stagger = float(os.environ.get("DSTPU_STAGGER_S",
                                   str(0.6 * prompt_len / 512)))
    if prompt_len != 512:
        label += f"{prompt_len}-tok prompts, "
    return bench_serving(
        None, n_requests=n_requests, prompt_len=prompt_len, max_new=64,
        token_budget=max(token_budget, prompt_len), peak_tflops=peak,
        model_path=path, quantization=quant, label=label, stagger_s=stagger,
        decode_burst=8 if stagger > 0 else None,
        # fp8 KV pages (r5): halves the pool vs bf16 — the lever that
        # broke the 24-request wall (tools/serving_frontier.py r5: 32
        # reqs x 512 prompt at 74.1 tok/s, prompt-SLA 1.0; the 24-req
        # bf16 control still compile-OOMs)
        kv_dtype=kv)


def main():
    attempts = [("llama2-7b", int(os.environ.get("DSTPU_7B_REQS", "16")),
                 1024),
                ("tinyllama-1.1b", 16, 2048)]
    if os.environ.get("DSTPU_7B_SKIP") == "1":
        attempts = attempts[1:]
    if os.environ.get("DSTPU_7B_SKIP_FALLBACK") == "1":
        # long-context caller: a tinyllama 512-prompt line would be
        # mislabeled as the 4k-prompt result — fail loudly instead
        attempts = attempts[:1]
    for arch, reqs, budget in attempts:
        try:
            line = run(arch, reqs, budget)
            print(json.dumps(line), flush=True)
            return
        except Exception as e:  # noqa: BLE001 — fall back to the next arch
            print(json.dumps({"attempt": arch, "error": str(e)[:200]}),
                  flush=True)
    raise SystemExit(1)


if __name__ == "__main__":
    main()
