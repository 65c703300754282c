#!/usr/bin/env python
"""The quickest proof that deepspeed_tpu still starts on the chip.

One process drives the system's main paths once, through the entry points a
user calls, at the published width of GPT-2-large (36 layers x 1280, 20
heads, vocab 50257, seq 1024) with random weights from ``--seed``:

  device     backend, versions, compile-cache directory, native ops
  train      deepspeed_tpu.initialize -> engine.train_batch, five steps
  kernels    every default-path Pallas kernel, compiled, against its
             reference (flash fwd+bwd, Adam/Lion buckets, int8 quantize
             rows, MoE forward, ragged wave attention)
  train-moe  three steps of a mixtral-style MoE model (`moe_train_model`)
  serve      inference/v2 engine + ContinuousBatchingScheduler answering 8
             staggered requests; prefill logits against the training model

``--chips 4`` runs ONLY the multi-chip phase and what it is compared with:
ZeRO-3 (explicit overlap schedule, default transport and planner) on a
dp=4 mesh against the one-device trajectory at the same global batch.

Prints one JSON object per phase; the LAST line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Exits non-zero, with the traceback and without that line, when JAX finds
no TPU or the moment any phase fails. Nothing here catches a phase's
exception. The process never starts a child (a chip belongs to one
process) and waits for the device with ``block_until_ready``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import tempfile
import time
from typing import Any, Dict, Tuple

#: loss-trajectory tolerance of the four-chip phase: what the repo's own
#: quantized-gradient ZeRO tests hold against the full-width baseline
#: (tests/unit/runtime/zero/test_zeropp.py); dryrun_multichip itself only
#: asks for finite losses and no involuntary rematerialization, both of
#: which are checked too.
MULTICHIP_LOSS_TOL = dict(rtol=0.05, atol=0.05)
#: tolerances of the kernels' own CPU tests, by input dtype
FLASH_TOL = {"float32": dict(rtol=2e-5, atol=5e-6),
             "bfloat16": dict(rtol=2e-2, atol=2e-2)}
FLASH_GRAD_TOL = {"float32": dict(rtol=5e-5, atol=5e-6),
                  "bfloat16": dict(rtol=6e-2, atol=6e-2)}
WAVE_TOL = {"float32": dict(rtol=2e-5, atol=2e-5),
            "bfloat16": dict(rtol=2e-2, atol=2e-2)}
MOE_TOL = {"float32": dict(rtol=1e-5, atol=1e-5),
           "bfloat16": dict(rtol=5e-2, atol=5e-2)}
#: serving parity (tests/unit/inference/v2/test_engine_v2.py holds fp32
#: engines to 2e-4; bf16 engines get the repo's bf16 kernel tolerance),
#: as max |got - ref| over max |ref|
SERVE_TOL = {"float32": 2e-4, "bfloat16": 2e-2}


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size the phases run at. The defaults are the real ones; the
    CPU test (tests/unit/ops/test_chip_compile.py) passes a tiny instance."""
    dtype: str = "bfloat16"
    # train / serve / multichip: the model and its batch
    preset: str = "gpt2-large"
    model_overrides: Tuple[Tuple[str, Any], ...] = ()
    micro: int = 4
    seq: int = 1024
    train_steps: int = 5
    # kernels
    flash: Tuple[int, int, int, int, int] = (1, 4096, 32, 4, 64)  # B,S,H,kvH,D
    flash_train: Tuple[int, int, int, int, int] = (4, 1024, 20, 20, 64)  # the
    # train phase's (and the benchmark cell's) attention call
    bucket_elems: int = 1 << 20
    quant_rows: Tuple[int, int] = (4096, 256)
    moe: Tuple[int, int, int, int] = (8192, 1024, 3584, 8)        # T,H,F,E
    moe_small_tokens: int = 1024     # a wave that takes the fused combine
    wave_heads: Tuple[int, int, int] = (32, 32, 128)               # H,kvH,D
    wave_page: int = 16
    wave_seqs: Tuple[Tuple[int, int], ...] = (
        (1, 300), (1, 17), (1, 511), (256, 128), (96, 0), (8, 40))
    # train-moe
    moe_steps: int = 3
    # serve
    n_requests: int = 8
    prompt_range: Tuple[int, int] = (64, 512)
    max_new: int = 32
    token_budget: int = 1024
    stagger_s: float = 0.05
    # multichip
    multichip_steps: int = 3


def emit(obj: Dict[str, Any]) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def rounded(value: Any) -> Any:
    """``engine.setup_totals`` (or any nest of it) at a millisecond."""
    if isinstance(value, float):
        return round(value, 3)
    if isinstance(value, dict):
        return {k: rounded(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [rounded(v) for v in value]
    return value


def memory() -> Dict[str, Any]:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def release() -> None:
    """Drop what the last phase left behind (engines are owned by their
    phase; this clears the process-global topology and telemetry)."""
    from deepspeed_tpu.runtime import topology as topo_mod
    from deepspeed_tpu.telemetry import reset_telemetry
    topo_mod.reset()
    reset_telemetry()
    gc.collect()


def on_tpu() -> bool:
    import jax
    return jax.devices()[0].platform == "tpu"


def max_err(got, ref, tol) -> float:
    """Assert ``got`` ~ ``ref`` under ``tol`` (rtol/atol); return max |err|."""
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    np.testing.assert_allclose(got, ref, **tol)
    return float(np.max(np.abs(got - ref))) if got.size else 0.0


def zipf_tokens(rng, vocab: int, shape) -> "np.ndarray":
    """Heavy-tailed token ids: a unigram distribution the model can learn
    within a few steps, so 'the loss falls' is a robust check on fresh
    batches."""
    import numpy as np
    return (np.minimum(rng.zipf(1.2, size=shape), vocab) - 1).astype(np.int32)


def train_model(sz: Sizes):
    import jax.numpy as jnp

    from deepspeed_tpu.models import gpt2_model
    # remat with its default policy (matmul and kernel outputs kept inside
    # the budget the engine reads from the chip): the chip's compiler
    # refuses the "attention_only" policy at this size (33.91G of 15.75G
    # hbm: it saves every unnamed [B,H,S,S] intermediate, and six MLP-wide
    # tensors per layer besides)
    return gpt2_model(sz.preset, dtype=jnp.dtype(sz.dtype), remat=True,
                      **dict(sz.model_overrides))


def train_config(sz: Sizes, micro: int, zero: Dict[str, Any]) -> Dict[str, Any]:
    """bf16, bf16 moments (SR store), clip 1.0 — 7.7 GB of state on one
    chip."""
    cfg = {
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "zero_optimization": zero,
        "gradient_clipping": 1.0,
    }
    if sz.dtype == "bfloat16":
        cfg["bf16"] = {"enabled": True}
        cfg["data_types"] = {"grad_accum_dtype": "bf16",
                             "optimizer_moment_dtype": "bf16",
                             "optimizer_moment_sq_dtype": "bf16"}
    return cfg


def run_steps(engine, batches) -> Tuple[list, list, float]:
    """``train_batch`` per batch; waits for loss AND the updated state.
    Returns (losses, step times, longest loss fetch AFTER the wait): if
    ``block_until_ready`` returned early the fetch would absorb the step."""
    import jax
    losses, times, fetch = [], [], 0.0
    for batch in batches:
        t0 = time.perf_counter()
        loss = engine.train_batch(batch)
        jax.block_until_ready((loss, engine.state))
        t1 = time.perf_counter()
        losses.append(float(loss))
        times.append(t1 - t0)
        fetch = max(fetch, time.perf_counter() - t1)
    return losses, times, fetch


def assert_finite(values, what: str) -> None:
    import math
    if not all(math.isfinite(v) for v in values):
        raise AssertionError(f"{what}: non-finite values {values}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(chips: int) -> Dict[str, Any]:
    import importlib.metadata as md

    import jax
    import jaxlib

    from deepspeed_tpu import get_accelerator
    from deepspeed_tpu.ops.op_builder.all_ops import ALL_OPS
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    devs = jax.devices()
    if len(devs) != chips:
        raise AssertionError(f"asked for {chips} chip(s), JAX sees {len(devs)}")
    cache_dir = enable_compile_cache()
    accel = get_accelerator()
    if on_tpu() and accel._name != "tpu":
        raise AssertionError(f"accelerator resolved to {accel._name!r} on a TPU")
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {
        "phase": "device", "platform": devs[0].platform,
        "device_kind": devs[0].device_kind, "count": len(devs),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu, "accelerator": accel._name,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": (len(os.listdir(cache_dir))
                                  if os.path.isdir(cache_dir) else 0),
        # built from csrc/ at first use (ops/op_builder); None = fell back
        "native_ops": {name: ("built" if cls().load() is not None
                              else "fallback")
                       for name, cls in sorted(ALL_OPS.items())},
        **memory(),
    }


def phase_train(sz: Sizes, seed: int) -> Dict[str, Any]:
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.ops.adam.pallas_adam import (opt_kernel_interpret,
                                                    opt_kernel_mode)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    model = train_model(sz)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=train_config(sz, sz.micro, {"stage": 1}),
        seed=seed)
    dp = engine.topology.data_parallel_size
    rng = np.random.default_rng(seed)
    batches = [{"input_ids": zipf_tokens(rng, model.config.vocab_size,
                                         (sz.micro * dp, sz.seq))}
               for _ in range(sz.train_steps)]
    losses, times, fetch = run_steps(engine, batches)
    assert_finite(losses, "train losses")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train loss did not fall: {losses}")
    kernel = engine._opt_kernel_choice() or opt_kernel_mode()
    interpret = opt_kernel_interpret()
    if on_tpu() and (kernel != "pallas" or interpret):
        raise AssertionError(f"optimizer kernel on a one-chip TPU resolved "
                             f"to {kernel!r}, interpret={interpret}")
    line = {
        "phase": "train", "model": sz.preset,
        "params": model.config.num_parameters(),
        "layers": model.config.num_layers, "hidden": model.config.hidden_size,
        "micro": sz.micro, "seq": sz.seq, "dp": dp,
        "losses": [round(v, 4) for v in losses],
        # information, not metrics: wall time of step 1 (compile included)
        # and the median of the later steps
        "first_step_s": round(times[0], 2),
        "steady_step_s": round(float(np.median(times[2:] or times)), 4),
        "fetch_after_block_until_ready_s": round(fetch, 5),
        "opt_kernel": kernel, "interpret": interpret,
        # where set-up went, by the engine's own account (import,
        # initialize, every program's first call: trace, lower, compile or
        # cache load, run; docs/OBSERVABILITY.md)
        "setup_totals": rounded(engine.setup_totals), **memory(),
    }
    # a later run in the same checkout reports what the cache saved
    marker = os.path.join(enable_compile_cache(), "chip_smoke_train.json")
    if os.path.exists(marker):
        with open(marker) as f:
            line["previous_run"] = json.load(f)
    if os.path.isdir(os.path.dirname(marker)):
        with open(marker, "w") as f:
            json.dump({"first_step_s": line["first_step_s"],
                       "compile_s": line["setup_totals"]["compile_s"],
                       "cache_hits": line["setup_totals"]["cache_hits"]}, f)
    del engine
    return line


def _check_flash(sz: Sizes, rng, shape) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.transformer import pallas_flash
    from deepspeed_tpu.ops.transformer.attention import (_xla_attention,
                                                         kernel_is_default)

    if on_tpu() and pallas_flash._auto_interpret():
        raise AssertionError("pallas_flash would run interpreted on a TPU")
    B, S, H, kvH, D = shape
    dt = jnp.dtype(sz.dtype)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)) * 0.5, dt)
    k = jnp.asarray(rng.normal(size=(B, S, kvH, D)) * 0.5, dt)
    v = jnp.asarray(rng.normal(size=(B, S, kvH, D)) * 0.5, dt)
    w = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

    def run(attn):
        # w is an ARGUMENT: closed over, its 33 MB would be baked into the
        # executable (and into the compile cache) as a constant
        def loss(q, k, v, w):
            out = attn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v, w)

    (_, out), grads = run(lambda q, k, v: pallas_flash.flash_attention_kernel(
        q, k, v, causal=True))
    (_, ref), ref_grads = run(lambda q, k, v: _xla_attention(
        q, k, v, True, None, None))
    jax.block_until_ready((out, grads, ref, ref_grads))
    errs = {"fwd": max_err(out, ref, FLASH_TOL[sz.dtype])}
    for name, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads):
        errs[name] = max_err(g, rg, FLASH_GRAD_TOL[sz.dtype])
    tiles = pallas_flash.choose_tiles(
        S, S, D, dt.itemsize, compiled=not pallas_flash._auto_interpret())
    return {"kernel": "flash fwd+bwd", "shape": list(shape),
            "tiles": {"fwd": list(tiles.fwd), "bwd": list(tiles.bwd)},
            "gate": f"attention.kernel_is_default on a TPU -> "
                    f"{kernel_is_default((B, S, H, D), (B, S, kvH, D), 'tpu')}",
            "interpret": pallas_flash._auto_interpret(), "max_abs_err": errs}


def _check_opt_buckets(sz: Sizes, rng) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.adam.pallas_adam import (
        adam_bucket_update, host_adam_step, host_lion_step,
        opt_kernel_interpret, opt_kernel_mode, sr_seed)
    from deepspeed_tpu.ops.lion.pallas_lion import lion_bucket_update

    interpret = opt_kernel_interpret()
    if on_tpu() and (interpret or opt_kernel_mode() != "pallas"):
        raise AssertionError("optimizer kernel gate is not compiled Pallas "
                             "on a TPU")
    n = sz.bucket_elems
    g = rng.normal(size=n).astype(np.float32)
    p = rng.normal(size=n).astype(np.float32)
    m = (rng.normal(size=n) * 0.1).astype(np.float32)
    v = (np.abs(rng.normal(size=n)) * 0.01).astype(np.float32)
    hyper = dict(lr=1e-3, weight_decay=0.01)
    step = jnp.asarray(3, jnp.int32)
    errs = {}

    # fp32 moments against the numpy statement of the same math
    # (test_opt_kernels.py::test_host_backend_matches_kernel tolerances)
    ph, mh, vh = p.copy(), m.copy(), v.copy()
    host_adam_step(ph, g, mh, vh, step=3, adamw=True, **hyper)
    pk, _, mk, vk = jax.jit(lambda *a: adam_bucket_update(
        *a, step=step, mode="adamw", sr=False, interpret=interpret,
        **hyper))(g, p, m, v)
    errs["adam_p"] = max_err(pk, ph, dict(rtol=1e-6, atol=1e-7))
    errs["adam_m"] = max_err(mk, mh, dict(rtol=1e-6, atol=1e-7))
    errs["adam_v"] = max_err(vk, vh, dict(rtol=1e-6, atol=1e-8))

    # bf16 moments, stochastic rounding, bf16 param cast: the master stays
    # fp32-exact; a stored moment is within one bf16 step of the fp32 value
    bf = jnp.bfloat16
    m16, v16 = jnp.asarray(m, bf), jnp.asarray(v, bf)
    ph, mh, vh = (p.copy(), np.asarray(m16, np.float32),
                  np.asarray(v16, np.float32))
    g16 = jnp.asarray(g, bf)
    host_adam_step(ph, np.asarray(g16, np.float32), mh, vh, step=3,
                   adamw=True, **hyper)
    pk, pc, mk, vk = jax.jit(lambda *a: adam_bucket_update(
        *a, step=step, mode="adamw", seed_m=sr_seed(step, 1, 0),
        seed_v=sr_seed(step, 2, 0), m_dtype=bf, v_dtype=bf, param_dtype=bf,
        interpret=interpret, **hyper))(g16, p, m16, v16)
    one_bf16_step = dict(rtol=2.0 ** -7, atol=1e-30)
    errs["adam_sr_p"] = max_err(pk, ph, dict(rtol=1e-6, atol=1e-7))
    errs["adam_sr_m"] = max_err(mk, mh, one_bf16_step)
    errs["adam_sr_v"] = max_err(vk, vh, one_bf16_step)
    if pc.dtype != bf or mk.dtype != bf:
        raise AssertionError((pc.dtype, mk.dtype))

    ph, mh = p.copy(), m.copy()
    host_lion_step(ph, g, mh, beta1=0.9, beta2=0.99, **hyper)
    pk, _, mk = jax.jit(lambda *a: lion_bucket_update(
        *a, sr=False, interpret=interpret, **hyper))(g, p, m)
    errs["lion_p"] = max_err(pk, ph, dict(rtol=1e-6, atol=1e-7))
    errs["lion_m"] = max_err(mk, mh, dict(rtol=1e-6, atol=1e-7))
    return {"kernel": "adam/lion bucket", "elems": n,
            "gate": f"DSTPU_OPT_KERNEL auto -> {opt_kernel_mode()}",
            "interpret": interpret, "max_abs_err": errs}


def _check_quant(sz: Sizes, rng) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.adam.pallas_adam import opt_kernel_interpret
    from deepspeed_tpu.ops.quantizer.pallas_quant import (quant_kernel_enabled,
                                                          quantize_rows_int8)

    G, gs = sz.quant_rows
    x = jnp.asarray(rng.normal(size=(G, gs)), jnp.float32)
    enabled = quant_kernel_enabled(gs, 8, True)
    if on_tpu() and not enabled:
        raise AssertionError("int8 quantize kernel gate is off on a TPU")

    def xla_rows(x):  # quantize_blockwise's symmetric int8 chain
        scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return (jnp.clip(jnp.round(x / scale), -128, 127).astype(jnp.int8),
                scale[:, 0])

    q, s = jax.jit(lambda x: quantize_rows_int8(
        x, interpret=opt_kernel_interpret()))(x)
    qr, sr = jax.jit(xla_rows)(x)
    q, qr = np.asarray(q, np.int32), np.asarray(qr, np.int32)
    # the CPU test demands byte identity; compiled, the two dividers may
    # round a tie differently, so the chip is held to one quantum and the
    # exact-match share is printed
    if np.max(np.abs(q - qr)) > 1:
        raise AssertionError("int8 payload differs by more than one quantum")
    return {"kernel": "quantize_rows_int8", "shape": [G, gs],
            "gate": f"DSTPU_QUANT_KERNEL auto -> "
                    f"{'pallas' if enabled else 'xla'}",
            "interpret": opt_kernel_interpret(),
            "payload_mismatch_frac": float(np.mean(q != qr)),
            "max_abs_err": {"scale": max_err(s, sr, dict(rtol=1e-6, atol=0))}}


def _check_moe(sz: Sizes, rng, tokens: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.moe.layer import MoE, moe_reference_forward
    from deepspeed_tpu.moe.sharded_moe import capacity, top_k_gating_indices
    from deepspeed_tpu.ops.transformer import pallas_moe

    _, H, F, E = sz.moe
    T = tokens
    dt = jnp.dtype(sz.dtype)
    geom = dict(top_k=2, activation="silu_gated", dtype=dt, tokens=T,
                num_experts=E, hidden=H)
    resolved = pallas_moe.moe_kernel_resolution(**geom)
    interpret = pallas_moe.moe_kernel_interpret()
    if on_tpu() and (resolved != "pallas" or interpret):
        raise AssertionError(f"MoE kernel gate on a one-chip TPU: "
                             f"{resolved!r}, interpret={interpret}")
    if not pallas_moe.moe_kernel_supported(**geom):
        raise AssertionError(f"MoE kernel refuses {geom}")
    moe = MoE(hidden_size=H, intermediate_size=F, num_experts=E, top_k=2)
    params = moe.init(jax.random.PRNGKey(int(rng.integers(1 << 30))), dt)
    # gate logits = the first E features of each token, exact in any
    # dtype: XLA may keep a bf16 matmul's fp32 result un-rounded inside
    # one program and not the other (xla_allow_excess_precision), which
    # flips the near-tie routes of ~0.5% of tokens between two programs
    # that are both right — seen on the chip in PR 21
    params["gate"] = jnp.eye(H, E, dtype=dt)
    tokens = jnp.asarray(rng.normal(size=(T, H)), dt)
    cap = capacity(T, E, moe.capacity_factor, moe.min_capacity)
    kw = dict(top_k=2, capacity=cap, activation="silu_gated", mask_pad=False)

    out, aux = jax.jit(pallas_moe.make_moe_forward(
        interpret=interpret, **kw))(params, tokens)
    ref, ref_aux = jax.jit(lambda p, t: moe_reference_forward(
        p, t, **kw))(params, tokens)

    # routes first: the kernel and the XLA gate must pick the same experts
    logits = (tokens @ params["gate"].astype(dt)).astype(jnp.float32)
    _, _, slot_tk, w_tk, _, _ = jax.jit(lambda x: pallas_moe.moe_route(
        x, top_k=2, capacity=cap, interpret=interpret))(logits)
    eidx, pos, keep, weight, _, _ = jax.jit(
        lambda x: top_k_gating_indices(x, 2, cap))(logits)
    want = np.where(np.asarray(keep), np.asarray(eidx) * cap
                    + np.asarray(pos), 0)
    route_mismatch = int(np.sum(np.asarray(slot_tk) != want))
    if route_mismatch:
        raise AssertionError(f"{route_mismatch} of {2 * T} routes differ "
                             f"from top_k_gating_indices")
    errs = {"out": max_err(out, ref, MOE_TOL[sz.dtype]),
            "combine_weight": max_err(w_tk, np.asarray(weight * keep),
                                      dict(rtol=1e-5, atol=1e-6)),
            "aux": max_err(aux, ref_aux, dict(rtol=1e-5, atol=1e-6))}
    return {"kernel": "moe forward (route, gather, ffn, combine)",
            "shape": [T, H, F, E], "capacity": cap,
            "fused_combine": pallas_moe.moe_fused_combine_fits(T, H),
            "gate": f"DSTPU_MOE_KERNEL auto -> {resolved}",
            "interpret": interpret, "route_mismatch": route_mismatch,
            "max_abs_err": errs}


def _check_wave(sz: Sizes, rng) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2.kernels.ragged_paged_attention import (
        _pallas_wave_default, ragged_paged_attention)
    from deepspeed_tpu.inference.v2.ragged.wave import WaveEntry, build_wave

    H, kvH, D = sz.wave_heads
    ps, bq = sz.wave_page, 8
    dt = jnp.dtype(sz.dtype)
    default = _pallas_wave_default()
    if on_tpu() and not default:
        raise AssertionError("ragged wave kernel gate is off on a TPU")
    # a mixed wave from the REAL host atom builder: decode rows at ragged
    # contexts, a continuing prefill chunk, fresh prompts
    entries, nxt = [], 1
    for uid, (q_len, seen) in enumerate(sz.wave_seqs):
        nb = -(-(seen + q_len) // ps)
        entries.append(WaveEntry(uid, np.zeros(q_len, np.int32), seen,
                                 list(range(nxt, nxt + nb))))
        nxt += nb
    desc = build_wave(entries, block_q=bq, block_size=ps)
    P = nxt + 1
    q = jnp.asarray(rng.normal(size=(len(desc.tokens), H, D)), dt)
    k = jnp.asarray(rng.normal(size=(kvH, P, ps, D)), dt)
    v = jnp.asarray(rng.normal(size=(kvH, P, ps, D)), dt)

    def run(use_pallas):
        return jax.jit(lambda q, k, v: ragged_paged_attention(
            q, k, v, jnp.asarray(desc.kv_lens),
            jnp.asarray(desc.page_indices), jnp.asarray(desc.cu_q_lens),
            block_q=bq, use_pallas=use_pallas))(q, k, v)

    n = desc.n_tokens
    err = max_err(run(True)[:n], run(False)[:n], WAVE_TOL[sz.dtype])
    return {"kernel": "ragged wave attention", "heads": [H, kvH, D],
            "page": ps, "wave": [list(s) for s in sz.wave_seqs],
            "tokens": int(n), "atoms": int(desc.kv_lens.shape[0]),
            "gate": f"DSTPU_RAGGED_ATTN auto -> "
                    f"{'pallas' if default else 'xla'}",
            "interpret": not on_tpu(), "max_abs_err": {"out": err}}


def phase_kernels(sz: Sizes, seed: int) -> Dict[str, Any]:
    import numpy as np

    from deepspeed_tpu.telemetry import NULL_TELEMETRY, setup_spans
    rng = np.random.default_rng(seed + 1)
    # no engine here: the phase whole is one first call of the program's
    # one first-call mechanism, which says what JAX traced and compiled
    with setup_spans.FirstCall("kernels", NULL_TELEMETRY) as compiled:
        checks = [_check_flash(sz, rng, sz.flash),
                  # a stream of its own: the checks after it keep the draws
                  # (and the tight Adam tolerances) they were written against
                  _check_flash(sz, np.random.default_rng(seed + 2),
                               sz.flash_train),
                  _check_opt_buckets(sz, rng),
                  _check_quant(sz, rng),
                  # `moe_train_model`'s dims (split FFN + token-major combine),
                  # then a small wave (the fused combine-scatter epilogue)
                  _check_moe(sz, rng, sz.moe[0]),
                  _check_moe(sz, rng, sz.moe_small_tokens),
                  _check_wave(sz, rng)]
    return {"phase": "kernels", "checks": checks,
            "compiled": rounded(compiled.numbers), **memory()}


def moe_train_model():
    """The smoke's MoE model: mixtral-8x7b's shape (8 experts, top-2, GQA)
    cut to 4 layers x 1024 so that its ZeRO-2 state fits one chip."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import mixtral_model

    return mixtral_model("mixtral-8x7b", dtype=jnp.bfloat16, remat=False,
                         num_layers=4, hidden_size=1024,
                         intermediate_size=3584, num_heads=16,
                         num_kv_heads=8, max_seq_len=1024)


def moe_train_config() -> Dict[str, Any]:
    return {
        "train_micro_batch_size_per_gpu": 8,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "zero_optimization": {"stage": 2},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "data_types": {"grad_accum_dtype": "bf16"},
    }


def phase_train_moe(sz: Sizes, seed: int, model=None, micro: int = 8, seq: int = 1024
                    ) -> Dict[str, Any]:
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.ops.transformer import pallas_moe

    model = model or moe_train_model()
    cfg = dict(moe_train_config(), train_micro_batch_size_per_gpu=micro)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg,
                                               seed=seed)
    c = model.config
    dp = engine.topology.data_parallel_size
    resolved = pallas_moe.moe_kernel_resolution(
        top_k=c.moe.top_k, activation="silu_gated", dtype=jnp.bfloat16,
        tokens=micro * seq, num_experts=c.moe.num_experts,
        hidden=c.hidden_size)
    if on_tpu() and resolved != "pallas":
        raise AssertionError(f"MoE training on a one-chip TPU resolved the "
                             f"expert path to {resolved!r}")
    rng = np.random.default_rng(seed + 2)
    batches = [{"input_ids": zipf_tokens(rng, c.vocab_size, (micro * dp, seq))}
               for _ in range(sz.moe_steps)]
    losses, times, _ = run_steps(engine, batches)
    assert_finite(losses, "train-moe losses")
    setup_totals = rounded(engine.setup_totals)
    del engine
    return {"phase": "train-moe", "layers": c.num_layers,
            "hidden": c.hidden_size, "experts": c.moe.num_experts,
            "top_k": c.moe.top_k, "micro": micro, "seq": seq,
            "moe_kernel_resolution": resolved,
            "losses": [round(v, 4) for v in losses],
            "first_step_s": round(times[0], 2),
            "steady_step_s": round(float(np.median(times[1:])), 4),
            "setup_totals": setup_totals, **memory()}


def phase_serve(sz: Sizes, seed: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2.config_v2 import (
        DeepSpeedTPStateManagerConfig, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.engine_v2 import build_engine
    from deepspeed_tpu.inference.v2.scheduler import ContinuousBatchingScheduler

    dt = jnp.dtype(sz.dtype)
    model = train_model(sz)
    block = 16
    longest = sz.prompt_range[1] + sz.max_new
    blocks_per_seq = -(-longest // block) + 1
    cfg = RaggedInferenceEngineConfig(
        state_manager=DeepSpeedTPStateManagerConfig(
            max_ragged_batch_size=sz.token_budget,
            max_ragged_sequence_count=max(64, sz.n_requests + 2),
            max_context=min(longest + block, model.config.max_seq_len)),
        kv_block_size=block, kv_cache_dtype=dt,
        num_kv_blocks=(sz.n_requests + 1) * blocks_per_seq + 8,
        max_prefill_chunk=sz.prompt_range[1], decode_burst=8)
    engine = build_engine(model, config=cfg, seed=seed)
    sched = ContinuousBatchingScheduler(engine, token_budget=sz.token_budget)

    rng = np.random.default_rng(seed + 3)
    vocab = model.config.vocab_size
    lens = np.linspace(*sz.prompt_range, sz.n_requests).astype(int)
    prompts = [rng.integers(0, vocab, size=(int(n),)) for n in rng.permutation(lens)]
    reqs = []
    t0 = time.perf_counter()
    while len(reqs) < len(prompts) or sched.has_work:
        due = int((time.perf_counter() - t0) / sz.stagger_s) + 1
        while len(reqs) < min(due, len(prompts)):
            reqs.append(sched.submit(prompts[len(reqs)],
                                     max_new_tokens=sz.max_new))
        if not sched.has_work:
            time.sleep(0.002)  # idle gap before the next arrival
        elif sched.step() == 0 and len(reqs) == len(prompts):
            break  # nothing schedulable: the checks below report it
    wall = time.perf_counter() - t0
    counts = [len(r.generated) for r in reqs]
    if not all(r.done for r in reqs) or counts != [sz.max_new] * len(reqs):
        raise AssertionError(f"requests incomplete: done="
                             f"{[r.done for r in reqs]} generated={counts}")
    if not all(0 <= t < vocab for r in reqs for t in r.generated):
        raise AssertionError("generated token outside the vocabulary")

    # one request's prefill logits against a plain full-context forward of
    # the training model on the same weights
    probe = prompts[0]
    got = engine.put([10_001], [probe])[0]
    engine.flush(10_001)
    with engine.mesh:
        ref, _ = jax.jit(model.apply)(engine.params, jnp.asarray(probe)[None, :])
    ref = np.asarray(ref[0, -1], np.float32)
    got = np.asarray(got, np.float32)
    assert_finite([float(np.max(np.abs(got)))], "serving logits")
    rel = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    if rel > SERVE_TOL[sz.dtype] or int(np.argmax(got)) != int(np.argmax(ref)):
        raise AssertionError(f"prefill logits differ from the full forward: "
                             f"rel err {rel:.3g} > {SERVE_TOL[sz.dtype]}")
    wave_impl = engine._impls["wave"].name
    kv_same = jnp.dtype(cfg.kv_cache_dtype) == dt
    pallas = wave_impl == "ragged_pallas" and kv_same
    if on_tpu() and not pallas:
        raise AssertionError(f"wave route on a TPU: {wave_impl}, kv {cfg.kv_cache_dtype}")
    line = {
        "phase": "serve", "model": sz.preset, "requests": len(reqs),
        "prompt_lens": [len(p) for p in prompts], "new_tokens": counts,
        "wall_s": round(wall, 2),
        "prefill_logits_rel_err": rel, "tolerance": SERVE_TOL[sz.dtype],
        "wave_route": "pallas" if pallas else "xla",
        "wave_route_why": f"registry picked {wave_impl}; KV pages "
                          f"{jnp.dtype(cfg.kv_cache_dtype).name} "
                          f"{'==' if kv_same else '!='} compute {dt.name}; "
                          f"learned positions (no ALiBi, no window)",
        # which bucket compiled, and what each first call's seconds went on
        "first_calls": {f"{program}{list(key)}": rounded(numbers) for
                        (program, key), numbers in engine.seen_buckets().items()},
        **memory(),
    }
    del engine, sched
    return line


@contextlib.contextmanager
def capture_fd2():
    """XLA's C++ warnings bypass sys.stderr; capture fd 2 to read them."""
    cap = tempfile.TemporaryFile(mode="w+b")
    old = os.dup(2)
    sys.stderr.flush()
    os.dup2(cap.fileno(), 2)
    try:
        yield cap
    finally:
        sys.stderr.flush()
        os.dup2(old, 2)
        os.close(old)


def phase_multichip(sz: Sizes, seed: int) -> Dict[str, Any]:
    import jax
    import numpy as np
    from jax.experimental import mesh_utils

    import deepspeed_tpu
    from deepspeed_tpu.runtime.topology import MeshTopology, TopologyConfig

    devs = jax.devices()
    n = len(devs)
    model = train_model(sz)
    rng = np.random.default_rng(seed + 4)
    micro = max(sz.micro // n, 1)
    batches = [{"input_ids": zipf_tokens(rng, model.config.vocab_size,
                                         (micro * n, sz.seq))}
               for _ in range(sz.multichip_steps)]
    zero3 = {"stage": 3, "overlap_comm": True}

    # the one-device trajectory at the same global batch (the plain fused
    # ZeRO-1 step of the one-chip smoke), built and freed before the
    # n-chip engine
    release()
    ref_engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=train_config(sz, micro * n, {"stage": 1}),
        seed=seed, topology=MeshTopology(TopologyConfig(), devices=devs[:1]))
    ref_losses, _, _ = run_steps(ref_engine, batches)
    ref_setup = rounded(ref_engine.setup_totals)
    del ref_engine
    release()

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=train_config(sz, micro, zero3), seed=seed)
    mesh_devices = engine.mesh.devices
    if on_tpu():
        want = mesh_utils.create_device_mesh(mesh_devices.shape, devices=devs)
        if [d.id for d in mesh_devices.flat] != [d.id for d in want.flat]:
            raise AssertionError("MeshTopology did not lay the mesh out "
                                 "with mesh_utils")
    with capture_fd2() as cap:
        losses, times, _ = run_steps(engine, batches)
    cap.seek(0)
    xla_stderr = cap.read().decode(errors="replace")
    sys.stderr.write(xla_stderr)
    if "Involuntary full rematerialization" in xla_stderr:
        raise AssertionError("SPMD partitioner fell back to full "
                             "rematerialization")
    assert_finite(losses + ref_losses, "multichip losses")
    np.testing.assert_allclose(losses, ref_losses, **MULTICHIP_LOSS_TOL)

    # no parameter or optimizer leaf lives whole on one device: every leaf
    # above ZeRO-3's persistence threshold (smaller ones stay replicated
    # by design) is cut n ways, and each device holds ~1/n of the bytes
    persist = engine.config.zero_config.stage3_param_persistence_threshold
    total = replicated = 0
    per_device, whole = {d.id: 0 for d in devs}, []
    flat, _ = jax.tree_util.tree_flatten_with_path(
        {"params": engine.state["params"], "opt": engine.state["opt"]})
    for path, leaf in flat:
        if not hasattr(leaf, "addressable_shards"):
            continue
        total += leaf.nbytes
        for s in leaf.addressable_shards:
            per_device[s.device.id] += s.data.nbytes
        cut = (len({s.device.id for s in leaf.addressable_shards}) == n
               and all(s.data.nbytes * n == leaf.nbytes
                       for s in leaf.addressable_shards))
        if not cut and leaf.size > persist:
            whole.append(jax.tree_util.keystr(path))
        elif not cut:
            replicated += leaf.nbytes
    if whole:
        raise AssertionError(f"{len(whole)} state leaves are not spread "
                             f"over {n} devices: {whole[:5]}")
    want_bytes = (total - replicated) / n + replicated
    if any(abs(b - want_bytes) > 0.01 * want_bytes
           for b in per_device.values()):
        raise AssertionError(f"per-device state bytes {per_device}, "
                             f"expected {want_bytes}")
    shares = {d: b / total for d, b in per_device.items()}
    line = {
        "phase": "multichip", "model": sz.preset, "dp": n,
        "zero": zero3, "micro_per_chip": micro, "global_batch": micro * n,
        "overlap_active": bool(engine._overlap_active),
        "overlap_fallback": engine._overlap_fallback,
        "mesh_device_ids": [d.id for d in mesh_devices.flat],
        "losses": [round(v, 4) for v in losses],
        "one_device_losses": [round(v, 4) for v in ref_losses],
        "loss_tolerance": MULTICHIP_LOSS_TOL,
        "state_bytes": total, "replicated_small_leaf_bytes": replicated,
        "per_device_state_share": {str(d): round(s, 4)
                                   for d, s in shares.items()},
        "first_step_s": round(times[0], 2),
        "steady_step_s": round(float(np.median(times[1:])), 4),
        "one_device_setup_totals": ref_setup,
        "setup_totals": rounded(engine.setup_totals), **memory(),
    }
    del engine
    return line


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX reports platform "
              f"{d0.platform!r} ({d0.device_kind})", file=sys.stderr)
        return 1

    sz = Sizes()
    emit(phase_device(args.chips))
    if args.chips == 4:
        phases = (phase_multichip,)
    else:
        phases = (phase_train, phase_kernels, phase_train_moe, phase_serve)
    for phase in phases:
        emit(phase(sz, args.seed))
        release()
    emit({"ok": True, "device": {"platform": d0.platform,
                                 "kind": d0.device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
