#!/usr/bin/env python
"""A/B microbenchmark of a sub-layer's hyper-connection coefficients on the
attached chip (PR 56), at the ``xing4-29b-a4b.train.mhc`` cell's shape: vec(X)
``[1, 8192, 14336]`` bfloat16 x Phi ``[14336, 24]``.

Three rungs of ``m = (x rsqrt(mean(x^2) + eps)) Phi``, forward alone and
forward + backward (the gradients to x and Phi of ``sum(w m)``):

- ``plain``: what ``TransformerLM._hc_coefficients`` had until PR 55: x cast to
  float32, normalised, then the product at ``Precision.HIGHEST``; plain
  autodiff.
- ``xla``: the scale taken out of the product, left to XLA
  (``pallas_hc.coeff_product(.., "xla")``, a ``custom_vjp``), and
  ``xla_autodiff``, the same forward under plain autodiff.
- ``kernel``: what ships, the Pallas launch ``hc_coeff_fwd`` at ``choose_tiles``'
  tiles (with ``--sweep`` at every ``(tm, tk)`` of ``SWEEP``) and XLA's backward.
- ``pair``: the rung NOT taken, kept here alone: the same forward with
  ``hc_coeff_bwd`` below, ONE launch that reads a tile of x once, writes
  ``dx``'s tile and accumulates ``dphi^T`` in float32 over the row tiles. It is
  0.3 ms a pass faster than XLA's backward, and the cell's step did not load
  with it (docs/KERNELS.md, "The streams' coefficients (PR 56)").

Beside them ``mixes`` (``TransformerLM._hc_mixes``: the sigmoids and the 20
Sinkhorn rounds from ``m``, alone), ``whole`` (a sub-layer's coefficients as the
model makes them now) and ``pre`` (the weighted read ``sum_i H_pre[i] X[i]``
alone: what riding it in the forward kernel could spare is its read of x).
``--phi float32`` gives Phi as float32 (three bfloat16 parts side by side).

One JSON line a reading on stdout and in ``chiprun_out/hc_coeff_ab.jsonl``:
``ms`` a pass (``--repeats`` passes a dispatch, each over streams of its own so
that none is shared or hoisted and a call from the host, 0.2 ms, is paid
once for all; the best of ``--windows`` windows of
``--calls`` dispatches, host clock around ``block_until_ready``; the readings
alternate within a window), ``floor_ms`` the bytes a pass must move (x read once
forward; read once and written once more backward) over the chip's 819 GB/s,
and ``err``: the largest distance from ``plain``'s result on this chip over
its largest element, for ``m``, ``dx`` and ``dphi``. ``--tiny`` rehearses the
script at a small shape (the CPU, interpret mode). No cell runs this file.
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu import models  # noqa: E402
from deepspeed_tpu.ops.transformer import pallas_hc  # noqa: E402

F32 = jnp.float32
HBM_BYTES_PER_S = 819e9      # one v5e chip (Google Cloud documentation, "TPU v5e")
SWEEP = [(128, 0), (256, 2048), (512, 2048), (1024, 2048), (512, 1024), (256, 3584),
         (512, 3584), (256, 7168), (1024, 1024)]      # tk 0: all of K


def plain(x, phi, eps):
    x = x.astype(F32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return jnp.einsum("bsk,kc->cbs", x, phi.astype(F32), precision=jax.lax.Precision.HIGHEST)


def factored(x, phi, eps):
    """`pallas_hc`'s forward for bfloat16 operands, under plain autodiff."""
    p = pallas_hc._dot("ck,bsk->cbs", phi.T, x)
    ss = jnp.sum(jnp.square(x.astype(F32)), axis=-1)
    return jax.lax.rsqrt(ss / x.shape[-1] + eps)[None] * p


def repeated(f):
    """``f`` over every one of ``firsts`` (as many first arguments, no two the
    same array, so that no pass shares work with another) in one program."""
    return jax.jit(lambda firsts, *rest: [f(first, *rest) for first in firsts])


def _bwd_kernel(x_ref, a_ref, bt_ref, gt_ref, coef_ref, dx_ref, dwt_ref):
    """One ``[tm, tk]`` tile of x: ``dx = a bt - coef x`` and its part of
    ``dwt = gt x``."""
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _first_of_k_tile():
        dwt_ref[...] = jnp.zeros_like(dwt_ref)

    x = x_ref[...]
    dx = jnp.dot(a_ref[...], bt_ref[...], preferred_element_type=F32)
    dx_ref[...] = (dx - coef_ref[...] * x.astype(F32)).astype(dx_ref.dtype)
    dwt_ref[...] += jnp.dot(gt_ref[...], x, preferred_element_type=F32)


def kernel_bwd(tiles, interpret):
    """`pallas_hc._products_bwd` as one launch at ``tiles``."""
    def products_bwd(x, a, bt, gt, coef):
        B, S, K = x.shape
        tm, tk, _ = tiles
        E = gt.shape[0]
        a, bt = pallas_hc._pad(a, 2, 128), pallas_hc._pad(bt, 0, 128)
        gt = pallas_hc._pad(gt, 0, pallas_hc.SUBLANES)
        dx, dwt = pl.pallas_call(
            _bwd_kernel,
            out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((gt.shape[0], K), F32)),
            grid=(K // tk, B, S // tm),
            in_specs=[pl.BlockSpec((None, tm, tk), lambda k, b, i: (b, i, k)),
                      pl.BlockSpec((None, tm, a.shape[2]), lambda k, b, i: (b, i, 0)),
                      pl.BlockSpec((bt.shape[0], tk), lambda k, b, i: (0, k)),
                      pl.BlockSpec((gt.shape[0], None, tm), lambda k, b, i: (0, b, i)),
                      pl.BlockSpec((None, tm, 1), lambda k, b, i: (b, i, 0))],
            out_specs=(pl.BlockSpec((None, tm, tk), lambda k, b, i: (b, i, k)),
                       pl.BlockSpec((gt.shape[0], tk), lambda k, b, i: (0, k))),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=64 << 20),
            interpret=interpret,
            name="hc_coeff_bwd",
        )(x, a, bt, gt, coef[..., None])
        return dx, dwt[:E]
    return products_bwd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--hidden", type=int, default=3584)
    ap.add_argument("--phi", default="bfloat16", choices=("bfloat16", "float32"))
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=4)
    ap.add_argument("--windows", type=int, default=4)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args()
    if a.tiny:
        a.rows, a.hidden, a.calls, a.windows, a.repeats = 256, 64, 2, 1, 2
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: --tiny rehearses the script, a time comes from the chip")
    model = models.xing4_model("xing4-tiny", dtype=jnp.bfloat16, remat=False,
                               hidden_size=a.hidden, hc_sinkhorn_iters=20)
    c = model.config
    n, rows = c.residual_streams, a.rows
    K, C = n * a.hidden, n * (n + 2)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (1, rows, K), F32).astype(jnp.bfloat16)
    phi = (jax.random.normal(ks[1], (K, C), F32) * 0.02).astype(a.phi)
    w = jax.random.normal(ks[2], (C, 1, rows), F32)
    hc = {"phi": phi, "bias": jax.random.normal(ks[3], (C,), F32) * 0.1,
          "alpha": jnp.asarray([0.5, 0.5, 0.5], F32)}

    forms = {"plain": lambda x, phi: plain(x, phi, c.hc_eps),
             "xla": lambda x, phi: pallas_hc.coeff_product(x, phi, c.hc_eps, "xla"),
             "xla_autodiff": lambda x, phi: factored(x, phi.astype(x.dtype), c.hc_eps)}
    own = pallas_hc.choose_tiles(rows, K, compiled=not a.tiny)

    def pair(t):
        """`pallas_hc.coeff_product`'s kernel route with the backward launch."""
        how = (t, a.tiny)
        f = jax.custom_vjp(lambda x, phi: pallas_hc._coeff_fwd(x, phi, c.hc_eps, how)[0])
        f.defvjp(lambda x, phi: pallas_hc._coeff_fwd(x, phi, c.hc_eps, how),
                 lambda kept, g: pallas_hc._coeff_bwd(c.hc_eps, how, kept, g,
                                                      products_bwd=kernel_bwd(t, a.tiny)))
        return f

    for tm, tk in [own[:2]] + (SWEEP if a.sweep and not a.tiny else []):
        if rows % tm == 0 and K % (tk or K) == 0:
            t = own if (tm, tk) == own[:2] else pallas_hc.tiles_of(tm, tk or K)
            forms[f"kernel.{tm}x{tk or K}"] = (
                lambda x, phi, t=t: pallas_hc.coeff_product(
                    x, phi, c.hc_eps, "kernel", tiles=t))
            forms[f"pair.{tm}x{tk or K}"] = pair(t)

    def mixes_loss(m, hc):
        pre, post, res = model._hc_mixes(hc, m)
        return jnp.sum(pre) + jnp.sum(post * post) + jnp.sum(res * res)

    def whole_loss(X, hc):
        pre, post, res = model._hc_coefficients(hc, X)
        return jnp.sum(pre) + jnp.sum(post * post) + jnp.sum(res * res)

    def pre_read(X, pre):
        return sum(pre[i][..., None] * s for i, s in enumerate(model._hc_streams(X))).astype(X.dtype)

    # a pass's streams: x scaled by a power of two (the same values to the bit)
    xs = [x * 2.0 ** -i for i in range(a.repeats)]
    ms = [jax.jit(forms["plain"])(x, phi) + 0.01 * i for i, x in enumerate(xs)]
    pre0 = jax.nn.sigmoid(ms[0][:n])
    readings = {}           # name -> (fn, firsts, the other arguments, bytes a pass must move)
    once = x.size * x.dtype.itemsize
    for name, f in forms.items():
        readings[name + ".fwd"] = (f, xs, (phi,), once)
        readings[name + ".fwd_bwd"] = (
            jax.value_and_grad(lambda x, phi, f=f: jnp.sum(w * f(x, phi)), (0, 1)),
            xs, (phi,), 3 * once)
    readings["mixes.fwd"] = (lambda m, hc: model._hc_mixes(hc, m), ms, (hc,), 0)
    readings["mixes.fwd_bwd"] = (jax.value_and_grad(mixes_loss, (0, 1)), ms, (hc,), 0)
    readings["whole.fwd"] = (lambda X, hc: model._hc_coefficients(hc, X), xs, (hc,), once)
    readings["whole.fwd_bwd"] = (jax.value_and_grad(whole_loss, (0, 1)), xs, (hc,), 3 * once)
    readings["pre.fwd"] = (pre_read, xs, (pre0,), once + once // n)

    ref, errs, loops = {}, {}, {}
    for name, (fn, firsts, rest, _) in readings.items():        # compile, warm, compare
        loops[name] = repeated(fn)
        out = jax.block_until_ready(loops[name](firsts, *rest))[0]
        form, kind = name.rsplit(".", 1)
        if form in forms:
            got = [out] if kind == "fwd" else [out[1][0], out[1][1]]
            if form == "plain":
                ref[kind] = got
            errs[name] = [float(jnp.max(jnp.abs(g.astype(F32) - r.astype(F32)))
                                / jnp.max(jnp.abs(r.astype(F32))))
                          for g, r in zip(got, ref[kind])]
        del out
    best = {name: float("inf") for name in readings}
    for _ in range(a.windows):
        for name, (_, firsts, rest, _) in readings.items():
            t = time.perf_counter()
            for _ in range(a.calls):
                out = loops[name](firsts, *rest)
            jax.block_until_ready(out)
            best[name] = min(best[name], (time.perf_counter() - t) / a.calls / a.repeats)
            del out
    os.makedirs("chiprun_out", exist_ok=True)
    device = jax.devices()[0]
    with open("chiprun_out/hc_coeff_ab.jsonl", "a") as out:
        for name, (_, _, _, moved) in readings.items():
            line = {"reading": name, "rows": rows, "K": K, "phi": a.phi,
                    "ms": 1e3 * best[name],
                    "floor_ms": 1e3 * moved / HBM_BYTES_PER_S if moved else None,
                    "err": errs.get(name), "device": device.device_kind,
                    "platform": device.platform}
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
