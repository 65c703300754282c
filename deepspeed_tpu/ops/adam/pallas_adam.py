"""Fused Pallas optimizer-update kernels: Adam/AdamW (LAMB rides the same
kernel with a trust-ratio epilogue).

TPU-native replacement for the per-leaf elementwise ``update()`` tree in
``runtime/optimizers.py`` — the port target named by the SNIPPETS header
(rewrite ``csrc/adam/multi_tensor_adam.cu`` as a Pallas kernel). One launch
serves a flat dtype-BUCKET of leaves (the fused-buffer discipline of
``runtime/zero/overlap.py``: small leaves concatenate into one lane-padded
flat buffer, huge leaves stand alone), reading grad + fp32 master + both
moments once, computing the whole chain in fp32 in-register, and writing

- the new fp32 master,
- the bf16 compute-param cast (same pass — no separate recast program),
- both moments at their STORED dtype with **in-kernel stochastic
  rounding** for bf16 stores,

collapsing the ~6 HBM round-trips per leaf per slot the XLA elementwise
tree could pay (g, p, m, v read + m, v, p, cast written across fusion
boundaries) to one read/write per buffer. The fusion discipline is
EQuARX's (arXiv:2506.17615) applied to the moment update: do the
narrow-width math inside the launch instead of as separate XLA ops.

Stochastic rounding
-------------------
The SR noise comes from an in-kernel counter-based hash PRNG
(triple32-style xorshift-multiply over ``seed ^ element_index``), seeded
from ``(step, slot, bucket)`` — replacing the host-side ``_sr_to_bf16``
tree pass and its per-leaf ``fold_in`` keys. A portable hash is used
instead of ``pltpu.prng_random_bits`` deliberately: the Mosaic PRNG has no
CPU interpret lowering at this jax version, and the hash produces
IDENTICAL bits in interpret and compiled mode, so the fixed-seed
determinism tests pin the exact draws production uses. The rounding rule
matches ``_sr_to_bf16`` exactly (add uniform low 16 bits, truncate), so
both paths are unbiased with the same variance; only the draw realization
differs (covered by the mean-preservation tests on BOTH paths,
tests/unit/ops/test_opt_kernels.py).

Dispatch
--------
``DSTPU_OPT_KERNEL`` gates every step path (fused engine step, pipelined
ZeRO micro, offload dev-step — all funnel through ``Optimizer.update``):

- ``''`` (default): auto — Pallas on TPU backends, XLA elementwise tree on
  CPU meshes (the audit mesh and tier-1 run the pre-PR program bitwise).
- ``'xla'``: bitwise escape hatch to the elementwise tree everywhere.
- ``'pallas'``: force the kernel (interpret mode on CPU — the tests' path).

The host numpy backend (``host_adam_step``) serves the legacy
``DeepSpeedCPUAdam`` shim and the ZeRO-Offload runner so the reference API
surface shares ONE statement of the math with the kernel dispatch.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_LANES = 128          # TPU lane width; bucket rows are [R, 128]
_BLOCK_ROWS = 512     # rows per grid step: 64k elems = 256 KB fp32/operand
_SR_SALT = 0x51AB51AB  # matches the 0x51AB key family of _sr_to_bf16


# ---------------------------------------------------------------------------
# dispatch resolution (shared by adam/lion/quantizer kernels)
# ---------------------------------------------------------------------------

def opt_kernel_mode(env_var: str = "DSTPU_OPT_KERNEL") -> str:
    """Resolve an optimizer/quantizer kernel gate to 'pallas' | 'xla'.

    ''/'auto' = Pallas on TPU, XLA elsewhere (CPU meshes keep the escape
    hatch as the DEFAULT, so tier-1 and the audit mesh run the pre-PR
    program bitwise); 'xla' and 'pallas' force."""
    mode = os.environ.get(env_var, "").strip().lower()
    if mode not in ("", "auto", "xla", "pallas"):
        raise ValueError(f"{env_var} must be ''|'auto'|'xla'|'pallas', "
                         f"got {mode!r}")
    if mode in ("xla", "pallas"):
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def opt_kernel_interpret() -> bool:
    """Pallas interpret mode off-TPU (CPU tests compile the kernel body to
    plain HLO — the same program GSPMD partitions for the lint entry)."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# counter-hash PRNG + stochastic rounding
# ---------------------------------------------------------------------------

def _hash32(x):
    """triple32 (Wellons) avalanche hash on uint32 — plain VPU arithmetic,
    identical under interpret and Mosaic compilation."""
    x = x ^ (x >> 17)
    x = x * jnp.uint32(0xED5AD4BB)
    x = x ^ (x >> 11)
    x = x * jnp.uint32(0xAC4C1B51)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x31848BAB)
    x = x ^ (x >> 14)
    return x


def sr_seed(step, slot: int, bucket: int):
    """The (step, slot, bucket) stream seed. ``slot`` follows the
    ``_narrow_state_tree`` numbering (exp_avg=1, exp_avg_sq=2, sum_sq=3)
    so the two SR slots of one step never share a stream; ``bucket`` is
    the launch index within the step. Traced on ``step``."""
    s = jnp.asarray(step, jnp.uint32) ^ jnp.uint32(_SR_SALT)
    s = _hash32(s ^ jnp.uint32((slot * 0x9E3779B9) & 0xFFFFFFFF))
    s = _hash32(s ^ jnp.uint32((bucket * 0x85EBCA6B) & 0xFFFFFFFF))
    return s


def _sr_to_bf16_bits(x_f32, noise_u32):
    """The _sr_to_bf16 rounding rule on explicit noise: add uniform low
    16 bits, truncate to the bf16 prefix. E[stored] == value."""
    bits = jax.lax.bitcast_convert_type(x_f32, jnp.uint32)
    bits = (bits + (noise_u32 & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(jnp.bfloat16)


def _store(x_f32, dtype, seed_scalar, idx_u32, use_sr: bool):
    """Narrow ``x`` to its stored dtype. bf16 stores are stochastically
    rounded from the (seed, element-index) hash stream; everything else is
    the plain RTN cast — exactly ``_narrow_state_tree``'s rule."""
    if use_sr and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16):
        return _sr_to_bf16_bits(x_f32, _hash32(idx_u32 ^ seed_scalar))
    return x_f32.astype(dtype)


def _global_idx(block_elems: int, shape) -> jax.Array:
    """uint32 global element index of each position in the current block
    (stable under block-size changes: index = bucket-flat offset)."""
    base = (pl.program_id(0) * block_elems).astype(jnp.uint32)
    rows = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    return base + rows * jnp.uint32(shape[1]) + cols


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _adam_kernel(g_ref, p_ref, m_ref, v_ref, scal_ref, seed_ref, *out_refs,
                 mode, beta1, beta2, eps, weight_decay,
                 sr_m, sr_v, m_dtype, v_dtype, param_dtype, block_elems):
    """One block of the fused step. ``scal`` = [lr, bcd1, bcd2, gscale]
    (bias-correction DENOMINATORS 1-b^t, matching the elementwise tree's
    division form so the fp32 math is bit-identical to optimizers.py).
    ``mode``: 'adam' (coupled wd) | 'adamw' (decoupled) | 'lamb' (no bias
    correction; emits the un-trust-scaled update for the XLA epilogue)."""
    f32 = jnp.float32
    lr = scal_ref[0]
    bcd1 = scal_ref[1]
    bcd2 = scal_ref[2]
    g = g_ref[:].astype(f32) * scal_ref[3]
    p = p_ref[:].astype(f32)
    m = m_ref[:].astype(f32)
    v = v_ref[:].astype(f32)

    if mode == "adam" and weight_decay:
        g = g + weight_decay * p
    m2 = beta1 * m + (1.0 - beta1) * g
    v2 = beta2 * v + (1.0 - beta2) * g * g

    if mode == "lamb":
        u = m2 / (jnp.sqrt(v2) + eps) + weight_decay * p
        refs = list(out_refs)
        refs.pop(0)[:] = u
    else:
        mhat = m2 / bcd1
        vhat = v2 / bcd2
        u = mhat / (jnp.sqrt(vhat) + eps)
        if mode == "adamw" and weight_decay:
            u = u + weight_decay * p
        p2 = p - lr * u
        refs = list(out_refs)
        refs.pop(0)[:] = p2
        if param_dtype is not None:
            refs.pop(0)[:] = p2.astype(param_dtype)

    idx = _global_idx(block_elems, g.shape) if (sr_m or sr_v) else None
    refs.pop(0)[:] = _store(m2, m_dtype, seed_ref[0], idx, sr_m)
    refs.pop(0)[:] = _store(v2, v_dtype, seed_ref[1], idx, sr_v)


def _pad_to_rows(x: jax.Array, padded: int) -> jax.Array:
    """Flat 1-D -> [R, 128] with inert zero tail padding (zeros are a
    fixed point of every supported update: g=p=m=v=0 -> all outputs 0)."""
    if x.size != padded:
        x = jnp.pad(x.reshape(-1), (0, padded - x.size))
    return x.reshape(padded // _LANES, _LANES)


def bucket_geometry(n: int, block_rows: int = _BLOCK_ROWS
                    ) -> Tuple[int, int, int]:
    """(padded_elems, block_rows, grid) for an n-element flat bucket."""
    rows = -(-n // _LANES)
    bm = min(block_rows, rows)
    rows_p = -(-rows // bm) * bm
    return rows_p * _LANES, bm, rows_p // bm


def adam_bucket_update(grads: jax.Array, master: jax.Array,
                       exp_avg: jax.Array, exp_avg_sq: jax.Array, *,
                       step, lr, beta1: float = 0.9, beta2: float = 0.999,
                       eps: float = 1e-8, weight_decay: float = 0.0,
                       mode: str = "adamw", grad_scale=None,
                       seed_m=None, seed_v=None,
                       m_dtype=jnp.float32, v_dtype=jnp.float32,
                       param_dtype=None, sr: bool = True,
                       block_rows: int = _BLOCK_ROWS,
                       interpret: bool = False, alias: bool = True):
    """One fused step on a flat bucket. Returns
    ``(master_out, param_cast, m_store, v_store)`` where ``master_out`` is
    the new fp32 master for 'adam'/'adamw' and the UN-trust-scaled LAMB
    update for 'lamb' (apply :func:`lamb_trust_epilogue` per leaf);
    ``param_cast`` is None unless ``param_dtype`` is given (or lamb).

    ``alias``: when the bucket needs no padding, the master/moment
    operands alias their outputs (``input_output_aliases``) so the jitted
    caller's donation is a true in-place update — the fp32 moments never
    exist twice at peak. The lint entry ``fused-optimizer-step`` machine-
    checks exactly this via the dead-donation rule."""
    assert grads.ndim == 1, "bucket updates operate on flat buffers"
    assert mode in ("adam", "adamw", "lamb"), mode
    n = grads.shape[0]
    padded, bm, grid = bucket_geometry(n, block_rows)
    stepf = jnp.asarray(step, jnp.float32)
    scal = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        1.0 - jnp.asarray(beta1, jnp.float32) ** stepf,
        1.0 - jnp.asarray(beta2, jnp.float32) ** stepf,
        jnp.asarray(1.0 if grad_scale is None else grad_scale, jnp.float32),
    ])
    zero_seed = jnp.zeros((), jnp.uint32)
    seeds = jnp.stack([zero_seed if seed_m is None else seed_m,
                       zero_seed if seed_v is None else seed_v])

    sr_m = sr and jnp.dtype(m_dtype) == jnp.dtype(jnp.bfloat16)
    sr_v = sr and jnp.dtype(v_dtype) == jnp.dtype(jnp.bfloat16)
    g2 = _pad_to_rows(grads, padded)
    p2 = _pad_to_rows(master, padded)
    m2 = _pad_to_rows(exp_avg, padded)
    v2 = _pad_to_rows(exp_avg_sq, padded)

    spec = pl.BlockSpec((bm, _LANES), lambda i: (i, 0))
    svec = pl.BlockSpec((4,), lambda i: (0,))
    seed_spec = pl.BlockSpec((2,), lambda i: (0,))
    rows_p = padded // _LANES
    shp = lambda dt: jax.ShapeDtypeStruct((rows_p, _LANES), dt)
    lamb = mode == "lamb"
    want_pc = param_dtype is not None and not lamb
    out_shape = [shp(jnp.float32)]
    if want_pc:
        out_shape.append(shp(param_dtype))
    out_shape += [shp(m_dtype), shp(v_dtype)]
    out_specs = [spec] * len(out_shape)

    aliases = {}
    if alias and padded == n:
        # operand indices: g=0 p=1 m=2 v=3; outputs: [p2, (pc), m, v].
        # p/m/v alias in->out when dtypes agree (they always do for the
        # moments — stored dtype in, stored dtype out); the dead grad
        # aliases the param cast when the compute dtype matches.
        pc_off = 1 if want_pc else 0
        if not lamb and jnp.dtype(master.dtype) == jnp.dtype(jnp.float32):
            aliases[1] = 0
        if want_pc and jnp.dtype(grads.dtype) == jnp.dtype(param_dtype):
            aliases[0] = 1
        if jnp.dtype(exp_avg.dtype) == jnp.dtype(m_dtype):
            aliases[2] = 1 + pc_off
        if jnp.dtype(exp_avg_sq.dtype) == jnp.dtype(v_dtype):
            aliases[3] = 2 + pc_off

    outs = pl.pallas_call(
        functools.partial(
            _adam_kernel, mode=mode, beta1=float(beta1), beta2=float(beta2),
            eps=float(eps), weight_decay=float(weight_decay),
            sr_m=sr_m, sr_v=sr_v, m_dtype=jnp.dtype(m_dtype),
            v_dtype=jnp.dtype(v_dtype),
            param_dtype=jnp.dtype(param_dtype) if want_pc else None,
            block_elems=bm * _LANES),
        grid=(grid,),
        in_specs=[spec, spec, spec, spec, svec, seed_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="adam_bucket",
    )(g2, p2, m2, v2, scal, seeds)

    outs = [o.reshape(-1)[:n] for o in outs]
    if lamb:
        return outs[0], None, outs[1], outs[2]
    if want_pc:
        return outs[0], outs[1], outs[2], outs[3]
    return outs[0], None, outs[1], outs[2]


def lamb_trust_epilogue(p_f32: jax.Array, update: jax.Array, *, lr,
                        min_coeff: float, max_coeff: float) -> jax.Array:
    """Per-leaf LAMB trust scaling over one leaf's slice of the bucket
    update (norms are per-LEAF reductions, so they stay an XLA epilogue —
    the elementwise chain that dominated the HBM traffic is in-kernel).
    Mirrors ``Optimizer._lamb_leaf``'s trust clause exactly."""
    w_norm = jnp.linalg.norm(p_f32)
    u_norm = jnp.linalg.norm(update)
    trust = jnp.where((w_norm > 0) & (u_norm > 0),
                      jnp.clip(w_norm / u_norm, min_coeff, max_coeff), 1.0)
    return p_f32 - lr * trust * update


# ---------------------------------------------------------------------------
# host (numpy) backend — the DeepSpeedCPUAdam / ZeRO-Offload statement of
# the same math (one source; the shims route here)
# ---------------------------------------------------------------------------

def host_adam_step(params: np.ndarray, grads: np.ndarray,
                   exp_avg: np.ndarray, exp_avg_sq: np.ndarray, *,
                   step: int, lr: float, beta1: float = 0.9,
                   beta2: float = 0.999, eps: float = 1e-8,
                   weight_decay: float = 0.0, adamw: bool = True) -> None:
    """In-place Adam/AdamW on flat contiguous fp32 host buffers (the
    ZeRO-Offload layout). Same math as :func:`_adam_kernel` mode
    'adam'/'adamw' in the multiply-by-reciprocal form the C++ kernel uses."""
    g = grads if adamw else grads + weight_decay * params
    exp_avg *= beta1
    exp_avg += (1 - beta1) * g
    exp_avg_sq *= beta2
    exp_avg_sq += (1 - beta2) * g * g
    bc1 = 1.0 / (1.0 - beta1 ** step)
    bc2 = 1.0 / (1.0 - beta2 ** step)
    update = (exp_avg * bc1) / (np.sqrt(exp_avg_sq * bc2) + eps)
    if adamw:
        update = update + weight_decay * params
    params -= lr * update


def host_lion_step(params: np.ndarray, grads: np.ndarray,
                   exp_avg: np.ndarray, *, lr: float, beta1: float = 0.9,
                   beta2: float = 0.99, weight_decay: float = 0.0) -> None:
    """In-place Lion on flat fp32 host buffers (see ``host_adam_step``)."""
    c = beta1 * exp_avg + (1 - beta1) * grads
    params -= lr * (np.sign(c) + weight_decay * params)
    exp_avg *= beta2
    exp_avg += (1 - beta2) * grads


def host_adagrad_step(params: np.ndarray, grads: np.ndarray,
                      sq_sum: np.ndarray, *, lr: float, eps: float = 1e-10,
                      weight_decay: float = 0.0) -> None:
    """In-place Adagrad on flat fp32 host buffers (see ``host_adam_step``)."""
    g = grads + weight_decay * params
    sq_sum += g * g
    params -= lr * g / (np.sqrt(sq_sum) + eps)
