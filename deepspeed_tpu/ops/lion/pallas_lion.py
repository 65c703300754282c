"""Fused Pallas Lion update kernel.

TPU-native counterpart of the reference ``csrc/lion`` multi-tensor kernel,
sharing the flat-bucket layout, dispatch gate (``DSTPU_OPT_KERNEL``), SR
hash stream, and aliasing discipline with ``ops/adam/pallas_adam.py`` (see
that module's docstring — this file is the one-moment sibling: Lion reads
grad + fp32 master + exp_avg and writes master, the bf16 compute-param
cast and the SR-narrowed moment in a single pass)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..adam.pallas_adam import (_LANES, _BLOCK_ROWS, _global_idx,
                                _pad_to_rows, _store, bucket_geometry)


def _lion_kernel(g_ref, p_ref, m_ref, scal_ref, seed_ref, *out_refs,
                 beta1, beta2, weight_decay, sr_m, m_dtype, param_dtype,
                 block_elems):
    """One block of the fused Lion step (``Optimizer._lion_leaf`` math:
    sign of the b1-interpolated moment, decoupled wd, b2 EMA store)."""
    f32 = jnp.float32
    lr = scal_ref[0]
    g = g_ref[:].astype(f32) * scal_ref[1]
    p = p_ref[:].astype(f32)
    m = m_ref[:].astype(f32)

    u = jnp.sign(beta1 * m + (1.0 - beta1) * g)
    if weight_decay:
        u = u + weight_decay * p
    p2 = p - lr * u
    m2 = beta2 * m + (1.0 - beta2) * g

    refs = list(out_refs)
    refs.pop(0)[:] = p2
    if param_dtype is not None:
        refs.pop(0)[:] = p2.astype(param_dtype)
    idx = _global_idx(block_elems, g.shape) if sr_m else None
    refs.pop(0)[:] = _store(m2, m_dtype, seed_ref[0], idx, sr_m)


def lion_bucket_update(grads: jax.Array, master: jax.Array,
                       exp_avg: jax.Array, *, lr, beta1: float = 0.9,
                       beta2: float = 0.99, weight_decay: float = 0.0,
                       grad_scale=None, seed_m=None,
                       m_dtype=jnp.float32, param_dtype=None,
                       sr: bool = True, block_rows: int = _BLOCK_ROWS,
                       interpret: bool = False, alias: bool = True):
    """One fused Lion step on a flat bucket. Returns
    ``(master_f32, param_cast_or_None, m_store)``; aliasing/padding
    semantics identical to :func:`~..adam.pallas_adam.adam_bucket_update`."""
    assert grads.ndim == 1, "bucket updates operate on flat buffers"
    n = grads.shape[0]
    padded, bm, grid = bucket_geometry(n, block_rows)
    scal = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(1.0 if grad_scale is None else grad_scale, jnp.float32),
    ])
    seeds = jnp.stack([jnp.zeros((), jnp.uint32) if seed_m is None
                       else seed_m])
    sr_m = sr and jnp.dtype(m_dtype) == jnp.dtype(jnp.bfloat16)
    g2 = _pad_to_rows(grads, padded)
    p2 = _pad_to_rows(master, padded)
    m2 = _pad_to_rows(exp_avg, padded)

    spec = pl.BlockSpec((bm, _LANES), lambda i: (i, 0))
    svec = pl.BlockSpec((2,), lambda i: (0,))
    seed_spec = pl.BlockSpec((1,), lambda i: (0,))
    rows_p = padded // _LANES
    shp = lambda dt: jax.ShapeDtypeStruct((rows_p, _LANES), dt)
    want_pc = param_dtype is not None
    out_shape = [shp(jnp.float32)]
    if want_pc:
        out_shape.append(shp(param_dtype))
    out_shape.append(shp(m_dtype))

    aliases = {}
    if alias and padded == n:
        # operands: g=0 p=1 m=2; outputs: [p2, (pc), m]
        if jnp.dtype(master.dtype) == jnp.dtype(jnp.float32):
            aliases[1] = 0
        if want_pc and jnp.dtype(grads.dtype) == jnp.dtype(param_dtype):
            aliases[0] = 1
        if jnp.dtype(exp_avg.dtype) == jnp.dtype(m_dtype):
            aliases[2] = 2 if want_pc else 1

    outs = pl.pallas_call(
        functools.partial(
            _lion_kernel, beta1=float(beta1), beta2=float(beta2),
            weight_decay=float(weight_decay), sr_m=sr_m,
            m_dtype=jnp.dtype(m_dtype),
            param_dtype=jnp.dtype(param_dtype) if want_pc else None,
            block_elems=bm * _LANES),
        grid=(grid,),
        in_specs=[spec, spec, spec, svec, seed_spec],
        out_specs=[spec] * len(out_shape),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="lion_bucket",
    )(g2, p2, m2, scal, seeds)

    outs = [o.reshape(-1)[:n] for o in outs]
    if want_pc:
        return outs[0], outs[1], outs[2]
    return outs[0], None, outs[1]
