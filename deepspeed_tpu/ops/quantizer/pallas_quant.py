"""Fused quantize+pack Pallas kernel for the int8 collective wire.

The PR 8 transport planner's grad wire (``quantized_reduce_scatter`` /
``quantized_all_gather`` / ``quantized_all_reduce``) quantizes with
``quantize_blockwise``: on the XLA path the group absmax reduction, the
scale select, the round/clip and the int8 cast are separate ops the
compiler may or may not fuse across the reshape boundaries — each miss is
an extra HBM round trip on a buffer that exists only to be put on the
wire. This kernel does the whole pass per group-row block in one launch:
read the fp32 groups once, write the packed int8 payload + fp32 scale
sideband once (the "pack" half: payload and scales emerge launch-ready for
the all-to-all, no separate gather/cast program).

BYTE-IDENTITY CONTRACT: the kernel computes exactly
``quantize_blockwise``'s symmetric int8 math (absmax/127 scale, zero-scale
-> 1, round-half-even, clip [-128, 127]) so the wire payload is
byte-identical to the XLA path — ``DSTPU_COMM_QUANT=0`` and existing
committed wire budgets are untouched. Enforced by
tests/unit/ops/test_opt_kernels.py::TestQuantKernel. The contract is
stated (and tested) for JITTED programs — every wire path runs inside a
jitted shard_map region — because XLA's divide-by-constant rewrite may
differ by one ulp between an eager op-by-op run and any compiled program;
within compiled programs both paths resolve identically.

Dispatch rides ``DSTPU_QUANT_KERNEL`` with the shared semantics of
``DSTPU_OPT_KERNEL`` (``''``=auto: Pallas on TPU, XLA on CPU meshes;
``'xla'``/``'pallas'`` force — see ops/adam/pallas_adam.py). Only the
symmetric int8 lane-aligned case takes the kernel; int4 packing,
asymmetric zero-points and sub-lane group sizes keep the XLA path (they
are not on the default wire). Dequantize stays XLA on purpose: it feeds
the local sum / consuming matmul directly and fuses there — the quantize
side was the extra pass."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..adam.pallas_adam import opt_kernel_interpret, opt_kernel_mode

_ROW_BLOCK = 32   # group rows per grid step (int8 sublane tile)


def quant_kernel_enabled(group_size: int, num_bits: int,
                         symmetric: bool) -> bool:
    """True when the fused kernel serves this quantization geometry."""
    return (num_bits == 8 and symmetric and group_size % 128 == 0
            and opt_kernel_mode("DSTPU_QUANT_KERNEL") == "pallas")


def _quant_rows_kernel(x_ref, q_out, s_out):
    x = x_ref[:].astype(jnp.float32)           # [bm, gs]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = absmax / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -128, 127).astype(jnp.int8)
    q_out[:] = q
    s_out[:] = scale


def quantize_rows_int8(groups: jax.Array, *, interpret=None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 row quantization of ``groups`` [G, group_size] in
    one fused launch. Returns ``(q int8 [G, gs], scale f32 [G])`` —
    byte-identical to the ``quantize_blockwise`` XLA path. Zero-padded
    rows (added to reach the row-block multiple) quantize to q=0/scale=1
    and are sliced off."""
    if interpret is None:
        interpret = opt_kernel_interpret()
    G, gs = groups.shape
    bm = min(_ROW_BLOCK, G)
    Gp = -(-G // bm) * bm
    x = groups.astype(jnp.float32)
    if Gp != G:
        x = jnp.pad(x, ((0, Gp - G), (0, 0)))
    spec = pl.BlockSpec((bm, gs), lambda i: (i, 0))
    q, s = pl.pallas_call(
        _quant_rows_kernel,
        grid=(Gp // bm,),
        in_specs=[spec],
        # scales leave as a [Gp, 1] column: Mosaic refuses a rank-1 block
        # that is not a multiple of the 128-lane tiling
        out_specs=[spec, pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Gp, gs), jnp.int8),
                   jax.ShapeDtypeStruct((Gp, 1), jnp.float32)],
        interpret=interpret,
        name="quantize_rows_int8",
    )(x)
    return q[:G], s[:G, 0]
