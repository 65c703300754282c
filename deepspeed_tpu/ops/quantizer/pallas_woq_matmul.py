"""Builder-written Pallas weight-only-quantized matmul kernel.

The TPU counterpart of the reference's dequant+GEMM inference kernels
(``inference/v2/kernels/core_ops/cuda_linear`` and
``csrc/quantization``): int8 groupwise-quantized weights stream
HBM→VMEM at ONE byte per element and are dequantized in-register inside
the matmul — the bf16 weight tensor never exists in HBM.

Why this kernel exists (tools/woq_matmul_ab.py on an earlier machine's
v5e, 2026-07-31; not measured on the current machine): at decode shapes
(M=8, llama2-7b MLP dims) XLA's einsum form of the same math runs 1.5x
SLOWER than plain bf16-dense — the int8→bf16 convert + per-group partial
products do not fuse into the dot's operand stream, so quantization
saves HBM *capacity* but loses *latency*. Fusing the dequant into the
matmul's VMEM pipeline makes the weight traffic half of dense, which is
the whole point of WOQ serving on a bandwidth-bound decode.

Measured outcome on the attached chip (chained-scan probe, interleaved,
best-of-3): dense bf16 1.13 ms/step, XLA int8 1.58, THIS KERNEL 1.48
(shallow per-group dots, bn 5504/2048), deep-dot variants 1.64-1.74.
The kernel beats the XLA quantized path (~7%) but not dense — every
path sits ~5-10x above its HBM-bandwidth ideal, i.e. this environment
imposes a per-matmul floor that dominates decode shapes (the same floor
the paged-decode crossover hit). Disposition mirrors that kernel:
parity-tested, opt-in via ``DSTPU_PALLAS_WOQ=1`` in
``quantized_matmul``, default XLA until the floor is re-measured on a
direct-attached TPU.

Layout contract (the ``quantize_kernel`` format, quantization.py:73):
  q     [G, gs, N] int8/int4    scale [G, 1, N]
  x     [M, K]  (K = G*gs)  →  out [M, N] = Σ_g (x_g @ q_g) · scale_g

Grid: (N / bn, G) — G minor, so each n-tile's group partials accumulate
sequentially in a VMEM f32 scratch (TPU-guaranteed grid order); the
tile writes out once at g == G-1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128   # minor-dim granularity for every block
_MIN_M = 16   # bf16 sublane minimum: x rows pad up to 16


def _woq_kernel(x_ref, q_ref, s_ref, out_ref, acc_ref):
    """One program: gk groups of K against one N tile; the int8 block is
    dequantized (convert + per-group scale) in VMEM, one dot per block.
    The DEFAULT is gk=1 (shallow, one gs-deep dot per program) — the
    measured-fastest form on the attached chip (1.48 ms/step vs 1.64-1.74
    for deeper bk tiles; see module docstring) — deeper tiles are the
    ``bk`` experiment knob. Either way HBM moves one byte per weight."""
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                   # [Mp, gk*gs] bf16
    q = q_ref[...]                                   # [gk, gs, bn] int8
    gk, gs, bn = q.shape
    if gk == 1:
        # shallow form (the default, measured fastest): scale the PARTIAL
        # PRODUCT — M*bn multiplies instead of gs*bn on the weight tile
        part = jax.lax.dot_general(
            x, q[0].astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [Mp, bn]
        acc_ref[...] += part * s_ref[0].astype(jnp.float32)
    else:
        # deep form (bk experiment knob): dequant the block in VMEM so
        # one bk-deep dot replaces gk shallow ones
        w = q.astype(x.dtype) * s_ref[...].astype(x.dtype)
        acc_ref[...] += jax.lax.dot_general(
            x, w.reshape(gk * gs, bn), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [Mp, bn]

    @pl.when(kb == pl.num_programs(1) - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _pick_bn(n: int, gs: int, mp: int = _MIN_M,
             vmem_budget: int = 1100 * 1024) -> int:
    """Largest lane-multiple tile of N that divides it and keeps the int8
    weight block + f32 accumulator (sized with the ACTUAL padded M, not
    the minimum) comfortably inside VMEM."""
    if n % _LANE:
        raise ValueError(f"N={n} is not a multiple of {_LANE}")
    best = 0
    for mult in range(1, n // _LANE + 1):
        bn = mult * _LANE
        if n % bn:
            continue
        if gs * bn + 4 * mp * bn > vmem_budget:
            break
        best = bn
    if not best:
        raise ValueError(
            f"no N tile fits the VMEM budget: even bn={_LANE} needs "
            f"{gs * _LANE + 4 * mp * _LANE} bytes (gs={gs}, Mp={mp}) > "
            f"{vmem_budget}; reduce the quantization group size or M")
    return best


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bk"))
def woq_matmul(x: jax.Array, q: jax.Array, scale: jax.Array,
               interpret: bool = False, bn: int | None = None,
               bk: int | None = None) -> jax.Array:
    """x [M, K] @ groupwise-quantized [K, N] weights -> [M, N].

    ``q`` [G, gs, N] int8, ``scale`` [G, 1, N] (the quantize_kernel
    format). M is padded to the bf16 sublane minimum internally. ``bn``
    overrides the N tile (must divide N; lane multiple); ``bk`` the K
    tile (a multiple of gs dividing K).
    """
    M, K = x.shape
    G, gs, N = q.shape
    assert K == G * gs, (K, G, gs)
    Mp = max(_MIN_M, -(-M // 8) * 8)
    bn = bn or _pick_bn(N, gs, Mp)
    bk = bk or _pick_bk(K, gs)
    gk = bk // gs
    assert bk % gs == 0 and G % gk == 0, (bk, gs, G)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))

    out = pl.pallas_call(
        _woq_kernel,
        grid=(N // bn, G // gk),
        in_specs=[
            pl.BlockSpec((Mp, bk), lambda n, kb: (0, kb)),
            pl.BlockSpec((gk, gs, bn), lambda n, kb: (kb, 0, n)),
            pl.BlockSpec((gk, 1, bn), lambda n, kb: (kb, 0, n)),
        ],
        out_specs=pl.BlockSpec((Mp, bn), lambda n, kb: (0, n)),
        scratch_shapes=[
            pltpu.VMEM((Mp, bn), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
        interpret=interpret,
        name="woq_matmul",
    )(x, q, scale)
    return out[:M]


def _pick_bk(k: int, gs: int) -> int:
    """Default K tile = one group (the shallow form). Deeper tiles trade
    per-group dots for one deep dot after a VMEM dequant — measured
    SLOWER on the attached v5e (1.64-1.74 vs 1.48 ms/step at llama MLP
    decode shapes, 2026-07-31), so depth is opt-in via the bk argument."""
    del k
    return gs
