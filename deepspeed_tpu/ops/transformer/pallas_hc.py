"""The hyper-connected streams' coefficients before their sigmoids: one pass
over vec(X) as it lies.

``coeff_product(x [B, S, K], phi [K, C], eps, route) -> m [C, B, S] float32``::

    m = s * (x phi)^T,    s = rsqrt(sum(x^2) / K + eps)    (a position at a time)

(``x`` as the model carries it, three-dimensional: a reshape in front of the
launch would name the fusion that PRODUCES the streams after this scope).

which is ``(x s) phi``, the RMS-normalised streams times ``Phi``
(``TransformerLM._hc_coefficients``), with the row's scale taken OUT of the
product: ``x`` enters the product as it lies. For bfloat16 streams that is a
bf16 x bf16 product with a float32 accumulator, ONE pass of the MXU whose
every product is exact in float32; the normalised ``x`` is a float32 value
and its product at ``Precision.HIGHEST`` six passes over a float32 copy of
the streams. The backward (a ``custom_vjp``) is::

    dx   = s (g phi^T) - (s^3 / K) <g, x phi> x        g = dL/dm  [C, B, S]
    dphi = x^T (s g)^T

**Precision.** Nothing float32 is rounded: a float32 ``phi`` and the float32
``s g`` are cut into three bfloat16 parts that add up to them exactly (8 + 8 +
8 bits of mantissa, as `pallas_segment_sum` cuts a scale) and laid side by side
along the product's narrow dimension (24 columns become 72, nine pairs of
parts 216: still one or two passes of the MXU's 128), so every product is
exact in the float32 accumulator. Streams of another type than bfloat16 (the
CPU tests' and an fp32 job's float32) take the same formula in float32 at
``HIGHEST``, unsplit.

**Routes.** The forward's two reductions over a row, `_products_fwd`:

- ``"kernel"``: the Pallas launch ``hc_coeff_fwd`` reads a ``[tm, tk]`` tile of
  a row of ``x`` once for both the product (``[C', tk] x [tm, tk]^T`` on the
  MXU, the positions in the lanes of the result) and the sum of squares
  (float32, VPU), and
  accumulates over the tiles of K: 0.38 ms at the Xing4 cell's shape, 76 % of
  the chip's bandwidth, where XLA's two fusions take 0.69 and the normalised
  form 0.74.
- ``"xla"``: the same two reductions in ``jax.numpy``.

The backward is XLA's on both routes (`_products_bwd`: one fusion makes ``dx``
from ``x`` and the narrow operands, one ``dphi``). A Pallas launch that made
both from one read of ``x`` was 0.3 ms a pass faster alone and was NOT taken:
with it the cell's step no longer loaded (docs/KERNELS.md, "The streams'
coefficients (PR 56)"; ``tools/hc_coeff_ab.py`` keeps it as a rung).

`choose_route` is the whole decision, a pure function of what a call can
observe (backend, the streams' type, K a multiple of 128, a row's positions
a multiple of a tile, the devices of the live mesh); `choose_tiles` the tiles. Runs in
interpret mode off the TPU.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_gmm import NUM_LANES, VMEM_CAP, _divisors

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = lax.Precision.HIGHEST

#: rows and columns of vec(X) a grid step holds, from the chip (v5e, PR 56;
#: docs/KERNELS.md: every tile from 128 x 14,336 to 1024 x 1024 read within
#: 1 %): the largest divisors up to these
ROW_TILE = 256
K_TILE = 2048
#: a bfloat16 block's rows come in 16s
SUBLANES = 16


class Tiles(NamedTuple):
    tm: int                 # positions of a row of x a grid step holds
    tk: int                 # columns of x a grid step holds
    vmem_limit_bytes: int


def vmem_bytes(tm: int, tk: int, narrow: int = 128) -> int:
    """Upper estimate of the launch's scoped VMEM: x's block twice (the
    pipeline's double buffers), x and its square in float32, and the narrow
    operand's and result's blocks."""
    return 2 * 2 * tm * tk + 2 * 4 * tm * tk + 2 * narrow * (2 * tk + 4 * tm)


def tiles_of(tm: int, tk: int) -> Tiles:
    """``(tm, tk)`` with the scoped VMEM the launch asks for at them."""
    need = vmem_bytes(tm, tk)
    return Tiles(tm, tk, min(VMEM_CAP, need + need // 2 + (8 << 20)))


def choose_tiles(S: int, K: int, *, compiled: bool = True) -> Optional[Tiles]:
    """The launch's tiles from a row's shape alone: the largest divisors up to
    ``ROW_TILE`` and ``K_TILE`` in whole 128s; None where there is none (K off
    the 128-lane layout, S without a 128-multiple divisor). Interpret mode
    (``compiled`` False) takes any divisor of S `pallas_gmm._divisors` names."""
    whole = lambda t: t % NUM_LANES == 0
    tm = next((t for t in _divisors(S, compiled)
               if t <= ROW_TILE and (whole(t) or not compiled)), None)
    tk = next((t for t in _divisors(K, True) if t <= K_TILE and whole(t)), None)
    return None if tm is None or tk is None else tiles_of(tm, tk)


def choose_route(S: int, K: int, dtype, backend: str, devices: int = 1) -> str:
    """The whole decision of :func:`coeff_product`: ``"kernel"`` or ``"xla"``.
    The kernel on a TPU for bfloat16 streams where :func:`choose_tiles` finds
    tiles; the XLA form on the CPU, for streams of another type (float32: the
    product stays at ``HIGHEST``), under a mesh of more than one device (GSPMD
    does not partition a ``pallas_call``) and for every other shape."""
    if backend != "tpu" or devices > 1 or jnp.dtype(dtype) != BF16:
        return "xla"
    return "kernel" if choose_tiles(S, K) is not None else "xla"


# -- the two passes' products -------------------------------------------------

def _fwd_kernel(x_ref, wt_ref, pt_ref, ss_ref):
    """One ``[tm, tk]`` tile of x: its part of ``wt x^T`` and of ``sum(x^2)``."""
    @pl.when(pl.program_id(2) == 0)
    def _first_of_row_tile():
        pt_ref[...] = jnp.zeros_like(pt_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    x = x_ref[...]
    pt_ref[...] += lax.dot_general(wt_ref[...], x, (((1,), (1,)), ((), ())),
                                   preferred_element_type=F32)
    x32 = x.astype(F32)
    ss_ref[...] += jnp.sum(x32 * x32, axis=1, keepdims=True)


def _pad(a, axis: int, to: int):
    """``a`` with zeros up to a multiple of ``to`` along ``axis``."""
    short = -a.shape[axis] % to
    return a if not short else jnp.pad(a, [(0, short if i == axis else 0)
                                           for i in range(a.ndim)])


def _kernel_fwd(x, wt, tiles: Tiles, interpret: bool):
    B, S, K = x.shape
    tm, tk, vmem_limit_bytes = tiles
    C = wt.shape[0]
    wt = _pad(wt, 0, SUBLANES)
    pt, ss = pl.pallas_call(
        _fwd_kernel,
        out_shape=(jax.ShapeDtypeStruct((wt.shape[0], B, S), F32),
                   jax.ShapeDtypeStruct((B, S, 1), F32)),
        grid=(B, S // tm, K // tk),
        in_specs=[pl.BlockSpec((None, tm, tk), lambda b, i, k: (b, i, k)),
                  pl.BlockSpec((wt.shape[0], tk), lambda b, i, k: (0, k))],
        out_specs=(pl.BlockSpec((wt.shape[0], None, tm), lambda b, i, k: (0, b, i)),
                   pl.BlockSpec((None, tm, 1), lambda b, i, k: (b, i, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * S * K * (wt.shape[0] + 1), transcendentals=0,
            bytes_accessed=x.size * x.dtype.itemsize + wt.size * 2 + 4 * B * S * (C + 1)),
        interpret=interpret,
        name="hc_coeff_fwd",
    )(x, wt)
    return pt[:C], ss[..., 0]


def _dot(spec: str, a, b):
    """``einsum(spec, a, b)`` into float32: float32 operands at ``HIGHEST``,
    bfloat16 ones in one pass (every product exact). The CPU's dot has no
    bf16 x bf16 -> f32 at every size: there they go in as float32."""
    if a.dtype == BF16 and jax.default_backend() == "cpu":
        a, b = a.astype(F32), b.astype(F32)
    return jnp.einsum(spec, a, b, precision=HIGHEST if a.dtype == F32 else None,
                      preferred_element_type=F32)


def _products_fwd(x, wt, how):
    """``(wt x^T [C', B, S], sum(x^2) [B, S])`` in float32; ``how``: the
    kernel's ``(tiles, interpret)`` or None for XLA."""
    if how is not None:
        return _kernel_fwd(x, wt, *how)
    return _dot("ck,bsk->cbs", wt, x), jnp.sum(jnp.square(x.astype(F32)), axis=-1)


def _products_bwd(x, a, bt, gt, coef):
    """``(a bt - coef x [B, S, K] as x, gt x [E, K] float32)`` from ``a [B, S,
    D]``, ``bt [D, K]``, ``gt [E, B, S]`` and ``coef [B, S]``."""
    dx = _dot("bsd,dk->bsk", a, bt) - coef[..., None] * x.astype(F32)
    return dx.astype(x.dtype), _dot("ebs,bsk->ek", gt, x)


# -- the operands, and the custom_vjp -----------------------------------------

def _parts(a, axis: int, one_pass: bool):
    """``a``'s bfloat16 parts side by side along ``axis`` -> ``(stacked,
    parts)``: a bfloat16 ``a`` is its own one part, a float32 one three that
    add up to it exactly; as it is (one part) where the products are not the
    one-pass kind."""
    if not one_pass or a.dtype == BF16:
        return a, 1
    # (`reduce_precision`, not a cast there and back: XLA may keep the excess
    # precision of such a pair, and the three parts would be one rounding)
    parts, rest = [], a.astype(F32)
    for _ in range(3):
        parts.append(lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7))
        rest = rest - parts[-1]
    return jnp.concatenate(parts, axis=axis).astype(BF16), 3


def _whole(stacked, parts: int, axis: int):
    """The sum of the ``parts`` groups `_parts` laid along ``axis``."""
    if parts == 1:
        return stacked
    shape = stacked.shape[:axis] + (parts, -1) + stacked.shape[axis + 1:]
    return jnp.sum(stacked.reshape(shape), axis=axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _coeff_product(x, phi, eps: float, how):
    return _coeff_fwd(x, phi, eps, how)[0]


def _multiplied(x, phi):
    """``(x, phi, one_pass)`` as the products take them: bfloat16 streams as
    they are (one pass; Phi in parts), any other type with Phi in float32."""
    one_pass = x.dtype == BF16
    return (x, phi, True) if one_pass else (x.astype(F32), phi.astype(F32), False)


def _coeff_fwd(x, phi, eps, how):
    """-> ``(m, (x, phi, s, p))``: the streams and Phi are kept as they came
    (a float32 copy is made again in the backward, not held)."""
    kept = (x, phi)
    x, phi, one_pass = _multiplied(x, phi)
    wt, parts = _parts(phi.T, 0, one_pass)
    pt, ss = _products_fwd(x, wt, how)
    p = _whole(pt, parts, 0)
    s = lax.rsqrt(ss / x.shape[-1] + eps)
    return s[None] * p, kept + (s, p)


def _coeff_bwd(eps, how, kept, g, products_bwd=_products_bwd):
    """``products_bwd``: the microbenchmark's rung in `_products_bwd`'s place."""
    x, phi, s, p = kept
    x_dtype, phi_dtype = x.dtype, phi.dtype
    x, phi, one_pass = _multiplied(x, phi)
    g = g.astype(F32)
    coef = s ** 3 / x.shape[-1] * jnp.sum(g * p, axis=0)
    gt, g_parts = _parts(s[None] * g, 0, one_pass)          # [E, B, S]
    bt, phi_parts = _parts(phi.T, 0, one_pass)              # [C', K]
    # dx's product wants every part of s g against every part of Phi
    a = jnp.moveaxis(gt, 0, -1)
    a = jnp.concatenate([a] * phi_parts, axis=-1) if phi_parts > 1 else a
    bt = (jnp.repeat(bt.reshape(phi_parts, -1, bt.shape[1]), g_parts, axis=0)
          .reshape(-1, bt.shape[1]) if g_parts > 1 else bt)
    dx, dwt = products_bwd(x, a, bt, gt, coef)
    return dx.astype(x_dtype), _whole(dwt, g_parts, 0).T.astype(phi_dtype)


_coeff_product.defvjp(_coeff_fwd, _coeff_bwd)


def coeff_product(x: jax.Array, phi: jax.Array, eps: float, route: str, *,
                  tiles: Optional[Tiles] = None,
                  interpret: Optional[bool] = None) -> jax.Array:
    """``rsqrt(mean(x^2) + eps) * (x phi)`` over ``x [B, S, K]`` -> ``[C, B, S]``
    float32 with its hand-written backward (the module's docstring), by
    ``route`` (:func:`choose_route`'s, or a test's or the microbenchmark's
    own). ``tiles`` / ``interpret``: the launch's tiles where they are not
    :func:`choose_tiles`', and interpret mode, the default off the TPU."""
    if route == "xla":
        return _coeff_product(x, phi, float(eps), None)
    if x.dtype != BF16:
        raise NotImplementedError(f"the kernel takes bfloat16 streams, not {x.dtype}")
    interp = jax.default_backend() == "cpu" if interpret is None else interpret
    tiles = tiles or choose_tiles(*x.shape[1:], compiled=not interp)
    if tiles is None:
        raise NotImplementedError(f"no tiles for streams {list(x.shape)}")
    return _coeff_product(x, phi, float(eps), (tiles, bool(interp)))
