"""In-repo Pallas TPU sorted segment sum: a chip's share's rows back to tokens.

``segment_sum(rows [m, h], segment [m], scale [m] | None, filled, segments)
-> [segments, h] float32``::

    out[t] = sum over the rows r < filled with segment[r] == t of scale[r] x rows[r]

The rows stand in segment order (``segment`` does not fall over the first
``filled`` rows), which is what ``moe/layer.py::MoE._held_rows`` brings the
held experts' buffer into by ONE gather of its ``cap`` rows; a token is a
segment and has at most ``top_k`` rows. Both movements of that path use it:
the combine with ``scale`` the routing weight of each row, the dispatch's
backward without. Rows from ``filled`` on may hold anything (a grouped
matmul's rows past its last group are no number on the TPU): they are
SELECTED away, never multiplied by 0.

The kernel goes over the rows once, a tile of ``tr`` at a time, and sums
them on the MXU against a one-hot band: a visit is one (segment tile, row
tile) pair that share a row, ``[ts, tr] x [tr, h]``, accumulated in the
segment tile's float32 output block, which stays in VMEM while the visits
stay in the tile. The visits are ``pallas_gmm._visits`` with the segment
tiles as its groups (a tile without rows is visited once, to be written as
zeros), so the grid has the static length ``m / tr + segments / ts``. **A
step's work does not follow the rows**: every step of that grid loads its
blocks and multiplies, the steps past the last visit against a band of
zeros; ``filled`` selects and never shortens.

**Precision.** The one-hot band is exact in any float type and a row enters
as it comes, but a float32 ``scale`` folded into the band would be rounded to
bfloat16 by the MXU. It is cut into three bfloat16 parts that add up to it
exactly (8 + 8 + 8 bits of mantissa) and the band is multiplied once by
each: every product ``part x row`` is exact in the float32 accumulator, so
the result is the float32 sum of ``scale x row`` to the last bits, as the
XLA form's. Without ``scale`` it is one product.

``choose_route`` is the whole decision between this kernel and the XLA form
(the same rows through ``jax.ops.segment_sum``), ``choose_tiles`` the tiles;
both are pure functions of what a call can observe. docs/KERNELS.md, "The
segment sum kernel (PR 38)", has the chip readings. Runs in interpret mode
off the TPU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_gmm import NUM_LANES, VMEM_BUDGET, VMEM_CAP, _divisors, _visits

#: segments a visit sums into and rows it sums, from the chip (v5e, PR 38;
#: docs/KERNELS.md): the band's product costs ``ts x tr`` a row tile and the
#: grid ``m / tr + segments / ts`` steps
SEGMENT_TILE = 128
ROW_TILE = 256


def vmem_bytes(ts: int, tr: int, h: int, itemsize: int) -> int:
    """Upper estimate of the kernel's scoped VMEM: the row block and the
    output block twice (the pipeline's double buffers), the selected rows,
    a product in float32 beside the accumulator and the band's parts."""
    return (2 * itemsize * tr * h + 2 * 4 * ts * h + itemsize * tr * h
            + 2 * 4 * ts * h + 4 * 4 * ts * tr)


def choose_tiles(m: int, segments: int, h: int, itemsize: int = 2, *,
                 compiled: bool = True) -> Optional[Tuple[int, int, int]]:
    """``(ts, tr, vmem_limit_bytes)``: the segment tile, the row tile and the
    scoped VMEM the kernel asks for, from the shape alone; None where no
    legal tiling exists (a width off the 128-lane layout, ``m`` or
    ``segments`` without a 128-multiple divisor, blocks over the budget).
    Interpret mode (``compiled`` False) takes any width and power-of-two
    tiles from 8."""
    if compiled and h % NUM_LANES:
        return None
    ts = next((t for t in _divisors(segments, compiled) if t <= SEGMENT_TILE), None)
    tr = next((t for t in _divisors(m, compiled) if t <= ROW_TILE), None)
    if ts is None or tr is None:
        return None
    need = vmem_bytes(ts, tr, h, itemsize)
    if need > VMEM_BUDGET:
        return None
    return ts, tr, min(VMEM_CAP, need + need // 2 + (8 << 20))


def choose_route(m: int, segments: int, h: int, dtype, backend: str,
                 devices: int) -> str:
    """The whole decision of :func:`segment_sum`: ``"kernel"`` or ``"xla"``,
    from the shape, the rows' type, the platform and the devices of the live
    mesh. The kernel on a TPU for 16-bit floats (a float32 row would be
    rounded by the MXU) where :func:`choose_tiles` finds tiles; the XLA form
    on the CPU, under a mesh of more than one device (GSPMD does not
    partition a ``pallas_call``) and for every other shape."""
    if backend != "tpu" or devices > 1:
        return "xla"
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize != 2:
        return "xla"
    if choose_tiles(m, segments, h, dtype.itemsize) is None:
        return "xla"
    return "kernel"


def _three_parts(scale):
    """float32 -> three float32 values, each one a bfloat16 holds, that add
    up to it exactly."""
    parts, rest = [], scale
    for _ in range(3):
        part = rest.astype(jnp.bfloat16).astype(jnp.float32)
        parts.append(part)
        rest = rest - part
    return parts


def _kernel(ends, group_of, tile_of, count, segment_ref, scale_ref, rows_ref,
            out_ref, *, ts: int, tr: int, scaled: bool):
    """One visit: the row tile's rows of the segment tile, summed into it."""
    s = pl.program_id(0)
    group = group_of[s]
    row0 = tile_of[s] * tr
    hi = ends[group]                # the row past the segment tile's last
    live = s < count[0]

    @pl.when((s == 0) | (group_of[jnp.maximum(s - 1, 0)] != group))
    def _first_of_tile():
        out_ref[...] = jnp.zeros_like(out_ref)

    mine = ((segment_ref[...] == group * ts + lax.broadcasted_iota(jnp.int32, (ts, tr), 0))
            & (row0 + lax.broadcasted_iota(jnp.int32, (1, tr), 1) < hi) & live)
    # selected in float32 (a 16-bit select wants its mask in another layout)
    if scaled:
        bands = [jnp.where(mine, part, 0.0) for part in _three_parts(scale_ref[...])]
    else:
        bands = [mine.astype(jnp.float32)]

    def add(x):
        for band in bands:
            out_ref[...] += jnp.dot(band.astype(x.dtype), x,
                                    preferred_element_type=jnp.float32)

    # the rows from ``filled`` on are nobody's and possibly no number:
    # selected away before the product (0 x NaN); the tile that holds
    # ``filled`` and the steps past the last visit, which stay on it
    filled = ends[ends.shape[0] - 1]

    @pl.when(row0 + tr <= filled)
    def _whole():
        add(rows_ref[...])

    @pl.when(row0 + tr > filled)
    def _cut():
        x = rows_ref[...]
        keep = row0 + lax.broadcasted_iota(jnp.int32, (tr, 1), 0) < filled
        add(jnp.where(keep, x.astype(jnp.float32), 0.0).astype(x.dtype))


@functools.partial(jax.jit, static_argnames=("segments", "tiles", "interpret"))
def _call(rows, segment, scale, filled, *, segments: int,
          tiles: Tuple[int, int, int], interpret: bool):
    m, h = rows.shape
    ts, tr, vmem_limit_bytes = tiles
    at = jnp.arange(m, dtype=jnp.int32)
    segment = jnp.where(at < filled, segment.astype(jnp.int32), segments)
    # rows a segment tile ends at: those of the segments before the next tile
    upto = jnp.sum(segment[None, :] < (jnp.arange(1, segments // ts + 1) * ts)[:, None],
                   axis=1, dtype=jnp.int32)
    _, ends, group_of, tile_of, count = _visits(
        jnp.diff(upto, prepend=0), m, tr, tail=False, empty=True)
    scaled = scale is not None
    lanes = lambda v: v.reshape(m // tr, 1, tr)
    by_tile = pl.BlockSpec((None, 1, tr), lambda s, en, go, to, ct: (to[s], 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, ts=ts, tr=tr, scaled=scaled),
        out_shape=jax.ShapeDtypeStruct((segments, h), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(m // tr + segments // ts,),
            in_specs=[by_tile, by_tile,
                      pl.BlockSpec((tr, h), lambda s, en, go, to, ct: (to[s], 0))],
            out_specs=pl.BlockSpec((ts, h), lambda s, en, go, to, ct: (go[s], 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem_limit_bytes),
        cost_estimate=pl.CostEstimate(
            flops=2 * (3 if scaled else 1) * (m // tr + segments // ts) * ts * tr * h,
            transcendentals=0,
            bytes_accessed=rows.size * rows.dtype.itemsize + 4 * segments * h + 8 * m),
        interpret=interpret,
        name="segment-sum",
    )(ends, group_of, tile_of, count, lanes(segment),
      lanes(scale.astype(jnp.float32) if scaled else jnp.zeros((m,), jnp.float32)), rows)


def kernel_segment_sum(rows: jax.Array, segment: jax.Array, scale: Optional[jax.Array],
                       filled, segments: int,
                       tiles: Optional[Tuple[int, int, int]] = None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """The kernel route of :func:`segment_sum` whatever ``choose_route`` says
    (tests, the microbenchmark); ``tiles`` default to :func:`choose_tiles`',
    in interpret mode off the TPU."""
    m, h = rows.shape
    interp = jax.default_backend() == "cpu" if interpret is None else interpret
    if tiles is None:
        tiles = choose_tiles(m, segments, h, rows.dtype.itemsize, compiled=not interp)
    if tiles is None:
        raise NotImplementedError(f"no tiles for rows [{m}, {h}] -> [{segments}, {h}]")
    return _call(rows, segment, scale, filled, segments=segments, tiles=tiles,
                 interpret=bool(interp))


def xla_segment_sum(rows: jax.Array, segment: jax.Array, scale: Optional[jax.Array],
                    filled, segments: int) -> jax.Array:
    """The same sum in plain ``jax.numpy``: each row in float32, times its
    ``scale``, added to its segment; the rows from ``filled`` on go nowhere."""
    real = jnp.arange(rows.shape[0]) < filled
    x = rows.astype(jnp.float32)
    if scale is not None:
        x = x * scale.astype(jnp.float32)[:, None]
    x = jnp.where(real[:, None], x, 0.0)
    return jax.ops.segment_sum(x, jnp.where(real, segment, segments),
                               num_segments=segments + 1,
                               indices_are_sorted=True)[:segments]


def segment_sum(rows: jax.Array, segment: jax.Array, scale: Optional[jax.Array],
                filled, segments: int, devices: int = 1) -> jax.Array:
    """``rows [m, h]`` in segment order, summed by segment over the first
    ``filled`` of them, each times its ``scale`` (float32; None: as it is)
    -> ``[segments, h]`` float32: the kernel where :func:`choose_route` says
    so, else :func:`xla_segment_sum`. ``devices``: those of the live mesh."""
    m, h = rows.shape
    if choose_route(m, segments, h, rows.dtype, jax.default_backend(), devices) == "kernel":
        return kernel_segment_sum(rows, segment, scale, filled, segments)
    return xla_segment_sum(rows, segment, scale, filled, segments)
