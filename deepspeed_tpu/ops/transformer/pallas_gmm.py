"""In-repo Pallas TPU grouped matmul: the no-drop expert path's products.

``grouped_matmul(rows [m, k], stack [g, k, n], group_sizes [g]) -> [m, n]``
multiplies each run of rows by its group's matrix: rows
``[sum(sizes[:i]), sum(sizes[:i+1]))`` by ``stack[i]``, what
``jax.lax.ragged_dot`` computes. ``moe/layer.py::MoE.dropless_forward`` sorts
a step's assignments by expert and calls it three times a layer; its backward
is two more products a call, so a step launches nine a layer (twelve where the
block's backward runs the forward again). Three kernels, bound with
``jax.custom_vjp`` whose residuals are the operands and nothing else:

- forward, ``rows x stack[group]``;
- row gradient, the same kernel against the transposed stack
  (``d_out x stack[group]^T``): the block is taken as it lies and contracted
  on its last axis inside the kernel, so no transposed copy of the stack is
  ever written;
- weight gradient, ``rows[group]^T x d_out[group] -> [g, k, n]``, which
  contracts over the ragged rows and accumulates in float32 in VMEM.

The design is the one ``jax.experimental.pallas.ops.tpu.megablox`` documents.
The rows are cut into tiles of ``tm``; the grid's last axis walks the VISITS
in row order, one visit a (group, row tile) pair that share a row, so a tile
that lies inside one group is visited once, as one matmul, and a tile that
several groups share once by each, multiplied in parts of 128 rows (those the
group has a row in) with the rows of the others masked. Which group and which
tile a visit is comes from four small integer arrays computed by XLA from
``group_sizes`` and handed to the kernel as scalar prefetch (``_visits``), so
the index maps can read them: consecutive visits inside one group name the
same block of the stack, which the pipeline then does not fetch again, and
consecutive visits of one row tile name the same output block, which is
written back once. The grid has a static length (``m / tm + g`` covers every
possible load); the steps past the last visit repeat its blocks and do
nothing.

**Rows past the groups' sum** (``group_sizes`` may sum to less than ``m``):
the forward and the row gradient write them as exact zeros and the weight
gradient leaves them out, whatever they hold (``ragged_dot`` leaves such
output rows uninitialised on the TPU). An empty group's slab of the weight
gradient is exactly 0. ``group_sizes`` summing to more than ``m`` is the
caller's error, as it is for ``ragged_dot``; the offsets are held to ``m``, so
nothing is read or written out of bounds.

Same arithmetic as the XLA form: operands as they come (bf16 in the cells),
float32 accumulation, results in the operands' type.

``choose_route`` is the whole decision between this kernel and ``ragged_dot``,
``choose_tiles`` the tiles; both are pure functions of what a call can
observe. docs/KERNELS.md, "The grouped matmul kernel (PR 33)", has the chip
readings behind both. Runs in interpret mode off the TPU.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NUM_LANES = 128
#: the three products of one differentiated call
KINDS = ("forward", "row_gradient", "weight_gradient")
# The launches are named ``ragged-dot-gmm-{fwd,dlhs,dw}``: they trace under
# XLA's own instruction name for a grouped matmul (``ragged-dot-none.N``),
# which is what a reader of the device trace finds a step's products by
# (benchmark/trace/moe.py::PRODUCT, ``^ragged-dot`` and not ``-metadata``).
# The kernel takes that product's place and keeps its signature, as PR 28's
# Adam launch kept the one its reader looks for.


# Scoped VMEM: what the chosen blocks may add up to, and the most a kernel
# asks the compiler for (v5e/v6e hold 128 MiB; the default limit is 16).
VMEM_BUDGET = 48 * 1024 * 1024
VMEM_CAP = 96 * 1024 * 1024
# Row tiles, from the chip (v5e, PR 33; docs/KERNELS.md has the sweeps). A
# tile inside one group is one matmul of its ``tm`` rows: 86-90 % of the MXU's
# peak at 512, 82-87 % at 256, 80-84 % at 128, and 46-53 % and falling from
# 1024 up. A tile that groups share is visited once by each (up to ``g - 1``
# second visits), and multiplied whole it costs ``tm`` rows whatever share is
# the group's: at 64 groups of a mean 512 rows that held 512 to 46-49 % and
# 256 to 54-58 %. Multiplied in parts of ``SUB_ROWS`` (the parts the group has
# a row in; a part under 128 rows fills the MXU no better), a shared tile
# costs what its groups' rows round up to: 58-62 % at 256 and 60-64 % at 512
# at the loads the cell of 64 groups draws, 84-86 % and 84-87 % at 8 groups of
# a mean 4,608 rows. 512 is 2-5 % of a product ahead, under 1 % of either
# cell's step, and its kernels are twice the code and their bodies twice the
# equations to trace: the 8-group cell's first step took 1.8 s longer with no
# throughput to show for it. So 256. The number of groups did not move the
# winner.
ROW_TILE_MAX = 256
SUB_ROWS = 128


@dataclasses.dataclass(frozen=True)
class GmmTiles:
    """Tiles of the three kernels of ``rows [m, k] x stack [g, k, n]``:
    ``fwd`` = (rows, n) of the forward (k whole), ``dlhs`` = (rows, k) of the
    row gradient (n whole), ``dw`` = (rows, k, n) of the weight gradient, and
    the scoped VMEM they ask the compiler for (None = its default)."""
    fwd: Tuple[int, int]
    dlhs: Tuple[int, int]
    dw: Tuple[int, int, int]
    vmem_limit_bytes: Optional[int] = None


# ---------------------------------------------------------------------------
# the schedule: which (group, row tile) each grid step visits
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("m", "tm", "tail", "empty"))
def _visits(group_sizes: jax.Array, m: int, tm: int, *, tail: bool,
            empty: bool):
    """-> (starts, ends, group_of, tile_of, count): the first row and the row
    past the last of every group, and for each of the grid's ``m // tm + g``
    steps the group and the row tile it visits, ``count`` [1] of them real
    (the later ones repeat the last). ``tail``: the rows past the groups' sum
    are one more group, index g, which has no matrix (the kernels that write
    rows write those as zeros). ``empty``: a group without rows is visited
    once all the same (the weight gradient has its slab to zero)."""
    g = group_sizes.shape[0]
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(jnp.int32)), m)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    if tail:
        starts = jnp.concatenate([starts, ends[-1:]])
        ends = jnp.concatenate([ends, jnp.full((1,), m, jnp.int32)])
    first = jnp.minimum(starts // tm, m // tm - 1)
    tiles = jnp.where(ends > starts, (ends - 1) // tm - first + 1,
                      1 if empty else 0)
    upto = jnp.cumsum(tiles)
    count = upto[-1:]
    step = jnp.minimum(jnp.arange(m // tm + g, dtype=jnp.int32), count - 1)
    # the first group whose visits end past the step (groups x steps is small)
    group_of = jnp.sum(upto[None, :] <= step[:, None], axis=1, dtype=jnp.int32)
    tile_of = first[group_of] + step - (upto - tiles)[group_of]
    return starts, ends, group_of, tile_of, count


def _row_mask(shape, row0, lo, hi):
    """[tm, width] bool: the tile's rows that lie in [lo, hi)."""
    rows = row0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= lo) & (rows < hi)


def _part_rows(tm: int, sub_rows: Optional[int] = None) -> int:
    """The rows of a shared tile's parts: ``SUB_ROWS`` (or what a caller
    asks for) where that divides the tile, else the whole tile (interpret
    mode's row tiles need not be 128-multiples)."""
    sub = sub_rows or SUB_ROWS
    return sub if tm % sub == 0 else tm


def _for_shared_parts(tm: int, sub: int, row0, lo, hi, body) -> None:
    """``body(rows, first)`` for each part of ``sub`` rows of the tile at
    ``row0`` that the group [lo, hi) has a row in: ``rows`` the part's slice
    of the tile, ``first`` its first row."""
    for part in range(tm // sub):
        first = row0 + part * sub
        pl.when((first < hi) & (first + sub > lo))(
            functools.partial(body, pl.ds(part * sub, sub), first))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _rows_kernel(starts, ends, group_of, tile_of, count, lhs_ref, rhs_ref,
                 out_ref, *, tm: int, sub: int, groups: int, transposed: bool):
    """One visit of ``lhs[tile] x stack[group]`` (or ``x stack[group]^T``):
    the tile's rows that are the group's are written, the others kept. A
    tile the group shares is multiplied in parts of ``sub`` rows, those the
    group has a row in."""
    s = pl.program_id(1)
    group = group_of[s]
    row0 = tile_of[s] * tm
    lo, hi = starts[group], ends[group]
    live = s < count[0]
    whole = (lo <= row0) & (row0 + tm <= hi)
    real = group < groups

    def product(lhs):
        contract = (((1,), (1,)), ((), ())) if transposed else (((1,), (0,)), ((), ()))
        return lax.dot_general(lhs, rhs_ref[...], contract,
                               preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(live & real & whole)
    def _inside():
        out_ref[...] = product(lhs_ref[...])

    @pl.when(live & real & jnp.logical_not(whole))
    def _shared():
        def part(rows, first):
            mask = _row_mask((sub, out_ref.shape[1]), first, lo, hi)
            out_ref[rows, :] = jnp.where(mask, product(lhs_ref[rows, :]), out_ref[rows, :])
        _for_shared_parts(tm, sub, row0, lo, hi, part)

    @pl.when(live & jnp.logical_not(real))
    def _past_the_groups():
        mask = _row_mask(out_ref.shape, row0, lo, hi)
        out_ref[...] = jnp.where(mask, jnp.zeros_like(out_ref), out_ref[...])


# The two launchers are jitted on their static arguments: a step traces each
# product many times (the block's policy reads its jaxpr twice, then the
# scan, its differentiation and the rematerialised copy), and the same shapes
# come back in every expert layer; the kernel's body and ``_visits`` are then
# traced once a distinct call and lowered once a program. Without it the two
# MoE cells' first step took 2.9 and 6.3 s longer than at ``ragged_dot``
# (chip, PR 33; tracing is Python and the chip's host is slow at it).
@functools.partial(jax.jit, static_argnames=("tile", "transposed", "vmem_limit_bytes",
                                             "interpret", "sub_rows"))
def _rows_call(lhs, stack, group_sizes, tile: Tuple[int, int], *, transposed: bool,
               vmem_limit_bytes: Optional[int], interpret: bool,
               sub_rows: Optional[int] = None):
    """``lhs [m, c] x stack[group]`` -> [m, w]: c and w are the stack's last
    two axes, or its last and its middle one when ``transposed``."""
    m, c = lhs.shape
    g = stack.shape[0]
    w = stack.shape[1] if transposed else stack.shape[2]
    tm, tw = tile
    meta = _visits(group_sizes, m, tm, tail=True, empty=False)

    def stack_idx(j, s, starts, ends, group_of, tile_of, count):
        group = jnp.minimum(group_of[s], g - 1)
        return (group, j, 0) if transposed else (group, 0, j)

    kernel = functools.partial(_rows_kernel, tm=tm, sub=_part_rows(tm, sub_rows),
                               groups=g, transposed=transposed)
    out_dtype = jnp.result_type(lhs.dtype, stack.dtype)
    call = dict(
        out_shape=jax.ShapeDtypeStruct((m, w), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(w // tw, m // tm + g),
            in_specs=[
                pl.BlockSpec((tm, c), lambda j, s, st, en, go, to, ct: (to[s], 0)),
                pl.BlockSpec((None, tw, c) if transposed else (None, c, tw), stack_idx),
            ],
            out_specs=pl.BlockSpec((tm, tw), lambda j, s, st, en, go, to, ct: (to[s], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * c * w, transcendentals=0,
            bytes_accessed=(lhs.size * (w // tw) * lhs.dtype.itemsize
                            + stack.size * stack.dtype.itemsize
                            + m * w * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret)
    if transposed:
        return pl.pallas_call(kernel, name="ragged-dot-gmm-dlhs", **call)(*meta, lhs, stack)
    return pl.pallas_call(kernel, name="ragged-dot-gmm-fwd", **call)(*meta, lhs, stack)


def _weights_kernel(starts, ends, group_of, tile_of, count, lhs_ref, dout_ref,
                    out_ref, acc_ref, *, tm: int, sub: int):
    """One visit of ``lhs[tile]^T x d_out[tile]`` over the group's rows of the
    tile, summed in float32 over the group's visits and written at its last."""
    s = pl.program_id(2)
    last = count[0] - 1
    group = group_of[s]
    row0 = tile_of[s] * tm
    lo, hi = starts[group], ends[group]
    live = s <= last
    whole = (lo <= row0) & (row0 + tm <= hi)
    contract = (((0,), (0,)), ((), ()))

    @pl.when(live & ((s == 0) | (group_of[jnp.maximum(s - 1, 0)] != group)))
    def _first_of_group():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live & whole)
    def _inside():
        acc_ref[...] += lax.dot_general(lhs_ref[...], dout_ref[...], contract,
                                        preferred_element_type=jnp.float32)

    @pl.when(live & jnp.logical_not(whole) & (hi > lo))
    def _shared():
        # both sides masked: a row that is not the group's may hold anything
        def part(rows, first):
            a, b = lhs_ref[rows, :], dout_ref[rows, :]
            a = jnp.where(_row_mask(a.shape, first, lo, hi), a, jnp.zeros_like(a))
            b = jnp.where(_row_mask(b.shape, first, lo, hi), b, jnp.zeros_like(b))
            acc_ref[...] += lax.dot_general(a, b, contract,
                                            preferred_element_type=jnp.float32)
        _for_shared_parts(tm, sub, row0, lo, hi, part)

    nxt = group_of[jnp.minimum(s + 1, pl.num_programs(2) - 1)]

    @pl.when(live & ((s == last) | (nxt != group)))
    def _last_of_group():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype", "vmem_limit_bytes",
                                             "interpret", "sub_rows"))
def _weights_call(lhs, dout, group_sizes, tile: Tuple[int, int, int], *, out_dtype,
                  vmem_limit_bytes: Optional[int], interpret: bool,
                  sub_rows: Optional[int] = None):
    """-> [g, k, n]: ``lhs[rows of group]^T x dout[rows of group]``."""
    m, k = lhs.shape
    n = dout.shape[1]
    g = group_sizes.shape[0]
    tm, tk, tn = tile
    meta = _visits(group_sizes, m, tm, tail=False, empty=True)
    return pl.pallas_call(
        functools.partial(_weights_kernel, tm=tm, sub=_part_rows(tm, sub_rows)),
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(k // tk, n // tn, m // tm + g),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, s, st, en, go, to, ct: (to[s], i)),
                pl.BlockSpec((tm, tn), lambda i, j, s, st, en, go, to, ct: (to[s], j)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda i, j, s, st, en, go, to, ct: (go[s], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * (n // tn) * lhs.dtype.itemsize
                            + dout.size * (k // tk) * dout.dtype.itemsize
                            + g * k * n * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret,
        name="ragged-dot-gmm-dw",
    )(*meta, lhs, dout)


# ---------------------------------------------------------------------------
# tiles and route
# ---------------------------------------------------------------------------

def rows_vmem_bytes(tm: int, c: int, tw: int, itemsize: int) -> int:
    """Upper estimate of the forward's or the row gradient's scoped VMEM:
    the three blocks twice (the pipeline's double buffers) and the product
    in float32 before it is rounded."""
    return 2 * itemsize * (tm * c + c * tw + tm * tw) + 4 * tm * tw


def weights_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """The weight gradient's: both row blocks and the output slab twice, the
    float32 accumulator and a product beside it."""
    return 2 * itemsize * (tm * tk + tm * tn + tk * tn) + 2 * 4 * tk * tn


def _divisors(length: int, compiled: bool):
    """The tiles of one axis, largest first: the whole of it, then every
    128-multiple that divides it (off the chip, every power of two from 8)."""
    steps = (range((length - 1) // NUM_LANES * NUM_LANES, 0, -NUM_LANES) if compiled
             else [2 ** e for e in range(12, 2, -1) if 2 ** e < length])
    return [length] + [t for t in steps if length % t == 0]


def _row_tile(m: int, compiled: bool) -> Optional[int]:
    """The largest legal row tile up to ``ROW_TILE_MAX``."""
    return next((t for t in _divisors(m, compiled) if t <= ROW_TILE_MAX), None)


def choose_tiles(m: int, k: int, n: int, g: int, itemsize: int = 2, *,
                 compiled: bool = True) -> Optional[GmmTiles]:
    """Tiles of the three kernels for ``rows [m, k] x stack [g, k, n]``, from
    the shape alone. The contracted axis is never cut (a visit is one
    matmul, and the stack's block stays in VMEM while the visits stay in a
    group); an output axis is taken whole where the blocks fit
    ``VMEM_BUDGET`` and else cut to its largest 128-multiple divisor that
    does (1408 = 11 x 128 has none but 128 itself). None where no legal
    tiling exists: ``m`` without a 128-multiple divisor, a width off the
    128-lane layout (``compiled``), or blocks no cut brings inside the
    budget. Interpret mode (``compiled`` False) takes any width and
    power-of-two row tiles from 8."""
    if compiled and (k % NUM_LANES or n % NUM_LANES):
        return None
    tm = _row_tile(m, compiled)
    if tm is None:
        return None

    def widest(c: int, w: int) -> Optional[int]:
        return next((t for t in _divisors(w, compiled)
                     if rows_vmem_bytes(tm, c, t, itemsize) <= VMEM_BUDGET), None)

    tn_fwd, tk_dlhs = widest(k, n), widest(n, k)
    dw = next(((tm, tk, tn) for tn in _divisors(n, compiled)
               for tk in _divisors(k, compiled)
               if weights_vmem_bytes(tm, tk, tn, itemsize) <= VMEM_BUDGET), None)
    if tn_fwd is None or tk_dlhs is None or dw is None:
        return None
    need = max(rows_vmem_bytes(tm, k, tn_fwd, itemsize),
               rows_vmem_bytes(tm, n, tk_dlhs, itemsize),
               weights_vmem_bytes(*dw, itemsize))
    return GmmTiles((tm, tn_fwd), (tm, tk_dlhs), dw,
                    min(VMEM_CAP, need + need // 2 + (8 << 20)))


def choose_route(m: int, k: int, n: int, g: int, dtype, backend: str,
                 devices: int) -> str:
    """The whole decision of :func:`grouped_matmul`: ``"kernel"`` or
    ``"xla"`` (``jax.lax.ragged_dot`` and its transposes), from the shape,
    the operands' type, the platform and the devices of the live mesh.

    The kernel on a TPU, for 16- and 32-bit floats, where :func:`choose_tiles`
    finds tiles; ``ragged_dot`` on the CPU (the tests' and the ``analysis/``
    artifacts' program is XLA's), under a mesh of more than one device (GSPMD
    does not partition a ``pallas_call``) and for every other shape."""
    if backend != "tpu" or devices > 1:
        return "xla"
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize not in (2, 4):
        return "xla"
    if choose_tiles(m, k, n, g, dtype.itemsize) is None:
        return "xla"
    return "kernel"


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Config:
    tiles: GmmTiles
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kernel(cfg: _Config, rows, stack, group_sizes):
    return _rows_call(rows, stack, group_sizes, cfg.tiles.fwd, transposed=False,
                      vmem_limit_bytes=cfg.tiles.vmem_limit_bytes,
                      interpret=cfg.interpret)


def _kernel_fwd(cfg, rows, stack, group_sizes):
    return _kernel(cfg, rows, stack, group_sizes), (rows, stack, group_sizes)


def _kernel_bwd(cfg, res, d_out):
    rows, stack, group_sizes = res
    limit = dict(vmem_limit_bytes=cfg.tiles.vmem_limit_bytes, interpret=cfg.interpret)
    d_out = d_out.astype(jnp.result_type(rows.dtype, stack.dtype))
    d_rows = _rows_call(d_out, stack, group_sizes, cfg.tiles.dlhs, transposed=True,
                        **limit).astype(rows.dtype)
    d_stack = _weights_call(rows, d_out, group_sizes, cfg.tiles.dw,
                            out_dtype=stack.dtype, **limit)
    return d_rows, d_stack, None


_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def kernel_grouped_matmul(rows: jax.Array, stack: jax.Array, group_sizes: jax.Array,
                          tiles: Optional[GmmTiles] = None,
                          interpret: Optional[bool] = None) -> jax.Array:
    """The kernel route of :func:`grouped_matmul` whatever ``choose_route``
    says (tests, the microbenchmark); ``tiles`` default to
    :func:`choose_tiles`', in interpret mode off the TPU."""
    m, k = rows.shape
    g, _, n = stack.shape
    interp = jax.default_backend() == "cpu" if interpret is None else interpret
    if tiles is None:
        tiles = choose_tiles(m, k, n, g, jnp.result_type(rows.dtype, stack.dtype).itemsize,
                             compiled=not interp)
    if tiles is None:
        raise NotImplementedError(
            f"no tiles for rows [{m}, {k}] x stack [{g}, {k}, {n}]")
    return _kernel(_Config(tiles, bool(interp)), rows, stack,
                   group_sizes.astype(jnp.int32))


def grouped_matmul(rows: jax.Array, stack: jax.Array, group_sizes: jax.Array,
                   devices: int = 1) -> jax.Array:
    """``rows [m, k] x stack [g, k, n]`` by groups of consecutive rows ->
    ``[m, n]``: the kernel where :func:`choose_route` says so, else
    ``jax.lax.ragged_dot``. ``devices``: those of the live mesh. See the
    module's text for the contract on rows past ``sum(group_sizes)`` (zeros by
    the kernel, unspecified by ``ragged_dot`` on a TPU)."""
    m, k = rows.shape
    g, _, n = stack.shape
    route = choose_route(m, k, n, g, jnp.result_type(rows.dtype, stack.dtype),
                         jax.default_backend(), devices)
    if route == "kernel":
        return kernel_grouped_matmul(rows, stack, group_sizes)
    return lax.ragged_dot(rows, stack, group_sizes)
