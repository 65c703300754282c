"""The selective scan of a state-space layer (Mamba-1, arXiv:2312.00752), chunked
along the row, with a backward of its own.

``selective_scan(a, dt_raw, A, B, C, D, dt_bias, first) -> m``, a token at a
time along the rows ``t`` of ``[R, Di]`` operands (``Di`` channels, ``N`` states a
channel), everything below in float32::

    dt_t = softplus(dt_raw_t + dt_bias)                          [Di]
    h_t  = exp(dt_t A) * h_{t-1} + (dt_t a_t) (x) B_t            [Di, N]
    m_t  = h_t C_t + D * a_t                                     [Di]

with ``h_{t-1}`` taken as 0 where ``first[t]`` is set (a packed document's first
token, a batch row's first position). ``a`` and ``dt_raw`` ``[R, Di]``, ``B`` and
``C`` ``[R, N]``, ``A`` ``[Di, N]`` (negative), ``D`` and ``dt_bias`` ``[Di]``,
``first`` ``[R]``; ``m`` comes back in ``a``'s dtype. The state ``[R, Di, N]`` (5.4
GB a layer at 16,384 rows of 5120 x 16) exists in neither pass: a row is taken
in CHUNKS, and what crosses a chunk's border is one ``[Di, N]`` state.

**Routes** (`choose_route`, a pure function of the backend, the shapes and the
live mesh's devices; no switch):

- ``"kernel"``: the Pallas pair ``ssm_scan_fwd`` / ``ssm_scan_bwd``. The grid is
  (tiles of ``Di``, chunks of `CHUNK` rows), the chunks innermost and in order
  (backward: in reverse), the state ``[N, tile]`` (states on the sublanes,
  channels on the lanes) carried across them in VMEM. A grid step takes the
  chunk's ``a`` and ``dt_raw`` tiles to float32 once, then walks its rows in a
  ``fori_loop`` of `UNROLL` rows a trip: a row's ``B_t``, ``C_t`` and reset flag are one column of a ``[2
  N + 8, chunk]`` block (``B^T``, ``C^T`` and ``1 - first`` stacked, the rows on
  the lanes), taken out by a one-hot product and a lane reduction, so no operand
  is transposed or widened in HBM. The forward also writes each chunk's ENTRY
  state, ``[chunks, N, Di]`` float32 (21 MB at 16,384 rows): the only residual
  beside the operands. The backward makes a chunk's states again from its
  entry state into VMEM (``[chunk, N, tile]``, with the decays), then walks the
  chunk from its last row to its first (the published kernel's own choice), and
  returns da, d dt_raw, dA, dB, dC, dD, d dt_bias; dB and dC leave as one
  partial a tile of ``Di``, summed by the caller, dA, dD and d dt_bias are
  accumulated across the chunks in VMEM.
- ``"xla"``: `scan_xla`, a ``lax.scan`` over chunks of `XLA_CHUNK` rows of an
  associative scan inside the chunk, each chunk under ``jax.checkpoint`` (so the
  backward holds the chunks' entry states and one chunk's ``[chunk, Di, N]``).
  The CPU's route, the kernel's test oracle, and the route under a mesh of
  more than one device (GSPMD does not partition a ``pallas_call``).

A reset is written INTO the decay: where ``first[t]`` is set the decay's
exponent is taken at ``dt = 1e30``, and ``exp(1e30 A) = 0`` exactly for the
negative ``A`` a scan layer has (``A = -exp(A_log)``), in the forward and the
backward alike, so that no gradient crosses a document's start.

`tile_vmem_bytes` reckons what a grid step holds, `choose_tile` picks the tile.
Runs in interpret mode off the TPU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NUM_LANES = 128

#: rows of a chunk of the kernel pair (the rows lie on the lanes of the ``B^T,
#: C^T`` block: one lane tile) and of the XLA route (whose chunk holds ``[chunk,
#: Di, N]`` float32: 21 MB at 64 rows of 5120 x 16)
CHUNK = 128
XLA_CHUNK = 64
#: channels of a grid step, the most (a multiple of the lanes that divides Di),
#: and the rows of a chunk's walk laid out side by side in one trip of the loop
#: (a row's state hangs on the row before it; what does not, the column taken
#: out of the stacked block, the decay's exponential, the products with B and
#: C, can then run ahead). From the chip (v5e, PR 57, `tools/ssm_scan_ab.py` at
#: 16,384 rows of 5120 x 16; docs/KERNELS.md): a row at a time the walk waits on
#: itself, 20.1 ms forward and 86.3 forward + backward at a tile of 512; 8 rows a
#: trip 5.6 and 26.4; a tile of 1024 with them 5.1 and 19.3; 16 rows or tiles of
#: 1280 and 2560 within 7 % of that (4.4 and 17.8 at best).
TILE = 1024
UNROLL = 8
#: what a reset puts in dt's place under the decay's exponent (module docstring)
RESET_DT = 1e30
#: rows of the stacked ``B^T, C^T, keep`` block past the two states' (a sublane tile)
_KEEP_ROWS = 8
VMEM_CAP = 96 * 1024 * 1024


def tile_vmem_bytes(chunk: int, tile: int, states: int, itemsize: int = 2, *,
                    backward: bool) -> int:
    """Upper estimate of the VMEM one grid step holds: the double-buffered
    operand and result blocks (``a``, ``dt_raw``, ``m`` and in the backward ``dm``,
    ``da``, ``d dt_raw`` at ``itemsize``; the stacked ``B^T, C^T, keep`` block and in
    the backward its gradient, float32), ``A``'s tile and the accumulators, the
    chunk's float32 copies and in the backward the chunk's states and decays,
    ``2 x [chunk, states, tile]`` float32."""
    rows = chunk * tile
    stacked = (2 * states + _KEEP_ROWS) * chunk * 4
    small = 6 * states * tile * 4 + 8 * tile * 4
    if backward:
        return (2 * (5 * rows * itemsize + 2 * stacked) + small + 5 * rows * 4
                + 2 * chunk * states * tile * 4)
    return 2 * (3 * rows * itemsize + stacked) + small + 4 * rows * 4


def choose_tile(channels: int, chunk: int = CHUNK, states: int = 16) -> Optional[int]:
    """The channels a grid step takes: the largest multiple of the lanes up to
    `TILE` that divides ``channels`` and whose backward step fits `VMEM_CAP`;
    None without one."""
    for tile in range(min(TILE, channels) // NUM_LANES * NUM_LANES, 0, -NUM_LANES):
        if channels % tile == 0 and tile_vmem_bytes(
                chunk, tile, states, backward=True) <= VMEM_CAP:
            return tile
    return None


def choose_route(rows: int, channels: int, states: int, backend: str,
                 devices: int = 1) -> str:
    """``"kernel"`` on a one-device TPU where `choose_tile` finds a tile and the
    states fill whole sublanes; else ``"xla"`` (the CPU, a mesh of several
    devices, another shape)."""
    if backend != "tpu" or devices > 1 or states % 8:
        return "xla"
    return "kernel" if choose_tile(channels, CHUNK, states) else "xla"


def _pad_rows(chunk: int, *arrays):
    """``arrays`` ``[R, ...]`` padded with zeros to whole chunks."""
    rows = arrays[0].shape[0]
    pad = -rows % chunk
    if not pad:
        return arrays
    return tuple(jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) for x in arrays)


# -- the XLA route -------------------------------------------------------------

def scan_xla(a, dt_raw, A, B, C, D, dt_bias, first, chunk: int = XLA_CHUNK):
    """The module docstring's recurrence in ``jax.numpy``: a ``lax.scan`` over
    chunks of ``chunk`` rows (a last, partial chunk is padded with rows that
    change no state), an associative scan inside a chunk, each chunk made again
    in its own backward."""
    rows, out_dtype = a.shape[0], a.dtype
    a, dt_raw, B, C, first = _pad_rows(chunk, a, dt_raw, B, C, first.astype(jnp.int32))
    A, D, dt_bias = A.astype(F32), D.astype(F32), dt_bias.astype(F32)

    def combine(left, right):
        (dl, xl), (dr, xr) = left, right
        return dl * dr, xl * dr + xr

    def one_chunk(h, xs):
        a, dt_raw, B, C, first = xs
        a32 = a.astype(F32)
        dt = jax.nn.softplus(dt_raw.astype(F32) + dt_bias)             # [T, Di]
        decay = jnp.exp(jnp.where(first[:, None] > 0, RESET_DT, dt)[:, :, None] * A)
        x = (dt * a32)[:, :, None] * B.astype(F32)[:, None, :]          # [T, Di, N]
        decays, states = lax.associative_scan(combine, (decay, x), axis=0)
        states = states + decays * h                                    # the entry state's part
        m = jnp.einsum("tdn,tn->td", states, C.astype(F32)) + D * a32
        return states[-1], m.astype(out_dtype)

    chunks = lambda x: x.reshape((x.shape[0] // chunk, chunk) + x.shape[1:])
    _, m = lax.scan(jax.checkpoint(one_chunk), jnp.zeros(A.shape, F32),
                    tuple(map(chunks, (a, dt_raw, B, C, first))))
    return m.reshape((-1,) + m.shape[2:])[:rows]


def scan_by_token(a, dt_raw, A, B, C, D, dt_bias, first):
    """The recurrence a token at a time, literally (a test's oracle)."""
    A, D, dt_bias = A.astype(F32), D.astype(F32), dt_bias.astype(F32)

    def step(h, xs):
        a, dt_raw, B, C, first = xs
        a32 = a.astype(F32)
        dt = jax.nn.softplus(dt_raw.astype(F32) + dt_bias)
        h = jnp.where(first > 0, 0.0, h)
        h = jnp.exp(dt[:, None] * A) * h + (dt * a32)[:, None] * B.astype(F32)[None, :]
        return h, h @ C.astype(F32) + D * a32
    _, m = lax.scan(step, jnp.zeros(A.shape, F32), (a, dt_raw, B, C, first.astype(jnp.int32)))
    return m.astype(a.dtype)


# -- the kernel pair -----------------------------------------------------------

def _column(stacked, t):
    """Column ``t`` of the stacked ``[2 N + 8, chunk]`` block as ``[2 N + 8, 1]``:
    a one-hot product and a lane reduction (no dynamic lane index)."""
    lanes = lax.broadcasted_iota(jnp.int32, (1, stacked.shape[1]), 1)
    return jnp.sum(jnp.where(lanes == t, stacked, 0.0), axis=1, keepdims=True)


def _walk(chunk: int, unroll: int, row, init):
    """``row(t, carry)`` over a chunk's rows in order, ``unroll`` of them laid out
    in one trip of the loop (Mosaic unrolls a ``fori_loop`` whole or not at all)."""
    if chunk % unroll:
        raise ValueError(f"a chunk of {chunk} rows in groups of {unroll}")

    def group(g, carry):
        for j in range(unroll):
            carry = row(g * unroll + j, carry)
        return carry
    return lax.fori_loop(0, chunk // unroll, group, init)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _fwd_kernel(a_ref, dtr_ref, bck_ref, A_ref, D_ref, bias_ref,
                m_ref, entry_ref, h_ref, a32, dt32, m32, *, states: int, chunk: int,
                unroll: int):
    """One chunk of one tile of channels: the entry state out, the chunk's rows
    walked in order, the state left in ``h_ref`` for the next chunk."""
    N = states

    @pl.when(pl.program_id(1) == 0)
    def _row_start():
        h_ref[...] = jnp.zeros_like(h_ref)

    entry_ref[0] = h_ref[...]
    a32[...] = a_ref[...].astype(F32)
    dt32[...] = _softplus(dtr_ref[...].astype(F32) + bias_ref[...])
    A, stacked, D = A_ref[...], bck_ref[...], D_ref[...]

    def row(t, h):
        col = _column(stacked, t)
        b, c, keep = col[:N], col[N:2 * N], col[2 * N:2 * N + 1]
        a_t, dt_t = a32[pl.ds(t, 1), :], dt32[pl.ds(t, 1), :]
        decay = jnp.exp((dt_t + (1.0 - keep) * RESET_DT) * A)           # [N, tile]
        h = decay * h + (dt_t * a_t) * b
        m32[pl.ds(t, 1), :] = jnp.sum(h * c, axis=0, keepdims=True) + D * a_t
        return h

    h_ref[...] = _walk(chunk, unroll, row, h_ref[...])
    m_ref[...] = m32[...].astype(m_ref.dtype)


def _bwd_kernel(a_ref, dtr_ref, bck_ref, A_ref, D_ref, bias_ref, entry_ref, dm_ref,
                da_ref, ddtr_ref, dbck_ref, dA_ref, dD_ref, dbias_ref,
                dh_ref, a32, dt32, dm32, da32, ddt32, hs, decays,
                *, states: int, chunk: int, unroll: int):
    """One chunk of one tile of channels, the chunks coming last to first: the
    chunk's states and decays made again from its entry state, then its rows
    walked backwards; ``dh_ref`` carries the state's cotangent to the chunk in
    front."""
    N = states

    @pl.when(pl.program_id(1) == 0)
    def _row_end():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        dA_ref[...] = jnp.zeros_like(dA_ref)
        dD_ref[...] = jnp.zeros_like(dD_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    a32[...] = a_ref[...].astype(F32)
    pre = dtr_ref[...].astype(F32) + bias_ref[...]
    dt32[...] = _softplus(pre)
    dm32[...] = dm_ref[...].astype(F32)
    A, stacked, D = A_ref[...], bck_ref[...], D_ref[...]
    entry = entry_ref[0]

    def again(t, h):
        col = _column(stacked, t)
        a_t, dt_t = a32[pl.ds(t, 1), :], dt32[pl.ds(t, 1), :]
        decay = jnp.exp((dt_t + (1.0 - col[2 * N:2 * N + 1]) * RESET_DT) * A)
        h = decay * h + (dt_t * a_t) * col[:N]
        hs[t] = h
        decays[t] = decay
        return h

    _walk(chunk, unroll, again, entry)
    lanes = lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def row(i, carry):
        dh, dA, dstacked = carry
        t = chunk - 1 - i
        col = _column(stacked, t)
        b, c = col[:N], col[N:2 * N]
        a_t, dt_t, dm_t = a32[pl.ds(t, 1), :], dt32[pl.ds(t, 1), :], dm32[pl.ds(t, 1), :]
        h_t, decay = hs[t], decays[t]
        before = jnp.where(t > 0, hs[jnp.maximum(t - 1, 0)], entry)
        dh = dh + c * dm_t
        g = dh * before * decay                       # d(dt A): 0 where the row resets
        dx = jnp.sum(dh * b, axis=0, keepdims=True)                      # d(dt a)
        ddt32[pl.ds(t, 1), :] = jnp.sum(g * A, axis=0, keepdims=True) + dx * a_t
        da32[pl.ds(t, 1), :] = dx * dt_t + D * dm_t
        dA = dA + g * dt_t
        # dB_t and dC_t: this tile's part, into column t
        part = jnp.concatenate([
            jnp.sum(dh * (dt_t * a_t), axis=1, keepdims=True),
            jnp.sum(h_t * dm_t, axis=1, keepdims=True),
            jnp.zeros((_KEEP_ROWS, 1), F32)], axis=0)
        dstacked = dstacked + jnp.where(lanes == t, part, 0.0)
        return dh * decay, dA, dstacked

    dh, dA, dstacked = _walk(
        chunk, unroll, row, (dh_ref[...], jnp.zeros_like(A), jnp.zeros_like(stacked)))
    dh_ref[...] = dh
    dA_ref[...] += dA
    dbck_ref[0] = dstacked
    dD_ref[...] += jnp.sum(dm32[...] * a32[...], axis=0, keepdims=True)
    ddtr = ddt32[...] * jax.nn.sigmoid(pre)
    dbias_ref[...] += jnp.sum(ddtr, axis=0, keepdims=True)
    ddtr_ref[...] = ddtr.astype(ddtr_ref.dtype)
    da_ref[...] = da32[...].astype(da_ref.dtype)


def _stack(B, C, first):
    """``B^T``, ``C^T`` and ``1 - first`` stacked, ``[2 N + 8, R]`` float32."""
    keep = 1.0 - (first > 0).astype(F32)
    return jnp.concatenate(
        [B.astype(F32).T, C.astype(F32).T,
         jnp.broadcast_to(keep[None, :], (_KEEP_ROWS, keep.shape[0]))], axis=0)


def _params(vmem: int, interpret: bool):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(VMEM_CAP, max(vmem + (8 << 20), 32 << 20))),
        interpret=interpret)


def _fwd_call(a, dt_raw, stacked, At, D, dt_bias, chunk: int, tile: int, interpret: bool,
              unroll: int = 1):
    """-> (``m`` ``[R, Di]``, the chunks' entry states ``[chunks, N, Di]`` float32)."""
    R, Di = a.shape
    N = At.shape[0]
    grid = (Di // tile, R // chunk)
    rows = pl.BlockSpec((chunk, tile), lambda i, c: (c, i))
    wide = pl.BlockSpec((stacked.shape[0], chunk), lambda i, c: (0, c))
    per_tile = lambda n: pl.BlockSpec((n, tile), lambda i, c: (0, i))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, states=N, chunk=chunk, unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid,
            in_specs=[rows, rows, wide, per_tile(N), per_tile(1), per_tile(1)],
            out_specs=[rows, pl.BlockSpec((1, N, tile), lambda i, c: (c, 0, i))],
            scratch_shapes=[pltpu.VMEM((N, tile), F32)]
            + [pltpu.VMEM((chunk, tile), F32)] * 3),
        out_shape=[jax.ShapeDtypeStruct((R, Di), a.dtype),
                   jax.ShapeDtypeStruct((R // chunk, N, Di), F32)],
        name="ssm_scan_fwd",
        **_params(tile_vmem_bytes(chunk, tile, N, a.dtype.itemsize, backward=False),
                  interpret),
    )(a, dt_raw, stacked, At, D, dt_bias)


def _bwd_call(a, dt_raw, stacked, At, D, dt_bias, entry, dm, chunk: int, tile: int,
              interpret: bool, unroll: int = 1):
    """-> (da, d dt_raw ``[R, Di]``, the stacked block's gradient a tile ``[tiles,
    2 N + 8, R]``, dA ``[N, Di]``, dD, d dt_bias ``[1, Di]``), float32 but the first two."""
    R, Di = a.shape
    N = At.shape[0]
    nc = R // chunk
    grid = (Di // tile, nc)
    back = lambda c: nc - 1 - c
    rows = pl.BlockSpec((chunk, tile), lambda i, c: (back(c), i))
    wide = pl.BlockSpec((stacked.shape[0], chunk), lambda i, c: (0, back(c)))
    per_tile = lambda n: pl.BlockSpec((n, tile), lambda i, c: (0, i))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, states=N, chunk=chunk, unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid,
            in_specs=[rows, rows, wide, per_tile(N), per_tile(1), per_tile(1),
                      pl.BlockSpec((1, N, tile), lambda i, c: (back(c), 0, i)), rows],
            out_specs=[rows, rows,
                       pl.BlockSpec((1, stacked.shape[0], chunk),
                                    lambda i, c: (i, 0, back(c))),
                       per_tile(N), per_tile(1), per_tile(1)],
            scratch_shapes=[pltpu.VMEM((N, tile), F32)]
            + [pltpu.VMEM((chunk, tile), F32)] * 5
            + [pltpu.VMEM((chunk, N, tile), F32)] * 2),
        out_shape=[jax.ShapeDtypeStruct((R, Di), a.dtype),
                   jax.ShapeDtypeStruct((R, Di), dt_raw.dtype),
                   jax.ShapeDtypeStruct((Di // tile, stacked.shape[0], R), F32),
                   jax.ShapeDtypeStruct((N, Di), F32),
                   jax.ShapeDtypeStruct((1, Di), F32),
                   jax.ShapeDtypeStruct((1, Di), F32)],
        name="ssm_scan_bwd",
        **_params(tile_vmem_bytes(chunk, tile, N, a.dtype.itemsize, backward=True),
                  interpret),
    )(a, dt_raw, stacked, At, D, dt_bias, entry, dm)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan_kernel(how: Tuple[int, int, bool, int], a, dt_raw, A, B, C, D, dt_bias, first):
    return _scan_kernel_fwd(how, a, dt_raw, A, B, C, D, dt_bias, first)[0]


def _scan_kernel_fwd(how, a, dt_raw, A, B, C, D, dt_bias, first):
    chunk, tile, interpret, unroll = how
    rows = a.shape[0]
    pa, pdt, pB, pC, pfirst = _pad_rows(chunk, a, dt_raw, B, C, first.astype(jnp.int32))
    stacked = _stack(pB, pC, pfirst)
    row = lambda v: v.astype(F32).reshape(1, -1)
    m, entry = _fwd_call(pa, pdt, stacked, A.astype(F32).T, row(D), row(dt_bias),
                         chunk, tile, interpret, unroll)
    # named so that a rematerialised block's backward need not scan again
    m = checkpoint_name(m[:rows], "ssm_m")
    entry = checkpoint_name(entry, "ssm_state")
    return m, (a, dt_raw, A, B, C, D, dt_bias, first, entry)


def _scan_kernel_bwd(how, res, dm):
    chunk, tile, interpret, unroll = how
    a, dt_raw, A, B, C, D, dt_bias, first, entry = res
    rows, N = a.shape[0], A.shape[1]
    pa, pdt, pB, pC, pfirst, pdm = _pad_rows(
        chunk, a, dt_raw, B, C, first.astype(jnp.int32), dm)
    row = lambda v: v.astype(F32).reshape(1, -1)
    da, ddtr, dstacked, dAt, dD, dbias = _bwd_call(
        pa, pdt, _stack(pB, pC, pfirst), A.astype(F32).T, row(D), row(dt_bias),
        entry, pdm, chunk, tile, interpret, unroll)
    dstacked = jnp.sum(dstacked, axis=0)[:, :rows]
    return (da[:rows], ddtr[:rows], dAt.T.astype(A.dtype),
            dstacked[:N].T.astype(B.dtype), dstacked[N:2 * N].T.astype(C.dtype),
            dD[0].astype(D.dtype), dbias[0].astype(dt_bias.dtype), None)


_scan_kernel.defvjp(_scan_kernel_fwd, _scan_kernel_bwd)


def scan_kernel(a, dt_raw, A, B, C, D, dt_bias, first, *, chunk: int = CHUNK,
                tile: Optional[int] = None, interpret: Optional[bool] = None,
                unroll: int = UNROLL):
    """The kernel route (module docstring); ``tile``: the channels a grid step
    takes (None: `choose_tile`'s), ``interpret``: None, off the TPU; ``unroll``:
    rows of a chunk's walk laid out side by side (`UNROLL`)."""
    if tile is None:
        tile = choose_tile(a.shape[1], chunk, A.shape[1])
    if tile is None or a.shape[1] % tile:
        raise ValueError(f"no tile of channels for {a.shape[1]} (given {tile})")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _scan_kernel((chunk, tile, bool(interpret), int(unroll)),
                        a, dt_raw, A, B, C, D, dt_bias, first)


def selective_scan(a, dt_raw, A, B, C, D, dt_bias, first, *, route: Optional[str] = None,
                   devices: int = 1):
    """The module docstring's scan by `choose_route` (or ``route`` given)."""
    if route is None:
        route = choose_route(a.shape[0], a.shape[1], A.shape[1], jax.default_backend(),
                             devices)
    if route == "kernel":
        return scan_kernel(a, dt_raw, A, B, C, D, dt_bias, first)
    return scan_xla(a, dt_raw, A, B, C, D, dt_bias, first)
