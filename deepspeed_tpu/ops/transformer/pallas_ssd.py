"""The state-space-duality core of a Mamba-2 layer (arXiv:2405.21060), chunked
along the row so that its work is matrix products, with a backward of its own.

``ssd(a, dt, A, B, C, D, first) -> m`` computes, a token at a time along the rows
``t`` of flat ``[R, ...]`` operands, for each of ``H`` heads of ``P`` channels over
``N`` states (one SCALAR decay a head; ``B`` and ``C`` shared by the ``H / G`` heads
of a group), everything below in float32::

    h_t = exp(dt_t A) h_{t-1} + dt_t a_t (x) B_t                  [H, P, N]
    m_t = h_t C_t + D a_t                                         [H, P]

with ``h_{t-1}`` taken as 0 where ``first[t]`` is set (a packed document's first
token, a batch row's first position). ``a`` ``[R, H x P]``, ``dt`` ``[R, H]``
(positive: the caller's softplus), ``A`` ``[H]`` (negative), ``B`` and ``C`` ``[R, G
x N]``, ``D`` ``[H]``, ``first`` ``[R]``; ``m`` comes back in ``a``'s dtype. The state
``[R, H, P, N]`` (68 GB a layer at 32,768 rows of 64 x 64 x 128) exists in neither
pass: inside a CHUNK of Q rows the recurrence is the masked product ``(L o C B^T)
(dt a)`` with ``L_ts = exp(sum_{s<r<=t} dt_r A)``, and what crosses a chunk's
border is one ``[H, P, N]`` state.

**Routes** (`choose_route`, a pure function of the backend, the shapes and the
live mesh's devices; no switch):

- ``"kernel"``: the Pallas pair ``ssd_fwd`` / ``ssd_bwd``. The grid is (tiles of
  `TILE_HEADS` heads, chunks of `CHUNK` rows), the chunks innermost and in order
  (backward: in reverse), the tile's state ``[heads x P, N]`` carried across them
  in VMEM. A grid step makes ``C B^T`` ``[Q, Q]`` ONCE for its heads, masks it
  (causal, and inside a document), and then, a head at a time, makes the decay
  mask ``exp(cum_t - cum_s)`` in VMEM from the chunk's cumulative log-decays (a
  column ``[Q, 1]`` against a row ``[1, Q]``: the caller lays both layouts out, so
  the kernel transposes nothing a head) and multiplies ``[Q, Q] x [Q, 128]``. P =
  64 is half a lane tile: two heads share a 128-lane block of ``a`` and of the
  state, the masked product runs once a head over the block and keeps its own
  half, the state's read-out and update run once a block at full width. The
  forward also writes each chunk's ENTRY state, ``[chunks, H x P, N]`` float32
  (268 MB at 32,768 rows): the only residual beside the operands. The
  backward walks the chunks from the last to the first with the state's adjoint
  in VMEM and returns da, d dt, d cum (the caller's cumulative sum and ``dt A``
  are XLA's, and so are their gradients), dB, dC (one partial a tile of heads,
  summed by the caller) and dD (a column of row sums, summed by the caller).
- ``"xla"``: `ssd_xla`, the same chunked form in ``jax.numpy`` under a ``lax.scan``
  over chunks of `XLA_CHUNK` rows, each chunk under ``jax.checkpoint``. The CPU's
  route, the kernel's test oracle beside `ssd_by_token`, and the route under a
  mesh of more than one device (GSPMD does not partition a ``pallas_call``).

The chunk is the kernel's choice (`CHUNK`; a configuration's ``mamba_chunk_size``
names the published kernels'): the mathematics does not depend on it.

A reset is written INTO the decay: inside a chunk ``L_ts`` is 0 unless s and t
lie in the same document, the entry state reaches only the rows before the
chunk's first reset, and only the rows after its last reset reach the state that
leaves, in the forward and the backward alike, so that no gradient crosses a
document's start. Runs in interpret mode off the TPU.
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

#: rows of a chunk of the kernel pair and of the XLA route (whose chunk holds
#: ``[H, chunk, chunk]`` float32: 4 MB at 64 heads), and the heads of a grid step
#: (from the chip, v5e, PR 65, `tools/ssd_ab.py` at 32,768 rows of 64 x 64 x 128;
#: docs/KERNELS.md: forward / forward + backward 4.03 / 17.5 ms at 256 x 16, 4.73 /
#: 19.6 at 256 x 8, 5.59 / 22.5 at 256 x 4; chunks of 128 6.77-4.91 / 26.4-20.6)
CHUNK = 256
XLA_CHUNK = 128
TILE_HEADS = 16
VMEM_CAP = 96 * 1024 * 1024


def choose_tile(heads: int, head_dim: int, groups: int = 1) -> Optional[int]:
    """The heads a grid step takes: the most up to `TILE_HEADS` that divide a
    group's heads and fill whole 128-lane blocks; None without one (a head
    size that neither divides the lanes nor is a multiple of them)."""
    if heads % groups or (NUM_LANES % head_dim and head_dim % NUM_LANES):
        return None
    per_block = max(1, NUM_LANES // head_dim)
    for tile in range(min(TILE_HEADS, heads // groups), 0, -1):
        if (heads // groups) % tile == 0 and tile % per_block == 0:
            return tile
    return None


def choose_route(rows: int, heads: int, head_dim: int, states: int, groups: int,
                 backend: str, devices: int = 1) -> str:
    """``"kernel"`` on a one-device TPU where `choose_tile` finds a tile, the
    states fill whole lane tiles and a head is at most one; else ``"xla"`` (the
    CPU, a mesh of several devices, another shape)."""
    if (backend != "tpu" or devices > 1 or states % NUM_LANES
            or head_dim > NUM_LANES):
        return "xla"
    return "kernel" if choose_tile(heads, head_dim, groups) else "xla"


def _pad_rows(chunk: int, *arrays):
    """``arrays`` ``[R, ...]`` padded with zeros to whole chunks (a padded row has
    ``dt`` 0: it changes no state)."""
    pad = -arrays[0].shape[0] % chunk
    if not pad:
        return arrays
    return tuple(jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) for x in arrays)


def _chunked(x, chunk: int):
    return x.reshape((x.shape[0] // chunk, chunk) + x.shape[1:])


def _by_chunk_cumsum(x, chunk: int):
    """The inclusive cumulative sum of ``x`` ``[R, ...]`` inside each chunk."""
    return jnp.cumsum(_chunked(x, chunk), axis=1).reshape(x.shape)


def _log_decays(la, first, axis: int = 0):
    """The inclusive cumulative sum of the log-decays ``la`` ``[..., Q, H]`` along
    ``axis``, started anew where ``first`` ``[..., Q]`` is set: a row's value is a
    sum over its own document's rows alone, so the differences the decay mask
    takes are exact sums and NO gradient, not even a rounding's, reaches a row
    before a document's start (a plain cumulative sum's backward adds and
    subtracts the same numbers in two orders). The values are NOT monotone
    across a document's start: every exponent of a difference is clamped at 0
    before it is taken (a masked-away ``exp(+400)`` is ``inf x 0``; PR 65's first
    limits run read NaN in eight seeds of ten for it)."""
    flags = jnp.broadcast_to(first[..., None] > 0, la.shape)

    def combine(left, right):
        (fl, vl), (fr, vr) = left, right
        return fl | fr, jnp.where(fr, vr, vl + vr)
    return lax.associative_scan(combine, (flags, la), axis=axis)[1]


# -- the XLA route and the oracle ----------------------------------------------

def ssd_by_token(a, dt, A, B, C, D, first, groups: int = 1):
    """The recurrence a token at a time, literally (a test's oracle)."""
    H = dt.shape[1]
    P, N = a.shape[1] // H, B.shape[1] // groups
    A, D = A.astype(F32), D.astype(F32)
    heads = lambda x: jnp.repeat(x.astype(F32).reshape(groups, N), H // groups, axis=0)

    def step(h, xs):
        a, dt, B, C, first = xs
        a32, dt = a.astype(F32).reshape(H, P), dt.astype(F32)
        h = jnp.where(first > 0, 0.0, h)
        h = (jnp.exp(dt * A)[:, None, None] * h
             + (dt[:, None] * a32)[:, :, None] * heads(B)[:, None, :])
        return h, (jnp.einsum("hpn,hn->hp", h, heads(C)) + D[:, None] * a32).reshape(-1)
    _, m = lax.scan(step, jnp.zeros((H, P, N), F32),
                    (a, dt, B, C, first.astype(jnp.int32)))
    return m.astype(a.dtype)


def ssd_xla(a, dt, A, B, C, D, first, groups: int = 1, chunk: int = XLA_CHUNK):
    """The module docstring's chunked form in ``jax.numpy``: a ``lax.scan`` over
    chunks of ``chunk`` rows (a last, partial chunk is padded with rows that
    change no state), each chunk made again in its own backward."""
    rows, out_dtype, H = a.shape[0], a.dtype, dt.shape[1]
    P, N = a.shape[1] // H, B.shape[1] // groups
    a, dt, B, C, first = _pad_rows(chunk, a, dt.astype(F32), B, C, first.astype(jnp.int32))
    A, D = A.astype(F32), D.astype(F32)
    at = jnp.arange(chunk)
    causal = at[:, None] >= at[None, :]

    def one_chunk(h, xs):
        a, dt, B, C, first = xs
        a32 = a.astype(F32).reshape(chunk, H, P)
        heads = lambda x: jnp.repeat(
            x.astype(F32).reshape(chunk, groups, N), H // groups, axis=1)
        Bh, Ch = heads(B), heads(C)
        cum, seg = _log_decays(dt * A, first), jnp.cumsum(first)
        mask = causal & (seg[:, None] == seg[None, :])
        decay = jnp.exp(jnp.minimum(cum[:, None, :] - cum[None, :, :], 0.0))    # [t, s, H]
        M = jnp.where(mask[:, :, None],
                      jnp.einsum("thn,shn->tsh", Ch, Bh) * decay * dt[None], 0.0)
        enters = jnp.exp(cum) * (seg == 0)[:, None]                             # [t, H]
        m = (jnp.einsum("tsh,shp->thp", M, a32)
             + enters[:, :, None] * jnp.einsum("thn,hpn->thp", Ch, h)
             + D[None, :, None] * a32)
        # (a row of an EARLIER document has a cum of its own document's: the
        # difference may be large and positive there, where the mask is 0)
        leaves = (jnp.exp(jnp.minimum(cum[-1] - cum, 0.0)) * dt
                  * (seg == seg[-1])[:, None])                                  # [s, H]
        h = ((jnp.exp(cum[-1]) * (seg[-1] == 0))[:, None, None] * h
             + jnp.einsum("sh,shp,shn->hpn", leaves, a32, Bh))
        return h, m.reshape(chunk, H * P).astype(out_dtype)

    _, m = lax.scan(jax.checkpoint(one_chunk), jnp.zeros((H, P, N), F32),
                    tuple(_chunked(x, chunk) for x in (a, dt, B, C, first)))
    return m.reshape(-1, H * P)[:rows]


# -- the kernel pair -----------------------------------------------------------

def _lanes_of(hb: int) -> int:
    """Sublanes of a tile's ``rows`` block: cum, dt and the segment row, to whole
    sublane tiles."""
    return -(-(2 * hb + 1) // 8) * 8


def _layouts(dt, cum, seg, hb: int):
    """A tile of heads' per-row scalars in both layouts a grid step reads:
    ``cols`` ``[tiles, R, 128]`` (lanes: the tile's cum, its dt, the segment) and
    ``rows`` ``[tiles, 2 hb + 1 -> 8s, R]`` (the same, the rows on the lanes)."""
    R, H = dt.shape
    tiles = H // hb
    by_tile = lambda x: x.reshape(R, tiles, hb).transpose(1, 0, 2)
    packed = jnp.concatenate(
        [by_tile(cum), by_tile(dt),
         jnp.broadcast_to(seg[None, :, None], (tiles, R, 1))], axis=-1)     # [tiles, R, 2hb+1]
    cols = jnp.pad(packed, ((0, 0), (0, 0), (0, NUM_LANES - packed.shape[-1])))
    rows = jnp.pad(packed.transpose(0, 2, 1),
                   ((0, 0), (0, _lanes_of(hb) - packed.shape[-1]), (0, 0)))
    return cols, rows


def _block_width(hb: int, head_dim: int) -> int:
    """Lanes of the blocks a grid step walks its heads in: a lane tile of
    ``128 / head_dim`` heads, or one head where a head is at least a tile."""
    return min(NUM_LANES, hb * head_dim) if head_dim < NUM_LANES else head_dim


def _by_head(values, width: int, head_dim: int, axis: int = 1):
    """Head j's value over head j's part of a lane block, the heads side by
    side: ``[Q, 1]`` columns over the lanes (``axis`` 1 -> ``[Q, width]``), or ``[1, 1]``
    scalars over the state's rows (``axis`` 0 -> ``[width, 1]``)."""
    if len(values) == 1:
        return values[0]
    shape = (values[0].shape[0], width) if axis == 1 else (width, 1)
    at = lax.broadcasted_iota(jnp.int32, shape, axis)
    out = values[-1]
    for j in range(len(values) - 2, -1, -1):
        out = jnp.where(at < (j + 1) * head_dim, values[j], out)
    return out


def _head_sums(x, heads: int, head_dim: int, axis: int = 1):
    """``x`` summed over each head's lanes (``axis`` 1: ``[Q, width]`` -> a ``[Q, 1]``
    a head) or over each head's state rows (``axis`` 0: ``[width, 1]`` -> a ``[1,
    1]`` a head)."""
    if heads == 1:
        return [jnp.sum(x, axis=axis, keepdims=True)]
    at = lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return [jnp.sum(jnp.where((at >= j * head_dim) & (at < (j + 1) * head_dim), x, 0.0),
                    axis=axis, keepdims=True) for j in range(heads)]


NT = (((1,), (1,)), ((), ()))       # [m, k] x [n, k] -> [m, n]


def _dot(x, y, dims=None):
    if dims is None:
        return lax.dot(x, y, preferred_element_type=F32)
    return lax.dot_general(x, y, dims, preferred_element_type=F32)


def _chunk_scalars(cols, rows, hb: int, chunk: int):
    """What a grid step reads of its chunk's per-row scalars: the segment column
    and row, whether a row lies before the chunk's first reset (``[Q, 1]``) and
    after its last."""
    seg_c, seg_r = cols[:, 2 * hb:2 * hb + 1], rows[2 * hb:2 * hb + 1, :]
    before = (seg_c == 0.0).astype(F32)
    after = (seg_c == seg_c[chunk - 1:chunk, :]).astype(F32)
    return seg_c, seg_r, before, after


def _fwd_kernel(a_ref, cols_ref, rows_ref, b_ref, c_ref, d_ref, m_ref, entry_ref, h_scr,
                *, hb: int, head_dim: int, chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _row_start():
        h_scr[...] = jnp.zeros_like(h_scr)

    entry_ref[0] = h_scr[...]
    dtype = a_ref.dtype
    cols, rows = cols_ref[0], rows_ref[0]
    Bm, Cm = b_ref[...], c_ref[...]
    seg_c, seg_r, before, after = _chunk_scalars(cols, rows, hb, chunk)
    ti = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # C B^T, once for the tile's heads: causal, and inside a document
    S = jnp.where((si <= ti) & (seg_c == seg_r), _dot(Cm, Bm, NT), 0.0)
    width = _block_width(hb, head_dim)
    k = width // head_dim               # heads of a lane block
    lane = lax.broadcasted_iota(jnp.int32, (chunk, width), 1)
    for p in range(hb // k):
        at = slice(p * width, (p + 1) * width)
        xa = a_ref[:, at]
        y = None
        enters, leaves, lasts = [], [], []
        for j in range(k):
            h = p * k + j
            cum_c, cum_r = cols[:, h:h + 1], rows[h:h + 1, :]
            dt_c, dt_r = cols[:, hb + h:hb + h + 1], rows[hb + h:hb + h + 1, :]
            # the decay mask, made here and never written: [Q, Q] a head
            M = S * jnp.exp(jnp.minimum(cum_c - cum_r, 0.0)) * dt_r
            yj = _dot(M.astype(dtype), xa)
            y = yj if y is None else jnp.where(lane < j * head_dim, y, yj)
            last = cum_c[chunk - 1:chunk, :]
            enters.append(jnp.exp(cum_c) * before)
            leaves.append(jnp.exp(jnp.minimum(last - cum_c, 0.0)) * dt_c * after)
            lasts.append(jnp.exp(last) * before[chunk - 1:chunk, :])
        hp = h_scr[at, :]
        a32 = xa.astype(F32)
        y = y + _by_head(enters, width, head_dim) * _dot(Cm, hp.astype(dtype), NT)
        m_ref[:, at] = (y + d_ref[:, at] * a32).astype(m_ref.dtype)
        xw = a32 * _by_head(leaves, width, head_dim)
        h_scr[at, :] = (_by_head(lasts, width, head_dim, axis=0) * hp
                        + _dot(xw.T.astype(dtype), Bm))


def _bwd_kernel(a_ref, cols_ref, rows_ref, b_ref, c_ref, d_ref, entry_ref, dm_ref,
                da_ref, dcols_ref, db_ref, dc_ref, dh_scr,
                *, hb: int, head_dim: int, chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _row_end():
        dh_scr[...] = jnp.zeros_like(dh_scr)

    dtype = a_ref.dtype
    cols, rows = cols_ref[0], rows_ref[0]
    Bm, Cm = b_ref[...], c_ref[...]
    N = Bm.shape[1]
    seg_c, seg_r, before, after = _chunk_scalars(cols, rows, hb, chunk)
    # everything [Q, Q] here is TRANSPOSED: the row is the key s, the lane the query t
    ri = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    qi = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask_t = (ri <= qi) & (seg_c == seg_r)
    St = jnp.where(mask_t, _dot(Bm, Cm, NT), 0.0)
    width = _block_width(hb, head_dim)
    k = width // head_dim
    lane = lax.broadcasted_iota(jnp.int32, (chunk, width), 1)
    row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    out_lane = lax.broadcasted_iota(jnp.int32, (chunk, NUM_LANES), 1)
    dSt = jnp.zeros((chunk, chunk), F32)
    dB = jnp.zeros((chunk, N), F32)
    dC = jnp.zeros((chunk, N), F32)
    dcols = jnp.zeros((chunk, NUM_LANES), F32)
    for p in range(hb // k):
        at = slice(p * width, (p + 1) * width)
        xa, dy = a_ref[:, at], dm_ref[:, at]
        a32, dy32 = xa.astype(F32), dy.astype(F32)
        d_lanes = d_ref[:, at]
        hp, dhp = entry_ref[0, at, :], dh_scr[at, :]
        cums = [cols[:, p * k + j:p * k + j + 1] for j in range(k)]
        dts = [cols[:, hb + p * k + j:hb + p * k + j + 1] for j in range(k)]
        lasts = [c[chunk - 1:chunk, :] for c in cums]
        enters = _by_head([jnp.exp(c) * before for c in cums], width, head_dim)
        # what of a row reaches the state that leaves, without its dt
        reach = _by_head([jnp.exp(jnp.minimum(l - c, 0.0)) * after
                          for c, l in zip(cums, lasts)], width, head_dim)
        dt_lanes = _by_head(dts, width, head_dim)
        carried = [jnp.exp(l) * before[chunk - 1:chunk, :] for l in lasts]
        # U: da without dt and D, the state's part first (V) and then a head's own
        V = reach * _dot(Bm, dhp.astype(dtype), NT)
        U = V
        inside = []
        for j in range(k):
            h = p * k + j
            mine = (lane >= j * head_dim) & (lane < (j + 1) * head_dim)
            decay_t = jnp.exp(jnp.minimum(rows[h:h + 1, :] - cums[j], 0.0))
            Uj = _dot((St * decay_t).astype(dtype), dy)
            U = U + (Uj if k == 1 else jnp.where(mine, Uj, 0.0))
            a_j = xa if k == 1 else jnp.where(mine, xa, jnp.zeros_like(xa))
            dS_j = _dot(a_j, dy, NT) * decay_t * dts[j]
            dSt = dSt + dS_j
            # what the decays inside the chunk get: each pair's dK K flows INTO its
            # query's row and OUT of its key's, both sums taken here in float32 (a
            # difference of row sums through a bfloat16 ``m`` cancels badly: the
            # published kernels' "stable" form is this one)
            G = St * dS_j
            flows_in = jnp.broadcast_to(jnp.sum(G, axis=0, keepdims=True),
                                        (NUM_LANES, chunk)).T[:, :1]
            inside.append(flows_in - jnp.sum(G, axis=1, keepdims=True))
        da_ref[:, at] = (dt_lanes * U + d_lanes * dy32).astype(da_ref.dtype)
        # the entry state's part of the result, made again in float32
        s_in = _head_sums(dy32 * enters * _dot(Cm, hp.astype(dtype), NT), k, head_dim)
        s_da = _head_sums(dy32 * a32, k, head_dim)
        s_u = _head_sums(a32 * U, k, head_dim)
        s_v = _head_sums(a32 * V, k, head_dim)
        s_state = _head_sums(jnp.sum(dhp * hp, axis=1, keepdims=True), k, head_dim, axis=0)
        for j in range(k):
            h = p * k + j
            at_last = (jnp.sum(dts[j] * s_v[j], axis=0, keepdims=True)
                       + carried[j] * s_state[j])
            dcum = (inside[j] + s_in[j] - dts[j] * s_v[j]
                    + jnp.where(row == chunk - 1, at_last, 0.0))
            dcols = jnp.where(out_lane == h, dcum, dcols)
            dcols = jnp.where(out_lane == hb + h, s_u[j], dcols)
            dcols = jnp.where(out_lane == 2 * hb + h, s_da[j], dcols)
        dye = dy32 * enters
        dC = dC + _dot(dye.astype(dtype), hp.astype(dtype))
        dB = dB + _dot((a32 * reach * dt_lanes).astype(dtype), dhp.astype(dtype))
        dh_scr[at, :] = (_by_head(carried, width, head_dim, axis=0) * dhp
                         + _dot(dye.T.astype(dtype), Cm))
    dSt = jnp.where(mask_t, dSt, 0.0)
    db_ref[0] = dB + _dot(dSt.astype(dtype), Cm)
    dc_ref[0] = dC + _dot(dSt.T.astype(dtype), Bm)
    dcols_ref[0] = dcols


def tile_vmem_bytes(chunk: int, hb: int, head_dim: int, states: int, itemsize: int = 2,
                    *, backward: bool) -> int:
    """Upper estimate of the VMEM one grid step holds: the double-buffered
    blocks, the carried state, and a dozen ``[Q, Q]`` float32 temporaries."""
    wide, bc, small = chunk * hb * head_dim, chunk * states, chunk * NUM_LANES * 4
    state = hb * head_dim * states * 4
    squares = 12 * chunk * chunk * 4
    if backward:
        return (2 * (3 * wide * itemsize + 2 * bc * itemsize + 2 * bc * 4 + 3 * small + state)
                + state + squares + 8 * wide * 4)
    return 2 * (2 * wide * itemsize + 2 * bc * itemsize + 2 * small + state) \
        + state + squares + 4 * wide * 4


def _params(vmem: int, interpret: bool):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(VMEM_CAP, max(vmem + (8 << 20), 32 << 20))),
        interpret=interpret)


def _fwd_call(a, cols, rows, B, C, d_lanes, *, chunk: int, hb: int, head_dim: int,
              groups: int, interpret: bool):
    """-> (``m`` ``[R, H x P]``, the chunks' entry states ``[chunks, H x P, N]``
    float32)."""
    R, width = a.shape
    N = B.shape[1] // groups
    tiles = width // (hb * head_dim)
    per_group = tiles // groups
    wide = pl.BlockSpec((chunk, hb * head_dim), lambda i, c: (c, i))
    shared = pl.BlockSpec((chunk, N), lambda i, c: (c, i // per_group))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, head_dim=head_dim, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(tiles, R // chunk),
            in_specs=[wide,
                      pl.BlockSpec((1, chunk, NUM_LANES), lambda i, c: (i, c, 0)),
                      pl.BlockSpec((1, rows.shape[1], chunk), lambda i, c: (i, 0, c)),
                      shared, shared,
                      pl.BlockSpec((1, hb * head_dim), lambda i, c: (0, i))],
            out_specs=[wide,
                       pl.BlockSpec((1, hb * head_dim, N), lambda i, c: (c, i, 0))],
            scratch_shapes=[pltpu.VMEM((hb * head_dim, N), F32)]),
        out_shape=[jax.ShapeDtypeStruct((R, width), a.dtype),
                   jax.ShapeDtypeStruct((R // chunk, width, N), F32)],
        name="ssd_fwd",
        **_params(tile_vmem_bytes(chunk, hb, head_dim, N, a.dtype.itemsize,
                                  backward=False), interpret),
    )(a, cols, rows, B, C, d_lanes)


def _bwd_call(a, cols, rows, B, C, d_lanes, entry, dm, *, chunk: int, hb: int,
              head_dim: int, groups: int, interpret: bool):
    """-> (da ``[R, H x P]``, the gradient's columns ``[tiles, R, 128]`` (lanes: d
    cum, d dt and D's row sums, a head each), dB and dC a tile ``[tiles, R, N]``),
    float32 but the first."""
    R, width = a.shape
    N = B.shape[1] // groups
    nc = R // chunk
    tiles = width // (hb * head_dim)
    per_group = tiles // groups
    back = lambda c: nc - 1 - c
    wide = pl.BlockSpec((chunk, hb * head_dim), lambda i, c: (back(c), i))
    shared = pl.BlockSpec((chunk, N), lambda i, c: (back(c), i // per_group))
    part = pl.BlockSpec((1, chunk, N), lambda i, c: (i, back(c), 0))
    columns = pl.BlockSpec((1, chunk, NUM_LANES), lambda i, c: (i, back(c), 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, head_dim=head_dim, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(tiles, nc),
            in_specs=[wide, columns,
                      pl.BlockSpec((1, rows.shape[1], chunk), lambda i, c: (i, 0, back(c))),
                      shared, shared,
                      pl.BlockSpec((1, hb * head_dim), lambda i, c: (0, i)),
                      pl.BlockSpec((1, hb * head_dim, N), lambda i, c: (back(c), i, 0)),
                      wide],
            out_specs=[wide, columns, part, part],
            scratch_shapes=[pltpu.VMEM((hb * head_dim, N), F32)]),
        out_shape=[jax.ShapeDtypeStruct((R, width), a.dtype),
                   jax.ShapeDtypeStruct((tiles, R, NUM_LANES), F32),
                   jax.ShapeDtypeStruct((tiles, R, N), F32),
                   jax.ShapeDtypeStruct((tiles, R, N), F32)],
        name="ssd_bwd",
        **_params(tile_vmem_bytes(chunk, hb, head_dim, N, a.dtype.itemsize,
                                  backward=True), interpret),
    )(a, cols, rows, B, C, d_lanes, entry, dm)


def _over_lanes(D, head_dim: int):
    """``D`` ``[H]`` as the launches read it: a head's scalar over its lanes."""
    return jnp.repeat(D.astype(F32), head_dim)[None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _core(how: Tuple[int, int, int, bool], a, dt, cum, seg, B, C, D):
    return _core_fwd(how, a, dt, cum, seg, B, C, D)[0]


def _core_fwd(how, a, dt, cum, seg, B, C, D):
    chunk, hb, groups, interpret = how
    head_dim = a.shape[1] // dt.shape[1]
    cols, rows = _layouts(dt, cum, seg, hb)
    m, entry = _fwd_call(a, cols, rows, B, C, _over_lanes(D, head_dim), chunk=chunk, hb=hb,
                         head_dim=head_dim, groups=groups, interpret=interpret)
    # named so that a rematerialised block's backward need not run the forward again
    m = checkpoint_name(m, "ssd_m")
    entry = checkpoint_name(entry, "ssd_state")
    return m, (a, dt, cum, seg, B, C, D, entry)


def _core_bwd(how, res, dm):
    chunk, hb, groups, interpret = how
    a, dt, cum, seg, B, C, D, entry = res
    R, H = dt.shape
    head_dim, N = a.shape[1] // H, B.shape[1] // groups
    cols, rows = _layouts(dt, cum, seg, hb)
    da, dcols, dB, dC = _bwd_call(
        a, cols, rows, B, C, _over_lanes(D, head_dim), entry, dm.astype(a.dtype), chunk=chunk, hb=hb,
        head_dim=head_dim, groups=groups, interpret=interpret)
    heads = lambda x: x.transpose(1, 0, 2).reshape(R, H)        # [tiles, R, hb] -> [R, H]
    shared = lambda x: jnp.sum(x.reshape(groups, -1, R, N), axis=1).transpose(
        1, 0, 2).reshape(R, groups * N)
    return (da, heads(dcols[..., hb:2 * hb]).astype(dt.dtype),
            heads(dcols[..., :hb]).astype(cum.dtype), None,
            shared(dB).astype(B.dtype), shared(dC).astype(C.dtype),
            jnp.sum(heads(dcols[..., 2 * hb:3 * hb]), axis=0).astype(D.dtype))


_core.defvjp(_core_fwd, _core_bwd)


def ssd_kernel(a, dt, A, B, C, D, first, groups: int = 1, *, chunk: int = CHUNK,
               tile: Optional[int] = None, interpret: Optional[bool] = None):
    """The kernel route (module docstring); ``tile``: the heads a grid step takes
    (None: `choose_tile`'s), ``interpret``: None, off the TPU. The cumulative
    log-decays inside a chunk and the documents' count are made here, by XLA."""
    rows, H = a.shape[0], dt.shape[1]
    head_dim = a.shape[1] // H
    if tile is None:
        tile = choose_tile(H, head_dim, groups)
    if tile is None or (H // groups) % tile or 3 * tile > NUM_LANES:
        raise ValueError(f"no tile of heads for {H} heads of {head_dim} in {groups} "
                         f"groups (given {tile})")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    a, dt, B, C, first = _pad_rows(chunk, a, dt.astype(F32), B, C, first.astype(jnp.int32))
    cum = _log_decays(_chunked(dt * A.astype(F32), chunk), _chunked(first, chunk),
                      axis=1).reshape(dt.shape)
    seg = _by_chunk_cumsum(first, chunk).astype(F32)
    m = _core((chunk, tile, groups, bool(interpret)), a, dt, cum, seg, B, C, D.astype(F32))
    return m[:rows]


def xla_chunk(published: int) -> int:
    """The XLA route's chunk for a configuration whose kernels were published
    with chunks of ``published`` rows: `XLA_CHUNK`, or the published one where it
    is less (a toy's rows then still cross chunks)."""
    return min(XLA_CHUNK, published)


def ssd(a, dt, A, B, C, D, first, groups: int = 1, *, route: Optional[str] = None,
        devices: int = 1, published_chunk: int = XLA_CHUNK):
    """The module docstring's core by `choose_route` (or ``route`` given)."""
    if route is None:
        H = dt.shape[1]
        route = choose_route(a.shape[0], H, a.shape[1] // H, B.shape[1] // groups, groups,
                             jax.default_backend(), devices)
    if route == "kernel":
        return ssd_kernel(a, dt, A, B, C, D, first, groups)
    return ssd_xla(a, dt, A, B, C, D, first, groups, xla_chunk(published_chunk))
