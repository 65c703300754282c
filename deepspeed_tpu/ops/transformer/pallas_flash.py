"""In-repo Pallas TPU flash attention — forward AND backward kernels.

The training-attention slot's fast path on a TPU from sequence 384 up. The
pair supports the full feature matrix the XLA reference path
(`attention._xla_attention`) already has — causal (bottom-right aligned via
``q_offset``), GQA-NATIVE (K/V stay at kv_heads), sliding window (shared
``sliding_window_allowed`` semantics), segment ids, ALiBi — and one mask the
XLA path builds densely, block diffusion's (below), with fp32
accumulation and a saved row LSE residual, bound with ``jax.custom_vjp`` so
the backward is blockwise too (no O(S^2) score re-materialization: backward
FLOPs are recomputed per tile, memory stays O(S) + the LSE).

Two kernels: ``flash_fwd`` and ONE fused ``flash_bwd`` that recomputes each
tile's probabilities once and takes dq, dk and dv from them (five matmuls,
one exp a logit; a dq kernel beside a dk/dv kernel paid seven and two). The
backward works on TRANSPOSED tiles S^T = K Q^T: per-query statistics (LSE,
di) are then rows that broadcast over sublanes, so they travel as
``[.., 1, Sq]`` rows and no ``[.., Sq, 128]`` lane-replicated copy of them
is ever written to HBM, and dk/dv need no transposed-left matmul. Tiles
come from the shape (:func:`choose_tiles`). dk and dv accumulate in VMEM over
a k-block's q-steps; dq belongs to the q-block, and how it leaves follows the
k-blocks a q-block meets (:func:`dq_mode`): one, and dq is the kernel's own
output in q's dtype; a few (``DQ_SUMMED_PARTIALS``: a static window's reach,
a row of two to four k-blocks), and every pair writes a float32 partial that
the caller sums; more, and dq is ONE float32 array in HBM that the pairs
which run read, add to and write back with the kernel's own copies (k-blocks
in ascending order, one rounding to q's dtype at the end): a skipped pair
touches nothing, nothing is summed afterwards, and a launch is one launch
however long its rows.

``q_offset`` and ``window`` ride scalar prefetch (SMEM), so they may be
TRACED values — the same compiled kernel serves the main training call
(offset 0), the Ulysses post-all-to-all call, and ring attention's per-hop
calls (offset ``(rank - owner) * s_local``, possibly negative = hop fully
in the future). The with-LSE entry point returns the per-row logsumexp so
ring attention can accumulate partial softmax state across ppermute hops
exactly (see ``sequence/ring_attention.py``).

A window that is a Python int when the launch is built, on the training
call (no ``q_offset``, as many keys as queries), is STATIC
(``FlashConfig.window``): both grids then hold only the blocks a window
can reach (a q-block's k-steps run from the block of its first row's
oldest visible key to the block of its diagonal, a k-block's q-steps the
other way round), so a sliding layer fetches and multiplies a window's
worth of keys however long the sequence is; the tiles are chosen knowing
the window and the launches are named ``flash_fwd_window`` /
``flash_bwd_window``. A traced window keeps the whole-sequence grids and
skips a tile's work alone.

The block-diffusion mask (``blockdiff=b``; BD3-LM's vectorised training
form, ``attention.blockdiff_attention``): the ``2 L`` query rows are a clean
and a noised copy of ``L`` positions, the keys the clean copy's. With ``b`` a
power of two a row's LAST visible key is ``pos | (b - 1)`` on the clean half
(its own block whole) and ``(pos | (b - 1)) - b`` on the noised half
(strictly earlier blocks), a limit that rises with the row inside each half:
block skipping, the wholly-visible test and the tile's compare keep the
causal mask's form with that limit in ``q_pos``'s place (``_block_limits``,
``_tile_logits``: ONE definition for the forward and the backward), a q-block
lies in one half, and the launches are named ``flash_fwd_blockdiff`` /
``flash_bwd_blockdiff``. It composes with segment ids (packed documents) and
grouped heads, and with nothing else: a window, ALiBi or ``q_offset`` beside
it is refused. The noised copy's own blocks (``b x b`` squares on a diagonal,
far narrower than any tile) are an einsum outside this module, joined by
``merge_partials``; a noised row with no clean key (a document's first block)
leaves here 0 with the sentinel LSE.

EVA's summaries (``summaries=(W, per)``; ``attention.eva_attention``): the
keys are one learned summary a chunk of the queries' row, ``per`` a window of
``W`` positions, and a row of window ``w`` sees the summaries of the windows
before it, keys ``0 .. w x per - 1``. ``W`` is a multiple of both q tiles
(``launch_tiles``), so a q-block has ONE limit, a scalar, in the place of the
block-diffusion mask's two (``_block_limits``; ``_limited`` says a launch has
such a limit): skipping, the wholly-visible test and the edge tile's compare
are the same code. A row of the first window leaves 0 with the sentinel LSE.
It composes with nothing else (no ids, window, ALiBi or ``q_offset``). EVA's
exact keys are a plain causal launch over the row folded to one batch row a
window. ``tag`` names a launch ``flash_fwd_<tag>`` / ``flash_bwd_<tag>`` and
its two residuals ``attn_o_<tag>`` / ``attn_lse_<tag>``.

A learned selection (``selected=``; ``attention.selected_attention``): a
causal launch over as many keys as queries with one operand more, the BITS of
"the query's selection holds the key" (one selection for all heads; int8
``[batch, Sq / 8, Sk]`` in ``attention.pack_selection``'s layout: bit planes of
128 queries): the first mask here that is data and not a rule of positions
and ids. A tile reads the packed block that holds its queries' planes (the
backward the transposed operand's, which ``_flash_bwd`` makes: a byte
transpose of the packed array), unpacks it (``attention.unpack_selection``: an
AND a plane, the planes side by side) and ANDs it with the causal and
same-document compare in ``_tile_logits``; tiles are skipped by position and
documents as without it, and a tile that runs is a full tile. Tiles and ``dq_mode`` are a
full causal layer's; the launches are named ``flash_fwd_dsa`` /
``flash_bwd_dsa``, their residuals ``attn_o_dsa`` / ``attn_lse_dsa``. It
composes with segment ids and grouped heads, and with nothing else.

The table of documents (a launch with segment ids; none is built, and no
operand added, without): ``_prepare`` reduces the ids once to each block's
LOWEST and HIGHEST id (``block_ranges``: for the forward's tiles and for the
backward's, the q side from ``q_segment_ids`` where given, under
``blockdiff`` the ``2 L`` rows'), a few hundred int32 a kernel that ride
scalar prefetch (SMEM) beside ``info`` and ``slopes``, so kernel bodies and
index maps both read them, and are residuals of the ``custom_vjp`` like the
ids. "Does q-block i have ANY visible key in k-block j" (``_should_run``, the
one definition behind ``_for_visible_tile``, the forward's ``k_blk`` and the
backward's ``q_blk``) then also
asks ``k_hi[j] >= q_lo[i] and k_lo[j] <= q_hi[i]``. Two ranges that do not
meet hold no equal pair, whatever the order of the ids, so no pair of such a
tile would pass ``_tile_logits``' compare and skipping it changes no bit
(a masked tile added exact zeros): the test is SOUND for any ids (ring hops,
random ids) and TIGHT for packed documents, whose ids rise along the row: a
k-block wholly in an earlier document is neither fetched nor multiplied. A
tile the test lets through keeps its segment compare. ``tiles_run`` counts
what the table spares, by the same predicate over every (i, j) at once.

Runs in interpret mode off-TPU (``pl.pallas_call(interpret=True)``) so the
CPU tier-1 tests validate numerics of the same program the chip runs.

Layout conventions (`launch_layout`; MXU-aligned tiles; ``b`` the batch row,
``kvh`` the key head, ``g`` the query head's place in its group of G):
  the key side leads with its heads in EITHER layout (GQA-folded)
    k, v, dk, dv  [B, Sk, kvH, D] -> [B*kvH, Sk, D]      (a transpose each)
  the query side by ``FlashConfig.layout``:
  ``"rows"`` (a head dim that is a multiple of the 128 lanes, query heads
  grouped over fewer key heads): where the
  projections leave it, heads side by side in a row; the index maps pick a
  head's 128-aligned columns, and no transpose stands before a launch or
  after it
    q, o, do, dq  [B, Sq, H, D]   -> [B, 1, Sq, H*D]     (a reshape), a block
                                     ``(1, 1, bq, D)`` at column block kvh*G + g
  ``"heads"`` (a narrower head: ``(bq, 64)`` is half a lane tile; or as many
  key heads as query heads): heads leading, a transpose before each launch and
  after it
    q, o, do, dq  [B, Sq, H, D]   -> [B*kvH, G, Sq, D]
  either way a q-side operand has rank 4 and a k-side one rank 3, the grids
  run over the folded rows ``b*kvH + kvh`` (one axis in ``"heads"``, the two
  axes ``b`` and ``kvh`` in ``"rows"``: `_Row`; the same tiles, skipped steps
  and order of accumulation) and
    lse, di                       -> [B*kvH, G, 1, Sq]  (fp32 rows)
  A dq that leaves a launch in float32 (`dq_mode` ``summed`` / ``in_place``)
  leads with its heads in either layout, and by rows ``di`` is a launch of its
  own (``flash_delta``). Why the keys are NOT read by rows, on the v5e (PR 51;
  docs/KERNELS.md): a k-block is fetched every grid step, and as 4 KB pieces
  of ``[B, Sk, kvH*D]`` the forward ran 15 % (a window's) to 27 % (a full
  layer's) slower than over one run of HBM; a q-block is fetched once a row of
  steps, and costs nothing that shows.
Inside the forward the running max and sum are lane-replicated
``[block_q, 128]`` scratch (lane replication is free; the one sublane->lane
transpose happens once a q block, when the LSE row is stored).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attention

NUM_LANES = 128
NUM_SUBLANES = 8
# Finite mask value (not -inf): keeps every exp()/max() chain NaN-free.
# A row that never sees an unmasked key ends with l == 0 and LSE stored as
# MASK_VALUE — a finite sentinel the ring-hop merge can exponentiate
# (exp(MASK - anything_real) underflows to exactly 0.0 in fp32).
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
# Floor used inside exponents: exp(MASK_VALUE - HALF_MASK) == 0 exactly,
# while any real logit (|s| << 1e30) keeps its exact max.
HALF_MASK = MASK_VALUE * 0.5


Tile = Tuple[int, int]  # (block_q, block_k)


@dataclasses.dataclass(frozen=True)
class FlashTiles:
    """The (block_q, block_k) of the forward and of the backward kernel,
    and the scoped VMEM they ask the compiler for (None = its default)."""
    fwd: Tile
    bwd: Tile
    vmem_limit_bytes: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class FlashConfig:
    """Static kernel configuration (hashable: rides custom_vjp
    nondiff_argnums and the pallas_call trace cache)."""
    causal: bool
    scale: float
    use_seg: bool
    use_alibi: bool
    use_window: bool
    kv_heads: int
    tiles: FlashTiles
    interpret: bool
    # the window where it is known when the launch is built (None: the
    # window, if any, is the traced scalar in SMEM): the grids are cut to it
    window: Optional[int] = None
    # the block-diffusion mask, ``(block length b, data tokens L)``: the 2L
    # query rows are a clean and a noised copy of L positions, the L keys
    # the clean copy's (module docstring); None: causal / window
    blockdiff: Optional[Tuple[int, int]] = None
    # EVA's far keys, ``(window W, summaries a window)``: the keys are one
    # summary a chunk of the queries' row, and a q-block (its rows lie in one
    # window: W is a multiple of both q tiles) sees the summaries of the
    # windows before its own, keys ``0 .. (first row // W) x per - 1``
    summaries: Optional[Tuple[int, int]] = None
    # a name of its own for the launches (``flash_fwd_<tag>``) and their two
    # residuals, for whoever reads a trace or lists kept names; one of
    # ``TAGS``, or None: by the mask
    tag: Optional[str] = None
    # a learned selection of keys (``attention.selected_attention``): the
    # launch has one operand more, the selection's bits (int8 ``[batch, Sq / 8,
    # Sk]``, ``attention.pack_selection``; the backward's transposed); a tile
    # unpacks its block of it beside the causal and same-document rule
    selected: bool = False
    # where the query side's heads lie (q, o, do, dq; module docstring,
    # `launch_layout`): ``"rows"``: ``[B, 1, S, heads * D]``, a head picked by
    # the index maps; ``"heads"``: heads leading, ``[B * kv_heads, G, S, D]``
    layout: str = "heads"
    # the VALUE heads' width where it is not the keys' (latent attention: keys
    # and queries of nope + rope = 192, values of 128): v, ``o`` and their
    # gradients are this wide, q, k and theirs the keys' width; None: one width
    v_dim: Optional[int] = None


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """Broadcast a lane-replicated [rows, 128] buffer to n columns. Every
    lane holds the same per-row value, so slicing or tiling are both
    exact."""
    if n <= NUM_LANES:
        return x[:, :n]
    if n % NUM_LANES:
        raise NotImplementedError(f"width {n} not a multiple of {NUM_LANES}")
    return jnp.concatenate([x] * (n // NUM_LANES), axis=1)


# The blocks a STATIC window reaches (offset 0, as many keys as queries).
# Each takes Python ints (grid sizes) or traced ints (index maps, kernels).

def _bound(pick, a, b):
    """``max`` / ``min`` of Python ints as a Python int, else traced."""
    if isinstance(a, int) and isinstance(b, int):
        return pick(a, b)
    return (jnp.maximum if pick is max else jnp.minimum)(a, b)


def _first_k_block(tile: Tile, i, window: int):
    """The lowest k-block that holds a key q-block ``i`` sees."""
    bq, bk = tile
    return _bound(max, i * bq - (window - 1), 0) // bk


def _last_k_block(tile: Tile, i):
    """The k-block of q-block ``i``'s last row's own key (the diagonal)."""
    bq, bk = tile
    return ((i + 1) * bq - 1) // bk


def _first_q_block(tile: Tile, j):
    """The lowest q-block that holds a row which sees k-block ``j``."""
    bq, bk = tile
    return (j * bk) // bq


def _last_q_block(tile: Tile, j, window: int, nq: int):
    """The highest q-block with a row inside the window of k-block ``j``'s
    last key."""
    bq, bk = tile
    return _bound(min, ((j + 1) * bk + window - 2) // bq, nq - 1)


def window_steps(tile: Tile, window: int, nq: int, nk: int) -> Tuple[int, int]:
    """(k-steps a q-block, q-steps a k-block) of the grids under a static
    ``window``: the most blocks any one block reaches."""
    k_steps = max(_last_k_block(tile, i) - _first_k_block(tile, i, window)
                  for i in range(nq)) + 1
    q_steps = max(_last_q_block(tile, j, window, nq) - _first_q_block(tile, j)
                  for j in range(nk)) + 1
    return k_steps, q_steps


# The block-diffusion mask (``FlashConfig.blockdiff = (b, L)``). Query row r
# of the 2L is position ``r`` of the clean copy (r < L) or ``r - L`` of the
# noised one; with b a power of two its LAST visible clean key is
# ``pos | (b - 1)`` for a clean row (its own block whole) and
# ``(pos | (b - 1)) - b`` for a noised one (strictly earlier blocks; below 0:
# none). A q-block lies in one half (its rows divide L), so the half is a
# scalar a tile, and the causal compare ``q_pos >= k_pos`` keeps its form
# with the limit in ``q_pos``'s place.

def _half_of(cfg: FlashConfig, bq: int, i):
    """(position of q-block ``i``'s first row in its half, what a row's
    limit loses there: 0 on the clean half, b on the noised)."""
    b, L = cfg.blockdiff
    noised = i * bq >= L
    return i * bq - jnp.where(noised, L, 0), jnp.where(noised, b, 0)


def _block_limits(cfg: FlashConfig, bq: int, i):
    """The last visible key of q-block ``i``'s first row and of its last
    (``bq`` is a multiple of b: a block of positions is never cut). Over
    EVA's summaries both are the q-block's one limit."""
    if cfg.summaries is not None:
        window, per = cfg.summaries
        limit = lax.div(i, window // bq) * per - 1
        return limit, limit
    b = cfg.blockdiff[0]
    first, lost = _half_of(cfg, bq, i)
    return first + b - 1 - lost, first + bq - 1 - lost


def _limited(cfg: FlashConfig) -> bool:
    """Whether a row's last visible key is a limit of its own, in
    ``q_pos``'s place (`_block_limits`): the block-diffusion mask, EVA's
    summaries."""
    return cfg.blockdiff is not None or cfg.summaries is not None


class _BlockDocs(NamedTuple):
    """One batch row's line of a launch's table of documents (module
    docstring; :func:`block_ranges` makes it): ``ref`` is the table flat,
    int32 ``[batch rows x 2 (nq + nk)]``, in SMEM for a kernel or an index
    map (a plain array for :func:`tiles_run`), ``row`` the batch row
    (folded row // kv_heads), ``nq`` / ``nk`` the launch's blocks."""
    ref: Any
    row: Any
    nq: int
    nk: int

    def meet(self, i, j):
        """Whether q-block ``i``'s range of segment ids and k-block ``j``'s
        meet. Where they do not, no id of the one equals an id of the other,
        whatever the order of the ids: the tile holds no pair that passes
        ``_tile_logits``' compare. (A step of a static window's grid may name
        a block past the last: it reads the last's line and is hidden by
        position.)"""
        nq, nk = self.nq, self.nk
        # (``lax`` and not ``jnp``: the test is traced in every index map and
        # kernel body of every launch, a dozen times a flash pair)
        q = lax.add(lax.mul(self.row, 2 * (nq + nk)), lax.min(i, nq - 1))
        k = lax.add(lax.mul(self.row, 2 * (nq + nk)), lax.min(j, nk - 1) + 2 * nq)
        q_lo, q_hi = self.ref[q], self.ref[lax.add(q, nq)]
        k_lo, k_hi = self.ref[k], self.ref[lax.add(k, nk)]
        return lax.bitwise_and(lax.ge(k_hi, q_lo), lax.le(k_lo, q_hi))


def _split_prefetch(cfg: FlashConfig, refs):
    """A kernel's arguments as (the scalar-prefetch operands ``(info,
    slopes[, table])``, the rest): the table is there where the launch has
    segment ids."""
    n = 3 if cfg.use_seg else 2
    return refs[:n], refs[n:]


def _docs_of(cfg: FlashConfig, prefetch, row: "_Row", blocks) -> Optional[_BlockDocs]:
    """The documents of a grid step's ``row`` among a launch's
    scalar-prefetch operands ``(info, slopes[, table])``; None for a launch
    without ids."""
    if not cfg.use_seg:
        return None
    return _BlockDocs(prefetch[2], row.batch, *blocks)


def _should_run(cfg: FlashConfig, tile: Tile, i, j, info_ref,
                docs: Optional[_BlockDocs] = None):
    """Whether q-block i has ANY unmasked key in k-block j (block-level
    flop skip): by position, info = [q_offset, window] (traced scalars in
    SMEM), and, where the launch has segment ids, by the two blocks' ranges
    of documents."""
    if not cfg.causal:
        return True if docs is None else docs.meet(i, j)
    bq, bk = tile
    if _limited(cfg):
        # the limit rises with the row inside each half: the block's last
        # row has its highest
        run = _block_limits(cfg, bq, i)[1] >= j * bk
    else:
        q_off = info_ref[0]
        # last q row of the block sits at or after the block's first key
        run = (q_off + (i + 1) * bq - 1) >= (j * bk)
        if cfg.use_window:
            w = info_ref[1]
            # first q row within window of the block's last key
            run = run & ((w <= 0) | ((q_off + i * bq) - (j * bk + bk - 1) < w))
    return run if docs is None else run & docs.meet(i, j)


def _fully_visible(cfg: FlashConfig, tile: Tile, i, j, info_ref):
    """Whether EVERY key of k-block j is visible to every row of q-block i
    under the causal (and window) mask: such a tile needs no positions,
    compare or select (its segment compare, where it has ids, stays). Only
    asked of causal configurations."""
    bq, bk = tile
    if _limited(cfg):
        return _block_limits(cfg, bq, i)[0] >= j * bk + bk - 1
    q_off = info_ref[0]
    # first q row sits at or after the block's last key
    full = (q_off + i * bq) >= (j * bk + bk - 1)
    if cfg.use_window:
        w = info_ref[1]
        # last q row within window of the block's first key
        full = full & ((w <= 0) | ((q_off + (i + 1) * bq - 1) - j * bk < w))
    return full


def _for_visible_tile(cfg: FlashConfig, tile: Tile, i, j, info_ref, docs,
                      body, also=None):
    """Run ``body(positional)`` for a tile with any unmasked key
    (``_should_run``); tiles wholly below the diagonal run it without the
    positional mask. ``also``: a further condition of the step (a static
    window's grid: the block the step names exists)."""
    if not cfg.causal and docs is None:
        body(False)
        return
    run = _should_run(cfg, tile, i, j, info_ref, docs)
    if also is not None:
        run = run & also
    if not cfg.causal:
        pl.when(run)(lambda: body(False))
        return
    full = _fully_visible(cfg, tile, i, j, info_ref)
    pl.when(run & full)(lambda: body(False))
    pl.when(run & jnp.logical_not(full))(lambda: body(True))


def _tile_logits(cfg: FlashConfig, tile: Tile, q, k, i, j, info_ref,
                 slopes_ref, head_idx, seg_col, seg_row, *,
                 positional: bool, transposed: bool = False, sel=None,
                 rows: Optional[int] = None):
    """Masked, scaled fp32 logits for one tile — ONE definition shared by
    the forward and the backward kernel so the recomputed tiles cannot
    diverge from the forward's. ``transposed`` gives S^T = K Q^T
    ``[block_k, block_q]`` (the backward's orientation: every per-query
    statistic is then a ROW, which broadcasts over sublanes for free).
    ``seg_col`` is the lane-replicated ``[rows, 128]`` segment ids of the
    tile's row axis, ``seg_row`` the ``[8, cols]`` ids of its column axis.
    ``positional`` False leaves out the causal/window mask (the caller has
    shown the tile to be wholly visible). ``sel``: the packed block of a
    selection's operand that holds the tile's queries (``FlashConfig.selected``;
    of a launch over ``rows`` queries), in the tile's own orientation: data, so
    it is unpacked and compared in every tile that runs."""
    lhs, rhs = (k, q) if transposed else (q, k)
    s = lax.dot_general(lhs, rhs, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    if cfg.scale != 1.0:
        s = s * cfg.scale
    bq, bk = tile
    q_axis = 1 if transposed else 0
    mask = None
    if cfg.use_seg:
        mask = _lanes(seg_col, s.shape[1]) == seg_row[:1, :]
    if sel is not None:
        picked = _attention.unpack_selection(sel, rows, (bq, i), axis=q_axis)
        mask = picked if mask is None else mask & picked
    if cfg.use_alibi or (cfg.causal and positional):
        q_pos = (lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
                 + (i * bq + info_ref[0]))
        k_pos = lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis) + j * bk
        if cfg.use_alibi:
            # bias = slope * (key_pos - query_pos), the row-shifted
            # HF-BLOOM form the XLA path uses (softmax is shift-invariant
            # per row)
            slope = slopes_ref[head_idx]
            s = s + slope * (k_pos - q_pos).astype(jnp.float32)
        if cfg.causal and positional and cfg.summaries is not None:
            cm = _block_limits(cfg, bq, i)[1] >= k_pos
            mask = cm if mask is None else mask & cm
        elif cfg.causal and positional and cfg.blockdiff is not None:
            first, lost = _half_of(cfg, bq, i)
            at = lax.broadcasted_iota(jnp.int32, s.shape, q_axis) + first
            cm = ((at | (cfg.blockdiff[0] - 1)) - lost) >= k_pos
            mask = cm if mask is None else mask & cm
        elif cfg.causal and positional:
            cm = q_pos >= k_pos
            if cfg.use_window:
                w = info_ref[1]
                cm = cm & ((w <= 0) | ((q_pos - k_pos) < w))
            mask = cm if mask is None else mask & cm
    if mask is not None:
        s = jnp.where(mask, s, MASK_VALUE)
    return s


def _head_index(row: "_Row", g, G):
    """Global query-head index of group ``g`` of a grid step's ``row``: the
    ALiBi slope lookup, a head's columns."""
    return row.head * G + g


#: the tags a caller may give a launch (``FlashConfig.tag``): EVA's two, and
#: the two-width launch (``FlashConfig.v_dim``) of latent attention (``"mla"``:
#: keys wider than the values) and of differential attention (``"diff"``: a
#: pair's two value heads side by side, twice the keys' width)
TAGS = ("eva_local", "eva_far", "mla", "diff")


def _compiler_params(cfg: FlashConfig, semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=cfg.tiles.vmem_limit_bytes)


# Where a launch's query side lies (``FlashConfig.layout``; module docstring).
# A kernel body takes a ``(rows, D)`` tile either way, ``ref[0, 0]``; these say
# which block of which array that is, so the grids, the tiles and the order of
# every sum are one layout's as the other's.

LAYOUTS = ("rows", "heads")


def launch_layout(q_shape, k_shape) -> str:
    """The layout of ONE launch over ``[B, S, heads, D]`` operands, written
    HERE alone (the kernel, `_prepare`, and ``attention.plan``, which the
    counters read, both ask this): ``"rows"`` where a head's columns are whole
    lane tiles (``D % 128 == 0``: a block ``(1, bq, D)`` of ``[B, S, H*D]`` is
    made of whole 4 KB tiles of the array's own HBM layout) AND the query heads
    are grouped over fewer key heads; else ``"heads"``. At head dim 64 a ``(bq,
    64)`` block is half a lane tile. With as many key heads as query heads
    (EVA's launches among them) the key side, which leads with its heads either
    way, is half the bytes, so half the transposes stay, and on the v5e (PR 51;
    docs/KERNELS.md, PERF.md section 6) the 16 x 16-head cells read 0.16 % and
    0.37 % SLOWER by rows where the 32-over-4 cells read 0.06 to 1.3 % faster."""
    return "rows" if q_shape[3] % NUM_LANES == 0 and q_shape[2] > k_shape[2] else "heads"


def _dims(cfg: FlashConfig, q, k) -> Tuple[int, int, int, int, int]:
    """(folded rows ``B * kv_heads``, G, Sq, Sk, D) of a launch's operands."""
    BK, Sk, D = k.shape
    if cfg.layout == "rows":
        return BK, q.shape[3] // D // cfg.kv_heads, q.shape[2], Sk, D
    return BK, q.shape[1], q.shape[2], Sk, D


def _value_dim(cfg: FlashConfig, D: int) -> int:
    """The width of v, ``o`` and their gradients in a launch whose keys are
    ``D`` wide (``FlashConfig.v_dim``)."""
    return D if cfg.v_dim is None else cfg.v_dim


class _Row:
    """The folded row ``batch row x kv_heads + key head`` of one grid step,
    from the step's leading ids: ONE id in ``"heads"`` (the folded row itself,
    the axis the operands lead with), TWO in ``"rows"`` (batch row and key
    head, the operands' own axes: no index map or kernel body divides).
    Each property is a scalar expression made where it is asked for (never
    kept: a kernel body asks inside ``pl.when``)."""

    def __init__(self, cfg: FlashConfig, ids):
        self._kv_heads, self._ids = cfg.kv_heads, ids
        self._split = cfg.layout == "rows"

    @property
    def batch(self):
        return self._ids[0] if self._split else lax.div(self._ids[0], self._kv_heads)

    @property
    def head(self):
        return self._ids[1] if self._split else lax.rem(self._ids[0], self._kv_heads)

    @property
    def folded(self):
        return self._ids[0] * self._kv_heads + self._ids[1] if self._split else self._ids[0]


def _row_axes(cfg: FlashConfig, BK: int) -> Tuple[int, ...]:
    """The grid's leading axes, over the folded rows (`_Row`): in the same
    order and as many steps in either layout."""
    return (BK // cfg.kv_heads, cfg.kv_heads) if cfg.layout == "rows" else (BK,)


def _row_rank(cfg: FlashConfig) -> int:
    """How many of a grid step's ids are its row's (`_row_axes`)."""
    return 2 if cfg.layout == "rows" else 1


def _row(cfg: FlashConfig, ids):
    """(a grid step's `_Row`, its other ids) of an index map's or a kernel's
    ``ids``."""
    n = _row_rank(cfg)
    return _Row(cfg, ids[:n]), ids[n:]


def _by_row(cfg: FlashConfig, index):
    """The index map ``index(row, *the step's other ids, *prefetch)``."""
    def index_map(*ids):
        row, rest = _row(cfg, ids)
        return index(row, *rest)
    return index_map


def _step_ids(cfg: FlashConfig, rest: int):
    """A kernel body's `_row` of the grid's ids (``rest``: after the rows')."""
    return _row(cfg, [pl.program_id(a) for a in range(_row_rank(cfg) + rest)])


def _q_block(cfg: FlashConfig, G: int, rows: int, D: int, heads: bool = False):
    """(block shape, ``index(row, g, i)``) of q-block ``i`` of query head ``g``
    of a grid step's ``row``: a ``(rows, D)`` tile of q, o, do or dq, at
    ``[0, 0]`` of its block. ``heads``: of an array that leads with its heads
    whatever the layout (dq in float32, `_bwd_call`)."""
    if cfg.layout == "rows" and not heads:
        index = lambda row, g, i: (row.batch, 0, i, _head_index(row, g, G))
    else:
        index = lambda row, g, i: (row.folded, g, i, 0)
    return (1, 1, rows, D), index


def _kv_block(rows: int, D: int):
    """(block shape, ``index(row, j)``) of k-block ``j`` of a grid step's
    ``row``: a ``(rows, D)`` tile of k, v, dk or dv, which lead with their
    heads in either layout."""
    return (1, rows, D), lambda row, j: (row.folded, j, 0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, cfg: FlashConfig, G: int, nk: int, head_dim: int,
                blocks: Tuple[int, int]):
    """``refs``: the scalar-prefetch operands ``(info, slopes[, table])``,
    then q, k, v, the q and k segment ids, the two outputs and the scratch.
    ``nk``: the k-steps of the grid; ``blocks``: the launch's (q-blocks,
    k-blocks)."""
    prefetch, (q_ref, k_ref, v_ref, qseg_ref, kseg_ref, sel_ref, o_ref, lse_ref,
               m_scr, l_scr, acc_scr) = _split_prefetch(cfg, refs)
    info, slopes = prefetch[:2]
    row, (g, i, step) = _step_ids(cfg, 3)
    tile = cfg.tiles.fwd
    docs = _docs_of(cfg, prefetch, row, blocks)
    # the k-block of this step: under a static window the steps start at
    # the first block the q-block reaches (a step past its diagonal names a
    # block the causal mask hides whole, and is skipped as one)
    j = step if cfg.window is None else _first_k_block(tile, i, cfg.window) + step

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, MASK_VALUE, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _compute(positional):
        q = q_ref[0, 0]          # [bq, D]
        k = k_ref[0]             # [bk, D]
        v = v_ref[0]
        qseg = qseg_ref[0] if cfg.use_seg else None
        kseg = kseg_ref[0] if cfg.use_seg else None
        s = _tile_logits(cfg, tile, q, k, i, j, info, slopes,
                         _head_index(row, g, G), qseg, kseg,
                         positional=positional,
                         sel=sel_ref[0] if cfg.selected else None,
                         rows=blocks[0] * tile[0])
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.maximum(m_next, HALF_MASK)
        p = jnp.exp(s - _lanes(m_safe, s.shape[1]))
        alpha = jnp.exp(jnp.maximum(m_prev, HALF_MASK) - m_safe)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = (acc_scr[...] * _lanes(alpha, head_dim)
                        + lax.dot(p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))

    _for_visible_tile(cfg, tile, i, j, info, docs, _compute)

    @pl.when(step == nk - 1)
    def _store():
        l = l_scr[...]
        m_safe = jnp.maximum(m_scr[...], HALF_MASK)
        inv = jnp.where(l == 0.0, 0.0, 1.0 / jnp.where(l == 0.0, 1.0, l))
        o_ref[0, 0] = (acc_scr[...] * _lanes(inv, head_dim)).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, MASK_VALUE,
                        m_safe + jnp.log(jnp.where(l == 0.0, 1.0, l)))
        # the per-row statistic leaves as a ROW [1, bq]: one XLU transpose
        # per q block here saves the [.., Sq, 128] lane-replicated copy in
        # HBM that the backward would otherwise read back
        lse_ref[0, 0] = lse.T[:1]


def _fwd_call(cfg: FlashConfig, q, k, v, qseg_c, kseg_r, table, slopes, info,
              sel=None):
    """-> o laid out as q is (``cfg.layout``), lse [BK, G, 1, Sq] (fp32 rows).
    ``table``: the forward tiles' :func:`block_ranges` (None: a launch without
    ids); ``sel``: a selection's operand ``[B, Sq / 8, Sk]``
    (``FlashConfig.selected``)."""
    BK, G, Sq, Sk, D = _dims(cfg, q, k)
    Dv = _value_dim(cfg, D)
    tile = bq, bk = cfg.tiles.fwd
    blocks = nq, nk = Sq // bq, Sk // bk
    if cfg.window is not None:
        nk = window_steps(tile, cfg.window, nq, nk)[0]
    rows = _row_axes(cfg, BK)
    grid = rows + (G, nq, nk)
    by_row = functools.partial(_by_row, cfg)
    prefetch = (info, slopes) + (() if table is None else (table.reshape(-1),))

    def k_blk(row, i, j, prefetch):
        """The k-block step ``j`` of q-block ``i`` fetches; a step that
        computes nothing re-names a block already resident, or one that a
        whole run of such steps shares: no DMA, or one a run. (A row's
        skipped steps lie before its first visible document and past its
        diagonal; block 0 is what the row before it ended on.)"""
        docs = _docs_of(cfg, prefetch, row, blocks)
        if cfg.window is not None:
            last = _last_k_block(tile, i)
            blk = jnp.minimum(_first_k_block(tile, i, cfg.window) + j, last)
            return blk if docs is None else lax.select(docs.meet(i, blk), blk, last)
        if cfg.causal or docs is not None:
            j = lax.select(_should_run(cfg, tile, i, j, prefetch[0], docs), j, 0)
        return j

    q_block, q_at = _q_block(cfg, G, bq, D)
    kv_block, kv_at = _kv_block(bk, D)
    # (the value side's blocks: the same index maps over a width of its own)
    o_block, v_block = _q_block(cfg, G, bq, Dv)[0], _kv_block(bk, Dv)[0]

    q_idx = by_row(lambda row, g, i, j, *_: q_at(row, g, i))
    kv_idx = by_row(lambda row, g, i, j, *prefetch: kv_at(row, k_blk(row, i, j, prefetch)))
    in_specs = [
        pl.BlockSpec(q_block, q_idx),
        pl.BlockSpec(kv_block, kv_idx),
        pl.BlockSpec(v_block, kv_idx),
    ]
    if cfg.use_seg:
        in_specs += [
            pl.BlockSpec((1, bq, NUM_LANES),
                         by_row(lambda row, g, i, j, *_: (row.batch, i, 0))),
            pl.BlockSpec((1, NUM_SUBLANES, bk), by_row(
                lambda row, g, i, j, *prefetch: (row.batch, 0, k_blk(row, i, j, prefetch)))),
        ]
    else:
        in_specs += [None, None]
    if sel is None:
        in_specs.append(None)
    else:
        # the packed rows that hold the q-block's bit planes
        packed, shared = _attention.selection_tile(Sq, bq)
        in_specs.append(pl.BlockSpec(
            (1, packed, bk), by_row(lambda row, g, i, j, *prefetch: (
                row.batch, i // shared, k_blk(row, i, j, prefetch)))))

    out_specs = [
        pl.BlockSpec(o_block, q_idx),
        pl.BlockSpec((1, 1, 1, bq), by_row(lambda row, g, i, j, *_: (row.folded, g, 0, i))),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(q.shape[:3] + (q.shape[3] // D * Dv,), q.dtype),
        jax.ShapeDtypeStruct((BK, G, 1, Sq), jnp.float32),
    ]
    kernel = functools.partial(_fwd_kernel, cfg=cfg, G=G, nk=nk, head_dim=Dv,
                               blocks=blocks)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((bq, NUM_LANES), jnp.float32),
                pltpu.VMEM((bq, NUM_LANES), jnp.float32),
                pltpu.VMEM((bq, Dv), jnp.float32),
            ]),
        out_shape=out_shape,
        compiler_params=_compiler_params(
            cfg, ("parallel",) * (len(rows) + 2) + ("arbitrary",)),
        interpret=cfg.interpret,
        # a name of its own for each mask whose grids or compare are its own,
        # and for a caller's tag (the benchmark's readers find the launches
        # by it; literal, so that a test can list every kernel's names)
        name=("flash_fwd_eva_local" if cfg.tag == "eva_local"
              else "flash_fwd_eva_far" if cfg.tag == "eva_far"
              else "flash_fwd_mla" if cfg.tag == "mla"
              else ("flash_fwd_diff" if cfg.window is None
                    else "flash_fwd_diff_window") if cfg.tag == "diff"
              else "flash_fwd_dsa" if cfg.selected
              else "flash_fwd_blockdiff" if cfg.blockdiff is not None
              else "flash_fwd" if cfg.window is None else "flash_fwd_window"),
    )(*prefetch, q, k, v, qseg_c, kseg_r, sel)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


#: The most k-blocks a q-block may meet for dq to leave as one float32
#: partial a k-block that the caller sums (v5e, PR 44; docs/KERNELS.md): a
#: pair that adds in place pays 1.2 us for its two copies of the tile, a
#: summed partial 0.08 us a pair, and what in-place adding wins is the skipped
#: pairs (0.2 us for 0.85) and the memory. At 3 (a window of two tiles) the sum
#: read 1.15 ms a layer less, at 8 and 16 adding in place 0.8 to 5.2 ms less.
DQ_SUMMED_PARTIALS = 4


def dq_partials(sq: int, sk: int, tile: Tile, window: Optional[int] = None) -> int:
    """The k-blocks a q-block of the backward's ``tile`` meets: every one, or
    under a static ``window`` the most its reach holds."""
    nq, nk = sq // tile[0], sk // tile[1]
    return nk if window is None else window_steps(tile, window, nq, nk)[0]


def dq_mode(sq: int, sk: int, tiles: FlashTiles,
            window: Optional[int] = None) -> str:
    """How the backward of a launch over ``sq`` queries and ``sk`` keys makes
    dq, from the k-blocks a q-block of ``tiles.bwd`` meets (`dq_partials`):
    ``"one_block"`` where that is one (dq is the kernel's own output, in q's
    dtype), ``"summed"`` up to ``DQ_SUMMED_PARTIALS`` (a float32 partial a
    k-block met, which the caller sums), ``"in_place"`` past it (ONE float32
    array a launch, which the pairs that run read, add to and write back).
    ``_bwd_call`` goes by it."""
    met = dq_partials(sq, sk, tiles.bwd, window)
    if met == 1:
        return "one_block"
    return "summed" if met <= DQ_SUMMED_PARTIALS else "in_place"


def _bwd_kernel(*refs, cfg: FlashConfig, G: int, steps: int,
                blocks: Tuple[int, int], in_place: bool):
    """dq, dk and dv of one (k-block, q-block) tile from ONE recomputed
    P^T = exp(K Q^T - lse): five matmuls, none with a transposed left
    operand except dq's (one XLU transpose of dS^T). dk/dv accumulate in
    scratch over the groups and q-blocks of their k-block. dq belongs to the
    q-block (`dq_mode`): every (k-block, q-block) pair has an output block of
    its own, which where a q-block meets several k-blocks the caller sums;
    or, ``in_place``, dq is ONE float32 array in HBM that starts at zero,
    and a pair that runs fetches its q-block's tile into ``dq_scr`` (the
    read runs under the tile's first four matmuls), adds its product and
    sends the tile back (the write runs under the next pair's logits: the
    next read of ANY tile waits for it, so a tile is never read before an
    earlier pair's sum has landed); a pair that is skipped touches nothing.
    ``refs``: the scalar-prefetch operands ``(info, slopes[, table])``, then
    q, k, v, the k and q segment ids, a selection's operand (transposed: ``[B,
    Sk, Sq / 8]``), do, lse, di, ``in_place`` the zeros dq starts from (aliased
    to it), the three outputs and the scratch.
    ``steps``: the q-steps of the grid (every q-block, or under a static
    window the q-blocks one k-block reaches); ``blocks``: the launch's
    (q-blocks, k-blocks)."""
    prefetch, refs = _split_prefetch(cfg, refs)
    (q_ref, k_ref, v_ref, kseg_ref, qseg_ref, sel_ref, do_ref, lse_ref, di_ref
     ), refs = refs[:9], refs[9:]
    if in_place:
        _, dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, dq_scr, dq_sem, dq_sent = refs
    else:
        dq_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    info, slopes = prefetch[:2]
    row, (j, g, step) = _step_ids(cfg, 3)
    tile = bq, _ = cfg.tiles.bwd
    docs = _docs_of(cfg, prefetch, row, blocks)
    # the q-block of this step, and whether there is one: a static
    # window's steps start at the k-block's own diagonal
    i, exists = step, None
    if cfg.window is not None:
        i = _first_q_block(tile, j) + step
        exists = i <= _last_q_block(tile, j, cfg.window, blocks[0])

    @pl.when((g == 0) & (step == 0))
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    if in_place:
        # a folded row's pairs are a chain of their own (the row axis may be
        # split between cores): no write in flight when it starts or ends
        @pl.when((j == 0) & (g == 0) & (step == 0))
        def _none_sent():
            dq_sent[0] = 0

        # (a static window's step without a q-block names a tile past the
        # last, and starts no copy of it)
        dq_tile = dq_ref.at[row.folded, g, pl.ds(pl.multiple_of(i * bq, bq), bq)]
        dq_read = pltpu.make_async_copy(dq_tile, dq_scr, dq_sem.at[0])
        dq_write = pltpu.make_async_copy(dq_scr, dq_tile, dq_sem.at[1])

        def _landed():
            @pl.when(dq_sent[0] == 1)
            def _wait():
                dq_write.wait()
                dq_sent[0] = 0
    elif (cfg.causal and cfg.window is None) or docs is not None:
        # every (k-block, q-block) pair has a dq block of its own, which the
        # caller sums: a skipped pair's is zero. (Under a static window a
        # step without a q-block writes nothing: the block it names holds
        # the step before's result, and the caller masks the blocks no pair
        # wrote; a pair that exists there is skipped by its documents alone.)
        skipped = jnp.logical_not(_should_run(cfg, tile, i, j, info, docs))
        if exists is not None:
            skipped = skipped & exists

        @pl.when(skipped)
        def _skipped():
            dq_ref[0, 0, 0] = jnp.zeros(dq_ref.shape[3:], dq_ref.dtype)

    def _compute(positional):
        q = q_ref[0, 0]          # [bq, D]
        k = k_ref[0]             # [bk, D]
        v = v_ref[0]
        do = do_ref[0, 0]
        kseg = kseg_ref[0] if cfg.use_seg else None
        qseg = qseg_ref[0] if cfg.use_seg else None
        st = _tile_logits(cfg, tile, q, k, i, j, info, slopes,
                          _head_index(row, g, G), kseg, qseg,
                          positional=positional, transposed=True,
                          sel=sel_ref[0] if cfg.selected else None,
                          rows=blocks[0] * bq)
        lse = lse_ref[0, 0]      # [1, bq]
        # rows whose LSE is the MASK_VALUE sentinel (no unmasked key
        # anywhere) contribute exactly 0
        pt = jnp.where(lse > HALF_MASK, jnp.exp(st - lse), 0.0)
        if in_place:
            _landed()
            dq_read.start()
        dv_scr[...] += lax.dot(pt.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[0, 0])
        if cfg.scale != 1.0:
            dst = dst * cfg.scale
        dk_scr[...] += lax.dot(dst.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)
        dq = lax.dot(dst.T.astype(k.dtype), k, preferred_element_type=jnp.float32)
        if in_place:
            dq_read.wait()
            dq_scr[:, :dq.shape[1]] += dq
            dq_write.start()
            dq_sent[0] = 1
        else:
            dq_ref[0, 0, 0] = dq.astype(dq_ref.dtype)

    _for_visible_tile(cfg, tile, i, j, info, docs, _compute, also=exists)

    @pl.when((g == G - 1) & (step == steps - 1))
    def _store():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    if in_place:
        pl.when((j == blocks[1] - 1) & (g == G - 1) & (step == steps - 1))(_landed)


def _delta_kernel(o_ref, do_ref, di_ref):
    """A q-block's ``rowsum(dO * O)`` as a ROW ``[1, bq]`` (as the forward
    leaves the LSE: lane-replicated, one XLU transpose)."""
    rows = jnp.sum(do_ref[0, 0].astype(jnp.float32) * o_ref[0, 0].astype(jnp.float32),
                   axis=1, keepdims=True)
    di_ref[0, 0] = jnp.broadcast_to(rows, (rows.shape[0], NUM_LANES)).T[:1]


def _delta_call(cfg: FlashConfig, o, do, BK: int, G: int, Sq: int, D: int):
    """-> di ``[BK, G, 1, Sq]`` (fp32 rows) from ``o`` and ``do`` BY ROWS
    (``[B, Sq, H x D]``), a launch of its own, ``flash_delta``: XLA would sum a
    head's columns only after a relayout of the whole float32 product (268 MB
    a layer at 16,384 x 32 x 128: found in the compiled step, PR 51), where
    with the heads leading it fuses product and sum into one pass. The same
    128 products summed in float32 either way."""
    bq = cfg.tiles.bwd[0]
    block, at = _q_block(cfg, G, bq, _value_dim(cfg, D))
    grid = _row_axes(cfg, BK) + (G, Sq // bq)
    return pl.pallas_call(
        _delta_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, _by_row(cfg, at))] * 2,
        out_specs=pl.BlockSpec((1, 1, 1, bq), _by_row(
            cfg, lambda row, g, i: (row.folded, g, 0, i))),
        out_shape=jax.ShapeDtypeStruct((BK, G, 1, Sq), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid)),
        interpret=cfg.interpret,
        name="flash_delta",
    )(o, do)


def _bwd_call(cfg: FlashConfig, q, k, v, kseg_c, qseg_r, table, slopes, info,
              o, lse, do, dlse, sel_t=None):
    """``table``: the backward tiles' :func:`block_ranges` (None: a launch
    without ids); ``sel_t``: a selection's operand transposed, ``[B, Sk, Sq /
    8]``.
    One launch whatever the shape. Where dq is added to in
    place (:func:`dq_mode`) it accumulates across the k-block axis, which is
    then ``"arbitrary"``; the folded-row axis stays ``"parallel"`` (a chip
    with two cores may split it: a row's dq tiles and its writes in flight
    are its own). q, ``o`` and ``do`` as ``cfg.layout`` has them, and dq comes
    back so. A dq that leaves the launch in float32
    (partials, or the one array added to in place) leads with its heads in
    EITHER layout: the sum or the cast that reads it anyway writes it where q
    lies, and a pair's tile of it is one run of HBM and not 4 KB pieces."""
    BK, G, Sq, Sk, D = _dims(cfg, q, k)
    Dv = _value_dim(cfg, D)
    by_rows = cfg.layout == "rows"
    tile = bq, bk = cfg.tiles.bwd
    blocks = nq, nk = Sq // bq, Sk // bk
    kvH = cfg.kv_heads
    W = cfg.window
    # the grid's q-steps a k-block: every q-block, or under a static window
    # the most one k-block reaches; and the k-blocks a q-block meets
    steps = nq if W is None else window_steps(tile, W, nq, nk)[1]
    met = dq_partials(Sq, Sk, tile, W)
    in_place = dq_mode(Sq, Sk, cfg.tiles, W) == "in_place"

    # di = rowsum(dO * O) (the softmax-jacobian diagonal term); a cotangent
    # on the LSE output folds in here: dL/ds = P*(dP - di) + dlse*P
    if by_rows:
        di = _delta_call(cfg, o, do, BK, G, Sq, D)
    else:
        di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        di = di.reshape(BK, G, 1, Sq)
    if dlse is not None:
        di = di - dlse.astype(jnp.float32)

    def q_step(i, j):
        """The q-block of step ``i`` of k-block ``j`` by position: under a
        static window a step past the last block reached names that one."""
        if W is None:
            return i
        return jnp.minimum(_first_q_block(tile, j) + i,
                           _last_q_block(tile, j, W, nq))

    def q_blk(row, i, j, prefetch):
        """The q-block the step fetches: one that computes nothing re-names
        a block already resident, or one that a whole run of such steps
        shares (no DMA, or one a run: the last block of all, or of the
        window's reach)."""
        docs = _docs_of(cfg, prefetch, row, blocks)
        if W is not None:
            blk = q_step(i, j)
            return blk if docs is None else lax.select(
                docs.meet(blk, j), blk, _last_q_block(tile, j, W, nq))
        if cfg.causal or docs is not None:
            i = lax.select(_should_run(cfg, tile, i, j, prefetch[0], docs), i, nq - 1)
        return i

    rows = _row_axes(cfg, BK)
    by_row = functools.partial(_by_row, cfg)
    q_block, q_at = _q_block(cfg, G, bq, D)
    kv_block, kv_at = _kv_block(bk, D)
    do_block, v_block = _q_block(cfg, G, bq, Dv)[0], _kv_block(bk, Dv)[0]
    q_idx = by_row(lambda row, j, g, i, *prefetch: q_at(row, g, q_blk(row, i, j, prefetch)))
    q_row_idx = by_row(lambda row, j, g, i, *prefetch: (
        row.folded, g, 0, q_blk(row, i, j, prefetch)))
    kv_idx = by_row(lambda row, j, g, i, *_: kv_at(row, j))

    seg_specs = [None, None]
    if cfg.use_seg:
        seg_specs = [
            pl.BlockSpec((1, bk, NUM_LANES),
                         by_row(lambda row, j, g, i, *_: (row.batch, j, 0))),
            pl.BlockSpec((1, NUM_SUBLANES, bq), by_row(
                lambda row, j, g, i, *prefetch: (row.batch, 0, q_blk(row, i, j, prefetch)))),
        ]
    sel_spec = None
    if sel_t is not None:
        packed, shared = _attention.selection_tile(Sq, bq)
        sel_spec = pl.BlockSpec(
            (1, bk, packed), by_row(lambda row, j, g, i, *prefetch: (
                row.batch, j, q_blk(row, i, j, prefetch) // shared)))
    prefetch = (info, slopes) + (() if table is None else (table.reshape(-1),))
    if in_place:
        # dq starts at zero and is the launch's own to add to, wherever
        # its tiles lie (a tile is copied whole lanes at a time: a narrower
        # head's is padded to them)
        dq_shape = (BK, G, Sq, -(-D // NUM_LANES) * NUM_LANES)
        zeros = [jnp.zeros(dq_shape, jnp.float32)]
        dq_spec = pl.BlockSpec(memory_space=pl.ANY)
        dq_scratch = [pltpu.VMEM((bq, dq_shape[3]), jnp.float32),
                      pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)]
        # (the zeros follow the operands a launch has: no ids, no id operands)
        aliases = {len(prefetch) + (8 if cfg.use_seg else 6)
                   + (sel_t is not None): 0}
    else:
        # a pair's own block, whatever its step fetched: partial ``j`` of its
        # q-block, or under a static window ``j`` less the first k-block the
        # q-block reaches; one partial IS the answer, in q's dtype and where
        # q lies, several are float32 and lead with their heads
        dq_block, dq_at = _q_block(cfg, G, bq, D, heads=met > 1)
        dq_shape = (met,) + (q.shape if met == 1 else (BK, G, Sq, D))
        zeros, dq_scratch, aliases = [], [], {}

        def dq_idx(row, j, g, i, *_):
            if W is None:
                return (j,) + dq_at(row, g, i)
            i = q_step(i, j)
            return (j - _first_k_block(tile, i, W),) + dq_at(row, g, i)
        dq_spec = pl.BlockSpec((1,) + dq_block, by_row(dq_idx))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, cfg=cfg, G=G, steps=steps,
                          blocks=blocks, in_place=in_place),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=rows + (nk, G, steps),
            in_specs=[
                pl.BlockSpec(q_block, q_idx),
                pl.BlockSpec(kv_block, kv_idx),
                pl.BlockSpec(v_block, kv_idx),
                *seg_specs,
                sel_spec,
                pl.BlockSpec(do_block, q_idx),
                pl.BlockSpec((1, 1, 1, bq), q_row_idx),
                pl.BlockSpec((1, 1, 1, bq), q_row_idx),
                *[dq_spec] * len(zeros),
            ],
            out_specs=[
                dq_spec,
                pl.BlockSpec(kv_block, kv_idx),
                pl.BlockSpec(v_block, kv_idx),
            ],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, Dv), jnp.float32), *dq_scratch]),
        out_shape=[jax.ShapeDtypeStruct(
                       dq_shape, q.dtype if met == 1 else jnp.float32),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        input_output_aliases=aliases,
        compiler_params=_compiler_params(
            cfg, ("parallel",) * len(rows) + (
                "arbitrary" if in_place else "parallel", "arbitrary", "arbitrary")),
        interpret=cfg.interpret,
        name=("flash_bwd_eva_local" if cfg.tag == "eva_local"
              else "flash_bwd_eva_far" if cfg.tag == "eva_far"
              else "flash_bwd_mla" if cfg.tag == "mla"
              else ("flash_bwd_diff" if W is None
                    else "flash_bwd_diff_window") if cfg.tag == "diff"
              else "flash_bwd_dsa" if cfg.selected
              else "flash_bwd_blockdiff" if cfg.blockdiff is not None
              else "flash_bwd" if W is None else "flash_bwd_window"),
    )(*prefetch, q, k, v, kseg_c, qseg_r, sel_t, do, lse, di, *zeros)
    if met == 1:
        return dq[0], dk, dv
    if in_place:
        dq = dq[..., :D].astype(q.dtype)
    else:
        if W is not None:
            # partial s of q-block i holds k-block first(i) + s, where there
            # is one: a block no pair wrote holds whatever was there
            reach = np.repeat([_last_k_block(tile, i) - _first_k_block(tile, i, W)
                               for i in range(nq)], bq)
            dq = jnp.where(jnp.asarray(np.arange(met)[:, None] <= reach[None, :]
                                       )[:, None, None, :, None], dq, 0.0)
        dq = jnp.sum(dq, axis=0).astype(q.dtype)
    if by_rows:
        # (the pass that sums or casts writes a head's rows to its columns)
        dq = dq.reshape(BK // kvH, kvH * G, Sq, D).transpose(0, 2, 1, 3).reshape(q.shape)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP binding
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: FlashConfig, q, k, v, segs, slopes, info, sel=None):
    """q as ``cfg.layout`` has it, k and v leading with their heads. ``segs``
    = (q ids as columns, k ids as rows, the forward tiles' table
    of documents, k ids as columns, q ids as rows, the backward tiles'
    table) or six Nones: the forward reads the first three, the backward
    (transposed tiles) the others. ``sel``: a selection's operand, int8 ``[B,
    Sq / 8, Sk]`` (``FlashConfig.selected``; the backward transposes it)."""
    return _fwd_call(cfg, q, k, v, *segs[:3], slopes, info, sel)


def _flash_fwd(cfg, q, k, v, segs, slopes, info, sel=None):
    o, lse = _fwd_call(cfg, q, k, v, *segs[:3], slopes, info, sel)
    # named HERE so that the residuals are the values a checkpoint policy
    # saves: with both kept, a rematerialised block's backward launches no
    # ``flash_fwd`` (models/transformer.py ``remat_policy``); either costs
    # the whole kernel to make again
    # (a tagged launch's carry the tag: its caller decides whether the order
    # of kept names lists them)
    tag = "_dsa" if cfg.selected else "" if cfg.tag is None else "_" + cfg.tag
    o, lse = checkpoint_name(o, "attn_o" + tag), checkpoint_name(lse, "attn_lse" + tag)
    return (o, lse), (q, k, v, segs, slopes, info, sel, o, lse)


def _flash_bwd(cfg, res, cts):
    q, k, v, segs, slopes, info, sel, o, lse = res
    do, dlse = cts  # a discarded LSE output arrives as a zero array
    dq, dk, dv = _bwd_call(cfg, q, k, v, *segs[3:], slopes, info,
                           o, lse, do, dlse,
                           None if sel is None else jnp.swapaxes(sel, 1, 2))
    return dq, dk, dv, None, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# public entry points ([B, S, H, D] layout, matching attention.py)
# ---------------------------------------------------------------------------


def _auto_interpret() -> bool:
    return jax.default_backend() == "cpu"


# The tile rule, from the chip (v5e, PR 25; docs/KERNELS.md "On the chip"
# has the sweep). A grid step costs about 0.35 us whatever it computes, so
# 128 x 128 tiles spend more time stepping than multiplying (5x slower at
# 1024 x 64, and slower than XLA); past 512 the step count stops mattering
# and what is left is how much of a causal tile is masked work. Forward,
# causal: 512 x 512 (skips the tile above the diagonal, leaves the one below
# it unmasked). Everything else (non-causal forward, every backward):
# 1024 x 1024, where one k-block holds every key of a 1024 sequence and dq
# needs no sum over k-blocks. A length the target does not divide takes the
# largest 128-multiple under it that does.
FWD_CAUSAL_TILE_TARGET: Tile = (512, 512)
TILE_TARGET: Tile = (1024, 1024)
# Under a static window the same targets won (v5e, PR 34; docs/KERNELS.md has
# the sweep at 16,384 x 32q/4kv x 128 under a window of 2048: forward 512 x
# 512 6.89 ms against 7.6-9.6, backward 1024 x 1024 20.93 against 21.07 at
# 512 x 512 and 23-28 at wider or narrower ones). A window NARROWER than a
# target caps it, at 512 or more (reckoned, not measured: a tile wider than
# the window's band is mostly masked work, and under 512 the steps bound).
WINDOW_TILE_FLOOR = 512
# Scoped VMEM. The budget is the compiler's own default limit, so the
# chosen tiles need no ``vmem_limit_bytes``; explicit larger tiles get the
# limit their estimate asks for, up to the cap (v5e/v6e hold 128 MiB).
VMEM_BUDGET = 16 * 1024 * 1024
VMEM_CAP = 96 * 1024 * 1024


def tile_vmem_bytes(tile: Tile, head_dim: int, itemsize: int, *,
                    backward: bool, v_dim: Optional[int] = None) -> int:
    """Upper estimate of the scoped VMEM one kernel needs: two fp32
    ``[bq, bk]`` temporaries (Mosaic streams the softmax chain through
    vregs and keeps about that much: 6.0-8.5 B per tile element with the
    blocks, by bisecting ``vmem_limit_bytes`` on the v5e compiler at
    512 x 512 to 1024 x 1024, head dim 64 and 128, masks and segment ids
    in or out), the double-buffered operand blocks, and the fp32
    accumulators. ``v_dim``: the value side's width where it is its own
    (``FlashConfig.v_dim``): q, k, dq, dk are ``head_dim`` wide, v, o, do, dv
    ``v_dim``, each padded to whole lane tiles (192 takes 256 lanes in VMEM),
    and the float32 tile dq is added to in place is counted (a launch of one
    width never counted it, and its tiles stay what they were)."""
    bq, bk = tile
    pad = lambda n: -(-n // NUM_LANES) * NUM_LANES   # blocks pad to whole lane tiles
    d = max(head_dim, NUM_LANES) if v_dim is None else pad(head_dim)
    dv = d if v_dim is None else pad(v_dim)
    if backward:      # q, dq and do; k, dk and v, dv; the two accumulators
        blocks = bq * (2 * d + dv) + bk * (2 * d + 2 * dv)
        acc = bk * (d + dv) + (0 if v_dim is None else bq * d)
    else:             # q and o; k and v; o's accumulator, the running max and sum
        blocks = (bq + bk) * (d + dv)
        acc = bq * (dv + 2 * NUM_LANES)
    return 2 * 4 * bq * bk + 2 * itemsize * blocks + 4 * acc


def _largest_tile(length: int, cap: int) -> Optional[int]:
    """Largest 128-multiple <= cap that divides ``length``."""
    for t in range(min(cap, length) // NUM_LANES * NUM_LANES, 0, -NUM_LANES):
        if length % t == 0:
            return t
    return None


def _fit(length: int, target: int, compiled: bool) -> Optional[int]:
    """The tile of one length: the largest legal one under the target, or
    the whole of a length under 128 (interpret mode only). A length whose
    only divisor under the target is the step-bound 128 (640, 896) looks
    up to ``TILE_TARGET`` instead, where the whole of it is a tile."""
    t = _largest_tile(length, target)
    if t == NUM_LANES:
        t = _largest_tile(length, max(TILE_TARGET))
    if t is None and length < NUM_LANES and not compiled:
        t = length
    return t


def choose_tiles(sq: int, sk: int, head_dim: int, itemsize: int = 2, *,
                 causal: bool = True, block_q: Optional[int] = None,
                 block_k: Optional[int] = None, compiled: bool = True,
                 window: Optional[int] = None,
                 v_dim: Optional[int] = None) -> Optional[FlashTiles]:
    """Tiles of the forward and backward kernels for one call, from what
    the call can observe: the two lengths, whether it is causal, a window
    that is static (``FlashConfig.window``; a traced one the choice cannot
    see), and (for the VMEM budget) the head dim and the operand size. Head
    dim 64 or 128, MHA or grouped heads and segment ids did not move the
    winner in the sweep, and neither did a static window of twice the widest
    tile; one narrower than a target caps it (``WINDOW_TILE_FLOOR``).
    Explicit ``block_q``/``block_k`` win and apply to both kernels,
    clamped to the lengths as the old 128-default was. None
    where no legal tile exists: a length that no 128-multiple divides, or
    (``compiled``, the TPU path) a tile off the 128-lane layout; interpret
    mode accepts any length under 128 whole. ``v_dim``: the value side's
    width where it is its own (`tile_vmem_bytes` reckons both)."""
    vmem = functools.partial(tile_vmem_bytes, head_dim=head_dim, itemsize=itemsize,
                             v_dim=v_dim)

    def pick(target: Tile, backward: bool) -> Optional[Tile]:
        bq = min(block_q, sq) if block_q else _fit(sq, target[0], compiled)
        bk = min(block_k, sk) if block_k else _fit(sk, target[1], compiled)
        if not bq or not bk or sq % bq or sk % bk:
            return None
        if compiled and (bq % NUM_LANES or bk % NUM_LANES):
            return None
        # step what was not asked for down to the next legal tile until
        # the kernel fits the budget
        while vmem((bq, bk), backward=backward) > VMEM_BUDGET:
            if not block_q and bq > NUM_LANES and (bq >= bk or block_k):
                bq = _largest_tile(sq, bq - NUM_LANES)
            elif not block_k and bk > NUM_LANES:
                bk = _largest_tile(sk, bk - NUM_LANES)
            else:
                break
        return bq, bk

    def under_window(target: Tile) -> Tile:
        if window is None:
            return target
        cap = max(WINDOW_TILE_FLOOR, -(-window // NUM_LANES) * NUM_LANES)
        return min(target[0], cap), min(target[1], cap)

    fwd = pick(under_window(FWD_CAUSAL_TILE_TARGET if causal else TILE_TARGET), False)
    bwd = pick(under_window(TILE_TARGET), True)
    if fwd is None or bwd is None:
        return None
    need = max(vmem(fwd, backward=False), vmem(bwd, backward=True))
    return FlashTiles(fwd, bwd,
                      None if need <= VMEM_BUDGET else min(need, VMEM_CAP))


def supports(q_shape, k_shape, block_q: Optional[int] = None,
             block_k: Optional[int] = None, compiled: bool = True, **kind) -> bool:
    """Shape gate of ONE launch: heads the kernel `folds` and a legal tile
    (`launch_tiles` under the launch's ``kind``). ``compiled=True`` (the TPU
    path) requires tiles on the 128-lane layout; ``compiled=False`` (interpret
    mode on CPU test meshes) accepts anything the tiles divide evenly."""
    return folds(q_shape, k_shape, kind.get("v_dim")) and launch_tiles(
        q_shape[1], k_shape[1], q_shape[3], block_q=block_q, block_k=block_k,
        compiled=compiled, **kind) is not None


def folds(q_shape, k_shape, v_dim: Optional[int] = None) -> bool:
    """Heads the kernel folds: query heads a multiple of the key heads, a
    head dim under the lanes' 128 or a multiple of it. With a value width of
    its own (``v_dim``) that holds for the values, and the keys may also be a
    multiple of half a lane tile (192: Mosaic takes the block whole and pads
    it to 256 lanes in VMEM; by heads alone, `launch_layout`)."""
    H, D, kvH = q_shape[2], q_shape[3], k_shape[2]
    wide = lambda d, step: d > NUM_LANES and d % step
    if v_dim is not None:
        return not (H % kvH or wide(D, NUM_LANES // 2) or wide(v_dim, NUM_LANES))
    return not (H % kvH or wide(D, NUM_LANES))


def launch_tiles(sq: int, sk: int, head_dim: int, itemsize: int = 2, *,
                 causal: bool = True, window: Optional[int] = None,
                 blockdiff: Optional[int] = None,
                 summaries: Optional[Tuple[int, int]] = None,
                 selected: bool = False, v_dim: Optional[int] = None,
                 block_q: Optional[int] = None, block_k: Optional[int] = None,
                 compiled: bool = True) -> Optional[FlashTiles]:
    """The tiles of ONE launch of ``sq`` queries over ``sk`` keys, by its
    kind; None: no legal tile. Which rule a kind takes is written HERE alone:
    the kernel (`_prepare`), the gate (`supports`) and ``attention.plan``,
    which routes and counters are read from, all ask this.

    - causal or not, under a static ``window`` or none: `choose_tiles`;
    - ``blockdiff`` (the block-diffusion mask's block length; ``sq`` is
      ``2 sk``): the causal choice for ``sk`` queries (a q-block then lies in
      one half of the rows), where the block length is a power of two that
      divides both q tiles (a block of positions is never cut);
    - ``summaries = (window, summaries a window)`` (EVA's far keys,
      ``FlashConfig.summaries``; ``sk`` is ``sq // window x`` that): the
      causal choice where both q tiles divide the window (a q-block then has
      ONE limit), else that choice under a q tile of one window;
    - ``selected`` (a learned selection's operand, ``FlashConfig.selected``;
      causal, as many keys as queries): the causal choice, its tiles as a
      full layer's, where both q tiles are whole bit planes of the operand
      (``attention.selection_tile``), with the scoped VMEM a tile of it adds
      (the packed block twice, its int32 copy and the unpacked planes: under 6
      bytes a pair);
    - ``v_dim`` (a value width of its own, ``FlashConfig.v_dim``; a causal or
      windowed launch alone): the same choice, the VMEM reckoned at both widths."""
    if v_dim is not None and (blockdiff is not None or summaries is not None or selected):
        return None
    choose = functools.partial(choose_tiles, head_dim=head_dim, itemsize=itemsize,
                               block_k=block_k, compiled=compiled, v_dim=v_dim)
    if blockdiff is not None:
        b = int(blockdiff)
        if sq != 2 * sk or b < 1 or b & (b - 1) or sk % b:
            return None
        tiles = choose(sk, sk, block_q=block_q)
        if tiles is None or tiles.fwd[0] % b or tiles.bwd[0] % b:
            return None
        return tiles
    if summaries is not None:
        span, per = summaries
        for bq in dict.fromkeys((block_q, block_q or span)):
            tiles = choose(sq, sk, block_q=bq)
            if tiles is not None and not (span % tiles.fwd[0] or span % tiles.bwd[0]):
                return tiles if sq // span * per == sk else None
        return None
    tiles = choose(sq, sk, causal=causal, window=window, block_q=block_q)
    if selected and tiles is not None:
        if not causal or window is not None or sq != sk or not all(
                _attention.selection_tile(sq, t[0], compiled) for t in (tiles.fwd, tiles.bwd)):
            return None
        need = max(
            tile_vmem_bytes(t, head_dim, itemsize, backward=back) + 6 * t[0] * t[1]
            for t, back in ((tiles.fwd, False), (tiles.bwd, True)))
        tiles = dataclasses.replace(
            tiles, vmem_limit_bytes=None if need <= VMEM_BUDGET else min(need, VMEM_CAP))
    return tiles


def static_window(window, sq: int, sk: int, q_offset=None) -> Optional[int]:
    """The window the grids are cut to: a Python int that can bind, on the
    training call (no ``q_offset``, as many keys as queries); else None."""
    if (isinstance(window, (int, np.integer)) and q_offset is None and sq == sk
            and 0 < window < sk):
        return int(window)
    return None


def block_ranges(q_ids: jax.Array, k_ids: jax.Array, tile: Tile) -> jax.Array:
    """A launch's table of documents (module docstring): each block's lowest
    and highest segment id, int32 ``[B, 2 (nq + nk)]``, a batch row's line
    ``[q_lo (nq), q_hi (nq), k_lo (nk), k_hi (nk)]`` (``_BlockDocs`` reads
    it). ``q_ids`` [B, Sq], ``k_ids`` [B, Sk]; ``tile``: the kernel's."""
    def lo_hi(ids, block):
        blocks = ids.astype(jnp.int32).reshape(ids.shape[0], -1, block)
        return [jnp.min(blocks, axis=2), jnp.max(blocks, axis=2)]
    return jnp.concatenate(lo_hi(q_ids, tile[0]) + lo_hi(k_ids, tile[1]), axis=1)


def tiles_run(q_ids: jax.Array, k_ids: jax.Array, tile: Tile, *,
              causal: bool = True, window=None,
              blockdiff: Optional[int] = None, q_offset=None
              ) -> Tuple[jax.Array, jax.Array]:
    """How far the documents cut one kernel's work: (the tiles the position
    test alone runs, the tiles run), each summed over the batch rows, for a
    launch over ``q_ids`` [B, Sq] and ``k_ids`` [B, Sk] with the kernel's
    ``tile`` (one head's: every head of a row runs the same tiles). The
    kernels' own table and predicate (``block_ranges``, ``_should_run``)
    over every (q-block, k-block) at once; under a static window the blocks
    the position test passes are the ones the cut grids hold."""
    (B, Sq), Sk = q_ids.shape, k_ids.shape[1]
    nq, nk = Sq // tile[0], Sk // tile[1]
    cfg = FlashConfig(
        causal=causal, scale=1.0, use_seg=True, use_alibi=False,
        use_window=window is not None, kv_heads=1, tiles=None, interpret=False,
        blockdiff=None if blockdiff is None else (int(blockdiff), Sk))
    if q_offset is None:     # bottom-right alignment, as ``_prepare``
        q_offset = 0 if blockdiff is not None else Sk - Sq
    info = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(0 if window is None else window, jnp.int32)])
    row, i, j = (lax.broadcasted_iota(jnp.int32, (B, nq, nk), axis) for axis in range(3))
    docs = _BlockDocs(block_ranges(q_ids, k_ids, tile).reshape(-1), row, nq, nk)
    count = lambda run: jnp.sum(jnp.broadcast_to(run, (B, nq, nk)), dtype=jnp.int32)
    return (count(_should_run(cfg, tile, i, j, info)),
            count(_should_run(cfg, tile, i, j, info, docs)))


def _prepare(q, k, v, causal, scale, segment_ids, q_segment_ids,
             alibi_slopes, window, q_offset, block_q, block_k, interpret,
             blockdiff=None, summaries=None, tag=None, selected=False,
             layout=None):
    """-> (the launch's ``FlashConfig``, q as the launch's layout has it and k
    and v leading with their heads (module docstring), the six id operands, the
    slopes, ``info``). ``layout``: None, `launch_layout`'s."""
    B, Sq, H, D = q.shape
    Sk, kvH = k.shape[1], k.shape[2]
    # a value width of its own makes the launch the two-width one
    v_dim = None if v.shape[3] == D else v.shape[3]
    if v_dim is not None:
        if tag not in (None, "mla", "diff") or k.shape[3] != D:
            raise ValueError(f"values of {v.shape[3]} beside keys of {k.shape[3]} and "
                             f"queries of {D}: the two-width launch is tagged 'mla' or 'diff'")
        tag = tag or "mla"
    if H % kvH:
        raise ValueError(f"query heads {H} not a multiple of kv heads {kvH}")
    G = H // kvH
    if window is not None and not causal:
        raise ValueError("sliding window is causal-only")
    if isinstance(window, (int, np.integer)) and (
            window <= 0 or (q_offset is None and window >= Sk)):
        window = None                # global, or a window that never binds
    cut = static_window(window, Sq, Sk, q_offset)
    interp = _auto_interpret() if interpret is None else interpret
    if tag is not None and tag not in TAGS:
        raise ValueError(f"tag {tag!r} is none of {TAGS}")
    if blockdiff is not None:
        if (not causal or window is not None or alibi_slopes is not None
                or q_offset is not None or Sq != 2 * Sk):
            raise ValueError(
                "the block-diffusion mask takes 2 x keys query rows (a clean "
                "and a noised copy) and no window, ALiBi or q_offset")
        q_offset = 0
    elif summaries is not None:
        if (not causal or window is not None or alibi_slopes is not None
                or q_offset is not None or segment_ids is not None
                or Sq // summaries[0] * summaries[1] != Sk):
            raise ValueError(
                "EVA's summaries take one key a chunk of the queries' row and "
                "no window, ALiBi, q_offset or segment ids")
        q_offset = 0
    if selected and (not causal or window is not None or alibi_slopes is not None
                     or q_offset is not None or blockdiff is not None
                     or summaries is not None or Sq != Sk):
        raise ValueError("a selection's operand takes a causal launch of as many "
                         "keys as queries and no window, ALiBi or q_offset")
    tiles = launch_tiles(Sq, Sk, D, q.dtype.itemsize, causal=bool(causal),
                         window=cut, blockdiff=blockdiff, summaries=summaries,
                         selected=selected, v_dim=v_dim,
                         block_q=block_q, block_k=block_k, compiled=not interp)
    if tiles is None:
        raise ValueError(f"seq lengths ({Sq}, {Sk}) have no legal tiles "
                         f"(block_q={block_q}, block_k={block_k})")
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    if layout is None:
        layout = launch_layout(q.shape, k.shape)
    if layout not in LAYOUTS or (layout == "rows" and D % NUM_LANES):
        raise ValueError(f"layout {layout!r}: one of {LAYOUTS}, 'rows' for a head "
                         f"dim of whole {NUM_LANES}-lane tiles (got {D})")

    if layout == "rows":
        # the query heads stay side by side where the projection left them:
        # the index maps pick a head's columns
        # (a unit axis in the heads' place: every q-side operand has rank 4
        # in either layout, a k-side one rank 3)
        q = q.reshape(B, 1, Sq, H * D)
    else:
        q = q.transpose(0, 2, 1, 3).reshape(B * kvH, G, Sq, D)
    # the keys and values lead with their heads either way (GQA-folded)
    k = k.transpose(0, 2, 1, 3).reshape(B * kvH, Sk, D)
    v = v.transpose(0, 2, 1, 3).reshape(B * kvH, Sk, v.shape[3])
    if math.frexp(scale)[0] == 0.5:
        # a power of two scales q EXACTLY in any float type, so the same
        # logits come out of the matmul already scaled and the kernels skip
        # one multiply per logit (XLA fuses this one into the transpose, or
        # with no transpose into what made q: the rotary embedding, the norm)
        q, scale = q * jnp.asarray(scale, q.dtype), 1.0
    cfg = FlashConfig(
        causal=bool(causal), scale=scale,
        use_seg=segment_ids is not None,
        use_alibi=alibi_slopes is not None,
        use_window=window is not None,
        kv_heads=kvH, tiles=tiles, interpret=bool(interp), window=cut,
        blockdiff=None if blockdiff is None else (int(blockdiff), Sk),
        summaries=None if summaries is None else tuple(map(int, summaries)),
        tag=tag, selected=bool(selected), layout=layout, v_dim=v_dim)

    segs = (None,) * 6
    if segment_ids is not None:
        qseg = (q_segment_ids if q_segment_ids is not None
                else segment_ids).astype(jnp.int32)
        kseg = segment_ids.astype(jnp.int32)

        def cols(ids, n):   # lane-replicated: the ids of a tile's row axis
            return lax.broadcast_in_dim(ids, (B, n, NUM_LANES), (0, 1))

        def rows(ids, n):   # sublane-replicated: the ids of its column axis
            return lax.broadcast_in_dim(ids, (B, NUM_SUBLANES, n), (0, 2))
        segs = (cols(qseg, Sq), rows(kseg, Sk), block_ranges(qseg, kseg, tiles.fwd),
                cols(kseg, Sk), rows(qseg, Sq), block_ranges(qseg, kseg, tiles.bwd))
    if alibi_slopes is not None:
        # ALiBi slopes are a positional SCHEDULE (the fixed geometric
        # sequence of Press et al. — explicitly not learned), so the
        # kernel treats them as constants: their cotangent is zero BY
        # CONTRACT, made explicit here rather than left to the custom-VJP
        # None. Training slopes as parameters requires the XLA path.
        slopes = lax.stop_gradient(
            jnp.asarray(alibi_slopes, jnp.float32).reshape(H))
    else:
        slopes = jnp.zeros((1,), jnp.float32)
    # bottom-right causal alignment, same contract as _xla_attention
    if q_offset is None:
        q_offset = Sk - Sq
    info = jnp.stack([
        jnp.asarray(q_offset, jnp.int32).reshape(()),
        jnp.asarray(window if window is not None else 0,
                    jnp.int32).reshape(()),
    ])
    return cfg, q, k, v, segs, slopes, info


def flash_attention_with_lse(
        q: jax.Array, k: jax.Array, v: jax.Array, *,
        causal: bool = True, scale: Optional[float] = None,
        segment_ids: Optional[jax.Array] = None,
        q_segment_ids: Optional[jax.Array] = None,
        alibi_slopes: Optional[jax.Array] = None,
        window: Optional[jax.Array] = None,
        q_offset=None, block_q: Optional[int] = None,
        block_k: Optional[int] = None, interpret: Optional[bool] = None,
        blockdiff: Optional[int] = None,
        summaries: Optional[Tuple[int, int]] = None, tag: Optional[str] = None,
        selected: Optional[jax.Array] = None, layout: Optional[str] = None
) -> Tuple[jax.Array, jax.Array]:
    """Flash attention returning ``(out [B, Sq, H, D], lse [B, H, Sq])``.

    q ``[B, Sq, H, D]``, k and v ``[B, Sk, kvH, D]``, as the projections leave
    them. ``layout`` says how the launches take the QUERY side (module
    docstring): in ``"rows"`` (a head dim of whole 128-lane tiles) as it is,
    ``[B, 1, Sq, H * D]`` by a reshape, and ``out`` and dq come back the same
    way: no transpose of q, ``out`` or their gradients before a launch or after
    it; in ``"heads"`` with the heads leading, a transpose each way. k and v
    (and dk, dv) lead with their heads in both. None: `launch_layout`'s, which
    is what ``attention.plan`` hands its entry points; both layouts give the
    same bits.

    ``lse`` is the per-row logsumexp of the masked scaled logits (fp32;
    rows with no unmasked key hold the finite ``MASK_VALUE`` sentinel) —
    the partial-softmax state ring attention accumulates across hops.
    Differentiable in q/k/v including through ``lse``. Tiles come from
    :func:`choose_tiles` unless ``block_q``/``block_k`` name them.

    ``blockdiff``: a block length b (a power of two) puts the launch under
    the block-diffusion mask's clean-key part: ``q`` holds ``2 x Sk`` rows,
    the clean copy of the ``Sk`` positions and then the noised copy, ``k``
    and ``v`` the clean copy's; a clean row sees the keys of its own and of
    earlier blocks, a noised row those of strictly earlier blocks (a row
    with none comes back 0 with the sentinel LSE, for ``merge_partials``);
    ``segment_ids`` / ``q_segment_ids`` keep both inside a document.

    ``summaries``: ``(window W, summaries a window)`` puts the launch over
    EVA's far keys (``attention.eva_attention``): ``k`` and ``v`` hold one
    summary a chunk of the ``Sq`` positions, and a row of window ``w`` sees
    the summaries of the windows before it, keys ``0 .. w x per - 1`` (a row
    of the first window none: 0 with the sentinel LSE). ``tag`` names the
    launches ``flash_fwd_<tag>`` / ``flash_bwd_<tag>``.

    ``selected``: the bits of "the query's learned selection holds the key"
    (``attention.select_topk``; int8 ``[B, Sq / 8, Sk]`` as
    ``attention.pack_selection`` lays them out), one selection for all heads: a
    tile unpacks its block of it beside the causal and same-document rule
    (launches ``flash_fwd_dsa`` / ``flash_bwd_dsa``); tiles are skipped by
    position and documents as without it.
    """
    B, Sq, H, D = q.shape
    packed = (B, _attention.packed_rows(Sq), k.shape[1])
    if selected is not None and selected.shape != packed:
        raise ValueError(f"selected {selected.shape}: the operand of {Sq} queries over "
                         f"{k.shape[1]} keys is bits, {packed} (attention.pack_selection)")
    cfg, q, k, v, segs, slopes, info = _prepare(
        q, k, v, causal, scale, segment_ids, q_segment_ids, alibi_slopes,
        window, q_offset, block_q, block_k, interpret, blockdiff, summaries, tag,
        selected is not None, layout)
    o, lse = _flash(cfg, q, k, v, segs, slopes, info, selected)
    Dv = _value_dim(cfg, D)
    if cfg.layout == "rows":
        out = o.reshape(B, Sq, H, Dv)
    else:
        out = o.reshape(B, H, Sq, Dv).transpose(0, 2, 1, 3)
    return out, lse.reshape(B, H, Sq)


def flash_attention_kernel(
        q: jax.Array, k: jax.Array, v: jax.Array, *,
        causal: bool = True, scale: Optional[float] = None,
        segment_ids: Optional[jax.Array] = None,
        q_segment_ids: Optional[jax.Array] = None,
        alibi_slopes: Optional[jax.Array] = None,
        window: Optional[jax.Array] = None,
        q_offset=None, block_q: Optional[int] = None,
        block_k: Optional[int] = None,
        interpret: Optional[bool] = None,
        layout: Optional[str] = None, tag: Optional[str] = None) -> jax.Array:
    """Flash attention, ``[B, S, H, D]`` in and out — the drop-in training
    kernel `attention.flash_attention` dispatches to at long sequence. q and
    the result go to and from the launches in ``layout``
    (`flash_attention_with_lse`: at a head dim of whole lane tiles as they are,
    no transpose either way). ``tag``: a two-width launch's name (`TAGS`)."""
    out, _ = flash_attention_with_lse(
        q, k, v, causal=causal, scale=scale, segment_ids=segment_ids,
        q_segment_ids=q_segment_ids, alibi_slopes=alibi_slopes,
        window=window, q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret, layout=layout, tag=tag)
    return out


def merge_partials(o_a, lse_a, o_b, lse_b):
    """Exactly merge two partial attention results over DISJOINT key sets.

    Inputs/outputs: ``o [B, S, H, D]``, ``lse [B, H, S]`` (fp32, with the
    ``MASK_VALUE`` sentinel for empty rows). This is the LSE-accumulation
    step ring attention applies across ppermute hops: because both partial
    outputs are already normalized by their own softmax sums, the merged
    output is the lse-weighted convex combination — no re-normalization of
    past hops, no NaNs when one (or both) sides saw only masked keys.
    """
    lse_m = jnp.maximum(lse_a, lse_b)
    ea = jnp.exp(lse_a - lse_m)
    eb = jnp.exp(lse_b - lse_m)
    lse_out = lse_m + jnp.log(ea + eb)
    wa = (ea / (ea + eb)).astype(o_a.dtype)
    wb = (eb / (ea + eb)).astype(o_b.dtype)
    # [B, H, S] -> [B, S, H, 1] to weight [B, S, H, D]
    expand = lambda w: w.transpose(0, 2, 1)[..., None]
    return o_a * expand(wa) + o_b * expand(wb), lse_out
