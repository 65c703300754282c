"""The learned selection a plane at a time: one Pallas launch for ``attention.dsa_select``.

A layer's selection (``attention.py``, "a learned selection of keys") is, for
every query ``t``, the ``topk`` visible keys of largest index score ``I[t, s] =
sum_j w[t, j] x ReLU(qI[t, j] . kI[s])``, a tie at the threshold to the lower
``s``, every visible key where there are ``topk`` or fewer
(``attention.select_topk``, THE definition), handed on as bits
(``attention.pack_selection``). The XLA form scores ALL keys for a block of
queries, builds the rule densely and counts 32 times over every pair of the
row. Here one launch, ``dsa_select``, never leaves VMEM between the scores and
the bits, and touches only the key blocks a query of the step can see.

Grid ``(rows, groups of 1,024 queries, the group's 8 bit planes)``, the planes
sequential: a step is ONE PLANE of 128 queries, ``[128 queries, block_k keys]``
tiles (queries down the sublanes, as the operand's packed rows lie), and the
output block is the group's ``[128, L]`` int8, resident over its 8 steps, into
which step ``j`` ORs bit ``j``: ``pack_selection``'s layout to the byte.

1. **Scores.** The key blocks from 0 to the plane's diagonal are asked of the
   flash pair's own test (``pallas_flash._should_run`` over
   ``block_ranges``' table: causal position and the documents' block ranges);
   a block that passes makes the sixteen float32 ``ReLU x w`` sums
   (``pallas_indexer_kl.index_tile``: the KL pair's own code and order), turns
   them into sortable uint32 keys (``attention._sortable``; a pair the query
   cannot see takes key 0, under every float's) and stores the tile in the
   next free SLOT of a VMEM scratch ``[L / block_k, 128, block_k]``; SMEM
   remembers which key block a slot holds. Blocks that do not pass cost a
   scalar test.
2. **The threshold, only where one exists.** A pass over the slots counts each
   query's visible keys (``key != 0``). A plane none of whose queries sees
   more than ``topk`` skips the bisection whole; else the 32 counting passes
   of ``attention._kth_by_bisection`` run over the filled slots alone
   (lane-wise partial counts ``[128, 128]``, one cross-lane sum a pass): the
   same algorithm over the same 32 bits, exact.
3. **The picks.** ``above | equal``, and where some query has more equals
   than places, of the equals the first ``topk - above``: a running count a
   query across the slots (ascending key blocks) plus the in-tile prefix by a
   triangular 0/1 product on the MXU (Mosaic has no ``cumsum``;
   ``pallas_moe.moe_route`` ranks the same way). A query with ``topk`` or
   fewer visible keys takes them all.
4. **The bits.** Each slot's picks become ``bit j`` of the resident block at
   the slot's key block; the block was zeroed at plane 0, so what no query
   sees stays 0.

float32 scores, exact top-k: on the launch's own scores the operand is
``pack_selection(select_topk(...))`` byte for byte. The sixteen terms are
summed in head order where XLA's reduce may take another, so a score may
differ from ``attention.index_scores``' in its last bit and a pair AT the
threshold change sides.
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

from . import attention as _attention
from . import pallas_flash as _pf
from .pallas_flash import NUM_LANES, Tile, block_ranges
from .pallas_indexer_kl import index_tile

#: the keys a slot holds (v5e, PR 52; docs/KERNELS.md has the readings)
BLOCK_K = 512
VMEM_FLOOR = 32 * 1024 * 1024
PLANES = 8      # the bit planes of a group: a byte's bits


@dataclasses.dataclass(frozen=True)
class SelectConfig:
    """Static configuration of the launch (hashable). ``tile``: (a plane's
    queries, a slot's keys); ``scores``: a second output, the launch's own
    float32 scores ``[B, L, L]`` (0 where a tile did not run), for the tests
    and the tools."""
    topk: int
    index_heads: int
    tile: Tile
    scores: bool
    interpret: bool
    vmem_limit_bytes: Optional[int]


def choose_tile(length: int, compiled: bool = True,
                block_k: Optional[int] = None) -> Optional[Tile]:
    """The launch's (queries a step, keys a slot) for a row of ``length``;
    None: no launch (the XLA form runs). A step is one bit plane of the
    operand's layout, the chip's 128, and a row whole groups of 8 planes;
    ``block_k`` names the slot (tests, the A/B tool)."""
    plane = _attention.selection_plane(length)
    if plane != _attention.SELECT_PLANE or length % (PLANES * plane):
        return None
    bk = min(block_k, length) if block_k else _pf._largest_tile(length, BLOCK_K)
    if not bk or length % bk or bk % NUM_LANES:
        return None
    return (plane, bk) if _attention.selection_tile(length, plane, compiled) else None


def vmem_bytes(tile: Tile, length: int, index_heads: int, head_dim: int,
               itemsize: int) -> int:
    """Upper estimate of the scoped VMEM the launch needs: the slots, the
    operands' blocks twice (the keys whole), the output's block twice, two
    dozen float32 tiles of temporaries and the triangular operand."""
    n, bk = tile
    lanes = lambda x: -(-x // NUM_LANES) * NUM_LANES
    blocks = (itemsize * (index_heads * n * lanes(head_dim) + head_dim * length)
              + 4 * (n * lanes(index_heads) + n * NUM_LANES + 8 * length) + n * length)
    return 4 * n * length + 2 * blocks + 4 * n * bk * 24 + 2 * bk * bk


# ---------------------------------------------------------------------------
# a step: one plane of queries
# ---------------------------------------------------------------------------


def _kernel(info_ref, table_ref, qi_ref, kt_ref, w_ref, qd_ref, kd_ref, *rest,
            cfg: SelectConfig, blocks: Tuple[int, int]):
    """``rest``: the output's block (the group's packed rows, int8 ``[1, 128,
    L]``), with ``cfg.scores`` the plane's scores ``[1, 128, L]``, then the
    scratch: the slots (uint32 ``[L / bk, 128, bk]``) and, in SMEM, each
    slot's key block."""
    if cfg.scores:
        out_ref, scores_ref, keys_scr, slots_scr = rest
    else:
        (out_ref, keys_scr, slots_scr), scores_ref = rest, None
    n, bk = cfg.tile
    u32, i32 = jnp.uint32, jnp.int32
    b, g, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    plane = g * PLANES + p
    flash = _pf.FlashConfig(causal=True, scale=1.0, use_seg=True, use_alibi=False,
                            use_window=False, kv_heads=1, tiles=None,
                            interpret=cfg.interpret)
    docs = _pf._BlockDocs(table_ref, b, *blocks)

    @pl.when(p == 0)
    def _clear():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)

    if scores_ref is not None:
        scores_ref[0] = jnp.zeros(scores_ref.shape[1:], scores_ref.dtype)

    # 1. the scores of the key blocks the plane can see, slot after slot
    w = w_ref[0].astype(jnp.float32)                            # [n, J]
    q_pos = plane * n + lax.broadcasted_iota(i32, (n, 1), 0)
    q_doc = qd_ref[0]                                           # [n, 1]

    def score(kb, filled):
        run = _pf._should_run(flash, cfg.tile, plane, kb, info_ref, docs)

        @pl.when(run)
        def _tile():
            keys = kt_ref[0, kb]                                # [d, bk]
            scores = index_tile(
                lambda j: lax.dot(qi_ref[0, j], keys, preferred_element_type=jnp.float32),
                lambda j: w[:, j:j + 1], cfg.index_heads)
            k_pos = kb * bk + lax.broadcasted_iota(i32, (1, bk), 1)
            seen = (k_pos <= q_pos) & (kd_ref[0, pl.ds(kb, 1), :] == q_doc)
            keys_scr[filled] = jnp.where(seen, _attention._sortable(scores), u32(0))
            slots_scr[filled] = kb
            if scores_ref is not None:
                scores_ref[0, :, pl.ds(pl.multiple_of(kb * bk, bk), bk)] = scores

        return filled + run.astype(i32)

    filled = lax.fori_loop(0, (plane * n + n - 1) // bk + 1, score, i32(0))

    def count(*tests):
        """Each query's count of the filled slots' keys that pass each of
        ``tests`` (of a ``[n, 128]`` piece of a slot): lane-wise partial
        counts, one cross-lane sum a test."""
        def slot(s, sums):
            tile = keys_scr[s]
            for c in range(bk // NUM_LANES):
                piece = tile[:, c * NUM_LANES:(c + 1) * NUM_LANES]
                sums = tuple(a + test(piece).astype(i32) for a, test in zip(sums, tests))
            return sums
        sums = lax.fori_loop(0, filled, slot, tuple(
            jnp.zeros((n, NUM_LANES), i32) for _ in tests))
        return [jnp.sum(a, axis=1, keepdims=True) for a in sums]

    wide = lambda x: jnp.broadcast_to(x, (n, NUM_LANES))

    # 2. the threshold of the queries that have one
    (visible,) = count(lambda x: x != 0)
    need = visible > cfg.topk                                   # [n, 1]

    def bisect():
        def bit(i, t):
            trial = t | (u32(1) << (31 - i).astype(u32))
            at = wide(trial)
            (n_at,) = count(lambda x: x >= at)
            return jnp.where(n_at >= cfg.topk, trial, t)
        return lax.fori_loop(0, 32, bit, jnp.zeros((n, 1), u32))

    some = jnp.max(visible) > cfg.topk
    kth = lax.cond(some, bisect, lambda: jnp.zeros((n, 1), u32))
    # (a query that takes every visible key: from the lowest key a float has)
    kth = jnp.where(need, kth, u32(1))
    kth_w = wide(kth)
    n_above, n_equal = count(lambda x: x > kth_w, lambda x: x == kth_w)
    places = cfg.topk - n_above
    crowded = jnp.max(n_equal - places) > 0

    # 3., 4. the picks as bit ``p`` of the group's block
    bit = jnp.where(p == PLANES - 1, i32(-128), i32(1) << p)    # an int8's sign bit

    def put(s, picked):
        at = pl.ds(pl.multiple_of(slots_scr[s] * bk, bk), bk)
        out_ref[0, :, at] = (out_ref[0, :, at].astype(i32)
                             | jnp.where(picked, bit, 0)).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(crowded))
    def _all_equals():
        def slot(s, carry):
            put(s, keys_scr[s] >= kth)
            return carry
        lax.fori_loop(0, filled, slot, 0)

    @pl.when(crowded)
    def _first_equals():
        tri = (lax.broadcasted_iota(i32, (bk, bk), 0)
               <= lax.broadcasted_iota(i32, (bk, bk), 1)).astype(jnp.bfloat16)

        def slot(s, before):
            keys = keys_scr[s]
            equal = keys == kth
            among = jnp.dot(equal.astype(jnp.bfloat16), tri,
                            preferred_element_type=jnp.float32).astype(i32)
            put(s, (keys > kth) | (equal & (before + among <= places)))
            return before + among[:, bk - 1:bk]
        lax.fori_loop(0, filled, slot, jnp.zeros((n, 1), i32))


# ---------------------------------------------------------------------------
# the launch
# ---------------------------------------------------------------------------


def _call(cfg: SelectConfig, q_idx, k_idx, w, documents):
    B, L, J, d = q_idx.shape
    n, bk = cfg.tile
    blocks = L // n, L // bk
    ids = documents.astype(jnp.int32)
    prefetch = (jnp.zeros((2,), jnp.int32), block_ranges(ids, ids, cfg.tile).reshape(-1))
    plane = lambda b, g, p, *_: g * PLANES + p
    operands = (q_idx.transpose(0, 2, 1, 3),
                k_idx.reshape(B, blocks[1], bk, d).transpose(0, 1, 3, 2),
                w, ids[:, :, None], ids.reshape(B, blocks[1], bk))
    specs = [
        pl.BlockSpec((1, J, n, d), lambda b, g, p, *_: (b, 0, plane(b, g, p), 0)),
        pl.BlockSpec((1, blocks[1], d, bk), lambda b, g, p, *_: (b, 0, 0, 0)),
        pl.BlockSpec((1, n, J), lambda b, g, p, *_: (b, plane(b, g, p), 0)),
        pl.BlockSpec((1, n, 1), lambda b, g, p, *_: (b, plane(b, g, p), 0)),
        pl.BlockSpec((1, blocks[1], bk), lambda b, g, p, *_: (b, 0, 0)),
    ]
    out_specs = [pl.BlockSpec((1, n, L), lambda b, g, p, *_: (b, g, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, L // PLANES, L), jnp.int8)]
    if cfg.scores:
        out_specs.append(pl.BlockSpec((1, n, L), lambda b, g, p, *_: (b, plane(b, g, p), 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, L, L), jnp.float32))
    return pl.pallas_call(
        functools.partial(_kernel, cfg=cfg, blocks=blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(B, L // (PLANES * n), PLANES),
            in_specs=specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((blocks[1], n, bk), jnp.uint32),
                            pltpu.SMEM((blocks[1],), jnp.int32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=cfg.vmem_limit_bytes),
        interpret=cfg.interpret,
        name="dsa_select",
    )(*prefetch, *operands)


def select(q_idx, k_idx, w, documents, topk: int, tile: Tile, *, scores: bool = False,
           interpret: Optional[bool] = None):
    """``attention.dsa_select``'s operand by the launch: the bits of ``s in
    S_t``, int8 ``[B, L / 8, L]`` (``attention.pack_selection``'s layout).
    q_idx [B, L, J, d], k_idx [B, L, d], w [B, L, J] (already scaled),
    documents [B, L] int; ``tile``: `choose_tile`'s. ``scores``: -> (the
    operand, the launch's own float32 scores ``[B, L, L]``)."""
    B, L, J, d = q_idx.shape
    need = vmem_bytes(tile, L, J, d, q_idx.dtype.itemsize)
    cfg = SelectConfig(
        topk=int(topk), index_heads=J, tile=tile, scores=bool(scores),
        interpret=_pf._auto_interpret() if interpret is None else bool(interpret),
        vmem_limit_bytes=min(max(need, VMEM_FLOOR), _pf.VMEM_CAP))
    out = _call(cfg, q_idx, k_idx, w, documents)
    return tuple(out) if scores else out[0]
