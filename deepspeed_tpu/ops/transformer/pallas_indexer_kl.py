"""The indexer's KL a tile at a time: a Pallas pair for ``attention.indexer_kl``.

What a layer's learned selection (``attention.py``, "a learned selection of
keys") is trained by is ``sum_t KL(p_t || softmax over S_t of I[t, .])``: ``p_t``
the mean over the main heads of ``exp(q . k x scale - lse)`` on the picked keys,
``I`` the indexer's scores. The XLA form (``attention._kl_rows`` under a scan of
query blocks) writes every head's logits against ALL keys to HBM and reads them
back, several times under ``value_and_grad``. Here both are made a tile at a
time in VMEM, and a tile no query of which can see a key is skipped by the flash
pair's own test (``pallas_flash._should_run``: causal position and the
documents' block ranges, sound because a selection holds visible pairs only).

Two launches over the same (q-block, k-block) tiles, the q-block the outer axis:

``indexer_kl_fwd``: per tile, each main head's ``exp(k q^T x scale - lse)``
summed and divided by the heads -> ``p``; each indexer head's ``ReLU(k_idx
q_idx^T) x w`` summed -> ``I``; both under the tile's bit planes of the
selection (``attention.unpack_selection`` of the packed block that holds
them). Across a q-block's k-blocks, online: the log-sum-exp of ``I`` over
the picked keys (``lse_I``), ``P = sum p``, ``A = sum p log p`` (0 where ``p ==
0``) and ``C = sum p I``; a row's KL is ``A - C + P x lse_I``. Out: one float32
``[B, 8, L]`` array of per-query ROWS (`ROWS`: the running statistics where
they were kept, the KL, ``lse_I``).

``indexer_kl_bwd``: makes ``p`` and ``I`` again (the main heads' logits are
recomputed, not spilled: a float32 ``[L, L]`` between the launches is 1.07 GB of
a block's working set at 16,384, beside a remat budget of 1.9 GB), takes ``dI =
P x exp(I - lse_I) - p`` on picked pairs (autodiff's own gradient: ``P`` is not
1 to rounding, the ``lse`` is a bf16 forward's) and per indexer head ``g_j = dI
x w_j x [dots_j > 0]``: ``dq_idx_j += g_j k_idx``, ``dk_idx += g_j^T q_idx_j``,
``dw_j += sum_s dI x ReLU(dots_j)``. dq and dw belong to the q-block and add up
in their output blocks over its k-blocks; dk is ONE float32 ``[L, d]`` block
that stays in VMEM for the whole launch (4 MB at 16,384 x 64) and takes the
tiles' sums in grid order: fixed, no atomics.

Tiles are TRANSPOSED, ``[block_k, block_q]`` (as the flash backward's): every
per-query value (a head's ``lse``, ``w_j``, ``P``, ``lse_I``, the running
statistics) is then a row that broadcasts over sublanes, a sum over keys runs
down the sublanes, and no matmul has a transposed left operand (dq is made
transposed, ``k_idx^T g_j``, ``[d, block_q]`` a head). The callers' arrays are
laid out once a launch in XLA (heads leading; the selection's packed bytes,
``k_idx`` and ``w`` transposed).

The arithmetic is ``_kl_rows``': matmul operands in the operands' dtype with
float32 accumulation, every ``exp``, ``log``, ReLU x w sum and reduction in
float32, the guard ``p > 0``.
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
from .pallas_flash import HALF_MASK, MASK_VALUE, NUM_LANES, Tile, block_ranges

#: the rows of the forward's ``[B, 8, L]`` output: the running max and sum of
#: ``exp(I - max)`` over the picked keys, ``P``, ``A``, ``C`` (kept where they
#: were accumulated), then a query's KL and ``lse_I``; the last is unused
ROWS = {"max": 0, "sum": 1, "P": 2, "A": 3, "C": 4, "kl": 5, "lse_I": 6}
N_ROWS = 8      # a whole tile of sublanes

#: (block_q, block_k) a launch looks for (v5e, PR 49; docs/KERNELS.md has the
#: readings): the backward keeps every indexer head's ReLU of a tile in VMEM
TILE_TARGET: Tile = (256, 256)
#: the widest tile side either launch takes (a length that only a wider one
#: divides has no tile: the XLA form runs)
TILE_CAP = 512
VMEM_FLOOR = 32 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))      # a @ b^T


@dataclasses.dataclass(frozen=True)
class KLConfig:
    """Static configuration of the pair (hashable)."""
    scale: float
    heads: int
    kv_heads: int
    index_heads: int
    tile: Tile
    documents: bool
    interpret: bool
    vmem_limit_bytes: Optional[int]


def choose_tile(length: int, compiled: bool = True, block_q: Optional[int] = None,
                block_k: Optional[int] = None) -> Optional[Tile]:
    """The pair's (block_q, block_k) for rows of ``length`` queries and keys
    (``pallas_flash``'s rule of one length, `TILE_TARGET`); None: no legal
    tile. ``block_q`` / ``block_k`` name them (tests, the A/B tool)."""
    bq = min(block_q, length) if block_q else _pf._fit(length, TILE_TARGET[0], compiled)
    bk = min(block_k, length) if block_k else _pf._fit(length, TILE_TARGET[1], compiled)
    if not bq or not bk or length % bq or length % bk or max(bq, bk) > TILE_CAP:
        return None
    if compiled and (bq % NUM_LANES or bk % NUM_LANES):
        return None
    # a q-block is whole bit planes of the selection's operand
    return (bq, bk) if _attention.selection_tile(length, bq, compiled) else None


def vmem_bytes(tile: Tile, length: int, heads: int, kv_heads: int, head_dim: int,
               index_heads: int, itemsize: int) -> int:
    """Upper estimate of the scoped VMEM the backward (the larger) needs: the
    operands' blocks twice, the indexer heads' ReLUs, a dozen float32 tiles
    of temporaries, dk whole."""
    bq, bk = tile
    lanes = lambda n: -(-n // NUM_LANES) * NUM_LANES
    blocks = itemsize * (heads * bq * lanes(head_dim) + kv_heads * bk * lanes(head_dim)
                         + 2 * index_heads * bq * NUM_LANES + 2 * bk * NUM_LANES) + bq * bk
    outs = 4 * (index_heads * NUM_LANES * bq + length * NUM_LANES)
    return 2 * (blocks + outs) + 4 * bq * bk * (index_heads + 12)


def tiles_of(batch: int, length: int, tile: Tile) -> int:
    """Every tile of a launch's grid, run or skipped."""
    return batch * (length // tile[0]) * (length // tile[1])


# ---------------------------------------------------------------------------
# a tile
# ---------------------------------------------------------------------------


def _visible(cfg: KLConfig, blocks, b, i, j, prefetch):
    """Whether q-block ``i`` of batch row ``b`` has any key it can see in
    k-block ``j``: the flash pair's own test over (info, [table])."""
    flash = _pf.FlashConfig(causal=True, scale=1.0, use_seg=cfg.documents,
                            use_alibi=False, use_window=False, kv_heads=1,
                            tiles=None, interpret=cfg.interpret)
    docs = _pf._BlockDocs(prefetch[1], b, *blocks) if cfg.documents else None
    return _pf._should_run(flash, cfg.tile, i, j, prefetch[0], docs)


def index_tile(dots_of, w_of, heads: int, relu_scr=None):
    """A tile of the indexer's scores, ``sum_j ReLU(dots_of(j)) x w_of(j)``,
    float32, the heads in order: ``dots_of(j)`` head j's products of the tile
    (float32), ``w_of(j)`` its weight a query, shaped to broadcast over the
    tile's keys. ``relu_scr``: where each head's ``ReLU(dots)`` is kept. The
    selection's launch (``pallas_select``) makes its scores with this too."""
    scores = None
    for j in range(heads):
        relu = jnp.maximum(dots_of(j), 0.0)
        if relu_scr is not None:
            relu_scr[j] = relu
        term = relu * w_of(j)
        scores = term if scores is None else scores + term
    return scores


def _tile(cfg: KLConfig, i, rows: int, q_ref, k_ref, lse_ref, sel_ref, qi_ref, ki_ref,
          w_ref, relu_scr=None):
    """One tile of q-block ``i`` of ``rows`` queries, transposed: (picked ``[bk,
    bq]`` bool, ``p``, ``I``), float32. ``relu_scr``: where each indexer head's
    ``ReLU(dots)`` is kept."""
    f32 = jnp.float32
    group = cfg.heads // cfg.kv_heads
    picked = _attention.unpack_selection(sel_ref[0], rows, (cfg.tile[0], i), axis=1)
    lse = lse_ref[0]                                        # [H, bq]
    total = None
    for kh in range(cfg.kv_heads):
        keys = k_ref[0, kh]                                 # [bk, D]
        for g in range(group):
            h = kh * group + g
            s = lax.dot_general(keys, q_ref[0, h], _NT, preferred_element_type=f32)
            e = jnp.exp(s * cfg.scale - lse[h:h + 1, :])
            total = e if total is None else total + e
    p = jnp.where(picked, total / cfg.heads, 0.0)
    w = w_ref[0].astype(f32)                                # [J, bq]
    keys = ki_ref[0]                                        # [bk, d]
    scores = index_tile(
        lambda j: lax.dot_general(keys, qi_ref[0, j], _NT, preferred_element_type=f32),
        lambda j: w[j:j + 1, :], cfg.index_heads, relu_scr)
    return picked, p, scores


def _row(ref, name: str):
    at = ROWS[name]
    return ref[0, at:at + 1, :]


def _fwd_kernel(*refs, cfg: KLConfig, blocks: Tuple[int, int]):
    """``refs``: the scalar-prefetch operands ``(info[, table])``, then q, k,
    lse, the selection (transposed), q_idx, k_idx, w (transposed), and the
    output's block of `ROWS`, which is the q-block's running state."""
    n = 2 if cfg.documents else 1
    prefetch, (q_ref, k_ref, lse_ref, sel_ref, qi_ref, ki_ref, w_ref, out_ref) = (
        refs[:n], refs[n:])
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    def put(name, value):
        at = ROWS[name]
        out_ref[0, at:at + 1, :] = value

    @pl.when(j == 0)
    def _init():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], jnp.float32)
        put("max", jnp.full((1, out_ref.shape[2]), MASK_VALUE, jnp.float32))

    @pl.when(_visible(cfg, blocks, b, i, j, prefetch))
    def _compute():
        picked, p, scores = _tile(cfg, i, blocks[0] * cfg.tile[0], q_ref, k_ref, lse_ref,
                                  sel_ref, qi_ref, ki_ref, w_ref)
        over = lambda x: jnp.sum(x, axis=0, keepdims=True)
        masked = jnp.where(picked, scores, MASK_VALUE)
        m_prev = _row(out_ref, "max")
        m_next = jnp.maximum(m_prev, jnp.max(masked, axis=0, keepdims=True))
        m_safe = jnp.maximum(m_next, HALF_MASK)
        alpha = jnp.exp(jnp.maximum(m_prev, HALF_MASK) - m_safe)
        put("sum", alpha * _row(out_ref, "sum") + over(jnp.exp(masked - m_safe)))
        put("max", m_next)
        put("P", _row(out_ref, "P") + over(p))
        some = p > 0
        put("A", _row(out_ref, "A") + over(
            jnp.where(some, p * jnp.log(jnp.where(some, p, 1.0)), 0.0)))
        put("C", _row(out_ref, "C") + over(p * scores))

    @pl.when(j == blocks[1] - 1)
    def _finish():
        l = _row(out_ref, "sum")
        none = l == 0.0
        lse_i = jnp.where(none, MASK_VALUE, jnp.maximum(_row(out_ref, "max"), HALF_MASK)
                          + jnp.log(jnp.where(none, 1.0, l)))
        put("lse_I", lse_i)
        put("kl", _row(out_ref, "A") - _row(out_ref, "C")
            + _row(out_ref, "P") * jnp.where(none, 0.0, lse_i))


def _bwd_kernel(*refs, cfg: KLConfig, blocks: Tuple[int, int]):
    """``refs``: the scalar-prefetch operands, then the forward's operands,
    k_idx transposed, the forward's `ROWS`, the outputs dq (transposed, ``[B,
    J, d, L]``), dw (transposed) and dk (whole), and the ReLUs' scratch."""
    n = 2 if cfg.documents else 1
    prefetch, (q_ref, k_ref, lse_ref, sel_ref, qi_ref, ki_ref, w_ref, kit_ref, rows_ref,
               dq_ref, dw_ref, dk_ref, relu_scr) = refs[:n], refs[n:]
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    f32 = jnp.float32
    bk = cfg.tile[1]

    @pl.when((i == 0) & (j == 0))
    def _init_dk():
        dk_ref[0] = jnp.zeros(dk_ref.shape[1:], f32)

    @pl.when(j == 0)
    def _init():
        dq_ref[0] = jnp.zeros(dq_ref.shape[1:], f32)
        dw_ref[0] = jnp.zeros(dw_ref.shape[1:], f32)

    @pl.when(_visible(cfg, blocks, b, i, j, prefetch))
    def _compute():
        picked, p, scores = _tile(cfg, i, blocks[0] * cfg.tile[0], q_ref, k_ref, lse_ref,
                                  sel_ref, qi_ref, ki_ref, w_ref, relu_scr)
        r = jnp.exp(jnp.where(picked, scores, MASK_VALUE) - _row(rows_ref, "lse_I"))
        di = jnp.where(picked, _row(rows_ref, "P") * r - p, 0.0)     # [bk, bq]
        w = w_ref[0].astype(f32)
        keys_t = kit_ref[0]                                          # [d, bk]
        dk = None
        for h in range(cfg.index_heads):
            relu = relu_scr[h]
            dw_ref[0, h:h + 1, :] += jnp.sum(di * relu, axis=0, keepdims=True)
            g = jnp.where(relu > 0, di * w[h:h + 1, :], 0.0).astype(keys_t.dtype)
            part = lax.dot(g, qi_ref[0, h], preferred_element_type=f32)   # [bk, d]
            dk = part if dk is None else dk + part
            dq_ref[0, h] += lax.dot(keys_t, g, preferred_element_type=f32)  # [d, bq]
        at = pl.ds(pl.multiple_of(j * bk, bk), bk)
        dk_ref[0, at, :] += dk


# ---------------------------------------------------------------------------
# the launches
# ---------------------------------------------------------------------------


def _operands(cfg: KLConfig, q_idx, k_idx, w, q, k, lse, selected, documents):
    """The callers' arrays as the tiles read them -> (prefetch, operands,
    specs, the launch's (q-blocks, k-blocks), the index map's k-block): heads
    leading, the selection's packed bytes and ``w`` transposed."""
    L = q.shape[1]
    bq, bk = cfg.tile
    blocks = L // bq, L // bk
    H, D = q.shape[2:]
    J, d = q_idx.shape[2:]
    packed, shared = _attention.selection_tile(L, bq)
    prefetch = (jnp.zeros((2,), jnp.int32),)
    if cfg.documents:
        ids = documents.astype(jnp.int32)
        prefetch += (block_ranges(ids, ids, cfg.tile).reshape(-1),)

    def k_blk(b, i, j, prefetch):
        # a step that computes nothing re-names block 0: no DMA, or one a run
        return lax.select(_visible(cfg, blocks, b, i, j, prefetch), j, 0)

    operands = (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), lse,
                jnp.swapaxes(selected, 1, 2), q_idx.transpose(0, 2, 1, 3), k_idx,
                jnp.swapaxes(w, 1, 2))
    specs = [
        pl.BlockSpec((1, H, bq, D), lambda b, i, j, *_: (b, 0, i, 0)),
        pl.BlockSpec((1, k.shape[2], bk, D),
                     lambda b, i, j, *pre: (b, 0, k_blk(b, i, j, pre), 0)),
        pl.BlockSpec((1, H, bq), lambda b, i, j, *_: (b, 0, i)),
        pl.BlockSpec((1, bk, packed),
                     lambda b, i, j, *pre: (b, k_blk(b, i, j, pre), i // shared)),
        pl.BlockSpec((1, J, bq, d), lambda b, i, j, *_: (b, 0, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j, *pre: (b, k_blk(b, i, j, pre), 0)),
        pl.BlockSpec((1, J, bq), lambda b, i, j, *_: (b, 0, i)),
    ]
    return prefetch, operands, specs, blocks, k_blk


def _params(cfg: KLConfig, semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=cfg.vmem_limit_bytes)


def _fwd_call(cfg: KLConfig, q_idx, k_idx, w, q, k, lse, selected, documents):
    """-> the forward's `ROWS`, float32 ``[B, 8, L]``."""
    B, L = q.shape[:2]
    prefetch, operands, specs, blocks, _ = _operands(
        cfg, q_idx, k_idx, w, q, k, lse, selected, documents)
    rows = pl.BlockSpec((1, N_ROWS, cfg.tile[0]), lambda b, i, j, *_: (b, 0, i))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg, blocks=blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(B,) + blocks, in_specs=specs,
            out_specs=rows),
        out_shape=jax.ShapeDtypeStruct((B, N_ROWS, L), jnp.float32),
        compiler_params=_params(cfg, ("parallel", "parallel", "arbitrary")),
        interpret=cfg.interpret,
        name="indexer_kl_fwd",
    )(*prefetch, *operands)


def _bwd_call(cfg: KLConfig, q_idx, k_idx, w, q, k, lse, selected, documents, rows):
    """-> (dq ``[B, L, J, d]``, dk ``[B, L, d]``, dw ``[B, L, J]``), float32."""
    B, L = q.shape[:2]
    bq, bk = cfg.tile
    J, d = q_idx.shape[2:]
    prefetch, operands, specs, blocks, k_blk = _operands(
        cfg, q_idx, k_idx, w, q, k, lse, selected, documents)
    specs += [
        pl.BlockSpec((1, d, bk), lambda b, i, j, *pre: (b, 0, k_blk(b, i, j, pre))),
        pl.BlockSpec((1, N_ROWS, bq), lambda b, i, j, *_: (b, 0, i)),
    ]
    dq, dw, dk = pl.pallas_call(
        functools.partial(_bwd_kernel, cfg=cfg, blocks=blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(B,) + blocks, in_specs=specs,
            out_specs=[
                pl.BlockSpec((1, J, d, bq), lambda b, i, j, *_: (b, 0, 0, i)),
                pl.BlockSpec((1, J, bq), lambda b, i, j, *_: (b, 0, i)),
                pl.BlockSpec((1, L, d), lambda b, i, j, *_: (b, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((J, bk, bq), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, J, d, L), jnp.float32),
                   jax.ShapeDtypeStruct((B, J, L), jnp.float32),
                   jax.ShapeDtypeStruct((B, L, d), jnp.float32)],
        # dk adds up over the q-blocks too: one row's grid is one chain
        compiler_params=_params(cfg, ("parallel", "arbitrary", "arbitrary")),
        interpret=cfg.interpret,
        name="indexer_kl_bwd",
    )(*prefetch, *operands, jnp.swapaxes(k_idx, 1, 2), rows)
    return dq.transpose(0, 3, 1, 2), dk, jnp.swapaxes(dw, 1, 2)


def _config(q_idx, q, k, scale, documents, tile, interpret) -> KLConfig:
    L, H, D = q.shape[1:]
    need = vmem_bytes(tile, L, H, k.shape[2], D, q_idx.shape[2], q.dtype.itemsize)
    return KLConfig(
        scale=float(scale), heads=H, kv_heads=k.shape[2], index_heads=q_idx.shape[2],
        tile=tile, documents=documents is not None,
        interpret=_pf._auto_interpret() if interpret is None else bool(interpret),
        vmem_limit_bytes=min(max(need, VMEM_FLOOR), _pf.VMEM_CAP))


def value(q_idx, k_idx, w, q, k, lse, selected, documents, scale: float, tile: Tile,
          interpret: Optional[bool] = None):
    """``attention.indexer_kl``'s value by the forward launch alone. q_idx [B,
    L, J, d], k_idx [B, L, d], w [B, L, J], q [B, L, H, D], k [B, L, kvH, D],
    lse [B, H, L] float32, selected the operand of ``attention.dsa_select``
    (bits, int8 [B, L / 8, L]), documents [B, L] int or
    None (tiles are then skipped by position alone)."""
    cfg = _config(q_idx, q, k, scale, documents, tile, interpret)
    rows = _fwd_call(cfg, q_idx, k_idx, w, q, k, lse, selected, documents)
    return jnp.sum(rows[:, ROWS["kl"]])


def value_and_gradients(q_idx, k_idx, w, q, k, lse, selected, documents, scale: float,
                        tile: Tile, interpret: Optional[bool] = None):
    """-> (the value, (dq_idx, dk_idx, dw) in their operands' dtypes, each
    rounded once from its float32 sum): both launches."""
    cfg = _config(q_idx, q, k, scale, documents, tile, interpret)
    rows = _fwd_call(cfg, q_idx, k_idx, w, q, k, lse, selected, documents)
    dq, dk, dw = _bwd_call(cfg, q_idx, k_idx, w, q, k, lse, selected, documents, rows)
    return jnp.sum(rows[:, ROWS["kl"]]), (
        dq.astype(q_idx.dtype), dk.astype(k_idx.dtype), dw.astype(w.dtype))
