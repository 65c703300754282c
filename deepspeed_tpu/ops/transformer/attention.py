"""Attention ops.

The training-attention slot of the reference's kernel stack
(``csrc/transformer/softmax_kernels.cu`` + inference ``blocked_flash``). On
TPU the hot path from sequence 384 up is the in-repo Pallas flash-attention
kernel pair (``pallas_flash.py`` — MXU-tiled, fp32 accumulation, blockwise
fwd AND bwd, tiles chosen from the shape); shorter sequences, and every
shape off-TPU (CPU test meshes), take a pure-XLA implementation with
identical semantics so tests validate numerics everywhere.
"""

from __future__ import annotations

import functools
import os
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


def alibi_slopes(num_heads: int):
    """Per-head ALiBi slopes (Press et al.; matches HF BLOOM's
    ``build_alibi_tensor`` closest-power-of-2 construction)."""
    import math

    import numpy as np

    closest = 2 ** math.floor(math.log2(num_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(closest) - 3)))
    slopes = base ** np.arange(1, closest + 1, dtype=np.float32)
    if closest != num_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * closest) - 3)))
        n_extra = min(closest, num_heads - closest)
        extra = extra_base ** np.arange(1, 1 + 2 * n_extra, 2, dtype=np.float32)
        slopes = np.concatenate([slopes, extra])
    return slopes.astype(np.float32)


def sliding_window_allowed(q_pos: jax.Array, k_pos: jax.Array,
                           window) -> jax.Array:
    """True where key ``k_pos`` is within the causal sliding window of query
    ``q_pos`` (broadcasting); ``window`` is a (possibly traced) scalar,
    <= 0 = global. ONE definition shared by the training kernel and all
    three paged serving programs so the four paths cannot diverge."""
    w = jnp.asarray(window, jnp.int32)
    return (w <= 0) | ((q_pos - k_pos) < w)


def _xla_attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                   scale: Optional[float], segment_ids: Optional[jax.Array],
                   alibi: Optional[jax.Array] = None,
                   window: Optional[jax.Array] = None,
                   q_offset: Optional[jax.Array] = None,
                   q_segment_ids: Optional[jax.Array] = None,
                   visible: Optional[jax.Array] = None) -> jax.Array:
    """Reference-semantics attention in pure XLA, GQA-NATIVE: K/V keep
    their kv_heads — query heads are grouped for the contractions, so
    grouped-query models never materialize a repeated KV. ``visible``
    [B, Sq, K] bool: a further mask, built by the caller (True = seen).

    Layout: inputs transpose to [B, H, S, D] up front so both einsums are
    plain batch matmuls over contiguous minor dims (XLA schedules the
    head-middle contraction of the model's [B, S, H, D] layout worse; the
    size of that effect is not measured on the current machine).
    """
    B, Sq, H, D = q.shape
    kvH = k.shape[2]
    G = H // kvH
    k_len = k.shape[1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qt = q.transpose(0, 2, 1, 3).reshape(B, kvH, G, Sq, D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qt, kt,
                        preferred_element_type=jnp.float32) * scale
    # named so that a block's policy can tell the [B, H, Sq, Sk] buffers from
    # what it keeps: ``checkpointing.SAVE_ORDER`` does not list the name, so
    # they are never a candidate and are made again in the backward
    logits = checkpoint_name(logits, "attn_big")
    # q_offset: absolute position of q row 0 (the chunked path passes the
    # chunk's start); default = bottom-right alignment for Sq < k_len
    if q_offset is None:
        q_offset = k_len - Sq
    q_pos = jnp.arange(Sq)[:, None] + q_offset
    k_pos = jnp.arange(k_len)[None, :]
    if alibi is not None:
        # bias = slope * (key_pos - query_pos): row-shifted form of HF
        # BLOOM's slope * key_pos (softmax is shift-invariant per row)
        rel = (k_pos - q_pos).astype(jnp.float32)  # [Sq, K]
        logits = logits + alibi.reshape(kvH, G)[None, :, :, None, None] * rel
    if causal:
        mask = q_pos >= k_pos
        if window is not None:
            # traced scalar — one compiled block serves gpt-neo's
            # alternating global/local pattern through the layer scan
            mask = mask & sliding_window_allowed(q_pos, k_pos, window)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    if segment_ids is not None:
        q_seg = q_segment_ids if q_segment_ids is not None else segment_ids
        seg_mask = q_seg[:, :, None] == segment_ids[:, None, :]
        logits = jnp.where(seg_mask[:, None, None], logits, -1e30)
    if visible is not None:
        logits = jnp.where(visible[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    probs = checkpoint_name(probs, "attn_big")
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vt)
    # (the values' own width: latent attention's are narrower than its keys)
    return out.reshape(B, H, Sq, v.shape[3]).transpose(0, 2, 1, 3)


def _xla_attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool, scale: Optional[float],
                           segment_ids: Optional[jax.Array],
                           alibi: Optional[jax.Array] = None,
                           window: Optional[jax.Array] = None,
                           chunk: int = 1024) -> jax.Array:
    """Query-chunked XLA attention: the XLA side's memory bound at long
    sequence.

    Identical math to :func:`_xla_attention`, but one query chunk at a time
    bounds the materialized scores to [B, H, chunk, S_k] instead of
    [B, H, S, S] — the buffer that makes plain XLA a compile OOM at
    seq >= 4096 full depth. Not the fast path: on the v5e one layer
    forward + backward at 4096 took 8.9-18.5 ms here against 2.4-4.7 ms in
    the in-repo kernel (PR 25, docs/KERNELS.md). `choose_route` takes it
    for a shape the kernel does not support, and under ``DSTPU_ATTN=xla``.
    """
    B, Sq, H, D = q.shape
    # Size the chunk so the per-chunk fp32 score transient
    # [B, H, chunk, S_k] stays under ~512 MB (a budget not measured on
    # the current machine).
    budget = 512 * 1024 * 1024
    per_row = H * k.shape[1] * 4  # fp32 logits bytes per (b, q-row)
    cap = max(128, budget // max(B * per_row, 1))
    while chunk > cap:
        chunk //= 2
    if Sq % chunk:
        # keep the memory bound: shrink to the largest divisor of Sq
        # rather than silently re-materializing the full [B, H, S, S]
        # buffer this path exists to avoid
        c = chunk
        while c > 1 and Sq % c:
            c -= 1
        chunk = c
        if chunk < 128:  # degenerate (prime-ish Sq): one-shot is honest
            return _xla_attention(q, k, v, causal, scale, segment_ids,
                                  alibi, window)
    nc = Sq // chunk
    qc = q.reshape(B, nc, chunk, H, D).transpose(1, 0, 2, 3, 4)
    sq_c = None
    if segment_ids is not None:
        sq_c = (segment_ids.reshape(B, nc, chunk)
                .transpose(1, 0, 2))  # [nc, B, chunk]
    # The same program repeated nc times. Offsets are static (bottom-right
    # causal alignment, same contract as _xla_attention: q row 0 sits at
    # absolute position k_len - Sq), so each causal chunk STATICALLY slices
    # K/V to its visible prefix — the flash-style flop skip (half the
    # attention flops on average), no kernel needed.
    base = k.shape[1] - Sq
    outs = []
    for i in range(nc):
        off = base + i * chunk
        end = off + chunk if causal else k.shape[1]
        outs.append(_xla_attention(
            qc[i], k[:, :end], v[:, :end], causal, scale,
            segment_ids[:, :end] if segment_ids is not None else None,
            alibi, window, q_offset=off,
            q_segment_ids=(sq_c[i] if sq_c is not None else None)))
    # [nc, B, chunk, H, Dv] -> [B, Sq, H, Dv]
    return jnp.stack(outs).transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, v.shape[3])


def attn_mode() -> str:
    """The validated ``DSTPU_ATTN`` value — ONE reader shared by this
    dispatch and ring attention so no caller can silently accept a typo
    ("XLA", "chunked"): an escape hatch that ignores a misspelling is no
    escape hatch at all."""
    mode = os.environ.get("DSTPU_ATTN", "")
    if mode not in ("", "xla", "pallas"):
        raise ValueError(f"DSTPU_ATTN must be ''|'xla'|'pallas', got "
                         f"{mode!r}")
    return mode


# At and above this query length the XLA route chunks its queries: the
# one-shot path materializes [B, H, S, S] fp32 scores (2.1 GiB per unit
# batch at 4k) next to a full-depth train state. A memory bound of the XLA
# fallback, not a crossover: the kernel's own is `kernel_is_default`.
XLA_CHUNK_MIN_SEQ = 4096

# The crossover, from the chip (v5e, PR 25; docs/KERNELS.md "On the chip"
# has the table): forward + backward device time of `_xla_attention`
# against the in-repo kernel pair at S in {256, 384, 512, 1024, 2048,
# 4096}, head dim 64 and 128, MHA and 32q/4kv, causal, and bert-style
# padded 512 and 1024. From 384 up the kernel won every shape (1.3x at 384,
# 1.7x-2.9x at 512, 3x-5x from 1024; at 4096, 2.9x-4.1x against the chunked
# route). At 256 XLA won 20 heads x 64 by 2.3x (one S x S tile is 256 KB a
# head: XLA's fusions are not HBM-bound there, and the kernel's one grid
# step a head is mostly overhead) and 32q/4kv x 64 by 1 %, and lost
# 16 heads x 128 by 3 %: head dim 128 doubles what XLA's score tensor costs
# a FLOP.
FLASH_MIN_SEQ = 384
FLASH_MIN_SEQ_WIDE_HEAD = 256    # head dim >= 128


def kernel_is_default(q_shape, k_shape, backend: str) -> bool:
    """Whether ``flash_attention`` takes the in-repo blockwise kernel for
    this call when nothing forces a route: a rule of shape and platform
    alone. Off the TPU the XLA path stays (tier-1 dispatch and the
    ``analysis/`` HLO artifacts are the CPU's)."""
    return choose_route(q_shape, k_shape, backend, "") == "kernel"


def choose_route(q_shape, k_shape, backend: str, mode: str,
                 blockdiff: Optional[int] = None,
                 eva: Optional[Tuple[int, int]] = None,
                 selected: Optional[int] = None) -> str:
    """The route of `flash_attention`, of `blockdiff_attention`, of
    `eva_attention` and of `selected_attention`: ``"kernel"`` (the in-repo
    blockwise pair), ``"xla"`` (one shot) or ``"xla_chunked"``: that of the
    call's `plan`."""
    return plan(q_shape, k_shape, backend, mode, blockdiff=blockdiff, eva=eva,
                selected=selected).route


class Launch(NamedTuple):
    """One launch of the flash pair: its ``tag`` (``"flash"``, ``"blockdiff"``,
    ``"eva_local"``, ``"eva_far"``, ``"dsa"``, ``"mla"``: the two-width launch of
    latent attention), a batch row's queries and keys, the tiles
    (``pallas_flash.launch_tiles``), the static window the grids are cut to, and
    where the launch takes its query side's heads (q, ``o`` and their gradients;
    ``pallas_flash.launch_layout``: ``"rows"``, as the projections leave them,
    ``[B, S, heads x D]`` and no transpose around the launch; ``"heads"``,
    transposed to lead, as the keys and values are in both)."""
    tag: str
    sq: int
    sk: int
    tiles: Any
    window: Optional[int] = None
    layout: str = "heads"


class Plan(NamedTuple):
    """The whole decision of one attention call: the route and, on the kernel
    route, the launches in order. The entry point launches FROM this value and
    the counters read it (``TransformerLM.attention_records``): a record
    cannot say what was not launched."""
    route: str
    launches: Tuple[Launch, ...] = ()

    def launch(self, tag: str) -> Optional[Launch]:
        """The launch tagged ``tag``; None without one."""
        return next((at for at in self.launches if at.tag == tag), None)

    def dq(self, tag: str) -> Optional[str]:
        """How the backward of the launch tagged ``tag`` makes dq
        (``pallas_flash.dq_mode``); None without such a launch."""
        from . import pallas_flash as _pf
        at = self.launch(tag)
        return None if at is None else _pf.dq_mode(at.sq, at.sk, at.tiles, at.window)

    def layout(self, tag: str) -> Optional[str]:
        """Where the launch tagged ``tag`` takes its query side's heads
        (``Launch.layout``); None without such a launch."""
        at = self.launch(tag)
        return None if at is None else at.layout


def plan(q_shape, k_shape, backend: str, mode: str, itemsize: int = 2, *,
         causal: bool = True, window=None, blockdiff: Optional[int] = None,
         eva: Optional[Tuple[int, int]] = None,
         selected: Optional[int] = None, v_dim: Optional[int] = None,
         tag: Optional[str] = None) -> Plan:
    """THE decision of the four entry points, a pure function of the two
    shapes, the platform, `attn_mode`'s value, the operands' size and the
    mask, which says what the kernel route would launch, each launch with its
    tiles and its layout (the entry point hands the kernel THIS layout):

    - `flash_attention` (``causal``, ``window``): one launch, its grids cut to
      a window that is static; with ``v_dim`` (the values' width where it is
      not the keys': ``q_shape`` and ``k_shape`` hold the keys') the two-width
      launch, tagged ``"mla"`` (or ``tag``: ``"diff"``, differential attention's
      pair of value heads side by side);
    - `blockdiff_attention` (``blockdiff``: the block length; ``q_shape`` holds
      both copies' ``2 L`` rows, ``k_shape`` the clean copy's ``L``): one
      launch of both copies' queries over the clean keys;
    - `eva_attention` (``eva = (window, chunk)``; as many key heads as query
      heads, a row of whole windows of whole chunks, else no launch): the
      row's windows folded to batch rows as one causal launch and, for a row
      of several, one launch of the row's queries over its summaries.

    ``mode == "pallas"`` takes the kernel wherever it CAN run every launch
    (heads it folds, a legal tile each; interpret mode off the TPU relaxes the
    128-lane tiles to plain divisibility); ``""`` where the chip showed it
    faster: on a TPU, from the crossover up in the rows ONE launch sees (the
    2 L; one window of EVA's); every other call, a refused ``"pallas"`` among
    them, is XLA's, chunked from `XLA_CHUNK_MIN_SEQ` up on a device."""
    from . import pallas_flash as _pf
    sq, sk = q_shape[1], k_shape[1]
    rows, launches = sq, ()
    if blockdiff is not None:
        launches = [("blockdiff", sq, sk, dict(blockdiff=blockdiff))]
    elif selected is not None:
        launches = [("dsa", sq, sk, dict(selected=True))] if sq == sk else []
    elif eva is not None:
        span, chunk = eva
        windows, rows = -(-sq // span), min(sq, span)
        if tuple(k_shape) == tuple(q_shape) and not (sq % windows or span % chunk):
            launches = [("eva_local", sq // windows, sq // windows, {})]
            if windows > 1:
                launches.append(("eva_far", sq, sq // chunk,
                                 dict(summaries=(span, span // chunk))))
    else:
        kind = dict(causal=causal, window=_pf.static_window(window, sq, sk))
        launches = [("flash", sq, sk, kind) if v_dim is None
                    else (tag or "mla", sq, sk, dict(kind, v_dim=v_dim))]
    compiled = backend != "cpu"
    min_rows = FLASH_MIN_SEQ_WIDE_HEAD if q_shape[3] >= 128 else FLASH_MIN_SEQ
    if mode == "pallas" or (mode == "" and backend == "tpu" and rows >= min_rows):
        made = tuple(
            Launch(tag, sq, sk, _pf.launch_tiles(
                sq, sk, q_shape[3], itemsize, compiled=compiled, **kind),
                kind.get("window"), _pf.launch_layout(q_shape, k_shape))
            for tag, sq, sk, kind in launches)
        if made and _pf.folds(q_shape, k_shape, v_dim) and all(at.tiles for at in made):
            return Plan("kernel", made)
    return Plan(_xla_route(q_shape[1], compiled))


def _xla_route(rows: int, compiled: bool) -> str:
    """The XLA side's name for a call over ``rows`` queries: chunked from
    `XLA_CHUNK_MIN_SEQ` up on a device."""
    return "xla_chunked" if rows >= XLA_CHUNK_MIN_SEQ and compiled else "xla"


def flash_attention(q: jax.Array,
                    k: jax.Array,
                    v: jax.Array,
                    causal: bool = True,
                    scale: Optional[float] = None,
                    segment_ids: Optional[jax.Array] = None,
                    alibi_slopes: Optional[jax.Array] = None,
                    window: Optional[jax.Array] = None,
                    tag: Optional[str] = None) -> jax.Array:
    """Multi-head attention, [B, S, H, D] layout, GQA-aware.

    On a TPU, every shape where the chip showed it faster
    (`kernel_is_default`) dispatches to the IN-REPO Pallas flash kernel
    pair (pallas_flash.py: blockwise forward and one fused backward,
    GQA-native, full feature matrix — causal, sliding window, segment
    ids, ALiBi, q_offset — tiles chosen from the shape);
    ``DSTPU_ATTN=xla`` is the escape hatch back to XLA (query-chunked at
    >= XLA_CHUNK_MIN_SEQ) and ``DSTPU_ATTN=pallas`` forces the kernel at
    any length (interpret mode off-TPU). Shapes under the crossover, and
    every shape off the TPU, keep the one-shot XLA path. `choose_route`
    is the decision table (docs/LONG_CONTEXT.md).
    ``alibi_slopes`` [num_heads] adds the ALiBi positional bias (bloom);
    ``window`` (0 = global) is the causal sliding window: a Python int is
    static, and the kernel's grids are then cut to it (a sliding layer
    fetches and multiplies a window's worth of keys); a traced scalar masks
    and skips inside whole-sequence grids. ``tag``: the name of a two-width
    launch (values of another width than the keys; ``pallas_flash.TAGS``).
    """
    mode = attn_mode()
    made = plan(q.shape, k.shape, jax.default_backend(), mode, q.dtype.itemsize,
                causal=causal, window=window,
                v_dim=None if v.shape[3] == q.shape[3] else v.shape[3], tag=tag)
    route = made.route
    if route == "kernel":
        from . import pallas_flash as _pf
        (at,) = made.launches
        _log_path_once(
            "pallas_flash_inrepo, tiles (block_q x block_k) forward "
            "%dx%d backward %dx%d, operands by %s%s" % (
                at.tiles.fwd + at.tiles.bwd + (at.layout,) + (
                    "" if at.window is None
                    else f", grids cut to a window of {at.window}",)))
        return _pf.flash_attention_kernel(
            q, k, v, causal=causal, scale=scale,
            segment_ids=segment_ids, alibi_slopes=alibi_slopes,
            window=window, layout=at.layout, tag=tag)
    if mode == "pallas":
        # an explicit DSTPU_ATTN=pallas that cannot be honored must
        # not pass silently (round-1 review: perf regressions hide in
        # silent fallbacks)
        _log_path_once(f"xla (DSTPU_ATTN=pallas REFUSED: shapes "
                       f"q={q.shape} k={k.shape} unsupported)")
    _log_path_once(route)
    if route == "xla_chunked":
        return _xla_attention_chunked(q, k, v, causal, scale, segment_ids,
                                      alibi_slopes, window)
    return _xla_attention(q, k, v, causal, scale, segment_ids, alibi_slopes,
                          window)


# ---------------------------------------------------------------------------
# the block-diffusion mask (BD3-LM's vectorised training form)
# ---------------------------------------------------------------------------
# A row holds L data tokens; the network runs on 2 L rows of activations, the
# clean copy (rows 0..L-1) and then the noised copy (rows L..2L-1), the same
# position for both copies of a token. With ``blk(i) = i // b``:
#   a clean query i sees the clean key j   iff blk(j) <= blk(i);
#   a noised query i sees the clean key j  iff blk(j) <  blk(i),
#              and the noised key j        iff blk(j) == blk(i);
#   a clean query sees no noised key;
# all of it inside a packed document. One softmax a query over what it sees.

def blockdiff_visible(q_noised, q_pos, k_noised, k_pos, block_length: int):
    """Whether a key is visible to a query under the block-diffusion mask
    (broadcasting; documents aside): THE definition, which the XLA route
    computes and the kernel route is tested against."""
    qb, kb = q_pos // block_length, k_pos // block_length
    return jnp.where(k_noised, q_noised & (kb == qb),
                     jnp.where(q_noised, kb < qb, kb <= qb))


def _xla_blockdiff_attention(q, k, v, documents, block_length: int,
                             scale: Optional[float], chunk: Optional[int]):
    """The whole mask over the ``2 L x 2 L`` concatenation, built densely
    and handed to `_xla_attention`; ``chunk``: queries at a time (a memory
    bound)."""
    S = q.shape[1]
    at = jnp.arange(S)
    noised, pos = at >= S // 2, at % (S // 2)
    doc = jnp.concatenate([documents, documents], axis=1)          # [B, 2L]

    def rows(lo, n):
        seen = blockdiff_visible(noised[lo:lo + n, None], pos[lo:lo + n, None],
                                 noised[None, :], pos[None, :], block_length)
        seen = seen[None] & (doc[:, lo:lo + n, None] == doc[:, None, :])
        return _xla_attention(q[:, lo:lo + n], k, v, False, scale, None, visible=seen)

    if chunk is None or S <= chunk or S % chunk:
        return rows(0, S)
    return jnp.concatenate([rows(lo, chunk) for lo in range(0, S, chunk)], axis=1)


def _own_block_attention(q, k, v, documents, block_length: int,
                         scale: Optional[float]):
    """The noised copy among itself: a query sees the keys of its own block
    (and document), ``[B, L / b, heads, b, b]`` logits. -> (out [B, L, H,
    D] normalised by its own sum, lse [B, H, L] float32), the partial
    softmax ``merge_partials`` joins with the clean keys'. A query always
    sees itself, so no row is empty."""
    B, L, H, D = q.shape
    kvH, b = k.shape[2], block_length
    G, n = H // kvH, L // block_length
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qb = q.reshape(B, n, b, kvH, G, D)
    kb, vb = k.reshape(B, n, b, kvH, D), v.reshape(B, n, b, kvH, D)
    logits = jnp.einsum("bnqhgd,bnkhd->bnhgqk", qb, kb,
                        preferred_element_type=jnp.float32) * scale
    doc = documents.reshape(B, n, b)
    same = doc[:, :, :, None] == doc[:, :, None, :]                 # [B,n,b,b]
    logits = jnp.where(same[:, :, None, None], logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)                         # [B,n,h,g,b]
    probs = jnp.exp(logits - lse[..., None]).astype(q.dtype)
    out = jnp.einsum("bnhgqk,bnkhd->bnqhgd", probs, vb)
    return (out.reshape(B, L, H, D),
            lse.transpose(0, 2, 3, 1, 4).reshape(B, H, L))


def blockdiff_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        block_length: int,
                        documents: Optional[jax.Array] = None,
                        scale: Optional[float] = None) -> jax.Array:
    """Attention under the block-diffusion mask (above). q [B, 2L, H, D],
    k, v [B, 2L, kvH, D]: the clean copy's rows, then the noised copy's;
    ``documents`` [B, L] int: each position's packed document (None: a row
    is one). -> [B, 2L, H, D].

    On the kernel route (`choose_route`) no tile without a visible pair is
    multiplied: ONE launch of the flash pair over the clean keys for both
    copies' queries (``pallas_flash``'s ``blockdiff``: the limit of a row in
    ``q_pos``'s place, block skipping as under the causal mask; launches
    ``flash_fwd_blockdiff`` / ``flash_bwd_blockdiff``), the noised copy's
    own blocks as an einsum, and `merge_partials` for the two partial
    softmaxes of a noised query (exact, differentiable through the LSE, safe
    for a row with no clean key: a document's first block). Elsewhere the
    whole mask in XLA, its queries in chunks from `XLA_CHUNK_MIN_SEQ` up on
    a device."""
    B, S, H, D = q.shape
    L = S // 2
    if S % 2 or L % block_length:
        raise ValueError(f"the block-diffusion mask needs 2 x L rows, L a "
                         f"multiple of the block length {block_length}; got {S}")
    if documents is None:
        documents = jnp.zeros((B, L), jnp.int32)
    documents = documents.astype(jnp.int32)
    made = plan(q.shape, (B, L) + k.shape[2:], jax.default_backend(), attn_mode(),
                q.dtype.itemsize, blockdiff=block_length)
    _log_path_once(f"blockdiff {made.route}")
    if made.route != "kernel":
        return _xla_blockdiff_attention(
            q, k, v, documents, block_length, scale,
            1024 if made.route == "xla_chunked" else None)
    from . import pallas_flash as _pf
    (at,) = made.launches
    o, lse = _pf.flash_attention_with_lse(
        q, k[:, :at.sk], v[:, :at.sk], causal=True, scale=scale, segment_ids=documents,
        q_segment_ids=jnp.concatenate([documents, documents], axis=1),
        blockdiff=block_length, layout=at.layout)
    own, own_lse = _own_block_attention(q[:, L:], k[:, L:], v[:, L:], documents,
                                        block_length, scale)
    noised, _ = _pf.merge_partials(o[:, L:], lse[:, :, L:], own, own_lse)
    return jnp.concatenate([o[:, :L], noised], axis=1)


# ---------------------------------------------------------------------------
# EVA attention (Zheng et al., arXiv:2302.04542, as EvaByte runs it)
# ---------------------------------------------------------------------------
# A row of L positions is cut, from its start, into windows of W positions
# and chunks of c (c divides W). Every chunk g has ONE learned summary key and
# value (`eva_summaries`). With ``win(i) = i // W``:
#   query i sees the exact key j    iff win(j) == win(i) and j <= i;
#   query i sees the summary of g   iff win(g c) < win(i)
#              (every chunk of every window that is complete before i's own);
# one softmax a query over both. No document ids enter.

def eva_visible(q_pos, k_at, k_summary, window: int, chunk: int):
    """Whether a key is visible to a query under EVA's mask (broadcasting):
    THE definition, which the XLA route computes and the kernel route is
    tested against. ``k_at``: an exact key's position, a summary's chunk
    index; ``k_summary``: which of the two a key is."""
    qw = q_pos // window
    exact = (k_at // window == qw) & (k_at <= q_pos)
    return jnp.where(k_summary, (k_at * chunk) // window < qw, exact)


def eva_summaries(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
                  chunk: int):
    """One summary key and value a chunk and head: ``w_j = softmax over the
    chunk's j of (k_j . phi_h)`` (float32), ``kbar = sum_j w_j k_j + mu_h``,
    ``vbar = sum_j w_j v_j``. k, v [B, L, H, D]; phi, mu [H, D] ->
    kbar, vbar [B, L // chunk, H, D] in k's dtype (a trailing part of a
    chunk has no summary: no query could see it)."""
    B, L, H, D = k.shape
    n = L // chunk
    f32 = jnp.float32
    kc = k[:, :n * chunk].reshape(B, n, chunk, H, D).astype(f32)
    vc = v[:, :n * chunk].reshape(B, n, chunk, H, D).astype(f32)
    w = jax.nn.softmax(jnp.sum(kc * phi.astype(f32), axis=-1), axis=2)[..., None]
    kbar = jnp.sum(w * kc, axis=2) + mu.astype(f32)
    return kbar.astype(k.dtype), jnp.sum(w * vc, axis=2).astype(v.dtype)


def _xla_eva_attention(q, k, v, kbar, vbar, window: int, chunk: int,
                       scale: Optional[float], rows: Optional[int]):
    """`eva_visible` built densely over the exact keys and the summaries
    side by side and handed to `_xla_attention`; ``rows``: queries at a time
    (a memory bound; they divide a window, so a part's keys are its own
    window's up to its last row and the summaries before that window)."""
    L = q.shape[1]

    def part(lo, n):
        first = lo // window * window                   # its window's first key
        summaries = (lo + n - 1) // window * window // chunk
        k_at = jnp.concatenate([jnp.arange(first, lo + n), jnp.arange(summaries)])
        seen = eva_visible(jnp.arange(lo, lo + n)[:, None], k_at[None, :],
                           (jnp.arange(k_at.size) >= lo + n - first)[None, :],
                           window, chunk)
        cat = lambda exact, far: jnp.concatenate(
            [exact[:, first:lo + n], far[:, :summaries]], axis=1)
        return _xla_attention(q[:, lo:lo + n], cat(k, kbar), cat(v, vbar), False,
                              scale, None, visible=seen[None])

    if rows is None or L <= rows or window % rows or L % rows:
        return part(0, L)
    return jnp.concatenate([part(lo, rows) for lo in range(0, L, rows)], axis=1)


def eva_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  kbar: jax.Array, vbar: jax.Array, window: int, chunk: int,
                  scale: Optional[float] = None) -> jax.Array:
    """Attention under EVA's mask (above). q, k, v [B, L, H, D] (as many key
    heads as query heads); kbar, vbar [B, L // chunk, H, D] from
    `eva_summaries`. -> [B, L, H, D].

    On the kernel route (`choose_route`) two launches of the flash pair and
    `merge_partials`: the exact keys as a plain causal launch over the row's
    windows, one window a batch row (``flash_*_eva_local``: no tile off a
    window's own diagonal exists, so none is stepped over), and the
    summaries as a launch of L queries over L / chunk keys under a q-block's
    limit (``pallas_flash``'s ``summaries``; ``flash_*_eva_far``); a query of
    the row's first window sees no summary and leaves the second launch 0
    with the sentinel LSE. A row no longer than a window is the first launch
    alone. Elsewhere the whole mask in XLA, its queries in parts from
    `XLA_CHUNK_MIN_SEQ` up on a device."""
    B, L, H, D = q.shape
    if k.shape != q.shape or window % chunk:
        raise ValueError(f"EVA attention takes as many key heads as query heads "
                         f"and a window ({window}) of whole chunks ({chunk}); "
                         f"got q {q.shape}, k {k.shape}")
    made = plan(q.shape, k.shape, jax.default_backend(), attn_mode(),
                q.dtype.itemsize, eva=(window, chunk))
    _log_path_once(f"eva {made.route}")
    if made.route != "kernel":
        return _xla_eva_attention(q, k, v, kbar, vbar, window, chunk, scale,
                                  1024 if made.route == "xla_chunked" else None)
    from . import pallas_flash as _pf
    local, *far = made.launches
    windows = L // local.sq
    fold = lambda a: a.reshape((B * windows, local.sq) + a.shape[2:])
    o, lse = _pf.flash_attention_with_lse(fold(q), fold(k), fold(v), causal=True,
                                          scale=scale, tag=local.tag,
                                          layout=local.layout)
    o = o.reshape(B, L, H, D)
    if not far:
        return o
    lse = lse.reshape(B, windows, H, local.sq).transpose(0, 2, 1, 3).reshape(B, H, L)
    far_o, far_lse = _pf.flash_attention_with_lse(
        q, kbar, vbar, causal=True, scale=scale,
        summaries=(window, window // chunk), tag=far[0].tag, layout=far[0].layout)
    return _pf.merge_partials(o, lse, far_o, far_lse)[0]


# ---------------------------------------------------------------------------
# a learned selection of keys (DeepSeek Sparse Attention's lightning indexer)
# ---------------------------------------------------------------------------
# Beside the main projections a layer holds an INDEXER: J small query heads
# and ONE key head of d dims, and a weight a query head. With ``visible(t, s)``
# = ``s <= t`` inside t's packed document:
#   I[t, s] = sum_j w[t, j] x ReLU(qI[t, j] . kI[s]), float32, for visible s;
#   S_t     = the k visible s of largest I[t, s], ties to the lower s (every
#             visible s where there are k or fewer): `select_topk`, EXACT;
#   o[t, h] = sum over s in S_t of softmax over S_t of (q[t, h] . k[s, g(h)]
#             x scale) v[s, g(h)]: one selection for every head;
#   L_I     = sum_t KL(p_t || softmax over S_t of I[t, .]), p_t the mean over
#             the heads of the main attention's own probabilities: `indexer_kl`.
# The selection is data: it has no gradient, and is handed on as BITS, one a
# (query, key) pair (`pack_selection`: int8 ``[B, L / 8, L]``), which already
# hold the causal and same-document rule.

#: queries whose scores ``[rows, J, L]`` float32 are held at once while the
#: selection is made (at 16,384 keys x 16 heads: 0.5 GB), and while the KL's
#: target holds every main head's probabilities beside them (32 heads: 0.27 GB)
SELECT_QUERY_BLOCK = 512
KL_QUERY_BLOCK = 128


def _query_blocks(a: jax.Array, n: int) -> jax.Array:
    """[B, L, ...] -> [L / n, B, n, ...]: blocks of ``n`` queries, leading."""
    return jnp.moveaxis(a.reshape((a.shape[0], a.shape[1] // n, n) + a.shape[2:]), 1, 0)


def index_scores(q_idx: jax.Array, k_idx: jax.Array, w: jax.Array) -> jax.Array:
    """``I[b, t, s] = sum_j w[b, t, j] x ReLU(q_idx[b, t, j] . k_idx[b, s])``,
    float32. q_idx [B, T, J, d], k_idx [B, S, d], w [B, T, J] (already scaled).
    The products of the operands' dtype are summed in float32; the sum over
    the heads is a float32 multiply-add (no matmul rounds ``w``)."""
    dots = jnp.einsum("btjd,bsd->btjs", q_idx, k_idx,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w.astype(jnp.float32)[..., None], axis=2)


def causal_in_document(q_pos, documents_q, documents_k):
    """``visible(t, s)`` [B, T, S]: key s at or before query t (``q_pos`` [T],
    the keys from the row's start) in t's packed document (ids [B, T], [B, S])."""
    k_pos = jnp.arange(documents_k.shape[1])
    return ((k_pos[None, None, :] <= q_pos[None, :, None])
            & (documents_q[:, :, None] == documents_k[:, None, :]))


def visible_counts(documents: jax.Array) -> jax.Array:
    """Each query's count of visible keys (`causal_in_document`), int32 [B, L],
    from the documents alone, for ids that change only where a document ends
    (a packed row's: ``TransformerLM._documents``): the query's place in its
    document, from 1."""
    at = jnp.arange(documents.shape[1], dtype=jnp.int32)[None]
    starts = jnp.pad(documents[:, 1:] != documents[:, :-1], ((0, 0), (1, 0)),
                     constant_values=True)
    return at - jax.lax.cummax(jnp.where(starts, at, 0), axis=1) + 1


def _sortable(x: jax.Array) -> jax.Array:
    """float32 -> uint32 in the floats' own order (-0.0 as 0.0)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x).astype(jnp.float32),
                                        jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))


def _kth_by_bisection(keys: jax.Array, k: int) -> jax.Array:
    """The k-th largest of each row of uint32 ``keys`` (0 where all are
    wanted): the largest T with ``count(keys >= T) >= k``, found a bit at a
    time from the top, 32 counting passes over the row, no sort."""
    def bit(i, t):
        trial = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= trial[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, trial, t)
    return jax.lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32))


def _kth_by_top_k(keys: jax.Array, k: int) -> jax.Array:
    """The same by ``lax.top_k`` over the keys as ordered int32."""
    ordered = jax.lax.bitcast_convert_type(keys ^ jnp.uint32(0x80000000), jnp.int32)
    kth = jax.lax.top_k(ordered, k)[0][..., -1]
    return jax.lax.bitcast_convert_type(kth, jnp.uint32) ^ jnp.uint32(0x80000000)


#: how `select_topk` finds a row's threshold, each exact; the chip chose
#: (docs/KERNELS.md, PR 48, has the readings)
THRESHOLDS = {"bisection": _kth_by_bisection, "top_k": _kth_by_top_k}
SELECT_THRESHOLD = "bisection"


def select_topk(scores: jax.Array, visible: jax.Array, k: int,
                how: Optional[str] = None) -> jax.Array:
    """THE definition of a query's selection: bool ``[..., T, S]``, True for the
    ``k`` visible keys of largest ``scores`` (float32 ``[..., T, S]``), a tie
    at the threshold to the lower s; every visible key where there are ``k`` or
    fewer. Exact in every form of ``how`` (`THRESHOLDS`; None: the default):
    the k-th largest score is found, what lies above it is taken, and of what
    equals it the first ``k - above`` (a prefix count, run only where some row
    has more equals than places)."""
    S = scores.shape[-1]
    if k >= S:
        return visible
    # an invisible key is 0, under every float's key
    keys = jnp.where(visible, _sortable(scores), jnp.uint32(0))
    kth = THRESHOLDS[how or SELECT_THRESHOLD](keys, k)[..., None]
    above = keys > kth
    equal = (keys == kth) & visible
    places = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    crowded = jnp.any(jnp.sum(equal, axis=-1, keepdims=True, dtype=jnp.int32) > places)
    first = lambda: equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32) <= places)
    return above | jax.lax.cond(crowded, first, lambda: equal)


# A selection's operand, the ONE layout every reader shares. Bits along the
# QUERY axis: a row of ``L`` queries is cut into groups of ``8 p`` (``p`` =
# `selection_plane`: 128, a short row's ``ceil(L / 8)``; the last group padded
# with zeros), a group is ``p`` packed rows, and bit ``j`` of packed row ``r``
# of group ``g`` holds query ``g x 8 p + j x p + r``. A tile of ``n`` queries is
# then whole bit PLANES of ``p`` queries (`selection_tile`): ``n / p`` bits of
# its group's packed rows, or every bit of ``n / 8 p`` groups', and its unpack
# is an AND a plane and the planes side by side, ``p`` sublanes each in a
# ``[q, k]`` tile and ``p`` lanes each in a ``[k, q]`` one: no shuffle either
# way, and the transposed readers' operand is the packed array's transpose.

SELECT_PLANE = 128


def selection_plane(length: int) -> int:
    """The queries a bit plane holds in a row of ``length``."""
    return min(SELECT_PLANE, -(-length // 8))


def packed_rows(length: int) -> int:
    """The packed rows of ``length`` queries: ``length / 8`` for whole groups."""
    p = selection_plane(length)
    return -(-length // (8 * p)) * p


def selection_tile(length: int, n: int, compiled: bool = False
                   ) -> Optional[Tuple[int, int]]:
    """How a tile of ``n`` queries (from a multiple of ``n`` on) of a row of
    ``length`` lies in the operand: (the packed rows of the block that holds
    it, the tiles that share such a block), the tile's block being ``tile //
    shared``. None where it is not whole planes of one group or whole groups;
    ``compiled``: nor where a plane is not the chip's 128 lanes."""
    p = selection_plane(length)
    if n % p or (compiled and p != SELECT_PLANE):
        return None
    if (8 * p) % n == 0:
        return p, 8 * p // n
    return (n // 8, 1) if n % (8 * p) == 0 else None


def pack_selection(picked: jax.Array) -> jax.Array:
    """bool ``[..., T, S]`` (T queries, a whole row's) -> the operand, int8
    ``[..., packed_rows(T), S]`` (layout above)."""
    *lead, T, S = picked.shape
    p = selection_plane(T)
    groups = packed_rows(T) // p
    padded = jnp.pad(picked, [(0, 0)] * len(lead) + [(0, groups * 8 * p - T), (0, 0)])
    bit = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))[:, None, None]
    planes = padded.reshape(*lead, groups, 8, p, S)
    packed = jnp.sum(jnp.where(planes, bit, jnp.uint8(0)), axis=-3, dtype=jnp.uint8)
    return jax.lax.bitcast_convert_type(packed.reshape(*lead, groups * p, S), jnp.int8)


def unpack_selection(packed: jax.Array, length: int,
                     tile: Optional[Tuple[int, Any]] = None, axis: int = -2
                     ) -> jax.Array:
    """`pack_selection`'s inverse, bool: the whole operand of a row of
    ``length`` queries, or ``tile = (n, i)``: queries ``i n .. (i + 1) n - 1``
    (``i`` may be traced) from the block `selection_tile` names for them.
    ``axis``: the packed one (the last in a transposed reader's block)."""
    axis %= packed.ndim
    p = selection_plane(length)
    n, i = tile if tile is not None else (8 * packed.shape[axis], 0)
    rows, shared = selection_tile(length, n)
    first = (i % shared) * (n // p) if shared > 1 else 0
    bits = packed.astype(jnp.int32)
    planes = [jax.lax.slice_in_dim(bits, g * p, (g + 1) * p, axis=axis) & (1 << (first + j))
              for g in range(rows // p) for j in range(min(8, n // p))]
    picked = jnp.concatenate(planes, axis=axis) != 0
    return picked if tile is not None else jax.lax.slice_in_dim(picked, 0, length, axis=axis)


def select_launch(length: int, backend: str, mode: str) -> Tuple[str, Any]:
    """How `dsa_select` makes a row of ``length`` queries' operand:
    ``("kernel", the launch's tile)`` on a TPU where the row is whole groups of
    8 planes of the chip's 128 lanes (``pallas_select.choose_tile``) and
    `attn_mode` does not ask for XLA; else ``("bisection", None)``: the XLA
    loop of `SELECT_QUERY_BLOCK` queries at a time (`SELECT_THRESHOLD`), on
    the CPU always (a model's interpreted runs too)."""
    if backend == "tpu" and mode != "xla":
        from . import pallas_select as _ps
        tile = _ps.choose_tile(length, compiled=True)
        if tile is not None:
            return "kernel", tile
    return SELECT_THRESHOLD, None


def dsa_select(q_idx: jax.Array, k_idx: jax.Array, w: jax.Array,
               documents: jax.Array, k: int) -> jax.Array:
    """Every query's selection as the operand the attention reads: the bits of
    ``s in S_t`` (`pack_selection`: int8 ``[B, packed_rows(L), L]``). No
    gradient passes. By `select_launch`: ONE launch a layer under scope
    ``select`` (``pallas_select``: the scores, the threshold and the bits a
    plane of 128 queries at a time in VMEM, over the key blocks the plane can
    see), or `dsa_select_xla`."""
    q_idx, k_idx, w = jax.lax.stop_gradient((q_idx, k_idx, w))
    form, tile = select_launch(documents.shape[1], jax.default_backend(), attn_mode())
    _log_path_once(f"dsa_select {form}")
    if form != "kernel":
        return dsa_select_xla(q_idx, k_idx, w, documents, k)
    from . import pallas_select as _ps
    with jax.named_scope("select"):
        return _ps.select(q_idx, k_idx, w, documents, k, tile)


def dsa_select_xla(q_idx: jax.Array, k_idx: jax.Array, w: jax.Array,
                   documents: jax.Array, k: int) -> jax.Array:
    """`dsa_select`'s operand in XLA: the scores (scope ``indexer``) and the
    selection (scope ``select``) a block of `SELECT_QUERY_BLOCK` queries at a
    time against every key, packed a group of the layout at a time: neither
    ``[L, J, L]`` nor a byte a pair is ever held whole."""
    B, L = documents.shape
    n = SELECT_QUERY_BLOCK if L % SELECT_QUERY_BLOCK == 0 else L
    # the queries packed at once: whole groups (of whole blocks), else the row
    m = max(n, 8 * selection_plane(L))
    m = m if L % m == 0 else L

    def block(xs):
        qb, wb, docs_q, first = xs
        # (``attn`` again: a loop's body stands between the caller's scope and
        # these, and a reader of ``attn/<name>`` wants the two side by side)
        with jax.named_scope("attn"), jax.named_scope("indexer"):
            scores = index_scores(qb, k_idx, wb)
        with jax.named_scope("attn"), jax.named_scope("select"):
            seen = causal_in_document(first + jnp.arange(n), docs_q, documents)
            return select_topk(scores, seen, k)

    def groups(xs):
        picked = jnp.moveaxis(jax.lax.map(block, xs), 0, 1).reshape(B, m, L)
        with jax.named_scope("attn"), jax.named_scope("select"):
            return pack_selection(picked)

    blocks = lambda a: a.reshape((L // m, m // n) + a.shape[1:])
    packed = jax.lax.map(groups, jax.tree.map(blocks, (
        _query_blocks(q_idx, n), _query_blocks(w, n), _query_blocks(documents, n),
        jnp.arange(0, L, n))))
    return jnp.moveaxis(packed, 0, 1).reshape(B, packed_rows(L), L)


def _xla_selected_attention(q, k, v, picked, scale):
    """-> (o [B, L, H, D], lse [B, H, L] float32): the softmax over the picked
    keys (``picked`` bool [B, L, S]: `unpack_selection`'s), densely."""
    B, L, H, D = q.shape
    kvH = k.shape[2]
    qt = q.transpose(0, 2, 1, 3).reshape(B, kvH, H // kvH, L, D)
    logits = jnp.einsum("bhgqd,bkhd->bhgqk", qt, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(picked[:, None, None], logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[..., None]).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", probs, v)
    return (out.reshape(B, H, L, D).transpose(0, 2, 1, 3), lse.reshape(B, H, L))


def selected_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       selected: jax.Array, documents: jax.Array, topk: int,
                       scale: Optional[float] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Attention over each query's selection (above). q [B, L, H, D], k, v
    [B, L, kvH, D]; ``selected`` the operand of `dsa_select` (bits, int8 [B,
    packed_rows(L), L]); ``documents`` [B, L] int. -> (o [B, L, H, D], lse [B,
    H, L] float32: the log-sum-exp over the picked keys, which `indexer_kl`
    reads).

    On the kernel route ONE launch of the flash pair whose tiles unpack their
    block of ``selected`` (``flash_fwd_dsa`` / ``flash_bwd_dsa``): dense tiles
    under the mask, skipped by position and documents as a full layer's; no
    key is gathered. Elsewhere the masked softmax in XLA over the operand
    unpacked (`unpack_selection`), its queries in chunks from
    `XLA_CHUNK_MIN_SEQ` up on a device."""
    B, L, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    made = plan(q.shape, k.shape, jax.default_backend(), attn_mode(),
                q.dtype.itemsize, selected=topk)
    _log_path_once(f"dsa {made.route}")
    if made.route == "kernel":
        from . import pallas_flash as _pf
        (at,) = made.launches
        return _pf.flash_attention_with_lse(
            q, k, v, causal=True, scale=scale, segment_ids=documents.astype(jnp.int32),
            selected=selected, layout=at.layout)
    chunk = 1024 if made.route == "xla_chunked" and L % 1024 == 0 else L
    picked = unpack_selection(selected, L)
    parts = [_xla_selected_attention(q[:, lo:lo + chunk], k, v,
                                     picked[:, lo:lo + chunk], scale)
             for lo in range(0, L, chunk)]
    return (jnp.concatenate([o for o, _ in parts], axis=1),
            jnp.concatenate([lse for _, lse in parts], axis=2))


def _kl_rows(q_idx, w, q, lse, picked, k_idx, k, scale):
    """``sum_t KL(p_t || softmax over S_t of I[t, .])`` over a block of queries
    (q_idx [B, n, J, d], w [B, n, J], q [B, n, H, D], lse [B, H, n], picked
    bool [B, n, L]: `unpack_selection`'s) against all keys: p_t the mean over
    the heads of ``exp(q . k x scale - lse)`` on the picked keys. float32."""
    B, n, H, D = q.shape
    kvH = k.shape[2]
    scores = jnp.where(picked, index_scores(q_idx, k_idx, w), -1e30)
    log_r = scores - jax.nn.logsumexp(scores, axis=-1, keepdims=True)
    qt = q.transpose(0, 2, 1, 3).reshape(B, kvH, H // kvH, n, D)
    logits = jnp.einsum("bhgqd,bkhd->bhgqk", qt, k,
                        preferred_element_type=jnp.float32) * scale
    probs = jnp.exp(logits - lse.reshape(B, kvH, H // kvH, n)[..., None])
    p = jnp.where(picked, jnp.mean(probs, axis=(1, 2)), 0.0)
    return jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_r), 0.0))


def _kl_blocks(L: int) -> int:
    return KL_QUERY_BLOCK if L % KL_QUERY_BLOCK == 0 else L


def _kl_operands(q_idx, w, q, lse, selected, n):
    """The XLA form's operands a block of ``n`` queries, the operand unpacked."""
    return (_query_blocks(q_idx, n), _query_blocks(w, n), _query_blocks(q, n),
            jnp.moveaxis(lse.reshape(lse.shape[:2] + (-1, n)), 2, 0),
            _query_blocks(unpack_selection(selected, q.shape[1]), n))


def kl_launch(made: Plan, length: int) -> Tuple[str, Any]:
    """Where `indexer_kl` runs beside a selected call whose `plan` is ``made``,
    over rows of ``length``: ``("kernel", the pair's tile)`` where the flash
    pair is a kernel and ``pallas_indexer_kl`` has a legal tile for the length
    (interpret mode off the TPU included), else the plan's own XLA route and
    None: the scan of `KL_QUERY_BLOCK` queries at a time."""
    if made.route != "kernel":
        return made.route, None
    from . import pallas_indexer_kl as _kl
    compiled = jax.default_backend() != "cpu"
    tile = _kl.choose_tile(length, compiled=compiled)
    return ("kernel", tile) if tile is not None else (_xla_route(length, compiled), None)


def _kl_launch(q, k):
    """`kl_launch` of a call's operands, said once a path."""
    route, tile = kl_launch(plan(q.shape, k.shape, jax.default_backend(), attn_mode(),
                                 q.dtype.itemsize, selected=True), q.shape[1])
    _log_path_once(f"indexer_kl {route}")
    return route, tile


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def indexer_kl(q_idx, k_idx, w, q, k, lse, selected, documents, scale: float):
    """The indexer's objective for one layer, ``sum over b, t of KL(p_t ||
    softmax over S_t of I[t, .])`` (above), float32. Differentiable in
    ``q_idx``, ``k_idx`` and ``w`` alone: the target (``q``, ``k``, ``lse`` of
    the main attention) is data. ``documents`` [B, L] int or None: each
    position's packed document, by which the kernel route skips tiles.

    On the kernel route (`kl_launch`: beside a selected call that is a kernel)
    the Pallas pair of ``pallas_indexer_kl``: every head's probabilities, the
    scores and the KL a tile at a time in VMEM, a tile no query of which sees a
    key skipped (``indexer_kl_fwd``; the differentiated forward also
    ``indexer_kl_bwd``). Elsewhere `_kl_rows` over a block of `KL_QUERY_BLOCK`
    queries at a time. Either way the forward of a differentiated call takes
    the three gradients as well (every head's scores are made once a step, not
    once more in the backward) and keeps them under the names ``indexer_kl_dq``
    / ``_dk`` / ``_dw``; the backward scales them."""
    route, tile = _kl_launch(q, k)
    if route == "kernel":
        from . import pallas_indexer_kl as _kl
        return _kl.value(q_idx, k_idx, w, q, k, lse, selected, documents, scale, tile)
    n = _kl_blocks(q.shape[1])
    each = lambda xs: _kl_rows(*xs, k_idx, k, scale)
    return jnp.sum(jax.lax.map(each, _kl_operands(q_idx, w, q, lse, selected, n)))


def _indexer_kl_fwd(q_idx, k_idx, w, q, k, lse, selected, documents, scale):
    B, L = q.shape[:2]
    route, tile = _kl_launch(q, k)
    if route == "kernel":
        from . import pallas_indexer_kl as _kl
        total, (dq, dk, dw) = _kl.value_and_gradients(
            q_idx, k_idx, w, q, k, lse, selected, documents, scale, tile)
    else:
        n = _kl_blocks(L)

        def each(carry, xs):
            qb, wb, *target = xs
            value, (dq, dw, dk) = jax.value_and_grad(
                lambda qb, wb, kb: _kl_rows(qb, wb, *target, kb, k, scale),
                argnums=(0, 1, 2))(qb, wb, k_idx)
            total, dk_sum = carry
            return (total + value, dk_sum + dk.astype(jnp.float32)), (dq, dw)

        (total, dk), (dq, dw) = jax.lax.scan(
            each, (jnp.zeros((), jnp.float32), jnp.zeros(k_idx.shape, jnp.float32)),
            _kl_operands(q_idx, w, q, lse, selected, n))
        whole = lambda a: jnp.moveaxis(a, 0, 1).reshape((B, L) + a.shape[3:])
        dq, dk, dw = whole(dq), dk.astype(k_idx.dtype), whole(dw)
    # (a name each: a policy reckons a name's bytes from ONE value)
    grads = tuple(checkpoint_name(g, "indexer_kl_" + name) for name, g in (
        ("dq", dq), ("dk", dk), ("dw", dw)))
    return total, grads


def _indexer_kl_bwd(scale, grads, ct):
    dq, dk, dw = (g * ct.astype(g.dtype) for g in grads)
    return dq, dk, dw, None, None, None, None, None


indexer_kl.defvjp(_indexer_kl_fwd, _indexer_kl_bwd)


@functools.lru_cache(None)
def _log_path_once(path: str) -> None:
    """Perf regressions hide in silent fallbacks (round-1 review): say
    which attention implementation this process is using, once per path."""
    from ...utils.logging import logger
    logger.info(f"flash_attention: using {path} path "
                f"(backend={jax.default_backend()})")
