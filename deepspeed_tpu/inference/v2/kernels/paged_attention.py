"""Paged attention over a blocked KV cache.

The TPU-native replacement for the reference's ragged CUDA kernel set
(``inference/v2/kernels/ragged_ops``: ``blocked_flash`` / ``atom_builder`` /
``linear_blocked_kv_rotary``, ``ragged_ops.cpp:20-47``). Two entry points
mirror the two static-shape programs the engine compiles:

- :func:`paged_decode_attention` — one new token per sequence, attention
  against that sequence's block table. On TPU dispatches to the Pallas
  ``paged_attention`` kernel (HBM-resident pages streamed block-by-block);
  elsewhere an XLA gather fallback with identical semantics.
- :func:`chunk_prefill_attention` — a chunk of one sequence's tokens
  attending to gathered history + themselves (causal), the SplitFuse
  prefill-chunk program.

Page layout everywhere: ``[kv_heads, num_pages, page_size, head_dim]``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ....ops.transformer.attention import sliding_window_allowed

NEG_INF = -2.3819763e38  # pallas kernel's mask value


def _paged_kernel_opted_in() -> bool:
    """Live env read (never cached): toggling mid-process must work."""
    import os
    return os.environ.get("DSTPU_PALLAS_PAGED", "0") == "1"


def _pallas_paged_available() -> bool:
    """Opt-IN via DSTPU_PALLAS_PAGED=1: XLA gather is the default decode
    path. The stock kernel's Mosaic lowering rejects head_dim-64 models
    inside the fused decode-burst scan (block spec (..., 64)) — a
    compile-time error a call-site try/except cannot catch. Not measured
    on the current machine."""
    return _paged_kernel_opted_in() and jax.default_backend() == "tpu"


def _gather_pages(pages: jax.Array, block_tables: jax.Array,
                  out_dtype=None) -> jax.Array:
    """pages [kvH, P, ps, D], block_tables [B, mp] -> [B, kvH, mp*ps, D].

    ``out_dtype``: upcast AFTER the gather — with a narrow KV store (fp8
    cache) only the batch's gathered blocks widen, not the whole pool."""
    g = jnp.take(pages, block_tables, axis=1)          # [kvH, B, mp, ps, D]
    if out_dtype is not None and g.dtype != out_dtype:
        g = g.astype(out_dtype)
    kvH, B, mp, ps, D = g.shape
    return g.transpose(1, 0, 2, 3, 4).reshape(B, kvH, mp * ps, D)


def _gqa_logits(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """q [B, H, D], k [B, kvH, C, D] -> logits [B, H, C] (fp32)."""
    B, H, D = q.shape
    kvH = k.shape[1]
    group = H // kvH
    qg = q.reshape(B, kvH, group, D)
    logits = jnp.einsum("bkgd,bkcd->bkgc", qg, k,
                        preferred_element_type=jnp.float32) * scale
    return logits.reshape(B, H, k.shape[2])


def _xla_paged_decode(q, k_pages, v_pages, context_lens, block_tables,
                      scale: float, alibi_slopes=None,
                      window=None) -> jax.Array:
    k = _gather_pages(k_pages, block_tables, out_dtype=q.dtype)
    v = _gather_pages(v_pages, block_tables, out_dtype=q.dtype)
    B, kvH, C, D = k.shape
    H = q.shape[1]
    logits = _gqa_logits(q, k, scale)                   # [B, H, C]
    if alibi_slopes is not None:
        # decode query sits at absolute position context_lens-1; keys at c
        rel = (jnp.arange(C)[None, :]
               - (context_lens[:, None] - 1)).astype(jnp.float32)  # [B, C]
        logits = logits + alibi_slopes[None, :, None] * rel[:, None, :]
    mask = jnp.arange(C)[None, :] < context_lens[:, None]
    if window is not None:
        # sliding window: the decode query (pos context_lens-1) sees only
        # the last `window` keys; 0 = global
        mask = mask & sliding_window_allowed(
            context_lens[:, None] - 1, jnp.arange(C)[None, :], window)
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    pg = probs.reshape(B, kvH, H // kvH, C)
    out = jnp.einsum("bkgc,bkcd->bkgd", pg, v)
    return out.reshape(B, H, D)


def paged_decode_attention(q: jax.Array,
                           k_pages: jax.Array,
                           v_pages: jax.Array,
                           context_lens: jax.Array,
                           block_tables: jax.Array,
                           scale: Optional[float] = None,
                           use_pallas: Optional[bool] = None,
                           alibi_slopes: Optional[jax.Array] = None,
                           window: Optional[jax.Array] = None) -> jax.Array:
    """q [B, H, D]; returns [B, H, D].

    ``context_lens[b]`` counts tokens *including* the one just written at
    position ``context_lens[b]-1``. ``alibi_slopes`` [H] adds the ALiBi
    bias (bloom); ``window`` (traced scalar, 0 = global) is the causal
    sliding window — XLA path only.
    """
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    if use_pallas is None:
        use_pallas = _pallas_paged_available()
    if alibi_slopes is not None or window is not None:
        use_pallas = False  # stock kernel has no bias/window inputs
    if k_pages.dtype != q.dtype:
        use_pallas = False  # narrow (fp8) KV store: XLA path upcasts the
        #                     gathered blocks; the kernel has no fp8 read
    if use_pallas:
        # builder-written kernel (pallas_paged_decode.py): GQA-native,
        # head_dim-64 capable, burst-scan compatible — the three gaps that
        # made the stock jax.experimental kernel unusable here (r2)
        from .pallas_paged_decode import paged_gqa_decode
        try:
            return paged_gqa_decode(q, k_pages, v_pages, context_lens,
                                    block_tables, scale=scale)
        except (ValueError, TypeError, NotImplementedError) as e:
            # shape/backend constraints the kernel cannot express; anything
            # else (real bugs) propagates
            global _KERNEL_FALLBACK_WARNED
            if not _KERNEL_FALLBACK_WARNED:
                _KERNEL_FALLBACK_WARNED = True
                from ....utils.logging import logger
                logger.warning(
                    f"paged_decode_attention: Pallas kernel rejected shapes "
                    f"q={q.shape} pages={k_pages.shape} "
                    f"({type(e).__name__}: {e}); using XLA gather fallback")
    return _xla_paged_decode(q, k_pages, v_pages, context_lens, block_tables,
                             scale, alibi_slopes, window)


_KERNEL_FALLBACK_WARNED = False


def ragged_chunk_attention(q: jax.Array,
                           k_pages: jax.Array,
                           v_pages: jax.Array,
                           history_lens: jax.Array,
                           block_tables: jax.Array,
                           scale: Optional[float] = None,
                           alibi_slopes: Optional[jax.Array] = None,
                           window: Optional[jax.Array] = None) -> jax.Array:
    """Batched SplitFuse attention: S sequences × T chunk tokens each.

    The one-program form of the reference's ``build_atoms`` +
    ``flash_attn_by_atoms`` (ragged_ops.cpp:20-47): every scheduled
    sequence-chunk (prefill of any length and single-token decodes alike)
    attends against its own blocked KV in a single dispatch.

    q [S, T, H, D] — chunk queries; query t of sequence s sits at absolute
    position ``history_lens[s] + t``. k_pages/v_pages [kvH, P, ps, D] with
    this step's KV already written. block_tables [S, mp]; context length per
    sequence is implied causally (ctx position c attends iff
    ``c <= history + t``). Returns [S, T, H, D].
    """
    S, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    k = _gather_pages(k_pages, block_tables, out_dtype=q.dtype)  # [S,kvH,C,D]
    v = _gather_pages(v_pages, block_tables, out_dtype=q.dtype)
    kvH, C = k.shape[1], k.shape[2]
    group = H // kvH
    # heads-major so both einsums are plain batch matmuls over contiguous
    # minor dims (same +11% layout win as ops/transformer _xla_attention)
    qg = q.reshape(S, T, kvH, group, D).transpose(0, 2, 3, 1, 4)  # [S,k,g,T,D]
    logits = jnp.einsum("skgtd,skcd->skgtc", qg, k,
                        preferred_element_type=jnp.float32) * scale
    pos_q = history_lens[:, None] + jnp.arange(T)[None, :]        # [S, T]
    if alibi_slopes is not None:
        rel = (jnp.arange(C)[None, None, :]
               - pos_q[:, :, None]).astype(jnp.float32)           # [S, T, C]
        logits = logits + (alibi_slopes.reshape(kvH, group)[None, :, :, None, None]
                           * rel[:, None, None])
    allowed = jnp.arange(C)[None, None, :] <= pos_q[:, :, None]   # [S, T, C]
    if window is not None:
        allowed = allowed & sliding_window_allowed(
            pos_q[:, :, None], jnp.arange(C)[None, None, :], window)
    logits = jnp.where(allowed[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("skgtc,skcd->skgtd", probs, v)
    return out.transpose(0, 3, 1, 2, 4).reshape(S, T, H, D)


def chunk_prefill_attention(q: jax.Array,
                            k_ctx: jax.Array,
                            v_ctx: jax.Array,
                            history_len: jax.Array,
                            scale: Optional[float] = None,
                            alibi_slopes: Optional[jax.Array] = None,
                            window: Optional[jax.Array] = None) -> jax.Array:
    """SplitFuse prefill-chunk attention for ONE sequence.

    q [T, H, D] — chunk queries at absolute positions history_len + i.
    k_ctx/v_ctx [kvH, C, D] — the sequence's gathered context (history +
    this chunk, already written). Causal: query i sees context positions
    <= history_len + i. Returns [T, H, D].
    """
    T, H, D = q.shape
    kvH, C, _ = k_ctx.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    group = H // kvH
    qg = q.reshape(T, kvH, group, D).transpose(1, 2, 0, 3)   # [kvH, g, T, D]
    logits = jnp.einsum("kgtd,kcd->kgtc", qg, k_ctx,
                        preferred_element_type=jnp.float32) * scale
    pos_q = history_len + jnp.arange(T)                          # [T]
    if alibi_slopes is not None:
        rel = (jnp.arange(C)[None, :] - pos_q[:, None]).astype(jnp.float32)
        logits = logits + (alibi_slopes.reshape(kvH, group)[:, :, None, None]
                           * rel[None, None])
    allowed = jnp.arange(C)[None, :] <= pos_q[:, None]
    if window is not None:
        allowed = allowed & sliding_window_allowed(
            pos_q[:, None], jnp.arange(C)[None, :], window)
    logits = jnp.where(allowed[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("kgtc,kcd->kgtd", probs, v_ctx)
    return out.transpose(2, 0, 1, 3).reshape(T, H, D)
