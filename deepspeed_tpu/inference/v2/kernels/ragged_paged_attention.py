"""Ragged paged attention — ONE Pallas launch for an arbitrary mixed wave.

The serving tentpole (ISSUE 6; *Ragged Paged Attention*, arXiv 2604.15464):
the previous engine dispatched every wave as TWO static atom classes
(decode rows through ``paged_gqa_decode``, prefill chunks through the
batched XLA ``ragged_chunk_attention``) whose bucket product is what forced
the scheduler's three-canonical-shapes restriction. This kernel processes
one *ragged wave* — any composition of prefill chunks and decode tokens —
against the blocked KV pool in a single launch.

Wave model (the reference's ``build_atoms``/``flash_attn_by_atoms`` made
TPU-native): the host splits every scheduled sequence-chunk into **atoms**
of at most ``block_q`` query tokens (a decode token is a 1-query atom; a
256-token prefill chunk is 32 atoms sharing one page table). Per-atom
descriptors ride scalar prefetch, so the DMA addresses of the pages are
known before each program body runs and the SAME compiled kernel serves
every wave composition of a bucket shape:

- ``cu_q_lens [A+1]`` — cumulative query counts (atom a owns flat query
  rows ``cu_q_lens[a]:cu_q_lens[a+1]``; zero-length atoms are padding);
- ``kv_lens   [A]``   — context length *including* the atom's own tokens;
- ``page_indices [A, MP]`` — the atom's sequence's block table.

Grid ``(A, kvH, MP)``: each program computes one atom's whole GQA query
group (``block_q x group`` rows — a decode atom therefore costs the same
MXU tile as the old per-sequence decode kernel, since 8 sublanes is the
hardware minimum anyway) against ONE streamed KV page, accumulating with
the same online-softmax machinery as ``ops/transformer/pallas_flash.py``
(fp32 running max + denominator, finite ``MASK_VALUE`` sentinel so empty
rows stay NaN-free, lane-broadcast m/l buffers). Causality is bottom-right
aligned per atom: query row ``t`` sits at absolute position
``kv_len - q_len + t``.

Dispatch policy mirrors ``paged_attention.py``: the Pallas kernel is the
TPU path (``DSTPU_RAGGED_ATTN=xla`` escape hatch, ``=pallas`` forces it —
interpret mode off-TPU, which is how the parity suite runs on the CPU
mesh); ALiBi / sliding-window models and narrow (fp8) KV stores take the
XLA fallback, which routes through the SAME atom layout so the two paths
cannot diverge semantically.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ....ops.transformer.pallas_flash import HALF_MASK, MASK_VALUE, NUM_LANES
from .paged_attention import ragged_chunk_attention


def _ragged_backend() -> str:
    """Live env read (never cached): '' = auto (Pallas on TPU, XLA
    elsewhere), 'pallas' = force the kernel (interpret mode off-TPU),
    'xla' = escape hatch."""
    import os
    return os.environ.get("DSTPU_RAGGED_ATTN", "")


def _pallas_wave_default() -> bool:
    mode = _ragged_backend()
    if mode == "pallas":
        return True
    if mode == "xla":
        return False
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _wave_kernel(q_lens_ref, kv_lens_ref, bt_ref,      # scalar prefetch
                 q_ref, k_ref, v_ref, out_ref,
                 acc_ref, m_ref, l_ref, *, page_size: int, group: int):
    """One (atom, kv_head, page) program: online-softmax accumulation of
    the atom's ``block_q x group`` query rows against one streamed page.
    Pages are consumed in grid order — sequential accumulation over the
    last grid dimension, the TPU-guaranteed execution order (same
    contract as ``pallas_paged_decode._decode_kernel``)."""
    a = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kv_lens_ref[a]
    # tokens of this atom's sequence that land in page j; <= 0 means a
    # pure bubble page (padding atoms have kv_len 0 and skip every page)
    valid = kv_len - j * page_size

    @pl.when(valid > 0)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)         # [bq*g, D] (pre-scaled)
        k = k_ref[0, 0].astype(jnp.float32)         # [ps, D]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [rows, ps]
        rows, ps = s.shape
        col = lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
        # row r holds query t = r // group of the atom (host fold order
        # [t, g]); its absolute position is kv_len - q_len + t
        t = lax.broadcasted_iota(jnp.int32, (rows, ps), 0) // group
        q_pos = (kv_len - q_lens_ref[a]) + t
        # causal, bottom-right aligned: key position j*ps + col visible
        # iff <= q_pos. For the atom's valid rows this also caps at
        # kv_len - 1; the (col < valid) term bounds the PADDED rows
        # (t >= q_len), whose output is discarded by the gather anyway.
        mask = (col < valid) & ((col + j * page_size) <= q_pos)
        s = jnp.where(mask, s, MASK_VALUE)
        m_prev = m_ref[:, :1]                       # [rows, 1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # HALF_MASK floor (pallas_flash machinery): fully-masked rows keep
        # p == 0 exactly and never produce inf - inf
        m_safe = jnp.maximum(m_next, HALF_MASK)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(jnp.maximum(m_prev, HALF_MASK) - m_safe)
        l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
        m_ref[:, :1] = m_next
        v = v_ref[0, 0].astype(jnp.float32)         # [ps, D]
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[:, :1]
        out_ref[0, 0] = (acc_ref[...] /
                         jnp.where(l > 0.0, l, 1.0)).astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# public wrapper: flat token stream in, flat token stream out
# ---------------------------------------------------------------------------


def _scatter_to_atoms(q: jax.Array, cu_q_lens: jax.Array, A: int,
                      block_q: int) -> jax.Array:
    """q [N, H, D] flat wave stream -> [A, block_q, H, D] atom tiles.

    Token i belongs to atom a = searchsorted(cu, i, right) - 1 at tile row
    i - cu[a]. Flat-stream PAD tokens (i >= cu[-1]) resolve to the last
    atom with rows >= block_q and are dropped by the scatter; their
    gathered output is garbage, which is fine — they are padding in the
    wave stream too.
    """
    N = q.shape[0]
    tok = jnp.arange(N, dtype=jnp.int32)
    a_of = jnp.clip(jnp.searchsorted(cu_q_lens.astype(jnp.int32), tok,
                                     side="right") - 1, 0, A - 1)
    row = tok - cu_q_lens[a_of]
    dest = jnp.where(row < block_q, a_of * block_q + row, A * block_q)
    flat = jnp.zeros((A * block_q,) + q.shape[1:], q.dtype)
    flat = flat.at[dest].set(q, mode="drop")
    return flat.reshape(A, block_q, *q.shape[1:]), dest


def _gather_from_atoms(out_tiled: jax.Array, dest: jax.Array) -> jax.Array:
    """[A, bq, H, D] atom tiles -> [N, H, D] flat stream (pad rows clip)."""
    A, bq = out_tiled.shape[:2]
    flat = out_tiled.reshape(A * bq, *out_tiled.shape[2:])
    return flat[jnp.clip(dest, 0, A * bq - 1)]


def ragged_paged_attention(q: jax.Array,
                           k_pages: jax.Array,
                           v_pages: jax.Array,
                           kv_lens: jax.Array,
                           page_indices: jax.Array,
                           cu_q_lens: jax.Array,
                           scale: Optional[float] = None,
                           block_q: int = 8,
                           use_pallas: Optional[bool] = None,
                           alibi_slopes: Optional[jax.Array] = None,
                           window: Optional[jax.Array] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """One ragged wave of attention: q [N, H, D] (flat token stream, any
    mix of prefill-chunk and decode tokens, atom-major) against the
    blocked pool; returns [N, H, D].

    ``kv_lens[a]`` counts the atom's visible context INCLUDING its own
    tokens; ``cu_q_lens`` is the [A+1] prefix sum of per-atom query
    counts (every atom <= ``block_q`` queries — the host wave builder's
    contract, ``ragged.wave.build_wave``); ``page_indices [A, MP]`` is
    each atom's block table. All three are TRACED i32 operands: one
    compiled program per (N, A, MP) bucket serves every composition.
    """
    N, H, D = q.shape
    kvH, P, ps, _ = k_pages.shape
    A, MP = page_indices.shape
    if H % kvH:
        raise ValueError(f"query heads {H} not a multiple of kv heads {kvH}")
    g = H // kvH
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    if use_pallas is None:
        use_pallas = _pallas_wave_default()
    if alibi_slopes is not None or window is not None:
        use_pallas = False  # bias/window ride the XLA atom path only
    if k_pages.dtype != q.dtype:
        use_pallas = False  # narrow (fp8) KV store: the XLA path upcasts
        #                     after its per-atom gather

    q_lens = (cu_q_lens[1:] - cu_q_lens[:-1]).astype(jnp.int32)
    q_tiled, dest = _scatter_to_atoms(q, cu_q_lens, A, block_q)

    if use_pallas:
        interp = (jax.default_backend() != "tpu") if interpret is None \
            else interpret
        # GQA fold [A, bq, H, D] -> [A, kvH, bq*g, D], row = t*g + gi
        qk = q_tiled.reshape(A, block_q, kvH, g, D)
        qk = qk.transpose(0, 2, 1, 3, 4).reshape(A, kvH, block_q * g, D)
        out = _wave_call(qk, k_pages, v_pages, q_lens, kv_lens, page_indices,
                         scale=scale, group=g, interpret=interp)
        out = out.reshape(A, kvH, block_q, g, D).transpose(0, 2, 1, 3, 4)
        out = out.reshape(A, block_q, H, D)
    else:
        # XLA fallback through the SAME atom layout: the batched chunk
        # reference with history = kv_len - q_len reproduces the kernel's
        # causal contract exactly on valid rows (padded rows differ and
        # are discarded by the gather below)
        out = ragged_chunk_attention(
            q_tiled, k_pages, v_pages, kv_lens - q_lens, page_indices,
            scale=scale, alibi_slopes=alibi_slopes, window=window)
    return _gather_from_atoms(out, dest)


@functools.partial(jax.jit, static_argnames=("scale", "group", "interpret"))
def _wave_call(q_tiled, k_pages, v_pages, q_lens, kv_lens, page_indices, *,
               scale: float, group: int, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    A, kvH, rows, D = q_tiled.shape
    ps = k_pages.shape[2]
    MP = page_indices.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(A, kvH, MP),
        in_specs=[
            pl.BlockSpec((1, 1, rows, D),
                         lambda a, k, j, ql, kl, bt: (a, k, 0, 0)),
            pl.BlockSpec((1, 1, ps, D),
                         lambda a, k, j, ql, kl, bt: (k, bt[a * MP + j], 0, 0)),
            pl.BlockSpec((1, 1, ps, D),
                         lambda a, k, j, ql, kl, bt: (k, bt[a * MP + j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, D),
                               lambda a, k, j, ql, kl, bt: (a, k, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((rows, NUM_LANES), jnp.float32),
            pltpu.VMEM((rows, NUM_LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(_wave_kernel, page_size=ps, group=group)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((A, kvH, rows, D), q_tiled.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ragged_paged_attention",
    )(q_lens.astype(jnp.int32), kv_lens.astype(jnp.int32),
      page_indices.astype(jnp.int32).reshape(-1),
      (q_tiled * scale).astype(q_tiled.dtype), k_pages, v_pages)
