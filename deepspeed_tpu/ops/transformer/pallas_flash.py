"""In-repo Pallas TPU flash attention — forward AND backward kernels.

The training-attention slot's long-context fast path. The stock JAX kernels
this repo previously imported cover only plain causal MHA: the GQA splash
kernel has no bias/window/segment support, and the stock flash kernel
repeats K/V up to the query head count. This kernel pair supports the full
feature matrix the XLA reference path (`attention._xla_attention`) already
has — causal (bottom-right aligned via ``q_offset``), GQA-NATIVE (K/V stay
at kv_heads), sliding window (shared ``sliding_window_allowed`` semantics),
segment ids, ALiBi — with fp32 accumulation and saved row-max/row-sum LSE
residuals, bound with ``jax.custom_vjp`` so the backward is blockwise too
(no O(S^2) score re-materialization: backward FLOPs are recomputed per
tile, memory stays O(S) + the LSE).

``q_offset`` and ``window`` ride scalar prefetch (SMEM), so they may be
TRACED values — the same compiled kernel serves the main training call
(offset 0), the Ulysses post-all-to-all call, and ring attention's per-hop
calls (offset ``(rank - owner) * s_local``, possibly negative = hop fully
in the future). The with-LSE entry point returns the per-row logsumexp so
ring attention can accumulate partial softmax state across ppermute hops
exactly (see ``sequence/ring_attention.py``).

Runs in interpret mode off-TPU (``pl.pallas_call(interpret=True)``) so the
CPU tier-1 tests validate numerics of the same program the chip runs.

Layout conventions (GQA-folded, MXU-aligned tiles):
  q  [B, Sq, H, D]   -> [B*kvH, G, Sq, D]
  k,v[B, Sk, kvH, D] -> [B*kvH, Sk, D]
LSE and the backward's di term are carried lane-broadcast ([..., 128]) in
kernel-facing buffers — sublane->lane transposes are the expensive shape on
TPU, lane replication is free.
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
NUM_SUBLANES = 8
# Finite mask value (not -inf): keeps every exp()/max() chain NaN-free.
# A row that never sees an unmasked key ends with l == 0 and LSE stored as
# MASK_VALUE — a finite sentinel the ring-hop merge can exponentiate
# (exp(MASK - anything_real) underflows to exactly 0.0 in fp32).
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
# Floor used inside exponents: exp(MASK_VALUE - HALF_MASK) == 0 exactly,
# while any real logit (|s| << 1e30) keeps its exact max.
HALF_MASK = MASK_VALUE * 0.5


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
    block_q: int
    block_k: int
    interpret: bool


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """Broadcast a lane-replicated [rows, 128] buffer to n columns. Every
    lane holds the same per-row value, so slicing or tiling are both
    exact."""
    if n <= NUM_LANES:
        return x[:, :n]
    if n % NUM_LANES:
        raise NotImplementedError(f"width {n} not a multiple of {NUM_LANES}")
    return jnp.concatenate([x] * (n // NUM_LANES), axis=1)


def _should_run(cfg: FlashConfig, i, j, info_ref):
    """Whether q-block i has ANY unmasked key in k-block j (block-level
    flop skip). info = [q_offset, window] (traced scalars in SMEM)."""
    if not cfg.causal:
        return True
    q_off = info_ref[0]
    bq, bk = cfg.block_q, cfg.block_k
    # last q row of the block sits at or after the block's first key
    run = (q_off + (i + 1) * bq - 1) >= (j * bk)
    if cfg.use_window:
        w = info_ref[1]
        # first q row within window of the block's last key
        run = run & ((w <= 0) | ((q_off + i * bq) - (j * bk + bk - 1) < w))
    return run


def _tile_logits(cfg: FlashConfig, q, k, i, j, info_ref, slopes_ref,
                 head_idx, qseg, kseg):
    """Masked, scaled fp32 logits for one (block_q, block_k) tile — ONE
    definition shared by the forward and both backward kernels so the
    recomputed tiles cannot diverge from the forward's."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    if cfg.scale != 1.0:
        s = s * cfg.scale
    bq, bk = s.shape
    rows = lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * cfg.block_q
    cols = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * cfg.block_k
    q_pos = rows + info_ref[0]
    if cfg.use_alibi:
        # bias = slope * (key_pos - query_pos), the row-shifted HF-BLOOM
        # form the XLA path uses (softmax is shift-invariant per row)
        slope = slopes_ref[head_idx]
        s = s + slope * (cols - q_pos).astype(jnp.float32)
    mask = None
    if cfg.use_seg:
        # qseg [bq, 128] lane-replicated; kseg [8, bk] sublane-replicated
        mask = _lanes(qseg, bk) == kseg[:1, :]
    if cfg.causal:
        cm = q_pos >= cols
        if cfg.use_window:
            w = info_ref[1]
            cm = cm & ((w <= 0) | ((q_pos - cols) < w))
        mask = cm if mask is None else mask & cm
    if mask is not None:
        s = jnp.where(mask, s, MASK_VALUE)
    return s


def _head_index(cfg: FlashConfig, b, g, G):
    """Global query-head index for (folded batch*kv_head, group) — the
    ALiBi slope lookup."""
    return (b % cfg.kv_heads) * G + g


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(info, slopes, q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                cfg: FlashConfig, G: int, nk: int, head_dim: int):
    b, g = pl.program_id(0), pl.program_id(1)
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, MASK_VALUE, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(_should_run(cfg, i, j, info))
    def _compute():
        q = q_ref[0, 0]          # [bq, D]
        k = k_ref[0]             # [bk, D]
        v = v_ref[0]
        qseg = qseg_ref[0] if cfg.use_seg else None
        kseg = kseg_ref[0] if cfg.use_seg else None
        s = _tile_logits(cfg, q, k, i, j, info, slopes,
                         _head_index(cfg, b, g, G), qseg, kseg)
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

    @pl.when(j == nk - 1)
    def _store():
        l = l_scr[...]
        m_safe = jnp.maximum(m_scr[...], HALF_MASK)
        inv = jnp.where(l == 0.0, 0.0, 1.0 / jnp.where(l == 0.0, 1.0, l))
        o_ref[0, 0] = (acc_scr[...] * _lanes(inv, head_dim)).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            l == 0.0, MASK_VALUE,
            m_safe + jnp.log(jnp.where(l == 0.0, 1.0, l)))


def _fwd_call(cfg: FlashConfig, q, k, v, qseg_b, kseg_b, slopes, info):
    BK, G, Sq, D = q.shape
    Sk = k.shape[1]
    bq, bk = cfg.block_q, cfg.block_k
    nq, nk = Sq // bq, Sk // bk
    grid = (BK, G, nq, nk)
    kvH = cfg.kv_heads

    def kv_idx(b, g, i, j, info, slopes):
        if cfg.causal:
            j = lax.select(_should_run(cfg, i, j, info), j, 0)
        return (b, j, 0)

    in_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, g, i, j, *_: (b, g, i, 0)),
        pl.BlockSpec((1, bk, D), kv_idx),
        pl.BlockSpec((1, bk, D), kv_idx),
    ]
    if cfg.use_seg:
        in_specs.append(pl.BlockSpec(
            (1, bq, NUM_LANES), lambda b, g, i, j, *_: (b // kvH, i, 0)))

        def kseg_idx(b, g, i, j, info, slopes):
            if cfg.causal:
                j = lax.select(_should_run(cfg, i, j, info), j, 0)
            return (b // kvH, 0, j)
        in_specs.append(pl.BlockSpec((1, NUM_SUBLANES, bk), kseg_idx))
    else:
        in_specs += [None, None]

    out_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, g, i, j, *_: (b, g, i, 0)),
        pl.BlockSpec((1, 1, bq, NUM_LANES),
                     lambda b, g, i, j, *_: (b, g, i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((BK, G, Sq, D), q.dtype),
        jax.ShapeDtypeStruct((BK, G, Sq, NUM_LANES), jnp.float32),
    ]
    kernel = functools.partial(_fwd_kernel, cfg=cfg, G=G, nk=nk, head_dim=D)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((bq, NUM_LANES), jnp.float32),
                pltpu.VMEM((bq, NUM_LANES), jnp.float32),
                pltpu.VMEM((bq, D), jnp.float32),
            ]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=cfg.interpret,
        name="flash_fwd",
    )(info, slopes, q, k, v, qseg_b, kseg_b)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _masked_p(cfg, s, lse_b):
    """exp(s - lse) with the empty-row guard: rows whose LSE is the
    MASK_VALUE sentinel (no unmasked key anywhere) contribute exactly 0."""
    p = jnp.exp(s - lse_b)
    return jnp.where(lse_b > HALF_MASK, p, 0.0)


def _dq_kernel(info, slopes, q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
               do_ref, lse_ref, di_ref, dq_ref, dq_scr, *,
               cfg: FlashConfig, G: int, nk: int):
    b, g = pl.program_id(0), pl.program_id(1)
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(_should_run(cfg, i, j, info))
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0, 0]
        qseg = qseg_ref[0] if cfg.use_seg else None
        kseg = kseg_ref[0] if cfg.use_seg else None
        s = _tile_logits(cfg, q, k, i, j, info, slopes,
                         _head_index(cfg, b, g, G), qseg, kseg)
        bk = s.shape[1]
        p = _masked_p(cfg, s, _lanes(lse_ref[0, 0], bk))
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(di_ref[0, 0], bk))
        if cfg.scale != 1.0:
            ds = ds * cfg.scale
        dq_scr[...] += lax.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _store():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(info, slopes, q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
                do_ref, lse_ref, di_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                cfg: FlashConfig, G: int, nq: int):
    b = pl.program_id(0)
    j, g, i = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when((g == 0) & (i == 0))
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(_should_run(cfg, i, j, info))
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0, 0]
        qseg = qseg_ref[0] if cfg.use_seg else None
        kseg = kseg_ref[0] if cfg.use_seg else None
        s = _tile_logits(cfg, q, k, i, j, info, slopes,
                         _head_index(cfg, b, g, G), qseg, kseg)
        bk = s.shape[1]
        p = _masked_p(cfg, s, _lanes(lse_ref[0, 0], bk))
        # dv += P^T @ dO   (contract the q rows)
        dv_scr[...] += lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(di_ref[0, 0], bk))
        if cfg.scale != 1.0:
            ds = ds * cfg.scale
        # dk += dS^T @ q
        dk_scr[...] += lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((g == G - 1) & (i == nq - 1))
    def _store():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_call(cfg: FlashConfig, q, k, v, qseg_b, kseg_b, slopes, info,
              o, lse, do, dlse):
    BK, G, Sq, D = q.shape
    Sk = k.shape[1]
    bq, bk = cfg.block_q, cfg.block_k
    nq, nk = Sq // bq, Sk // bk
    kvH = cfg.kv_heads

    # di = rowsum(dO * O) (the softmax-jacobian diagonal term); a cotangent
    # on the LSE output folds in here: dL/ds = P*(dP - di) + dlse*P
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        di = di - dlse.astype(jnp.float32)
    di_b = lax.broadcast_in_dim(di, (BK, G, Sq, NUM_LANES), (0, 1, 2))
    lse_b = lax.broadcast_in_dim(lse, (BK, G, Sq, NUM_LANES), (0, 1, 2))

    def kv_idx(b, g, i, j, info, slopes):
        if cfg.causal:
            j = lax.select(_should_run(cfg, i, j, info), j, 0)
        return (b, j, 0)

    def q_row_idx(b, g, i, j, *_):
        return (b, g, i, 0)

    seg_specs = [None, None]
    if cfg.use_seg:
        def kseg_idx(b, g, i, j, info, slopes):
            if cfg.causal:
                j = lax.select(_should_run(cfg, i, j, info), j, 0)
            return (b // kvH, 0, j)
        seg_specs = [
            pl.BlockSpec((1, bq, NUM_LANES),
                         lambda b, g, i, j, *_: (b // kvH, i, 0)),
            pl.BlockSpec((1, NUM_SUBLANES, bk), kseg_idx),
        ]
    # ---- dq: same grid walk as the forward -------------------------------
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg=cfg, G=G, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BK, G, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), q_row_idx),
                pl.BlockSpec((1, bk, D), kv_idx),
                pl.BlockSpec((1, bk, D), kv_idx),
                *seg_specs,
                pl.BlockSpec((1, 1, bq, D), q_row_idx),
                pl.BlockSpec((1, 1, bq, NUM_LANES), q_row_idx),
                pl.BlockSpec((1, 1, bq, NUM_LANES), q_row_idx),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, D), q_row_idx),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((BK, G, Sq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=cfg.interpret,
        name="flash_bwd_dq",
    )(info, slopes, q, k, v, qseg_b, kseg_b, do, lse_b, di_b)

    # ---- dk/dv: k-blocks outer, (group, q-block) accumulated in scratch --
    def kv_col_idx(b, j, g, i, *_):
        return (b, j, 0)

    def q_bwd_idx(b, j, g, i, info, slopes):
        if cfg.causal:
            i = lax.select(_should_run(cfg, i, j, info), i, nq - 1)
        return (b, g, i, 0)

    seg_specs2 = [None, None]
    if cfg.use_seg:
        def qseg_bwd_idx(b, j, g, i, info, slopes):
            if cfg.causal:
                i = lax.select(_should_run(cfg, i, j, info), i, nq - 1)
            return (b // kvH, i, 0)
        seg_specs2 = [
            pl.BlockSpec((1, bq, NUM_LANES), qseg_bwd_idx),
            pl.BlockSpec((1, NUM_SUBLANES, bk),
                         lambda b, j, g, i, *_: (b // kvH, 0, j)),
        ]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg=cfg, G=G, nq=nq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BK, nk, G, nq),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), q_bwd_idx),
                pl.BlockSpec((1, bk, D), kv_col_idx),
                pl.BlockSpec((1, bk, D), kv_col_idx),
                *seg_specs2,
                pl.BlockSpec((1, 1, bq, D), q_bwd_idx),
                pl.BlockSpec((1, 1, bq, NUM_LANES), q_bwd_idx),
                pl.BlockSpec((1, 1, bq, NUM_LANES), q_bwd_idx),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, D), kv_col_idx),
                pl.BlockSpec((1, bk, D), kv_col_idx),
            ],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((BK, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((BK, Sk, D), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary")),
        interpret=cfg.interpret,
        name="flash_bwd_dkv",
    )(info, slopes, q, k, v, qseg_b, kseg_b, do, lse_b, di_b)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP binding
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: FlashConfig, q, k, v, qseg_b, kseg_b, slopes, info):
    o, lse = _fwd_call(cfg, q, k, v, qseg_b, kseg_b, slopes, info)
    return o, lse


def _flash_fwd(cfg, q, k, v, qseg_b, kseg_b, slopes, info):
    o, lse = _fwd_call(cfg, q, k, v, qseg_b, kseg_b, slopes, info)
    return (o, lse), (q, k, v, qseg_b, kseg_b, slopes, info, o, lse)


def _flash_bwd(cfg, res, cts):
    q, k, v, qseg_b, kseg_b, slopes, info, o, lse = res
    do, dlse = cts  # a discarded LSE output arrives as a zero array
    dq, dk, dv = _bwd_call(cfg, q, k, v, qseg_b, kseg_b, slopes, info,
                           o, lse, do, dlse)
    return dq, dk, dv, None, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# public entry points ([B, S, H, D] layout, matching attention.py)
# ---------------------------------------------------------------------------


def _auto_interpret() -> bool:
    return jax.default_backend() == "cpu"


def supports(q_shape, k_shape, block_q: int = 128, block_k: int = 128,
             compiled: bool = True) -> bool:
    """Shape gate. ``compiled=True`` (the TPU path) additionally requires
    MXU-aligned k-tiles (128-multiple key length); ``compiled=False`` (the
    interpret path driven on CPU test meshes) accepts anything the clamped
    blocks divide evenly."""
    B, Sq, H, D = q_shape
    Sk, kvH = k_shape[1], k_shape[2]
    if H % kvH:
        return False
    if D > NUM_LANES and D % NUM_LANES:
        return False
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    if Sq % bq or Sk % bk:
        return False
    return bk % NUM_LANES == 0 or not compiled


def _prepare(q, k, v, causal, scale, segment_ids, q_segment_ids,
             alibi_slopes, window, q_offset, block_q, block_k, interpret):
    B, Sq, H, D = q.shape
    Sk, kvH = k.shape[1], k.shape[2]
    if H % kvH:
        raise ValueError(f"query heads {H} not a multiple of kv heads {kvH}")
    G = H // kvH
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    if Sq % bq or Sk % bk:
        raise ValueError(f"seq lengths ({Sq}, {Sk}) not divisible by "
                         f"blocks ({bq}, {bk})")
    if window is not None and not causal:
        raise ValueError("sliding window is causal-only")
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    interp = _auto_interpret() if interpret is None else interpret
    cfg = FlashConfig(
        causal=bool(causal), scale=scale,
        use_seg=segment_ids is not None,
        use_alibi=alibi_slopes is not None,
        use_window=window is not None,
        kv_heads=kvH, block_q=bq, block_k=bk, interpret=bool(interp))

    # GQA-folded layout
    q4 = q.transpose(0, 2, 1, 3).reshape(B * kvH, G, Sq, D)
    k3 = k.transpose(0, 2, 1, 3).reshape(B * kvH, Sk, D)
    v3 = v.transpose(0, 2, 1, 3).reshape(B * kvH, Sk, D)

    qseg_b = kseg_b = None
    if segment_ids is not None:
        qseg = q_segment_ids if q_segment_ids is not None else segment_ids
        qseg_b = lax.broadcast_in_dim(
            qseg.astype(jnp.int32), (B, Sq, NUM_LANES), (0, 1))
        kseg_b = lax.broadcast_in_dim(
            segment_ids.astype(jnp.int32), (B, NUM_SUBLANES, Sk), (0, 2))
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
    return cfg, q4, k3, v3, qseg_b, kseg_b, slopes, info, (B, H, kvH, G)


def flash_attention_with_lse(
        q: jax.Array, k: jax.Array, v: jax.Array, *,
        causal: bool = True, scale: Optional[float] = None,
        segment_ids: Optional[jax.Array] = None,
        q_segment_ids: Optional[jax.Array] = None,
        alibi_slopes: Optional[jax.Array] = None,
        window: Optional[jax.Array] = None,
        q_offset=None, block_q: int = 128, block_k: int = 128,
        interpret: Optional[bool] = None
) -> Tuple[jax.Array, jax.Array]:
    """Flash attention returning ``(out [B, Sq, H, D], lse [B, H, Sq])``.

    ``lse`` is the per-row logsumexp of the masked scaled logits (fp32;
    rows with no unmasked key hold the finite ``MASK_VALUE`` sentinel) —
    the partial-softmax state ring attention accumulates across hops.
    Differentiable in q/k/v including through ``lse``.
    """
    B, Sq, H, D = q.shape
    cfg, q4, k3, v3, qseg_b, kseg_b, slopes, info, dims = _prepare(
        q, k, v, causal, scale, segment_ids, q_segment_ids, alibi_slopes,
        window, q_offset, block_q, block_k, interpret)
    _, _, kvH, G = dims
    o, lse = _flash(cfg, q4, k3, v3, qseg_b, kseg_b, slopes, info)
    out = o.reshape(B, kvH, G, Sq, D).reshape(B, H, Sq, D)
    out = out.transpose(0, 2, 1, 3)
    return out, lse.reshape(B, H, Sq)


def flash_attention_kernel(
        q: jax.Array, k: jax.Array, v: jax.Array, *,
        causal: bool = True, scale: Optional[float] = None,
        segment_ids: Optional[jax.Array] = None,
        q_segment_ids: Optional[jax.Array] = None,
        alibi_slopes: Optional[jax.Array] = None,
        window: Optional[jax.Array] = None,
        q_offset=None, block_q: int = 128, block_k: int = 128,
        interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention, ``[B, S, H, D]`` in and out — the drop-in training
    kernel `attention.flash_attention` dispatches to at long sequence."""
    out, _ = flash_attention_with_lse(
        q, k, v, causal=causal, scale=scale, segment_ids=segment_ids,
        q_segment_ids=q_segment_ids, alibi_slopes=alibi_slopes,
        window=window, q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret)
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
