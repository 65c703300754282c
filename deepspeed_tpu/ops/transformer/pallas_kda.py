"""The core of a Kimi Delta Attention layer (KDA; Kimi Linear, arXiv:2510.26692),
chunked along the row so that its work is matrix products, with a backward of its
own.

``kda(q, k, v, g, beta, first) -> o`` computes, a token at a time along the rows
``t`` of flat ``[R, ...]`` operands, for each of ``H`` heads with a state ``S`` of
``[K, V]`` (keys x values), everything below in float32::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``S_{t-1}`` taken as 0 where ``first[t]`` is set (a packed document's first
token, a batch row's first position). ``q`` and ``k`` ``[R, H x K]`` (the caller's:
normalised a head, ``q`` scaled), ``v`` ``[R, H x V]``, ``g`` ``[R, H x K]`` float32 (a
log-decay a CHANNEL, not positive), ``beta`` ``[R, H]`` (the write strength),
``first`` ``[R]``; ``o`` ``[R, H x V]`` comes back in ``v``'s dtype. The state a token
``[R, H, K, V]`` (68 GB a layer at 32,768 rows of 32 x 128 x 128) exists in neither
pass.

**The chunked form** (`_chunk`, ONE function that every route runs). With ``G_t``
the sum of ``g`` over the chunk's rows up to t, each row's write is ``S_t =
Diag(exp(g_t)) S_{t-1} + k_t u_t^T`` for a pseudo-value ``u_t = beta_t (v_t - k_t^T
Diag(exp(g_t)) S_{t-1})``. Unrolled over a chunk of Q rows that enter with ``S_0``::

    L_ts  = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])       s <  t   [Q, Q]
    A_ts  =        sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])       s <= t   [Q, Q]
    U     = (I + L)^-1 (beta v - (beta k o exp(G)) S_0)                    [Q, V]
    O     = (q o exp(G)) S_0 + A U
    S_Q   = Diag(exp(G_Q)) S_0 + (k o exp(G_Q - G))^T U

- **The decay's range.** ``exp(G_t - G_s)`` is NOT ``exp(G_t) exp(-G_s)`` in
  float32: a ``g`` of -5 a token sums to -320 over 64 rows and ``exp(88)`` is
  float32's end. The chunk is walked in sub-blocks of `SUB` rows. A pair of rows
  in DIFFERENT sub-blocks takes the running sum at the last row before t's
  sub-block as its reference ``G_r`` (s <= r < t): ``exp(G_t - G_r)`` and
  ``exp(G_r - G_s)`` are both at most 1 and their product is the pair's decay, so
  those pairs are matrix products of a decayed ``q`` (or ``beta k``) with a decayed
  ``k``. A pair inside ONE sub-block takes ``exp(G_t - G_s)`` itself, a column s at
  a time (`SUB` elementwise passes and lane sums a chunk): what the published
  kernels do too. No exponent is ever positive (each is clamped at 0: the sums
  restart at a document's first row, so they are not monotone across it, and a
  masked-away ``exp(+400)`` is ``inf x 0``).
- **The triangular solve.** ``I + L`` is unit lower-triangular; its inverse is made
  by blocks, in forward substitution's order (`_tri_inverse`): the diagonal
  sub-blocks by their nilpotent series (exact, and tame over 16 rows), then pairs
  of blocks joined with two products of ``[Q, Q]`` a doubling, float32 at full
  precision. The series over a whole chunk is NOT used: with keys that resemble
  one another (SiLU outputs share a direction; a frequent token repeats) its
  terms grow binomially and cancel, and past float32's end they are NaN. The
  solve's derivative is written out (`_solve`: two products with the inverse
  already made), so no pass differentiates the inverse's making.
- **A reset is written into the masks**: ``L`` and ``A`` are 0 unless s and t lie
  in the same document, the entry state reaches only the rows before the chunk's
  first reset, only the rows after its last reset reach the state that leaves,
  and the running sums restart at a document's first row (``pallas_ssd.
  _log_decays``), forward and backward alike: neither state nor gradient, not
  even a rounding's, crosses a document's start.

**Routes** (`choose_route`, a pure function of the backend, the shapes and the
live mesh's devices; no switch):

- ``"kernel"``: the Pallas pair ``kda_fwd`` / ``kda_bwd``. The grid is (heads, spans
  of `SPAN` rows), the spans innermost and in order (backward: in reverse), a
  head's state ``[V, K]`` (transposed: the decay a channel lies on the lanes)
  carried across them in VMEM. A grid step walks its span's chunks of `CHUNK`
  rows in order; the decayed ``q`` and ``k`` are made in VMEM and never written.
  The forward also writes each SPAN's entry state, ``[spans, H, V, K]`` float32
  (268 MB at 32,768 rows; a chunk's would be 1.07 GB): the only residual beside
  the operands. The backward makes a span's chunks' entry states again from the
  span's (in VMEM), then walks the chunks from the last to the first with the
  state's adjoint in VMEM; a chunk's derivative is `_chunk`'s own (``jax.vjp``
  traced into the kernel: every product in it is one of `_mm` / `_mm_nt`, whose
  derivatives are written as products the chip's compiler takes).
- ``"xla"``: `kda_xla`, the same `_chunk` under a ``lax.scan`` over chunks of
  `XLA_CHUNK` rows (`xla_chunk`: of a quarter of a sequence shorter than four of
  them), each chunk under ``jax.checkpoint``, the heads by ``vmap``. The CPU's
  route, the kernel's test oracle beside `kda_by_token`, and the route under a
  mesh of more than one device (GSPMD does not partition a ``pallas_call``).

The chunk is the kernel's choice (the published kernels use 64 too): the
mathematics does not depend on it. Runs in interpret mode off the TPU.
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

from .pallas_ssd import _by_chunk_cumsum, _chunked, _pad_rows

F32 = jnp.float32
NUM_LANES = 128

#: rows of a chunk (the triangular solve's size), of a sub-block (pairs inside it
#: take their decay directly) and of a grid step's span (whose entry state the
#: forward writes); the XLA route's chunk
CHUNK = 128
SUB = 16
SPAN = 256
XLA_CHUNK = 64
VMEM_LIMIT = 64 * 1024 * 1024


def choose_route(rows: int, heads: int, key_dim: int, value_dim: int, backend: str,
                 devices: int = 1) -> str:
    """``"kernel"`` on a one-device TPU where a head's keys and values fill whole
    lane tiles; else ``"xla"`` (the CPU, a mesh of several devices, another shape)."""
    if backend != "tpu" or devices > 1 or key_dim % NUM_LANES or value_dim % NUM_LANES:
        return "xla"
    return "kernel"


# -- products whose derivatives are products the chip's compiler takes ---------------

def _dot(a, b, dims=None, precision=None):
    if dims is None:
        return lax.dot(a, b, precision=precision, preferred_element_type=F32)
    return lax.dot_general(a, b, dims, precision=precision, preferred_element_type=F32)


_NT = (((1,), (1,)), ((), ()))      # [m, k] x [n, k] -> [m, n]


@jax.custom_vjp
def _mm(a, b):
    """``a @ b`` in float32 from operands of any dtype."""
    return _dot(a, b)


@jax.custom_vjp
def _mm_nt(a, b):
    """``a @ b^T``."""
    return _dot(a, b, _NT)


def _mm_bwd(res, d):
    a, b = res
    return (_mm_nt(d.astype(b.dtype), b).astype(a.dtype),
            _mm(a.T, d.astype(a.dtype)).astype(b.dtype))


def _mm_nt_bwd(res, d):
    a, b = res
    return (_mm(d.astype(b.dtype), b).astype(a.dtype),
            _mm(d.T.astype(a.dtype), a).astype(b.dtype))


_mm.defvjp(lambda a, b: (_mm(a, b), (a, b)), _mm_bwd)
_mm_nt.defvjp(lambda a, b: (_mm_nt(a, b), (a, b)), _mm_nt_bwd)

#: the triangular solve's and the running sums' products are float32 operands at
#: full precision
_EXACT = lax.Precision.HIGHEST


@jax.custom_vjp
def _sums(mask, g):
    """``mask @ g`` for a 0/1 ``mask`` ``[Q, Q]``, float32 at full precision: the
    log-decays' running sums inside a chunk."""
    return _dot(mask, g, precision=_EXACT)


_sums.defvjp(lambda mask, g: (_sums(mask, g), mask),
             lambda mask, d: (jnp.zeros_like(mask), _dot(mask.T, d, precision=_EXACT)))


def _tri_inverse(L, base: int):
    """``(I + L)^-1`` of a strictly lower-triangular ``L`` ``[Q, Q]`` float32, by blocks
    (the stable order: forward substitution's). The diagonal blocks of ``base`` rows
    are inverted by their nilpotent series, ``(I + N)(I + N^2)(I + N^4) ...``, ``N`` the
    block of ``-L`` (exact; over ``base`` = 16 rows its terms stay under 6,435 times the
    entries' size, where the series over a whole chunk of keys that resemble one
    another passes float32's end: the chip's first window read NaN for it). Then
    pairs of blocks are joined, ``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1,
    B^-1]]``, as ``D - D X D`` with ``D`` the block-diagonal inverse so far and ``X``
    the couplings ``C`` alone: two products of ``[Q, Q]`` a doubling."""
    Q = L.shape[0]
    ti = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    si = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    together = lambda rows: ti // rows == si // rows
    power = jnp.where(together(base), -L, 0.0)
    inverse = (ti == si).astype(F32) + power
    reach = 2
    while reach < base:
        power = _dot(power, power, precision=_EXACT)
        inverse = inverse + _dot(inverse, power, precision=_EXACT)
        reach *= 2
    rows = base
    while rows < Q:
        coupled = jnp.where(together(2 * rows) & ~together(rows), L, 0.0)
        inverse = inverse - _dot(inverse, _dot(coupled, inverse, precision=_EXACT),
                                 precision=_EXACT)
        rows *= 2
    return inverse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _solve(base: int, L, rhs):
    """``(I + L)^-1 rhs``, float32; ``base``: `_tri_inverse`'s."""
    return _dot(_tri_inverse(L, base), rhs, precision=_EXACT)


def _solve_fwd(base, L, rhs):
    inverse = _tri_inverse(L, base)
    u = _dot(inverse, rhs, precision=_EXACT)
    return u, (inverse, u)


def _solve_bwd(base, res, du):
    inverse, u = res
    d_rhs = _dot(inverse.T, du, precision=_EXACT)
    return -_dot(d_rhs, u, _NT, precision=_EXACT), d_rhs


_solve.defvjp(_solve_fwd, _solve_bwd)


# -- one chunk: what every route runs ---------------------------------------------

def _row(x, i: int):
    """Row ``i`` of ``x`` ``[Q, n]`` as ``[1, n]``, by a masked sum (its derivative
    is a select, where a slice's is a pad)."""
    at = lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    return jnp.sum(jnp.where(at == i, x, 0.0), axis=0, keepdims=True)


def _rows(x, lo: int, hi: int):
    """Rows ``lo:hi`` of ``x`` ``[Q, n]`` (its derivative lays the rows among zeros by
    a concatenation, where a slice's own is a pad)."""
    @jax.custom_vjp
    def cut(x):
        return x[lo:hi]

    def back(_, d):
        parts = [jnp.zeros((lo, x.shape[1]), d.dtype), d,
                 jnp.zeros((x.shape[0] - hi, x.shape[1]), d.dtype)]
        return (jnp.concatenate([p for p in parts if p.shape[0]], axis=0),)
    cut.defvjp(lambda x: (cut(x), None), back)
    return cut(x)


def _place(x3, j: int):
    """Row ``j`` of every sub-block of ``x3`` ``[blocks, sub, n]`` as ``[blocks, 1, n]``
    (its derivative is a select)."""
    @jax.custom_vjp
    def pick(x3):
        return x3[:, j:j + 1, :]

    def back(_, d):
        at = lax.broadcasted_iota(jnp.int32, (1, x3.shape[1], 1), 1)
        return (jnp.where(at == j, d, 0.0),)
    pick.defvjp(lambda x3: (pick(x3), None), back)
    return pick(x3)


def _pair_products(xs, y, G, sub: int, dtype):
    """``[sum_c x_t[c] y_s[c] exp(G_t[c] - G_s[c]) for x in xs]`` ``[Q, Q]`` over the
    pairs s <= t (anything elsewhere; the caller masks), float32; the module
    docstring's two cases."""
    Q, width = y.shape
    blocks = Q // sub
    rows = lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
    # pairs across sub-blocks, a sub-block of rows t at a time: the reference is
    # the last row before it
    across = [[jnp.zeros((sub, Q), F32)] for _ in xs]
    for block in range(1, blocks):
        lo, hi = block * sub, (block + 1) * sub
        ref = _row(G, lo - 1)
        to = jnp.exp(jnp.minimum(_rows(G, lo, hi) - ref, 0.0))
        since = jnp.where(rows < lo, jnp.exp(jnp.minimum(ref - G, 0.0)), 0.0)
        ys = (y * since).astype(dtype)
        for found, x in zip(across, xs):
            found.append(_mm_nt((_rows(x, lo, hi) * to).astype(dtype), ys))
    out = [found[0] if blocks == 1 else jnp.concatenate(found, axis=0) for found in across]
    # pairs inside a sub-block, a column s at a time
    by_block = lambda a: a.reshape(blocks, sub, width)
    G3, y3, x3s = by_block(G), by_block(y), [by_block(x) for x in xs]
    lane = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    base = lax.broadcasted_iota(jnp.int32, (Q, Q), 0) // sub * sub
    for j in range(sub):
        decayed = jnp.exp(jnp.minimum(G3 - _place(G3, j), 0.0)) * _place(y3, j)
        here = lane == base + j
        out = [jnp.where(here, jnp.sum(x3 * decayed, axis=2, keepdims=True).reshape(Q, 1), o)
               for o, x3 in zip(out, x3s)]
    return out


#: what the normalisation of q and k adds to a head's sum of squares (``fla``'s)
NORM_EPS = 1e-6


def _unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + NORM_EPS)


def _chunk(state, q, k, v, g, beta, seg_c, seg_r, sub: int):
    """One chunk of Q rows of one head. ``state`` ``[V, K]`` float32 (the state that
    enters, transposed); ``q``, ``k`` ``[Q, K]`` and ``v`` ``[Q, V]`` in the operands'
    dtype (q and k as projected: normalised here); ``g`` ``[Q, K]`` float32, the
    log-decays; ``beta`` ``[Q, 1]`` float32; ``seg_c`` ``[Q, 1]`` / ``seg_r`` ``[1, Q]`` the
    resets so far in the chunk -> (``o`` ``[Q, V]`` float32, the state that leaves)."""
    Q, dtype = q.shape[0], q.dtype
    ti = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    si = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    same = seg_c == seg_r
    q32 = _unit(q.astype(F32)) * q.shape[1] ** -0.5
    k32 = _unit(k.astype(F32))
    kb32 = k32 * beta
    # the running sums restart at a document's first row: a row's sum is over its
    # own document's rows alone
    G = _sums(((si <= ti) & same).astype(F32), g)
    L, A = _pair_products([kb32, q32], k32, G, sub, dtype)
    L = jnp.where((si < ti) & same, L, 0.0)
    A = jnp.where((si <= ti) & same, A, 0.0)
    # what of the entry state a row still sees: nothing after the first reset
    enters = jnp.where(seg_c == 0.0, jnp.exp(G), 0.0)
    held = state.astype(dtype)
    u = _solve(sub, L, v.astype(F32) * beta - _mm_nt((kb32 * enters).astype(dtype), held))
    u_op = u.astype(dtype)
    o = _mm_nt((q32 * enters).astype(dtype), held) + _mm(A.astype(dtype), u_op)
    # what of a row reaches the state that leaves: nothing before the last reset
    last, seg_last = _row(G, Q - 1), seg_c[Q - 1:Q, :]
    leaves = jnp.where(seg_c == seg_last, jnp.exp(jnp.minimum(last - G, 0.0)), 0.0)
    carried = jnp.where(seg_last == 0.0, jnp.exp(last), 0.0)            # [1, K]
    return o, carried * state + _mm(u_op.T, (k32 * leaves).astype(dtype))


# -- the oracle and the XLA route ---------------------------------------------------

def kda_by_token(q, k, v, g, beta, first):
    """The recurrence a token at a time, literally (a test's oracle)."""
    H = beta.shape[1]
    K, V = q.shape[1] // H, v.shape[1] // H

    def step(S, xs):
        q, k, v, g, b, first = xs
        q, k, g = (x.astype(F32).reshape(H, K) for x in (q, k, g))
        q, k = _unit(q) * K ** -0.5, _unit(k)
        v, b = v.astype(F32).reshape(H, V), b.astype(F32)
        S = jnp.exp(g)[:, :, None] * jnp.where(first > 0, 0.0, S)
        S = S + b[:, None, None] * k[:, :, None] * (
            v - jnp.einsum("hk,hkv->hv", k, S))[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q, S).reshape(-1)
    _, o = lax.scan(step, jnp.zeros((H, K, V), F32),
                    (q, k, v, g, beta, first.astype(jnp.int32)))
    return o.astype(v.dtype)


def _operands(q, k, v, g, beta, first, chunk: int, pad_to: int):
    """The operands over rows padded to whole ``pad_to`` (a padded row has ``beta``
    0 and ``g`` 0: it changes no state), and the resets so far in each chunk of
    ``chunk`` rows."""
    q, k, v, g, beta, first = _pad_rows(
        pad_to, q, k, v, g.astype(F32), beta.astype(F32), first.astype(jnp.int32))
    return q, k, v, g, beta, _by_chunk_cumsum(first, chunk).astype(F32)


def kda_xla(q, k, v, g, beta, first, chunk: int = XLA_CHUNK, sub: int = SUB):
    """The module docstring's chunked form under a ``lax.scan`` over chunks of
    ``chunk`` rows (a last, partial chunk is padded), each chunk made again in its
    own backward, the heads side by side."""
    rows, H = q.shape[0], beta.shape[1]
    K, V = q.shape[1] // H, v.shape[1] // H
    sub = min(sub, chunk)
    q, k, v, g, beta, seg = _operands(q, k, v, g, beta, first, chunk, chunk)
    by_head = lambda x: _chunked(x, chunk).reshape(-1, chunk, H, x.shape[1] // H)
    heads = jax.vmap(functools.partial(_chunk, sub=sub),
                     in_axes=(0, 1, 1, 1, 1, 1, None, None), out_axes=(1, 0))

    def one_chunk(state, xs):
        *wide, seg = xs
        o, state = heads(state, *wide, seg[:, None], seg[None, :])
        return state, o.reshape(chunk, H * V).astype(v.dtype)

    _, o = lax.scan(jax.checkpoint(one_chunk), jnp.zeros((H, V, K), F32),
                    (*map(by_head, (q, k, v, g, beta)), _chunked(seg, chunk)))
    return o.reshape(-1, H * V)[:rows]


# -- the kernel pair -----------------------------------------------------------

def _seg_layouts(seg, chunk: int):
    """The resets so far a row, in both layouts a grid step reads: ``[R, 128]`` (a
    row's value over the lanes) and ``[chunks, 8, chunk]`` (a chunk's rows on the
    lanes)."""
    cols = jnp.broadcast_to(seg[:, None], (seg.shape[0], NUM_LANES))
    rows = jnp.broadcast_to(_chunked(seg, chunk)[:, None, :], (seg.shape[0] // chunk, 8, chunk))
    return cols, rows


def _rows_of(i, chunk: int):
    return pl.ds(pl.multiple_of(i * chunk, chunk), chunk)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, segc_ref, segr_ref, o_ref, entry_ref,
                state_scr, *, chunk: int, sub: int):
    @pl.when(pl.program_id(1) == 0)
    def _row_start():
        state_scr[...] = jnp.zeros_like(state_scr)

    entry_ref[0, 0] = state_scr[...]

    def one_chunk(i, state):
        at = _rows_of(i, chunk)
        o, state = _chunk(state, q_ref[at, :], k_ref[at, :], v_ref[at, :], g_ref[at, :],
                          beta_ref[0, at, :], segc_ref[at, :][:, :1], segr_ref[i][:1, :], sub)
        o_ref[at, :] = o.astype(o_ref.dtype)
        return state

    state_scr[...] = lax.fori_loop(0, q_ref.shape[0] // chunk, one_chunk, state_scr[...])


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, segc_ref, segr_ref, entry_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, adjoint_scr, states_scr,
                *, chunk: int, sub: int):
    @pl.when(pl.program_id(1) == 0)
    def _row_end():
        adjoint_scr[...] = jnp.zeros_like(adjoint_scr)

    chunks = q_ref.shape[0] // chunk

    def read(i):
        at = _rows_of(i, chunk)
        return at, (q_ref[at, :], k_ref[at, :], v_ref[at, :], g_ref[at, :],
                    beta_ref[0, at, :]), (segc_ref[at, :][:, :1], segr_ref[i][:1, :])

    def again(i, state):        # the chunks' entry states, from the span's
        states_scr[i] = state
        _, wide, segs = read(i)
        return _chunk(state, *wide, *segs, sub)[1]

    lax.fori_loop(0, chunks, again, entry_ref[0, 0])

    def back(r, adjoint):
        i = chunks - 1 - r
        at, wide, segs = read(i)
        _, pull = jax.vjp(lambda state, *wide: _chunk(state, *wide, *segs, sub),
                          states_scr[i], *wide)
        adjoint, dq, dk, dv, dg, dbeta = pull((do_ref[at, :].astype(F32), adjoint))
        for ref, d in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv), (dg_ref, dg)):
            ref[at, :] = d.astype(ref.dtype)
        dbeta_ref[0, at, :] = dbeta
        return adjoint

    adjoint_scr[...] = lax.fori_loop(0, chunks, back, adjoint_scr[...])


def _params(interpret: bool):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret)


def _specs(span: int, chunk: int, K: int, V: int, spans: int, backward: bool):
    at = (lambda c: spans - 1 - c) if backward else (lambda c: c)
    keys = pl.BlockSpec((span, K), lambda h, c: (at(c), h))
    values = pl.BlockSpec((span, V), lambda h, c: (at(c), h))
    segc = pl.BlockSpec((span, NUM_LANES), lambda h, c: (at(c), 0))
    segr = pl.BlockSpec((span // chunk, 8, chunk), lambda h, c: (at(c), 0, 0))
    entry = pl.BlockSpec((1, 1, V, K), lambda h, c: (at(c), h, 0, 0))
    beta = pl.BlockSpec((1, span, 1), lambda h, c: (h, at(c), 0))
    return keys, values, beta, segc, segr, entry


def _fwd_call(q, k, v, g, beta, segc, segr, *, heads: int, span: int, chunk: int, sub: int,
              interpret: bool):
    """-> (``o`` ``[R, H x V]``, the spans' entry states ``[spans, H, V, K]`` float32).
    ``beta`` ``[H, R, 1]``: a head's column."""
    R, K, V = q.shape[0], q.shape[1] // heads, v.shape[1] // heads
    spans = R // span
    keys, values, column, sc, sr, entry = _specs(span, chunk, K, V, spans, backward=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(heads, spans),
            in_specs=[keys, keys, values, keys, column, sc, sr],
            out_specs=[values, entry],
            scratch_shapes=[pltpu.VMEM((V, K), F32)]),
        out_shape=[jax.ShapeDtypeStruct((R, heads * V), v.dtype),
                   jax.ShapeDtypeStruct((spans, heads, V, K), F32)],
        name="kda_fwd", **_params(interpret),
    )(q, k, v, g, beta, segc, segr)


def _bwd_call(q, k, v, g, beta, segc, segr, entry, do, *, heads: int, span: int, chunk: int,
              sub: int, interpret: bool):
    """-> (dq, dk, dv in the operands' dtype, dg and d beta ``[H, R, 1]`` float32)."""
    R, K, V = q.shape[0], q.shape[1] // heads, v.shape[1] // heads
    spans = R // span
    keys, values, column, sc, sr, state = _specs(span, chunk, K, V, spans, backward=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(heads, spans),
            in_specs=[keys, keys, values, keys, column, sc, sr, state, values],
            out_specs=[keys, keys, values, keys, column],
            scratch_shapes=[pltpu.VMEM((V, K), F32),
                            pltpu.VMEM((span // chunk, V, K), F32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), jax.ShapeDtypeStruct(g.shape, F32),
                   jax.ShapeDtypeStruct(beta.shape, F32)],
        name="kda_bwd", **_params(interpret),
    )(q, k, v, g, beta, segc, segr, entry, do)


def _columns(beta):
    """``beta`` ``[R, H]`` as the launches read it: ``[H, R, 1]``, a head's column."""
    return beta.T[:, :, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _core(how: Tuple[int, int, int, int, bool], q, k, v, g, beta, seg):
    return _core_fwd(how, q, k, v, g, beta, seg)[0]


def _core_fwd(how, q, k, v, g, beta, seg):
    heads, span, chunk, sub, interpret = how
    o, entry = _fwd_call(q, k, v, g, _columns(beta), *_seg_layouts(seg, chunk), heads=heads,
                         span=span, chunk=chunk, sub=sub, interpret=interpret)
    # named so that a rematerialised block's backward need not run the forward again
    o = checkpoint_name(o, "kda_o")
    entry = checkpoint_name(entry, "kda_state")
    return o, (q, k, v, g, beta, seg, entry)


def _core_bwd(how, res, do):
    heads, span, chunk, sub, interpret = how
    q, k, v, g, beta, seg, entry = res
    *wide, dbeta = _bwd_call(
        q, k, v, g, _columns(beta), *_seg_layouts(seg, chunk), entry, do.astype(v.dtype),
        heads=heads, span=span, chunk=chunk, sub=sub, interpret=interpret)
    return (*wide, dbeta[:, :, 0].T, None)


_core.defvjp(_core_fwd, _core_bwd)


def kda_kernel(q, k, v, g, beta, first, *, chunk: int = CHUNK, sub: int = SUB,
               span: int = SPAN, interpret: Optional[bool] = None):
    """The kernel route (module docstring); ``interpret``: None, off the TPU. XLA
    makes the padding and the resets' count a chunk, nothing else."""
    rows, H = q.shape[0], beta.shape[1]
    if span % chunk or chunk % sub or sub % 8:
        raise ValueError(f"a span ({span}) of whole chunks ({chunk}) of whole sub-blocks "
                         f"({sub}) of whole sublane tiles")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    q, k, v, g, beta, seg = _operands(q, k, v, g, beta, first, chunk, span)
    o = _core((H, span, chunk, sub, bool(interpret)), q, k, v, g, beta, seg)
    return o[:rows]


def xla_chunk(row: int) -> Tuple[int, int]:
    """The XLA route's (chunk, sub-block) over sequences of ``row`` rows each:
    `XLA_CHUNK` and `SUB`, or, where a sequence is shorter than four such chunks,
    the largest power of two in a quarter of it (8 at least) in two sub-blocks: a
    short sequence still crosses chunks, and its pairs sub-blocks. The mathematics
    does not depend on either."""
    chunk = min(XLA_CHUNK, max(8, 1 << (max(row // 4, 1).bit_length() - 1)))
    return chunk, min(SUB, chunk // 2)


def kda(q, k, v, g, beta, first, *, route: Optional[str] = None, devices: int = 1,
        row: Optional[int] = None):
    """The module docstring's core by `choose_route` (or ``route`` given); ``row``:
    the rows of one sequence where several lie end to end (None: all of them)."""
    if route is None:
        H = beta.shape[1]
        route = choose_route(q.shape[0], H, q.shape[1] // H, v.shape[1] // H,
                             jax.default_backend(), devices)
    if route == "kernel":
        return kda_kernel(q, k, v, g, beta, first)
    return kda_xla(q, k, v, g, beta, first, *xla_chunk(row or q.shape[0]))
