"""What the `test_pallas_flash_*.py` files share; no test lives here, and pytest
does not collect it. They are the numerics-parity suite for the in-repo Pallas
flash attention kernel (ops/transformer/pallas_flash.py) vs the fp32 XLA
reference (`attention._xla_attention`) — forward AND gradients, across the
training feature matrix: causal x GQA x sliding-window x segment-ids x ALiBi x
q_offset, and every mask a launch has learned since. They run on the CPU
tier-1 mesh via ``pl.pallas_call(interpret=True)`` — the same program the chip
compiles.

Documented tolerances:
- fp32 inputs vs fp32 reference: max abs err <= 5e-6 forward, 5e-6 grads
  (both paths accumulate in fp32; differences are reduction-order only).
- bf16 inputs vs the fp32-input reference: max abs err <= 2e-2 forward /
  6e-2 grads — bf16 has ~3 decimal digits; the kernel's fp32 accumulators
  keep the error at input-quantization scale rather than sqrt(S) growth.

What a case costs here (measured, PR 58): interpret mode lowers and compiles a
launch anew at EVERY call (about a second, whatever its grid), pays per grid
step after that (``B x heads x q-blocks x k-blocks``), and outside a
``jax.jit`` every other operation of a call is compiled one by one. So a case
takes its output and its gradients from one jitted program (`out_and_grads`),
and its shape is the smallest that still crosses every boundary its assertion
is about.
"""

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import (_xla_attention,
                                                     alibi_slopes)
from deepspeed_tpu.ops.transformer.pallas_flash import flash_attention_kernel

FP32_TOL = dict(rtol=2e-5, atol=5e-6)
GRAD_TOL = dict(rtol=5e-5, atol=5e-6)
BF16_TOL = dict(rtol=2e-2, atol=2e-2)
BF16_GRAD_TOL = dict(rtol=6e-2, atol=6e-2)


def _qkv(B=2, S=256, H=8, kvH=2, D=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype) * 0.3
    k = jnp.asarray(rng.normal(size=(B, S, kvH, D)), dtype) * 0.3
    v = jnp.asarray(rng.normal(size=(B, S, kvH, D)), dtype) * 0.3
    return q, k, v


def _seg(B=2, S=256, seed=0):
    """Sorted ids (packed documents): long sequences keep whole tiles
    inside one document and whole tiles across two."""
    ids = np.random.default_rng(seed).integers(0, 3, (B, S))
    return jnp.asarray(np.sort(ids, axis=1) if S > 256 else ids, jnp.int32)


# the feature matrix: every feature alone plus the interacting pairs, at
# one tile a sequence (S=256 -> the chooser's 256 x 256) ...
CASES = {
    "causal": dict(causal=True),
    "noncausal": dict(causal=False),
    "window": dict(causal=True, window=64),
    "segids": dict(causal=False, segids=True),
    "segids_causal": dict(causal=True, segids=True),
    "alibi": dict(causal=True, alibi=True),
    "alibi_window": dict(causal=True, alibi=True, window=96),
    "window_segids": dict(causal=True, window=64, segids=True),
    # ... and over 2+ blocks each way with block_q != block_k at head dim
    # 64: skipped, wholly visible and diagonal tiles all occur, the
    # backward sums dq over k-blocks (512-wide) or holds every key (1024);
    # one row a batch: what these are about lies along the sequence
    "tiles_256x512": dict(causal=True, S=1024, B=1, tiles=(256, 512)),
    "tiles_512x256": dict(causal=True, S=1024, B=1, tiles=(512, 256)),
    "tiles_256x1024": dict(causal=True, S=1024, B=1, tiles=(256, 1024)),
    "tiles_auto_2048": dict(causal=True, S=2048, B=1),
    "tiles_q_offset": dict(causal=True, S=1024, B=1, tiles=(256, 512),
                           q_offset=512),
    "tiles_segids": dict(causal=False, segids=True, S=1024,
                         tiles=(512, 256)),
    "tiles_window": dict(causal=True, window=300, S=1024,
                         tiles=(256, 512)),
    "tiles_window_segids_alibi": dict(causal=True, window=300, segids=True,
                                      alibi=True, S=1024, B=1, tiles=(256, 512)),
    # a window that is a Python int on the training call is STATIC: the
    # grids hold the blocks it reaches alone (one tile; q-blocks narrower
    # and wider than k-blocks; square tiles narrower than the window and a
    # window that ends on a block's edge; with documents and ALiBi)
    "static_window": dict(causal=True, window=64, static=True),
    "static_window_256x512": dict(causal=True, window=300, static=True,
                                  S=1024, B=1, tiles=(256, 512)),
    "static_window_512x256": dict(causal=True, window=300, static=True,
                                  segids=True, S=1024, B=1, tiles=(512, 256)),
    "static_window_128x128": dict(causal=True, window=256, static=True,
                                  segids=True, S=1024, B=1, tiles=(128, 128)),
    "static_window_auto_2048": dict(causal=True, window=700, static=True,
                                    segids=True, alibi=True, S=2048, B=1),
}


def _run_pair(case, kvH=2, dtype=jnp.float32, seed=0):
    """-> q, k, v, reference(q, k, v), kernel(q, k, v). A case with
    ``q_offset`` attends the LAST rows of q (from that position on)
    against all of k/v."""
    S = case.get("S", 256)
    q, k, v = _qkv(B=case.get("B", 2), S=S, kvH=kvH, seed=seed, dtype=dtype)
    off = case.get("q_offset")
    if off is not None:
        q = q[:, off:]
    D = q.shape[-1]
    scale = 1.0 / (D ** 0.5)
    seg = _seg(B=q.shape[0], S=S, seed=seed) if case.get("segids") else None
    qseg = seg[:, off:] if (seg is not None and off is not None) else None
    sl = (jnp.asarray(alibi_slopes(q.shape[2])) if case.get("alibi")
          else None)
    w = (jnp.asarray(case["window"], jnp.int32) if case.get("window")
         else None)
    if case.get("static"):
        w = case["window"]
    bq, bk = case.get("tiles", (None, None))

    def reference(q, k, v):
        return _xla_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), case["causal"], scale,
                              seg, alibi=sl, window=w, q_offset=off,
                              q_segment_ids=qseg)

    def kernel(q, k, v):
        return flash_attention_kernel(
            q, k, v, causal=case["causal"], scale=scale, segment_ids=seg,
            q_segment_ids=qseg, alibi_slopes=sl, window=w, q_offset=off,
            block_q=bq, block_k=bk, interpret=True)

    return q, k, v, reference, kernel


def out_and_grads(fns, w, *args):
    """For each of ``fns`` (one function, or the two sides of a comparison):
    ``fn(*args)`` and the gradients of ``sum(fn(*args) x w)`` (``w`` None: of
    the sum of squares; a callable: of ``w(fn(*args))``, for a ``fn`` that
    returns more than the output) by every argument. All of it is ONE jitted
    program."""
    def side(fn):
        def loss(*args):
            out = fn(*args)
            if callable(w):
                return w(out), out
            return jnp.sum(jnp.square(out) if w is None else out * w), out
        return jax.value_and_grad(loss, argnums=tuple(range(len(args))), has_aux=True)
    one = callable(fns)
    got = jax.jit(lambda *args: [side(fn)(*args) for fn in ((fns,) if one else fns)])(*args)
    got = [(out, grads) for (_, out), grads in got]
    return got[0] if one else got


# the documents' and the dq files' packed rows
DOC_TILE = 32


def _packed_ids(S, order):
    """[2, S] ids. ``packed``: row 0 is four documents with ids rising along
    the row (one of 5 tokens, shorter than a tile and ending inside one; one
    spanning several tiles; one that ends ON a tile's edge), row 1 is one
    document. ``random``: every id drawn alone, tiles of one id among them."""
    if order == "random":
        ids = np.random.default_rng(5).integers(0, 4, (2, S))
        ids[0, DOC_TILE:2 * DOC_TILE] = 9       # a whole tile no other id meets
        ids[1, :DOC_TILE] = 3
        return jnp.asarray(ids, jnp.int32)
    ends = np.zeros((2, S), np.int32)
    ends[0, [4, 4 + (S * 3) // 8, S // 2 + DOC_TILE - 1]] = 1
    return jnp.asarray(np.cumsum(ends, 1) - ends, jnp.int32)
