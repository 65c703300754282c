"""Numerics-parity suite for the in-repo Pallas flash attention kernel
(ops/transformer/pallas_flash.py) vs the fp32 XLA reference
(`attention._xla_attention`) — forward AND gradients, across the training
feature matrix: causal x GQA x sliding-window x segment-ids x ALiBi x
q_offset. Runs on the CPU tier-1 mesh via ``pl.pallas_call(interpret=True)``
— the same program the chip compiles.

Documented tolerances:
- fp32 inputs vs fp32 reference: max abs err <= 5e-6 forward, 5e-6 grads
  (both paths accumulate in fp32; differences are reduction-order only).
- bf16 inputs vs the fp32-input reference: max abs err <= 2e-2 forward /
  6e-2 grads — bf16 has ~3 decimal digits; the kernel's fp32 accumulators
  keep the error at input-quantization scale rather than sqrt(S) growth.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import (_xla_attention,
                                                     alibi_slopes)
from deepspeed_tpu.ops.transformer.pallas_flash import (
    MASK_VALUE, flash_attention_kernel, flash_attention_with_lse,
    merge_partials)

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
    # backward sums dq over k-blocks (512-wide) or holds every key (1024)
    "tiles_256x512": dict(causal=True, S=1024, tiles=(256, 512)),
    "tiles_512x256": dict(causal=True, S=1024, tiles=(512, 256)),
    "tiles_256x1024": dict(causal=True, S=1024, tiles=(256, 1024)),
    "tiles_auto_2048": dict(causal=True, S=2048, B=1),
    "tiles_q_offset": dict(causal=True, S=1024, tiles=(256, 512),
                           q_offset=512),
    "tiles_segids": dict(causal=False, segids=True, S=1024,
                         tiles=(512, 256)),
    "tiles_window": dict(causal=True, window=300, S=1024,
                         tiles=(256, 512)),
    "tiles_window_segids_alibi": dict(causal=True, window=300, segids=True,
                                      alibi=True, S=1024, tiles=(256, 512)),
    # a window that is a Python int on the training call is STATIC: the
    # grids hold the blocks it reaches alone (one tile; q-blocks narrower
    # and wider than k-blocks; square tiles narrower than the window and a
    # window that ends on a block's edge; with documents and ALiBi)
    "static_window": dict(causal=True, window=64, static=True),
    "static_window_256x512": dict(causal=True, window=300, static=True,
                                  S=1024, tiles=(256, 512)),
    "static_window_512x256": dict(causal=True, window=300, static=True,
                                  segids=True, S=1024, tiles=(512, 256)),
    "static_window_128x128": dict(causal=True, window=256, static=True,
                                  segids=True, S=1024, tiles=(128, 128)),
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


@pytest.mark.parametrize("name", sorted(CASES))
@pytest.mark.parametrize("kvH", [1, 2, 8])
def test_forward_parity_fp32(eight_devices, name, kvH):
    q, k, v, reference, kernel = _run_pair(CASES[name], kvH=kvH)
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(reference(q, k, v)), **FP32_TOL)


@pytest.mark.parametrize("name", sorted(CASES))
def test_grad_parity_fp32(eight_devices, name):
    q, k, v, reference, kernel = _run_pair(CASES[name])
    g_ref = jax.grad(lambda *a: jnp.sum(jnp.square(reference(*a))),
                     argnums=(0, 1, 2))(q, k, v)
    g_ker = jax.grad(lambda *a: jnp.sum(jnp.square(kernel(*a))),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_ker, g_ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   err_msg=f"{name}:{nm}", **GRAD_TOL)


@pytest.mark.parametrize("name", ["causal", "window", "alibi",
                                  "segids_causal", "tiles_256x512"])
def test_bf16_inputs_vs_fp32_reference(eight_devices, name):
    """bf16 training inputs against the fp32 reference: the fp32
    accumulation contract (errors stay at input-quantization scale)."""
    q, k, v, reference, kernel = _run_pair(CASES[name], dtype=jnp.bfloat16,
                                           seed=3)
    got = kernel(q, k, v).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(reference(q, k, v)), **BF16_TOL)
    g_ref = jax.grad(lambda *a: jnp.sum(jnp.square(reference(*a))),
                     argnums=(0, 1, 2))(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    g_ker = jax.grad(lambda q, k, v: jnp.sum(jnp.square(kernel(q, k, v))),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_ker, g_ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   err_msg=f"{name}:{nm}", **BF16_GRAD_TOL)


def test_q_offset_matches_chunked_contract(eight_devices):
    """q_offset = absolute position of q row 0 (bottom-right alignment):
    a query chunk against the full K must match the XLA path's q_offset
    semantics, forward and grads — this is the contract the Ulysses and
    ring calls rely on."""
    q, k, v = _qkv(S=256, kvH=2, seed=5)
    qc = q[:, 128:]
    scale = 1.0 / (q.shape[-1] ** 0.5)

    def loss_ref(qc, k, v):
        return jnp.sum(jnp.square(_xla_attention(
            qc, k, v, True, scale, None, q_offset=128)))

    def loss_ker(qc, k, v):
        return jnp.sum(jnp.square(flash_attention_kernel(
            qc, k, v, causal=True, scale=scale, q_offset=128,
            interpret=True)))

    ref, g_ref = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(qc, k, v)
    got, g_ker = jax.value_and_grad(loss_ker, argnums=(0, 1, 2))(qc, k, v)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    for a, b in zip(g_ker, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **GRAD_TOL)


def test_traced_q_offset_and_window(eight_devices):
    """q_offset and window ride scalar prefetch, so TRACED values (the
    ring per-hop offsets, gpt-neo's scanned per-layer windows) must work
    under jit without retracing the kernel per value."""
    q, k, v = _qkv(S=128, kvH=2, seed=6)
    scale = 1.0 / (q.shape[-1] ** 0.5)

    @jax.jit
    def f(q, k, v, off, w):
        return flash_attention_kernel(q, k, v, causal=True, scale=scale,
                                      q_offset=off, window=w,
                                      interpret=True)

    for off, w in ((0, 0), (0, 32), (64, 48)):
        qq = q if off == 0 else q[:, :64]
        ref = _xla_attention(qq, k, v, True, scale, None,
                             window=jnp.asarray(w, jnp.int32),
                             q_offset=off)
        got = f(qq, k, v, jnp.asarray(off, jnp.int32),
                jnp.asarray(w, jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   err_msg=f"off={off} w={w}", **FP32_TOL)


def test_lse_matches_reference_logsumexp(eight_devices):
    """The saved LSE residual must be the true per-row logsumexp of the
    masked scaled logits — ring accumulation and the backward both build
    on it."""
    q, k, v = _qkv(B=1, S=128, H=2, kvH=2, D=64, seed=7)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    _, lse = flash_attention_with_lse(q, k, v, causal=True, scale=scale,
                                      interpret=True)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.arange(128)[:, None] >= jnp.arange(128)[None, :]
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    ref = jax.scipy.special.logsumexp(logits, axis=-1)  # [B, H, S]
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # ~21 s: the hop/LSE merge contract is exercised
# end-to-end by tests/unit/runtime/test_ring_attention.py
# (ring_matches_dense, ring_flash_body parity and gradients); this is the
# kernel-level restatement of the same accumulation identity.
def test_ring_lse_accumulation_equivalence(eight_devices):
    """The ring-attention hop contract: per-hop kernel partials merged via
    LSE accumulation (merge_partials) — including hops entirely in the
    future (all-masked: lse == MASK_VALUE sentinel) — must equal one-shot
    attention over the concatenated keys, forward and grads."""
    B, S, H, kvH, D = 2, 128, 4, 2, 64
    q, k, v = _qkv(B=B, S=S, H=H, kvH=kvH, D=D, seed=8)
    scale = 1.0 / (D ** 0.5)
    sp, s = 4, S // 4

    def ring_merged(q, k, v):
        """Emulates _ring_local_flash for the rank holding the LAST q
        shard (sees every block) and rank 0 (sees only its own)."""
        outs = []
        for r in (sp - 1, 0):
            qr = q[:, r * s:(r + 1) * s]
            from deepspeed_tpu.ops.transformer.pallas_flash import (
                flash_attention_with_lse)
            o = jnp.zeros_like(qr)
            lse = jnp.full((B, H, s), MASK_VALUE, jnp.float32)
            for owner in range(sp):
                o_h, lse_h = flash_attention_with_lse(
                    qr, k[:, owner * s:(owner + 1) * s],
                    v[:, owner * s:(owner + 1) * s],
                    causal=True, scale=scale, q_offset=(r - owner) * s,
                    interpret=True)
                o, lse = merge_partials(o, lse, o_h, lse_h)
            outs.append(o)
        return outs

    ref = _xla_attention(q, k, v, True, scale, None)
    got_last, got_first = ring_merged(q, k, v)
    np.testing.assert_allclose(np.asarray(got_last),
                               np.asarray(ref[:, -s:]), **FP32_TOL)
    np.testing.assert_allclose(np.asarray(got_first),
                               np.asarray(ref[:, :s]), **FP32_TOL)

    # grads flow through the merge's LSE weights
    def loss_merged(q, k, v):
        a, b = ring_merged(q, k, v)
        return jnp.sum(jnp.square(a)) + jnp.sum(jnp.square(b))

    def loss_ref(q, k, v):
        r = _xla_attention(q, k, v, True, scale, None)
        return (jnp.sum(jnp.square(r[:, -s:]))
                + jnp.sum(jnp.square(r[:, :s])))

    g_m = jax.grad(loss_merged, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_m, g_r, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   err_msg=nm, **GRAD_TOL)


def test_remat_attention_only_policy_composes(eight_devices):
    """jax.checkpoint with the attention_only policy (which names no
    tensor inside the kernel) must recompute nothing quadratic and still
    produce exact grads — the kernel's O(S) LSE residuals replace the
    attn_big checkpoint."""
    q, k, v = _qkv(S=128, kvH=2, seed=9)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    policy = jax.checkpoint_policies.save_anything_except_these_names(
        "attn_big")

    @functools.partial(jax.checkpoint, policy=policy)
    def block(q, k, v):
        return flash_attention_kernel(q, k, v, causal=True, scale=scale,
                                      interpret=True)

    g_ck = jax.grad(lambda *a: jnp.sum(jnp.square(block(*a))),
                    argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: jnp.sum(jnp.square(_xla_attention(
        a[0], a[1], a[2], True, scale, None))), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ck, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **GRAD_TOL)


def test_alibi_slopes_are_nondifferentiable_by_contract(eight_devices):
    """ALiBi slopes are a fixed positional schedule (Press et al. do not
    learn them); the kernel stop-gradients them EXPLICITLY — this test
    pins that contract so the zero cotangent reads as intent, not a bug.
    Training slopes as parameters requires the XLA path."""
    q, k, v = _qkv(S=128, kvH=2, seed=11)
    sl = jnp.asarray(alibi_slopes(q.shape[2]))
    g = jax.grad(lambda s: jnp.sum(jnp.square(flash_attention_kernel(
        q, k, v, causal=True, alibi_slopes=s, interpret=True))))(sl)
    np.testing.assert_array_equal(np.asarray(g), np.zeros_like(g))


def test_unknown_dstpu_attn_rejected(eight_devices, monkeypatch):
    """A typo'd escape hatch must fail loudly, in both dispatch sites."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    q, k, v = _qkv(S=128, kvH=2, seed=12)
    monkeypatch.setenv("DSTPU_ATTN", "XLA")
    with pytest.raises(ValueError, match="DSTPU_ATTN"):
        attn_mod.flash_attention(q, k, v, causal=True)


def test_dispatch_env_gates(eight_devices, monkeypatch):
    """DSTPU_ATTN routes: 'pallas' forces the in-repo kernel on the CPU
    mesh; 'xla' keeps the XLA path; both agree numerically."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    q, k, v = _qkv(S=128, kvH=2, seed=10)
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    got = attn_mod.flash_attention(q, k, v, causal=True)
    monkeypatch.setenv("DSTPU_ATTN", "xla")
    ref = attn_mod.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **FP32_TOL)


# ---------------------------------------------------------------------------
# the tile rule and the route rule: pure Python, no kernel runs
# ---------------------------------------------------------------------------

TILE_SHAPES = [
    # Sq, Sk, head_dim, itemsize
    (1024, 1024, 64, 2),     # the benchmark cell
    (1024, 1024, 128, 2),
    (4096, 4096, 64, 2),
    (4096, 4096, 128, 4),
    (2048, 8192, 128, 2),    # a query chunk against a longer key
    (512, 512, 64, 2),       # bert
    (256, 256, 64, 2),
    (384, 384, 64, 2),       # whole: no smaller 128-multiple but 128
    (640, 640, 64, 2),
    (1536, 1536, 96, 2),
]


@pytest.mark.parametrize("sq,causal,fwd,bwd", [
    (1024, True, (512, 512), (1024, 1024)),     # what the chip sweep chose
    (1024, False, (1024, 1024), (1024, 1024)),
    (4096, True, (512, 512), (1024, 1024)),
    (512, True, (512, 512), (512, 512)),
    (640, True, (640, 640), (640, 640)),        # never the step-bound 128
    (1536, True, (512, 512), (768, 768)),
])
def test_tile_rule_is_the_measured_one(sq, causal, fwd, bwd):
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    t = pf.choose_tiles(sq, sq, 64, causal=causal)
    assert (t.fwd, t.bwd) == (fwd, bwd)


@pytest.mark.parametrize("tile,window,nq,nk,steps", [
    # (k-steps a q-block, q-steps a k-block) of the cut grids
    ((512, 512), 2048, 32, 32, (5, 5)),      # the cell's forward: 5 of 32 k-blocks
    ((1024, 1024), 2048, 16, 16, (3, 3)),    # its backward: 3 of 16
    ((512, 512), 2049, 32, 32, (5, 5)),
    ((512, 512), 2050, 32, 32, (6, 6)),      # one key past a block's edge
    ((256, 512), 300, 4, 2, None),
    ((512, 256), 300, 2, 4, None),
    ((128, 128), 1, 8, 8, (1, 1)),           # a window of the token itself
    ((128, 128), 10 ** 6, 8, 8, (8, 8)),     # one that never binds: all of them
])
def test_a_static_window_cuts_the_grids_to_its_reach(tile, window, nq, nk, steps):
    """The blocks the cut grids visit are the blocks with a visible pair,
    found here from the mask itself, position by position."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    bq, bk = tile
    i, j = np.indices((nq * bq, nk * bk))
    seen = ((j <= i) & (i - j < window)).reshape(nq, bq, nk, bk).any(axis=(1, 3))
    for qb in range(nq):
        reached = np.flatnonzero(seen[qb])
        assert (reached[0], reached[-1]) == (pf._first_k_block(tile, qb, window),
                                             pf._last_k_block(tile, qb))
        assert len(reached) == reached[-1] - reached[0] + 1
    for kb in range(nk):
        reached = np.flatnonzero(seen[:, kb])
        assert (reached[0], reached[-1]) == (pf._first_q_block(tile, kb),
                                             pf._last_q_block(tile, kb, window, nq))
    got = pf.window_steps(tile, window, nq, nk)
    assert got == (seen.sum(1).max(), seen.sum(0).max())
    assert steps is None or got == steps


def test_a_static_window_is_in_the_config_the_name_and_the_tiles(eight_devices):
    """A Python int on the training call is static (cut grids, kernels named
    ``*_window``); a traced one, one with a ``q_offset``, or one that cannot
    bind is not."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    q, k, v = _qkv(S=1024)
    prep = lambda w, **kw: pf._prepare(q, k, v, True, None, None, None, None, w,
                                       kw.get("q_offset"), None, None, True)[0]
    assert prep(300).window == 300 and prep(300).use_window
    assert prep(jnp.asarray(300)).window is None and prep(jnp.asarray(300)).use_window
    assert prep(300, q_offset=0).window is None and prep(300, q_offset=0).use_window
    for never in (0, -1, 1024, 5000, None):
        assert prep(never).window is None and not prep(never).use_window
    text = str(jax.make_jaxpr(lambda q, k, v: jax.grad(
        lambda q: jnp.sum(flash_attention_kernel(q, k, v, window=300, interpret=True)))(q))(
            q, k, v))
    assert "flash_fwd_window" in text and "flash_bwd_window" in text
    # the measured window (2048) keeps the measured tiles; a narrower one caps them
    at = lambda w: pf.choose_tiles(16384, 16384, 128, window=w)
    assert (at(2048).fwd, at(2048).bwd) == (at(None).fwd, at(None).bwd) == ((512, 512), (1024, 1024))
    assert (at(700).fwd, at(700).bwd) == ((512, 512), (512, 512))
    assert (at(64).fwd, at(64).bwd) == ((512, 512), (512, 512))


@pytest.mark.parametrize("sq,sk,d,itemsize", TILE_SHAPES)
def test_chosen_tiles_are_legal(sq, sk, d, itemsize):
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    tiles = pf.choose_tiles(sq, sk, d, itemsize)
    for (bq, bk), backward in ((tiles.fwd, False), (tiles.bwd, True)):
        assert sq % bq == 0 and sk % bk == 0
        assert bq % 128 == 0 and bk % 128 == 0      # the 128-lane layout
        assert pf.tile_vmem_bytes((bq, bk), d, itemsize,
                                  backward=backward) <= pf.VMEM_BUDGET
    # inside the budget the compiler's own limit stands
    assert tiles.vmem_limit_bytes is None
    assert pf.supports((1, sq, 8, d), (1, sk, 8, d))


def test_explicit_tiles_win_and_oversize_ones_raise_the_limit():
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    t = pf.choose_tiles(1024, 1024, 64, block_q=128, block_k=256)
    assert t.fwd == t.bwd == (128, 256) and t.vmem_limit_bytes is None
    # one argument alone: the other comes from the rule
    t = pf.choose_tiles(1024, 1024, 64, block_q=256)
    assert t.fwd == (256, pf.FWD_CAUSAL_TILE_TARGET[1])
    assert t.bwd == (256, pf.TILE_TARGET[1])
    # clamped to the lengths, as the 128-default was
    assert pf.choose_tiles(128, 256, 64, block_q=512,
                           block_k=512).fwd == (128, 256)
    big = pf.choose_tiles(4096, 4096, 128, block_q=2048, block_k=2048)
    assert big.fwd == (2048, 2048)
    assert pf.VMEM_BUDGET < big.vmem_limit_bytes <= pf.VMEM_CAP


@pytest.mark.parametrize("sq,sk,kw,legal", [
    (192, 192, {}, False),                   # no 128-multiple divides
    (1000, 1000, {}, False),
    (64, 64, {}, False),                     # compiled: off the lane layout
    (64, 64, {"compiled": False}, True),     # interpret: whole
    (1024, 1024, {"block_k": 384}, False),   # explicit tile does not divide
    (64, 128, {}, False),                    # a short q against 128 keys
    (64, 128, {"compiled": False}, True),
])
def test_lengths_without_a_legal_tile(sq, sk, kw, legal):
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    assert (pf.choose_tiles(sq, sk, 64, **kw) is not None) == legal
    compiled = kw.get("compiled", True)
    assert pf.supports((1, sq, 4, 64), (1, sk, 4, 64),
                       block_k=kw.get("block_k"),
                       compiled=compiled) == legal


ROUTE_SHAPES = [
    # q shape, k shape
    ((4, 1024, 20, 64), (4, 1024, 20, 64)),     # the benchmark cell
    ((1, 4096, 32, 64), (1, 4096, 4, 64)),      # GQA long
    ((8, 512, 16, 64), (8, 512, 16, 64)),       # bert
    ((16, 256, 20, 64), (16, 256, 20, 64)),     # XLA won it 2.3x
    ((16, 256, 16, 128), (16, 256, 16, 128)),   # the kernel won it by 3 %
    ((10, 384, 32, 64), (10, 384, 4, 64)),
    ((2, 128, 8, 64), (2, 128, 2, 64)),
    ((1, 1000, 8, 64), (1, 1000, 8, 64)),       # no legal tile
    ((1, 2048, 8, 192), (1, 2048, 8, 192)),     # head dim off the lanes
]


@pytest.mark.parametrize("q_shape,k_shape", ROUTE_SHAPES)
def test_route_rule_is_shape_and_platform_only(q_shape, k_shape, monkeypatch):
    """Off the TPU nothing takes the kernel unasked; on it, exactly the
    supported shapes at or over the measured crossover do; no environment
    variable enters the rule."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    for backend in ("cpu", "gpu", "METAL"):
        assert not attn_mod.kernel_is_default(q_shape, k_shape, backend)
    min_seq = (attn_mod.FLASH_MIN_SEQ_WIDE_HEAD if q_shape[3] >= 128
               else attn_mod.FLASH_MIN_SEQ)
    want = pf.supports(q_shape, k_shape) and q_shape[1] >= min_seq
    assert attn_mod.kernel_is_default(q_shape, k_shape, "tpu") == want
    monkeypatch.setenv("DSTPU_ATTN", "xla")
    assert attn_mod.kernel_is_default(q_shape, k_shape, "tpu") == want


@pytest.mark.parametrize("q_shape,kv_heads,backend,mode,route", [
    ((4, 1024, 20, 64), 20, "tpu", "", "kernel"),     # the GPT-2 cell
    ((1, 4096, 16, 128), 16, "tpu", "", "kernel"),    # the OLMoE cell
    ((1, 2048, 32, 64), 4, "tpu", "", "kernel"),      # GQA 32q/4kv
    ((4, 256, 20, 64), 20, "tpu", "", "xla"),
    ((4, 256, 16, 128), 16, "tpu", "", "kernel"),
    ((4, 384, 20, 64), 20, "tpu", "", "kernel"),
    ((1, 2048, 8, 192), 8, "tpu", "", "xla"),         # head dim off the lanes
    ((1, 4096, 8, 192), 8, "tpu", "", "xla_chunked"),
    ((1, 4000, 16, 64), 16, "tpu", "", "xla"),        # no 128-multiple tile
    ((1, 4104, 16, 64), 16, "tpu", "", "xla_chunked"),
    ((1, 8192, 16, 128), 16, "tpu", "", "kernel"),
    # the trinity-mini cell: 32q/4kv x 128 at 16,384 with segment ids, under
    # a window of 2048 and under none (neither is an argument of the route)
    ((1, 16384, 32, 128), 4, "tpu", "", "kernel"),
    ((1, 16384, 32, 128), 4, "tpu", "xla", "xla_chunked"),
    ((1, 16384, 32, 128), 4, "cpu", "", "xla"),
    ((1, 16384, 32, 128), 4, "cpu", "pallas", "kernel"),
    ((2, 128, 8, 64), 2, "tpu", "", "xla"),           # under the crossover
    ((1, 8192, 16, 128), 16, "tpu", "xla", "xla_chunked"),
    ((4, 1024, 20, 64), 20, "tpu", "xla", "xla"),
    ((4, 1024, 20, 64), 20, "cpu", "", "xla"),
    ((1, 4096, 16, 128), 16, "cpu", "", "xla"),       # never chunked on the CPU
    ((1, 4096, 16, 128), 16, "cpu", "xla", "xla"),
    ((2, 128, 8, 64), 2, "cpu", "pallas", "kernel"),  # interpret
    ((2, 100, 8, 64), 2, "cpu", "pallas", "kernel"),  # one interpret tile
    ((1, 8192, 16, 128), 16, "cpu", "pallas", "kernel"),
    ((2, 128, 6, 64), 4, "cpu", "pallas", "xla"),     # heads do not divide
    ((2, 128, 8, 64), 2, "tpu", "pallas", "kernel"),  # forced under the crossover
    ((2, 100, 8, 64), 2, "tpu", "pallas", "xla"),     # no compiled tile
    ((1, 4096, 8, 192), 8, "tpu", "pallas", "xla_chunked"),
    # the sdar-30b-a3b cell: the block-diffusion mask (a block length in the
    # key heads' place: (kv heads, b)), 32q/4kv x 128, 2 x 8192 query rows (a
    # clean and a noised copy) over the clean copy's 8192 keys, segment ids
    ((1, 16384, 32, 128), (4, 4), "tpu", "", "kernel"),
    ((1, 16384, 32, 128), (4, 4), "tpu", "xla", "xla_chunked"),
    ((1, 16384, 32, 128), (4, 4), "cpu", "", "xla"),
    ((1, 16384, 32, 128), (4, 4), "cpu", "pallas", "kernel"),
    ((8, 128, 8, 16), (2, 4), "cpu", "pallas", "kernel"),   # the tiny preset, interpret
    ((8, 128, 8, 16), (2, 4), "tpu", "pallas", "xla"),      # head dim and tiles off the lanes
    ((1, 16384, 32, 128), (4, 6), "tpu", "", "xla_chunked"),  # b no power of two
    ((1, 16384, 32, 128), (4, 256), "tpu", "", "kernel"),   # a block of 256 divides the tiles
    ((1, 16384, 32, 128), (4, 2048), "tpu", "", "xla_chunked"),  # wider than a tile: never cut
    ((1, 256, 32, 128), (4, 4), "tpu", "", "kernel"),       # 2 x 128 rows: at the crossover
    ((1, 128, 32, 128), (4, 4), "tpu", "", "xla"),          # 2 x 64: under it, and no tile
    # the keye-vl2-30b-a3b cell: a learned selection's operand (("dsa", kv
    # heads, topk) in the key heads' place), 32q/4kv x 128 over 16,384 rows: a
    # full layer's tiles and route, whatever topk is
    ((1, 16384, 32, 128), ("dsa", 4, 2048), "tpu", "", "kernel"),
    ((1, 16384, 32, 128), ("dsa", 4, 2048), "tpu", "xla", "xla_chunked"),
    ((1, 16384, 32, 128), ("dsa", 4, 2048), "cpu", "", "xla"),
    ((1, 16384, 32, 128), ("dsa", 4, 2048), "cpu", "pallas", "kernel"),
    ((1, 16384, 32, 128), ("dsa", 4, 64), "tpu", "", "kernel"),
    ((4, 64, 4, 16), ("dsa", 2, 8), "cpu", "pallas", "kernel"),       # the tiny preset, interpret
    ((4, 64, 4, 16), ("dsa", 2, 8), "tpu", "pallas", "xla"),          # tiles off the lanes
    ((1, 128, 32, 128), ("dsa", 4, 2048), "tpu", "", "xla"),          # under the crossover
    ((1, 1024, 32, 128), ("dsa", 4, 2048), "tpu", "", "kernel"),      # one group of the operand's bits
    ((1, 512, 32, 128), ("dsa", 4, 2048), "tpu", "", "xla"),          # a plane under the chip's 128 lanes
    ((1, 512, 32, 128), ("dsa", 4, 2048), "cpu", "pallas", "kernel"),  # interpret mode takes it
    # the evabyte-6.5b cell: EVA's mask (("eva", window, chunk) in the key
    # heads' place: as many key heads as query heads), 32 x 128 at 32,768, and
    # a group of 4 of its heads, which is what one launch of the cell holds
    ((1, 32768, 32, 128), ("eva", 2048, 16), "tpu", "", "kernel"),
    ((1, 32768, 4, 128), ("eva", 2048, 16), "tpu", "", "kernel"),
    ((1, 32768, 32, 128), ("eva", 2048, 16), "tpu", "xla", "xla_chunked"),
    ((1, 32768, 32, 128), ("eva", 2048, 16), "cpu", "", "xla"),
    ((1, 32768, 32, 128), ("eva", 2048, 16), "cpu", "pallas", "kernel"),
    ((2, 128, 4, 16), ("eva", 32, 4), "cpu", "pallas", "kernel"),     # the tiny preset, interpret
    ((2, 128, 4, 16), ("eva", 32, 4), "tpu", "pallas", "xla"),        # tiles off the lanes
    ((1, 1024, 32, 128), ("eva", 2048, 16), "tpu", "", "kernel"),     # inside one window: causal
    ((1, 128, 32, 128), ("eva", 2048, 16), "tpu", "", "xla"),         # under the crossover
    ((1, 32768 + 2048 + 16, 32, 128), ("eva", 2048, 16), "tpu", "", "xla_chunked"),  # no whole windows
    ((1, 8192, 32, 128), ("eva", 256, 16), "tpu", "", "kernel"),      # the crossover is a window's
    ((1, 8192, 32, 64), ("eva", 256, 16), "tpu", "", "xla_chunked"),  # narrow heads: under it
    ((1, 8192, 32, 128), ("eva", 384, 16), "tpu", "", "xla_chunked"),  # 8192 is no multiple of 384
])
def test_route_table(q_shape, kv_heads, backend, mode, route, monkeypatch):
    """`choose_route` is the whole decision of `flash_attention`, of
    `blockdiff_attention` and of `eva_attention`, a pure function: the TPU's
    rows are checked here on the CPU, and an environment that asks for another
    route moves none of them."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    blockdiff = eva = selected = None
    if isinstance(kv_heads, tuple) and kv_heads[0] == "eva":
        kv_heads, eva = q_shape[2], kv_heads[1:]
    elif isinstance(kv_heads, tuple) and kv_heads[0] == "dsa":
        _, kv_heads, selected = kv_heads
    elif isinstance(kv_heads, tuple):     # the mask's rows: keys are half the queries
        kv_heads, blockdiff = kv_heads
    k_shape = (q_shape[0], q_shape[1] // (2 if blockdiff else 1), kv_heads, q_shape[3])
    monkeypatch.setenv("DSTPU_ATTN", "xla" if route == "kernel" else "pallas")
    assert attn_mod.choose_route(q_shape, k_shape, backend, mode, blockdiff, eva,
                                 selected) == route


def test_attention_reads_one_environment_variable():
    import inspect
    import re

    from deepspeed_tpu.ops.transformer import attention as attn_mod
    names = set(re.findall(r"DSTPU_[A-Z0-9_]+", inspect.getsource(attn_mod)))
    assert names == {"DSTPU_ATTN"}


def test_route_rule_takes_the_benchmark_cell_and_leaves_the_cpu(
        eight_devices, monkeypatch):
    """The cell's call (gpt2-large, micro 4 x 1024) is a kernel shape on the
    TPU; on this CPU mesh the very same call still traces the XLA path."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    monkeypatch.delenv("DSTPU_ATTN", raising=False)
    shape = (4, 1024, 20, 64)
    assert attn_mod.kernel_is_default(shape, shape, "tpu")
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = jax.jit(attn_mod.flash_attention).lower(q, q, q).as_text()
    assert "pallas" not in text and "custom_call" not in text


def test_log_names_the_path_and_the_tiles(eight_devices, monkeypatch):
    """`_log_path_once` says which route a call took and, for the kernel,
    the tiles of both kernels: a silent fallback or a silent 128-tile is
    how this path lost its speed before."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    said = []
    monkeypatch.setattr(attn_mod, "_log_path_once", said.append)
    q, k, v = _qkv(B=1, S=1024, H=2, kvH=2, seed=13)
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    attn_mod.flash_attention(q, k, v, causal=True)
    monkeypatch.delenv("DSTPU_ATTN")
    attn_mod.flash_attention(q, k, v, causal=True)       # CPU: XLA
    assert said == ["pallas_flash_inrepo, tiles (block_q x block_k) "
                    "forward 512x512 backward 1024x1024, operands by heads", "xla"]


# ---------------------------------------------------------------------------
# the block-diffusion mask (a clean and a noised copy of every row)
# ---------------------------------------------------------------------------

def _blockdiff_case(L=128, b=4, H=4, kvH=2, D=16, seed=0):
    """2 L query rows (clean, then noised), their keys and values, and
    documents whose ends cut blocks: one ends ON a block's last position (the
    next document's first block has no clean key behind it), one inside a
    block, one of a single token."""
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 2 * L, h, D)), jnp.float32) * 0.5
               for h in (H, kvH, kvH))
    ends = np.zeros((2, L), np.int32)
    ends[0, [7, 21, 22, 70]] = 1
    ends[1, [0, L - 2]] = 1
    return q, k, v, jnp.asarray(np.cumsum(ends, 1) - ends, jnp.int32)


def _dense_blockdiff(q, k, v, doc, b):
    from deepspeed_tpu.ops.transformer.attention import _xla_blockdiff_attention
    return _xla_blockdiff_attention(q, k, v, doc, b, None, None)


@pytest.mark.parametrize("b,tiles", [(4, None), (4, (32, 32)), (4, (16, 64)),
                                     (4, (64, 16)), (16, (32, 32)), (32, (32, 64))])
def test_blockdiff_kernel_route_matches_the_dense_mask(b, tiles, monkeypatch):
    """`blockdiff_attention` on the kernel route (one flash launch over the
    clean keys for both copies' queries, the own-block einsum, the merge)
    against the whole mask built densely, forward and backward, over tiles
    that make skipped, wholly visible and edge blocks in both halves; a
    document's first block (no clean key: the kernel's row is empty and the
    merge takes the own block alone) and a block cut by a document's end."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    q, k, v, doc = _blockdiff_case()
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    if tiles is not None:
        real = pf.flash_attention_with_lse
        monkeypatch.setattr(pf, "flash_attention_with_lse", functools.partial(
            real, block_q=tiles[0], block_k=tiles[1]))
    w = jnp.asarray(np.random.default_rng(1).normal(size=q.shape), jnp.float32)
    run = lambda fn: jax.value_and_grad(
        lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2))(q, k, v)
    kernel = lambda q, k, v: attn_mod.blockdiff_attention(q, k, v, b, doc)
    np.testing.assert_allclose(kernel(q, k, v), _dense_blockdiff(q, k, v, doc, b), **FP32_TOL)
    (got, got_g), (want, want_g) = run(kernel), run(
        lambda q, k, v: _dense_blockdiff(q, k, v, doc, b))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, c in zip(got_g, want_g):
        np.testing.assert_allclose(a, c, **GRAD_TOL)


def test_blockdiff_launch_multiplies_no_hidden_quadrant():
    """The launch under the mask is ONE flash pair over the clean keys: its
    LSE says which rows saw a key (a document's first block's noised rows:
    none), its launches carry the mask's name, and its grids are the causal
    ones over the keys for twice the q-blocks."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    L, b = 128, 4
    q, k, v, doc = _blockdiff_case(L, b)
    out, lse = flash_attention_with_lse(
        q, k[:, :L], v[:, :L], causal=True, segment_ids=doc,
        q_segment_ids=jnp.concatenate([doc, doc], 1), blockdiff=b, block_q=32, block_k=32)
    lse = np.asarray(lse)
    # row 0's documents start at 0, 8, 22, 23, 71: the noised rows of their
    # first blocks have no clean key, every clean row has itself
    assert (lse[0, :, :L] > MASK_VALUE / 2).all()
    empty = {L + p for start in (0, 8, 22, 23, 71) for p in range(start, (start | 3) + 1)}
    assert {int(r) for r in np.flatnonzero(lse[0, 0] < MASK_VALUE / 2)} == empty
    assert not np.asarray(out)[0, sorted(empty)].any()
    text = str(jax.make_jaxpr(lambda q, k, v: jax.grad(lambda q: jnp.sum(
        flash_attention_with_lse(q, k, v, causal=True, blockdiff=b)[0]))(q))(
            q, k[:, :L], v[:, :L]))
    assert "flash_fwd_blockdiff" in text and "flash_bwd_blockdiff" in text
    tiles = pf.launch_tiles(16384, 8192, 128, blockdiff=4)
    assert tiles == pf.choose_tiles(8192, 8192, 128, causal=True)
    assert pf.launch_tiles(16384, 8192, 128, blockdiff=6) is None
    assert pf.launch_tiles(16380, 8190, 128, blockdiff=4) is None
    with pytest.raises(ValueError, match="block-diffusion"):
        flash_attention_with_lse(q, k[:, :L], v[:, :L], blockdiff=b, window=16)
    with pytest.raises(ValueError, match="block-diffusion"):
        flash_attention_with_lse(q, k, v, blockdiff=b)


# ---------------------------------------------------------------------------
# the table of documents: a tile whose keys all lie in other documents
# ---------------------------------------------------------------------------

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


def _documents_pair(mask, order, S=256):
    """-> q, k, v, reference(q, k, v), kernel(q, k, v), count: the two
    routes of one masked call at tiles of 32 x 32 over `_packed_ids`, and the
    arguments `tiles_run` takes for it."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    t = DOC_TILE
    if mask.startswith("blockdiff"):
        b, L = int(mask[len("blockdiff"):]), S // 2
        q, k, v = _qkv(S=S, H=4, kvH=2, D=16, seed=3)
        doc = _packed_ids(L, order)
        both = jnp.concatenate([doc, doc], axis=1)

        def kernel(q, k, v):
            # `blockdiff_attention`'s kernel route with the tiles named
            o, lse = flash_attention_with_lse(
                q, k[:, :L], v[:, :L], causal=True, segment_ids=doc,
                q_segment_ids=both, blockdiff=b, block_q=t, block_k=t, interpret=True)
            own, own_lse = attn_mod._own_block_attention(
                q[:, L:], k[:, L:], v[:, L:], doc, b, None)
            noised, _ = merge_partials(o[:, L:], lse[:, :, L:], own, own_lse)
            return jnp.concatenate([o[:, :L], noised], axis=1)
        reference = lambda q, k, v: attn_mod._xla_blockdiff_attention(
            q, k, v, doc, b, None, None)
        return q, k, v, reference, kernel, dict(q_ids=both, k_ids=doc, blockdiff=b)
    kw = {"causal": dict(causal=True),
          "noncausal": dict(causal=False),
          "window_static": dict(causal=True, window=80),
          "window_traced": dict(causal=True, window=jnp.asarray(80, jnp.int32)),
          # a ring hop: the local queries are the row's second half, the
          # keys its first (the owner one rank behind)
          "ring_hop": dict(causal=True, q_offset=S // 2)}[mask]
    q, k, v = _qkv(S=S, H=4, kvH=2, D=16, seed=3)
    seg = _packed_ids(S, order)
    qseg = None
    if mask == "ring_hop":
        q, k, v, qseg, seg = q[:, S // 2:], k[:, :S // 2], v[:, :S // 2], seg[:, S // 2:], seg[:, :S // 2]
    reference = lambda q, k, v: _xla_attention(
        q, k, v, kw["causal"], None, seg, window=kw.get("window"),
        q_offset=kw.get("q_offset"), q_segment_ids=qseg)
    kernel = lambda q, k, v: flash_attention_kernel(
        q, k, v, segment_ids=seg, q_segment_ids=qseg, block_q=t, block_k=t,
        interpret=True, **kw)
    return q, k, v, reference, kernel, dict(
        q_ids=seg if qseg is None else qseg, k_ids=seg, **kw)


DOC_MASKS = ["causal", "noncausal", "window_static", "window_traced",
             "blockdiff4", "blockdiff32", "ring_hop"]


@pytest.mark.parametrize("order", ["packed", "random"])
@pytest.mark.parametrize("mask", DOC_MASKS)
def test_tiles_of_other_documents_are_skipped_and_nothing_moves(eight_devices, mask, order):
    """Forward and gradients against the dense mask where whole tiles lie in
    other documents, under every grid (whole-sequence causal, a static and a
    traced window, non-causal, block diffusion at b 4 and b 32, a ring hop
    with ``q_offset`` and ``q_segment_ids``): tight for packed documents
    (tiles ARE skipped), sound for ids in any order."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    q, k, v, reference, kernel, count = _documents_pair(mask, order)
    by_position, run = pf.tiles_run(tile=(DOC_TILE, DOC_TILE), **count)
    assert 0 < int(run) < int(by_position)
    w = jnp.asarray(np.random.default_rng(1).normal(size=q.shape), jnp.float32)
    if mask == "ring_hop":
        # a query whose document lies in another hop has no key here: the
        # kernel leaves 0 (and the sentinel LSE), the dense softmax a mean
        seen, same = _dense_visible(**count)
        keyed = jnp.asarray((seen[None] & same).any(axis=2))[:, :, None, None]
        assert not np.asarray(jnp.where(keyed, 0.0, kernel(q, k, v))).any()
        w = jnp.where(keyed, w, 0.0)
    np.testing.assert_allclose(kernel(q, k, v) * w, reference(q, k, v) * w, **FP32_TOL)
    grads = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(grads(kernel), grads(reference)):
        np.testing.assert_allclose(got, want, **GRAD_TOL)


@pytest.mark.parametrize("causal", [False, True])
def test_a_block_of_a_document_no_query_is_in_is_never_fetched_into_the_product(
        eight_devices, causal):
    """A k-block whose document no query belongs to holds NaN keys and values:
    a tile that was multiplied and masked (0 x NaN) would poison the output,
    dq, dk and dv; skipped, everything is finite, equal to the reference over
    zeros there, and the block's own dk and dv are 0."""
    t, S = DOC_TILE, 128
    q, k, v = _qkv(B=1, S=S, H=2, kvH=1, D=16, seed=4)
    kseg = jnp.asarray(np.repeat([0, 7, 1, 1], t)[None], jnp.int32)
    qseg = jnp.asarray(np.repeat([0, 0, 1, 1], t)[None], jnp.int32)
    foreign = (kseg == 7)[0][None, :, None, None]
    poison = lambda a: jnp.where(foreign, jnp.nan, a)
    kernel = lambda q, k, v: flash_attention_kernel(
        q, poison(k), poison(v), causal=causal, segment_ids=kseg,
        q_segment_ids=qseg, block_q=t, block_k=t, interpret=True)
    reference = lambda q, k, v: _xla_attention(
        q, jnp.where(foreign, 0.0, k), jnp.where(foreign, 0.0, v), causal, None,
        kseg, q_segment_ids=qseg)
    np.testing.assert_allclose(kernel(q, k, v), reference(q, k, v), **FP32_TOL)
    grads = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.square(fn(*a))),
                                argnums=(0, 1, 2))(q, k, v)
    got, want = grads(kernel), grads(reference)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, **GRAD_TOL)
    assert not np.asarray(got[1])[0, t:2 * t].any() and not np.asarray(got[2])[0, t:2 * t].any()


def test_a_noised_row_with_no_clean_key_leaves_zero_and_the_sentinel(eight_devices):
    """Under the table too: the noised rows of a document's first block see
    no clean key (the tiles before them are other documents', skipped now),
    and come back 0 with the sentinel LSE for `merge_partials`."""
    L, b, t = 128, 4, DOC_TILE
    q, k, v = _qkv(S=2 * L, H=4, kvH=2, D=16, seed=3)
    doc = _packed_ids(L, "packed")
    out, lse = flash_attention_with_lse(
        q, k[:, :L], v[:, :L], causal=True, segment_ids=doc,
        q_segment_ids=jnp.concatenate([doc, doc], 1), blockdiff=b,
        block_q=t, block_k=t, interpret=True)
    lse = np.asarray(lse)
    starts = [0] + [int(p) + 1 for p in np.flatnonzero(np.diff(np.asarray(doc[0])))]
    assert starts == [0, 5, 53, 96]       # the last one on a tile's edge
    empty = sorted({L + p for s in starts for p in range(s, (s | (b - 1)) + 1)})
    assert [int(r) for r in np.flatnonzero(lse[0, 0] < MASK_VALUE / 2)] == empty
    assert (lse[0][:, empty] == MASK_VALUE).all()
    assert not np.asarray(out)[0, empty].any()
    assert (lse[0, :, :L] > MASK_VALUE / 2).all() and (lse[1, :, L + b:] > MASK_VALUE / 2).all()


def _pallas_operands(fn, *args):
    """Operands of every ``pallas_call`` in ``fn``'s jaxpr, by kernel name."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params.get("name") or eqn.params["name_and_src_info"].name
                found[name] = len(eqn.invars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_a_launch_without_ids_builds_no_table(eight_devices):
    """No segment ids: info, slopes and q, k, v (and do, lse, di; past
    ``DQ_SUMMED_PARTIALS`` k-blocks the zeros a dq that is added to in place
    starts from) are all a launch is handed, as before the table; with ids
    the two id operands and ONE more scalar-prefetch operand."""
    q, k, v = _qkv(B=1, S=128, H=2, kvH=1, D=16)
    seg = _packed_ids(128, "packed")[:1]
    loss = lambda ids: lambda q, k, v: jnp.sum(flash_attention_kernel(
        q, k, v, causal=True, segment_ids=ids, block_q=32, block_k=32, interpret=True))
    grad = lambda ids: jax.grad(loss(ids), argnums=(0, 1, 2))
    assert _pallas_operands(grad(None), q, k, v) == {"flash_fwd": 5, "flash_bwd": 8}
    assert _pallas_operands(grad(seg), q, k, v) == {"flash_fwd": 8, "flash_bwd": 11}
    in_place = jax.grad(lambda q, k, v: jnp.sum(flash_attention_kernel(
        q, k, v, causal=True, block_q=16, block_k=16, interpret=True)), argnums=(0, 1, 2))
    assert _pallas_operands(in_place, q, k, v) == {"flash_fwd": 5, "flash_bwd": 9}


def _dense_visible(q_ids, k_ids, causal=True, window=None, blockdiff=None, q_offset=None):
    """(visible by position [Sq, Sk], same document [B, Sq, Sk]) bools."""
    from deepspeed_tpu.ops.transformer.attention import (blockdiff_visible,
                                                         sliding_window_allowed)
    Sq, Sk = q_ids.shape[1], k_ids.shape[1]
    if blockdiff is not None:
        at = np.arange(Sq)
        seen = blockdiff_visible((at >= Sk)[:, None], (at % Sk)[:, None],
                                 np.zeros((1, Sk), bool), np.arange(Sk)[None], blockdiff)
    else:
        q_pos = np.arange(Sq)[:, None] + (Sk - Sq if q_offset is None else q_offset)
        k_pos = np.arange(Sk)[None, :]
        seen = np.ones((Sq, Sk), bool)
        if causal:
            seen = q_pos >= k_pos
            if window is not None:
                seen = seen & np.asarray(sliding_window_allowed(q_pos, k_pos, window))
    return np.asarray(seen), np.asarray(q_ids)[:, :, None] == np.asarray(k_ids)[:, None, :]


@pytest.mark.parametrize("tile", [(32, 32), (64, 32), (32, 64)])
@pytest.mark.parametrize("mask", DOC_MASKS)
def test_the_tile_count_is_the_dense_masks(mask, tile):
    """`tiles_run` against a brute-force count over the dense mask: the
    position test runs exactly the tiles with a pair visible by position,
    and for ids rising along the row a tile is run exactly when the dense
    mask (position AND same document) has a True in it; for ids in any
    order no tile with a True is left out."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    bq, bk = tile
    any_in_tiles = lambda m: m.reshape(m.shape[0], m.shape[1] // bq, bq,
                                       m.shape[2] // bk, bk).any(axis=(2, 4))
    for order in ("packed", "random"):
        count = _documents_pair(mask, order)[-1]
        by_position, run = (int(n) for n in pf.tiles_run(tile=tile, **count))
        seen, same = _dense_visible(**count)
        want_position = any_in_tiles(np.broadcast_to(seen, same.shape))
        want_run = any_in_tiles(seen[None] & same)
        assert by_position == want_position.sum()
        if order == "packed" and mask != "blockdiff32":
            assert run == want_run.sum() < by_position
        else:
            # (under block diffusion a noised tile over nothing but a
            # document's FIRST block has no pair though its ranges meet: at
            # b 32, a tile's width, one or two of them are run for nothing)
            assert want_run.sum() <= run <= by_position
            assert run - want_run.sum() <= (2 if order == "packed" else run)


# ---------------------------------------------------------------------------
# EVA's two launches (attention.eva_attention; the evabyte-6.5b cell)
# ---------------------------------------------------------------------------

def _eva_case(L=128, H=2, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (2, L, H, D), jnp.float32) for k in ks)


@pytest.mark.parametrize("window,per,tiles", [
    (32, 8, (32, 32)),       # a q-block a window, a k-block four windows' summaries
    (32, 8, (16, 8)),        # two q-blocks a window, a k-block one window's: no edge tile
    (64, 4, (32, 4)),        # narrow summaries: a k-block a window's, skipped and whole tiles
    (32, 8, None),           # the tiles the shape gives (a q tile of one window)
])
def test_the_summaries_launch_matches_the_dense_mask(window, per, tiles):
    """A launch of L queries over ``L / window x per`` summary keys under a
    q-block's limit (``summaries=``) against the mask built densely (a row
    of window w sees keys ``0 .. w x per - 1``), outputs, LSE and gradients;
    the first window's rows see nothing: 0 with the sentinel LSE and no
    gradient."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    L = 128
    q, _, _ = _eva_case(L)
    S = L // window * per
    k, v = (a[:, :S] for a in _eva_case(L, seed=1)[:2])
    kw = {} if tiles is None else dict(block_q=tiles[0], block_k=tiles[1])
    seen = (jnp.arange(S)[None, :] < (jnp.arange(L) // window * per)[:, None])

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        s = jnp.where(seen[None, None], s, -jnp.inf)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.where(seen[None, None], jnp.exp(s - jnp.where(
            jnp.isfinite(lse), lse, 0.0)[..., None]), 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse

    kernel = lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal=True, summaries=(window, per), tag="eva_far", **kw)
    (out, lse), (want, want_lse) = kernel(q, k, v), dense(q, k, v)
    np.testing.assert_allclose(out, want, **FP32_TOL)
    first = np.arange(L) < window
    assert not np.asarray(out)[:, first].any()
    assert (np.asarray(lse)[:, :, first] < MASK_VALUE / 2).all()
    np.testing.assert_allclose(np.asarray(lse)[:, :, ~first],
                               np.asarray(want_lse)[:, :, ~first], **FP32_TOL)
    w = jnp.asarray(np.random.default_rng(1).normal(size=q.shape), jnp.float32)
    u = jnp.asarray(np.random.default_rng(2).normal(size=lse.shape), jnp.float32)

    def scalar(fn):
        def f(q, k, v):
            o, l = fn(q, k, v)
            return jnp.sum(o * w) + jnp.sum(jnp.where(first[None, None], 0.0, l) * u)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(scalar(kernel), scalar(dense)):
        np.testing.assert_allclose(a, c, **GRAD_TOL)
    text = str(jax.make_jaxpr(lambda q: jax.grad(lambda q: jnp.sum(kernel(q, k, v)[0]))(q))(q))
    assert "flash_fwd_eva_far" in text and "flash_bwd_eva_far" in text


def test_summary_tiles_keep_a_q_block_inside_a_window():
    """The cell's shape takes the causal tiles (both divide a window of
    2048); a q tile wider than the window is replaced by one window; keys no
    compiled tile divides have none."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    cell = pf.launch_tiles(32768, 2048, 128, summaries=(2048, 128))
    assert cell.fwd == (512, 512) and cell.bwd == (1024, 1024)
    tiny = pf.launch_tiles(128, 32, 16, summaries=(32, 8), compiled=False)
    assert tiny.fwd[0] == 32 and tiny.bwd[0] == 32
    assert pf.launch_tiles(4096, 200, 128, summaries=(2048, 100)) is None    # off the lanes
    with pytest.raises(ValueError, match="summaries"):
        flash_attention_with_lse(*_eva_case(128), causal=True, summaries=(32, 8))


def test_position_ids_run_the_block_diagonal():
    """Segment ids that are a function of position (``position // window``)
    and not of documents: ONE causal launch over the row is the launches a
    window the program runs (``tag='eva_local'``), outputs and LSE, and its
    table of documents runs the block diagonal's tiles alone."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    W, L = 32, 128
    tile = (W // 2, W // 2)
    q, k, v = _eva_case(L)
    ids = jnp.broadcast_to(jnp.arange(L) // W, (2, L)).astype(jnp.int32)
    whole, whole_lse = flash_attention_with_lse(
        q, k, v, causal=True, segment_ids=ids, block_q=tile[0], block_k=tile[1])
    fold = lambda a: a.reshape((2 * L // W, W) + a.shape[2:])
    local, lse = flash_attention_with_lse(fold(q), fold(k), fold(v), causal=True,
                                          tag="eva_local")
    np.testing.assert_allclose(local.reshape(whole.shape), whole, **FP32_TOL)
    np.testing.assert_allclose(
        lse.reshape(2, L // W, 2, W).transpose(0, 2, 1, 3).reshape(2, 2, L),
        whole_lse, **FP32_TOL)
    by_position, run = pf.tiles_run(ids, ids, tile)
    blocks = L // tile[0]
    assert int(by_position) == 2 * blocks * (blocks + 1) // 2
    # a window is two tiles wide: three tiles of its 2 x 2 square lie on or
    # under the diagonal
    assert int(run) == 2 * (L // W) * 3
    # a tagged launch names its residuals after the tag: the block's policy
    # never lists them
    text = str(jax.make_jaxpr(lambda q: jax.grad(lambda q: jnp.sum(
        flash_attention_with_lse(fold(q), fold(k), fold(v), tag="eva_local")[0]))(q))(q))
    assert "attn_o_eva_local" in text and "flash_bwd_eva_local" in text


# ---------------------------------------------------------------------------
# dq where a q-block meets several k-blocks: one float32 array a launch that
# the pairs which run add to in place (``dq_mode`` ``in_place``)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,sk,window,cell,want", [
    (1024, 1024, None, "gpt2-large.train.seq1k", "one_block"),
    (4096, 4096, None, "olmoe-1b-7b.train.seq4k", "summed"),
    (5120, 5120, None, "the first length past DQ_SUMMED_PARTIALS k-blocks", "in_place"),
    (8192, 8192, None, "instella-moe-16b-a3b.train.seq8k", "in_place"),
    (16384, 16384, None, "trinity-mini.train.seq16k, the full layer", "in_place"),
    (16384, 16384, 2048, "trinity-mini.train.seq16k, a sliding layer", "summed"),
    (16384, 8192, None, "sdar-30b-a3b.train.bd8k (both copies' rows over the clean keys)", "in_place"),
    (2048, 2048, None, "evabyte-6.5b.train.seq32k, a window's exact keys", "summed"),
    (32768, 2048, None, "evabyte-6.5b.train.seq32k, the row's summaries", "summed"),
    (512, 512, None, "a short row", "one_block"),
    (1024, 1024, 512, "one k-block's length under a window that caps the tiles", "summed"),
])
def test_dq_mode_is_the_k_blocks_a_q_block_meets(sq, sk, window, cell, want):
    """`dq_mode` over the tiles each cell's launch chooses: one k-block a
    q-block keeps dq the kernel's own output; up to ``DQ_SUMMED_PARTIALS`` a
    partial each, summed; more, and dq is added to in place."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    tiles = pf.choose_tiles(sq, sk, 128, causal=True, window=window)
    if sk == sq // 2:
        tiles = pf.launch_tiles(sq, sk, 128, blockdiff=4)
    elif sk == sq // 16:
        tiles = pf.launch_tiles(sq, sk, 128, summaries=(2048, 128))
    assert pf.dq_mode(sq, sk, tiles, window) == want, cell


def _in_place_pair(mask, G, S=128, t=16):
    """-> (q, k, v), reference(q, k, v), kernel(q, k, v), (sq, sk, window):
    one masked call at tiles of ``t`` over eight k-blocks (so dq is added to
    in place), two key heads of ``G`` query heads each."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    rng = np.random.default_rng(7)
    draw = lambda rows, heads: jnp.asarray(rng.normal(size=(2, rows, heads, 16)),
                                           jnp.float32) * 0.3
    ids = _packed_ids(S, "packed")
    if mask == "blockdiff4":
        q, k, v = draw(2 * S, 2 * G), draw(S, 2), draw(S, 2)
        both = jnp.concatenate([ids, ids], axis=1)
        at = jnp.arange(2 * S)
        seen = attn_mod.blockdiff_visible((at >= S)[:, None], (at % S)[:, None],
                                          jnp.zeros((1, S), bool), jnp.arange(S)[None], 4)
        seen = seen[None] & (both[:, :, None] == ids[:, None, :])
        # (a noised row with no clean key: the dense softmax's mean over
        # nothing is not the kernel's 0; such rows carry no weight)
        keyed = seen.any(axis=2)[:, :, None, None]
        reference = lambda q, k, v: jnp.where(keyed, _xla_attention(
            q, k, v, False, None, None, visible=seen), 0.0)
        kernel = lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=True, segment_ids=ids, q_segment_ids=both, blockdiff=4,
            block_q=t, block_k=t, interpret=True)[0]
        return (q, k, v), reference, kernel, (2 * S, S, None)
    if mask == "eva_far":
        S, window, per = 2 * S, 32, 8
        q, k, v = draw(S, 2 * G), draw(S // window * per, 2), draw(S // window * per, 2)
        seen = (jnp.arange(k.shape[1])[None, :] < (jnp.arange(S) // window * per)[:, None])
        keyed = seen.any(axis=1)[None, :, None, None]
        reference = lambda q, k, v: jnp.where(keyed, _xla_attention(
            q, k, v, False, None, None, visible=jnp.broadcast_to(seen, (2,) + seen.shape)), 0.0)
        kernel = lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=True, summaries=(window, per), tag="eva_far",
            block_q=16, block_k=8, interpret=True)[0]
        return (q, k, v), reference, kernel, (S, k.shape[1], None)
    kw, seg = {"causal": (dict(causal=True), None),
               "causal_ids": (dict(causal=True), ids),
               "noncausal_ids": (dict(causal=False), ids),
               "window_static": (dict(causal=True, window=80), ids)}[mask]
    q, k, v = draw(S, 2 * G), draw(S, 2), draw(S, 2)
    reference = lambda q, k, v: _xla_attention(
        q, k, v, kw["causal"], None, seg, window=kw.get("window"))
    kernel = lambda q, k, v: flash_attention_kernel(
        q, k, v, segment_ids=seg, block_q=t, block_k=t, interpret=True, **kw)
    return (q, k, v), reference, kernel, (S, S, kw.get("window"))


IN_PLACE_MASKS = ["causal", "causal_ids", "noncausal_ids", "window_static",
                  "blockdiff4", "eva_far"]


@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("mask", IN_PLACE_MASKS)
def test_dq_added_to_in_place_is_the_dense_masks(eight_devices, mask, G):
    """dq, dk and dv against the mask built densely where every q-block meets
    several k-blocks and some pairs are skipped (by position, by documents,
    by a q-block's limit): the pairs that run add into ONE float32 array,
    those skipped leave it alone."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    (q, k, v), reference, kernel, (sq, sk, window) = _in_place_pair(mask, G)
    tile = (16, 8) if mask == "eva_far" else (16, 16)
    assert pf.dq_mode(sq, sk, pf.FlashTiles(tile, tile), window) == "in_place"
    w = jnp.asarray(np.random.default_rng(1).normal(size=q.shape), jnp.float32)
    grads = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(kernel(q, k, v), reference(q, k, v), **FP32_TOL)
    for got, want in zip(grads(kernel), grads(reference)):
        np.testing.assert_allclose(got, want, **GRAD_TOL)


@pytest.mark.parametrize("nq,nk,G", [(1, 5, 1), (2, 6, 1), (1, 5, 4)])
def test_a_tile_read_again_the_step_after_it_was_written(eight_devices, nq, nk, G):
    """The smallest grids that add in place: with ONE q-block and one head a
    key head a q-block's tile is read again one grid step after it was written
    (k-block j + 1), with two q-blocks two steps after, with a group of four
    one group later.
    (Interpret mode copies in order: the chip's run of the same grids is
    `tools/attn_blockdiff_ab.py`'s, docs/KERNELS.md.)"""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    t = 32
    rng = np.random.default_rng(nq * 10 + nk)
    q, k, v = (jnp.asarray(rng.normal(size=(2, rows * t, h, 16)), jnp.float32) * 0.3
               for rows, h in ((nq, 2 * G), (nk, 2), (nk, 2)))
    ids = _packed_ids(nk * t, "random" if nk * t % 64 else "packed")
    q_ids = ids[:, (nk - nq) * t:]
    assert pf.dq_mode(nq * t, nk * t, pf.FlashTiles((t, t), (t, t))) == "in_place"
    kernel = lambda q, k, v: flash_attention_kernel(
        q, k, v, causal=True, segment_ids=ids, q_segment_ids=q_ids,
        block_q=t, block_k=t, interpret=True)
    reference = lambda q, k, v: _xla_attention(q, k, v, True, None, ids, q_segment_ids=q_ids)
    grads = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.square(fn(*a))), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(grads(kernel), grads(reference)):
        np.testing.assert_allclose(got, want, **GRAD_TOL)


@pytest.mark.parametrize("mask", ["blockdiff16", "window_static"])
def test_a_q_block_no_k_block_reaches_keeps_its_zeros(eight_devices, mask):
    """A q-block whose every pair is skipped (a noised block with no clean key
    behind it; under a static window, whose grid also has steps without a
    q-block, a block of a document no key is in) is never read, added to or
    written: its dq is exactly 0, and with NaN keys and values in a k-block no
    query meets everything is finite and that block's dk and dv are 0."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    t, S = 16, 128
    rng = np.random.default_rng(11)
    draw = lambda rows, heads: jnp.asarray(rng.normal(size=(1, rows, heads, 16)),
                                           jnp.float32) * 0.3
    blocks = lambda ids: jnp.asarray(np.repeat(ids, t)[None], jnp.int32)
    kseg = blocks([0, 0, 7, 7, 1, 1, 1, 1])
    foreign = (kseg == 7)[0][None, :, None, None]
    poison = lambda a: jnp.where(foreign, jnp.nan, a)
    if mask == "blockdiff16":
        # noised blocks 0 and 4 (q-blocks 8 and 12) see strictly earlier
        # blocks: none, and blocks of documents 0 and 7 alone
        qseg = blocks([0, 0, 0, 0, 1, 1, 1, 1] * 2)
        q, k, v, untouched, window = draw(2 * S, 4), draw(S, 2), draw(S, 2), (8, 12), None
        kernel = lambda q, k, v: flash_attention_with_lse(
            q, poison(k), poison(v), causal=True, segment_ids=kseg, q_segment_ids=qseg,
            blockdiff=16, block_q=t, block_k=t, interpret=True)[0]
    else:
        qseg = blocks([0, 0, 5, 5, 1, 1, 1, 1])
        q, k, v, untouched, window = draw(S, 4), draw(S, 2), draw(S, 2), (2, 3), 72
        kernel = lambda q, k, v: flash_attention_kernel(
            q, poison(k), poison(v), causal=True, window=window, segment_ids=kseg,
            q_segment_ids=qseg, block_q=t, block_k=t, interpret=True)
    assert pf.dq_mode(q.shape[1], S, pf.FlashTiles((t, t), (t, t)), window) == "in_place"
    out = kernel(q, k, v)
    dq, dk, dv = jax.grad(lambda *a: jnp.sum(jnp.square(kernel(*a))), argnums=(0, 1, 2))(q, k, v)
    for a in (out, dq, dk, dv):
        assert np.isfinite(np.asarray(a)).all()
    for i in untouched:
        assert not np.asarray(out)[0, i * t:(i + 1) * t].any()
        assert not np.asarray(dq)[0, i * t:(i + 1) * t].any()
    assert np.asarray(dq)[0, :t].any() and np.asarray(dq)[0, 7 * t:8 * t].any()
    assert not np.asarray(dk)[0, 2 * t:4 * t].any() and not np.asarray(dv)[0, 2 * t:4 * t].any()


def _one_block_cases():
    """The inputs `flash_bwd_one_block_parent.npz` was recorded over (commit
    1a1a644, the tree before dq was added to in place, interpret mode on the
    CPU): one k-block of 64 under two q-blocks of 32, grouped heads."""
    rng = np.random.default_rng(44)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32) * 0.3
    # causal (bottom-right aligned: both pairs run)
    yield "causal", (draw(1, 64, 4, 16), draw(1, 64, 2, 16), draw(1, 64, 2, 16)), dict(
        causal=True, block_q=32, block_k=64)
    # non-causal, with documents: the second q-block's meet no key (its
    # pair is skipped, its dq the kernel's own zeros)
    kseg = jnp.zeros((1, 64), jnp.int32)
    qseg = jnp.asarray(np.repeat([[0, 3]], 32, axis=1), jnp.int32)
    yield "documents", (draw(1, 64, 4, 16), draw(1, 64, 2, 16), draw(1, 64, 2, 16)), dict(
        causal=False, segment_ids=kseg, q_segment_ids=qseg, block_q=32, block_k=64)


@pytest.mark.parametrize("name", ["causal", "documents"])
def test_one_k_block_keeps_the_path_it_had_bit_for_bit(eight_devices, name):
    """Where a q-block meets ONE k-block dq is the kernel's own output in q's
    dtype, as before: gradients equal to the bit to what the parent gave."""
    import os
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    recorded = np.load(os.path.join(os.path.dirname(__file__), "data",
                                    "flash_bwd_one_block_parent.npz"))
    (q, k, v), kw = {n: (x, kw) for n, x, kw in _one_block_cases()}[name]
    assert pf.dq_mode(64, 64, pf.FlashTiles((32, 64), (32, 64))) == "one_block"
    grads = jax.grad(lambda q, k, v: jnp.sum(jnp.square(flash_attention_kernel(
        q, k, v, interpret=True, **kw))), argnums=(0, 1, 2))(q, k, v)
    for n, got in zip(("dq", "dk", "dv"), grads):
        np.testing.assert_array_equal(np.asarray(got), recorded[f"{name}_{n}"])
    if name == "documents":
        assert not np.asarray(grads[0])[0, 32:].any()


@pytest.mark.parametrize("cell,rows,G,sq,sk,kw", [
    ("trinity-mini.train.seq16k, the full layer", 4, 8, 16384, 16384, dict(ids=True)),
    ("sdar-30b-a3b.train.bd8k", 4, 8, 16384, 8192, dict(ids=True, blockdiff=4)),
    ("instella-moe-16b-a3b.train.seq8k", 32, 1, 8192, 8192, dict(ids=True)),
])
def test_a_long_rows_backward_is_one_launch_and_one_float32_dq(cell, rows, G, sq, sk, kw):
    """From the jaxpr alone, at the cells' shapes: ONE ``flash_bwd*`` call
    with no loop around it, no float32 value with an axis of slots, and the
    largest float32 value of the whole pair ``rows x G x Sq x D x 4`` bytes
    (dq itself, and ``dO x O`` before its row sum)."""
    kvH, B = (rows, 1) if rows <= 4 else (rows // 2, 2)
    D = 128
    shape = lambda s, h: jax.ShapeDtypeStruct((B, s, h, D), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((B, sk), jnp.int32)

    def loss(q, k, v, ids):
        q_ids = jnp.concatenate([ids, ids], axis=1) if "blockdiff" in kw else None
        return jnp.sum(flash_attention_with_lse(
            q, k, v, causal=True, segment_ids=ids, q_segment_ids=q_ids,
            blockdiff=kw.get("blockdiff"), interpret=True)[0].astype(jnp.float32))
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        shape(sq, kvH * G), shape(sk, kvH), shape(sk, kvH), ids).jaxpr
    launches, floats = [], []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                launches.append((eqn.params.get("name")
                                 or eqn.params["name_and_src_info"].name, inside))
                continue
            floats.extend(v.aval for v in eqn.outvars
                          if getattr(v.aval, "dtype", None) == jnp.float32)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, inside + ((name,) if name in ("scan", "while") else ()))
    walk(jaxpr, ())
    backward = [(n, inside) for n, inside in launches if n.startswith("flash_bwd")]
    assert len(backward) == 1 and backward[0][1] == (), cell
    assert max(len(a.shape) for a in floats) <= 4
    assert max(a.size * 4 for a in floats) == rows * G * sq * D * 4


# ---------------------------------------------------------------------------
# a learned selection's operand (PR 48)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tiles,documents,dq", [
    ((64, 64), True, "summed"),       # four k-blocks a q-block: partials summed
    ((32, 32), True, "in_place"),     # eight: dq added to where it lies
    ((128, 128), False, "summed"),    # two, no ids: the operand alone beside the causal rule
    ((256, 256), True, "one_block"),  # one tile a row: dq the kernel's own output
])
def test_selected_launch_matches_the_masked_softmax(tiles, documents, dq):
    """The flash pair reading a selection's operand (``flash_*_dsa``) against
    the masked softmax in XLA: output, LSE and the three gradients (through
    both outputs), grouped heads, with and without packed documents, in every
    way the backward makes dq. The operand is `dsa_select`'s: a subset of the
    causal, same-document pairs, a row of the document's first positions
    picking fewer than k."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    rng = np.random.default_rng(0)
    B, L, H, kvH, D, J, d, K = 2, 256, 4, 2, 32, 2, 8, 24
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    q, k, v = f(B, L, H, D), f(B, L, kvH, D), f(B, L, kvH, D)
    docs = jnp.asarray(np.stack([np.arange(L) >= 100, np.arange(L) >= 37]).astype(np.int32))
    docs = docs if documents else jnp.zeros_like(docs)
    sel = attn_mod.dsa_select(f(B, L, J, d), f(B, L, d), f(B, L, J), docs, K)
    picked = attn_mod.unpack_selection(sel, L)
    assert sel.dtype == jnp.int8 and sel.shape == (B, L // 8, L)
    assert int(picked[0, 5].sum()) == 6 and int(picked[1, 200].sum()) == K
    made = pf.launch_tiles(L, L, D, 4, selected=True, block_q=tiles[0], block_k=tiles[1],
                           compiled=False)
    assert pf.dq_mode(L, L, made) == dq

    def both(fn):
        def loss(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(jnp.sin(lse))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))
    want = both(lambda q, k, v: attn_mod._xla_selected_attention(
        q, k, v, picked, D ** -0.5))(q, k, v)
    got = both(lambda q, k, v: pf.flash_attention_with_lse(
        q, k, v, causal=True, segment_ids=docs if documents else None, selected=sel,
        block_q=tiles[0], block_k=tiles[1], interpret=True))(q, k, v)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4)


def test_selected_launch_names_its_kernels_and_residuals_and_refuses_the_rest():
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    q = jnp.zeros((1, 128, 4, 16)); k = v = jnp.zeros((1, 128, 2, 16))
    sel = jnp.full((1, 16, 128), -1, jnp.int8)     # every pair's bit
    fn = lambda q, k, v: jnp.sum(pf.flash_attention_with_lse(
        q, k, v, causal=True, selected=sel, interpret=True)[0])
    text = str(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2)))(q, k, v))
    assert "flash_fwd_dsa" in text and "flash_bwd_dsa" in text
    assert "attn_o_dsa" in text and "attn_lse_dsa" in text
    for bad in (dict(causal=False), dict(window=16), dict(q_offset=0), dict(blockdiff=4)):
        with pytest.raises(ValueError):
            pf.flash_attention_with_lse(q, k, v, **{"causal": True, **bad},
                                        selected=sel, interpret=True)
    # a byte a pair (the operand before PR 50) is refused by name, not misread
    with pytest.raises(ValueError, match="pack_selection"):
        pf.flash_attention_with_lse(q, k, v, causal=True, interpret=True,
                                    selected=jnp.ones((1, 128, 128), jnp.int8))
    # the tiles are a full causal layer's at the cell's shape, with the scoped
    # VMEM the operand's tile adds; no other launch kind's tiles move
    full = pf.launch_tiles(16384, 16384, 128)
    mine = pf.launch_tiles(16384, 16384, 128, selected=True)
    assert (mine.fwd, mine.bwd) == (full.fwd, full.bwd) == ((512, 512), (1024, 1024))
    assert full.vmem_limit_bytes is None and mine.vmem_limit_bytes > pf.VMEM_BUDGET
    assert pf.dq_mode(16384, 16384, mine) == "in_place"
    # a q tile that is not whole bit planes of the operand has no launch
    assert pf.launch_tiles(256, 256, 32, 4, selected=True, block_q=16, block_k=64,
                           compiled=False) is None
    assert pf.launch_tiles(256, 256, 32, 4, block_q=16, block_k=64, compiled=False) is not None


# ---------------------------------------------------------------------------
# where a launch takes its operands' heads (PR 51)
# ---------------------------------------------------------------------------

def _layout_cases():
    """name -> (q, k, v, the launch's keywords): every kind of launch at head
    dim 128, small rows and interpret mode's tiles, so that among them dq leaves
    in each of its three ways."""
    rng = np.random.default_rng(51)
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.4)
    B, S, D = 2, 256, 128
    qkv = lambda H, kvH, sq=S, sk=S: (f(B, sq, H, D), f(B, sk, kvH, D), f(B, sk, kvH, D))
    docs = jnp.asarray(np.sort(rng.integers(0, 3, (B, S)), axis=1), jnp.int32)
    picked = (rng.random((B, S, S)) < 0.3) & np.tril(np.ones((S, S), bool))
    from deepspeed_tpu.ops.transformer.attention import pack_selection
    return {
        # one tile a row: dq the kernel's own output
        "plain_causal": qkv(4, 4) + (dict(causal=True),),
        # a power of two, which q is multiplied by before the launch
        "plain_scale_pow2": qkv(4, 2) + (dict(causal=True, scale=0.125, block_q=64,
                                              block_k=64),),
        # a static window's cut grids, its partials masked and summed
        "window": qkv(4, 2) + (dict(causal=True, window=100, block_q=64, block_k=64),),
        # eight k-blocks a q-block: dq added to in place, tiles of other documents skipped
        "segment_ids": qkv(4, 2) + (dict(causal=True, segment_ids=docs, block_q=32,
                                         block_k=32),),
        "grouped_32q_4kv": tuple(a[:1] for a in qkv(32, 4)) + (
            dict(causal=True, segment_ids=docs[:1], block_q=64, block_k=64),),
        "blockdiff": qkv(4, 2, sq=2 * S) + (dict(
            causal=True, blockdiff=4, segment_ids=docs, block_q=32, block_k=32,
            q_segment_ids=jnp.concatenate([docs, docs], axis=1)),),
        "eva_local": qkv(4, 4) + (dict(causal=True, tag="eva_local"),),
        "eva_far": qkv(4, 4, sk=S // 64 * 16) + (dict(
            causal=True, summaries=(64, 16), tag="eva_far", block_q=32, block_k=16),),
        "selected": qkv(4, 2) + (dict(causal=True, segment_ids=docs, block_q=128,
                                      block_k=64, selected=pack_selection(jnp.asarray(picked))),),
    }


def _layout_pair(name, grads):
    """The launch ``name`` in both layouts: (o, lse) or (dq, dk, dv) of a loss
    through both outputs."""
    q, k, v, kw = _layout_cases()[name]
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)

    def run(layout):
        def loss(q, k, v):
            o, lse = flash_attention_with_lse(q, k, v, interpret=True, layout=layout, **kw)
            return jnp.sum(o * w) + jnp.sum(jnp.sin(lse)), (o, lse)
        if grads:
            return jax.grad(lambda *a: loss(*a)[0], argnums=(0, 1, 2))(q, k, v)
        return loss(q, k, v)[1]
    return run("rows"), run("heads")


@pytest.mark.parametrize("grads", [False, True], ids=["forward", "gradients"])
@pytest.mark.parametrize("name", sorted(_layout_cases()))
def test_both_layouts_give_the_same_bits(eight_devices, name, grads):
    """``"rows"`` (``[B, S, heads x D]`` blocks through the index maps) against
    ``"heads"`` (the transposes): the same tiles in the same order, so ``o``,
    ``lse``, ``dq``, ``dk`` and ``dv`` are equal BIT FOR BIT."""
    rows, heads = _layout_pair(name, grads)
    for a, b in zip(rows, heads):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if not grads:     # (and the answer is no zero: a row sees a key)
        assert float(jnp.max(jnp.abs(rows[0]))) > 0.01


def _transposes(fn, *args):
    """The sizes of the values a ``transpose`` makes in ``fn``'s jaxpr."""
    sizes = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "transpose":
                sizes.extend(v.aval.size for v in eqn.outvars)
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return sizes


@pytest.mark.parametrize("name", sorted(_layout_cases()))
def test_a_launch_by_rows_has_no_transpose_around_it(eight_devices, name):
    """The jaxpr of the forward and the three gradients by rows holds no
    ``transpose`` of q, ``o`` or ``do``: what is left are the four of the key
    side (k, v, ``dk``, ``dv`` lead with their heads in either layout: an eighth
    of q's size under 32 query heads over 4), per-row statistics (1 / 128 of q),
    a selection's packed bits, and ONE of dq's size where dq leaves the launch
    in float32 with its heads leading (partials to sum, the array added to in
    place): the pass that sums or casts it writes a head's rows to its
    columns."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    q, k, v, kw = _layout_cases()[name]
    run = lambda layout: lambda q, k, v: flash_attention_with_lse(
        q, k, v, interpret=True, layout=layout, **kw)

    def loss(q, k, v):
        o, lse = run("rows")(q, k, v)
        return jnp.sum(o) + jnp.sum(lse)
    large = lambda sizes: sorted(n for n in sizes if n >= k.size)
    assert large(_transposes(run("rows"), q, k, v)) == [k.size] * 2         # k, v
    tiles = pf._prepare(q, k, v, True, kw.get("scale"), kw.get("segment_ids"),
                        kw.get("q_segment_ids"), None, kw.get("window"), None,
                        kw.get("block_q"), kw.get("block_k"), True, kw.get("blockdiff"),
                        kw.get("summaries"), kw.get("tag"), "selected" in kw)[0].tiles
    one_block = pf.dq_mode(q.shape[1], k.shape[1], tiles, pf.static_window(
        kw.get("window"), q.shape[1], k.shape[1])) == "one_block"
    assert large(_transposes(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)) == sorted(
        [k.size] * 4 + ([] if one_block else [q.size]))
    forced = _transposes(jax.grad(lambda *a: jnp.sum(run("heads")(*a)[0]),
                                  argnums=(0, 1, 2)), q, k, v)
    assert large(forced) == sorted([q.size] * 4 + [k.size] * 4)   # q, o, do, dq; k, v, dk, dv


@pytest.mark.parametrize("q_shape,kv_heads,layout", [
    ((4, 1024, 20, 64), 20, "heads"),        # the GPT-2 cell: half a lane tile a head
    ((1, 2048, 32, 64), 4, "heads"),
    ((1, 16384, 32, 128), 4, "rows"),        # the Trinity, SDAR and Keye cells' heads
    ((1, 2048, 8, 256), 2, "rows"),          # two lane tiles a head
    ((1, 2048, 8, 128), 4, "rows"),
    ((1, 4096, 16, 128), 16, "heads"),       # the OLMoE cell: as many key heads as query heads
    ((2, 8192, 16, 128), 16, "heads"),       # the Instella cell
    ((1, 32768, 4, 128), 4, "heads"),        # one launch of the EvaByte cell
    ((2, 128, 8, 16), 2, "heads"),           # a tiny preset
])
def test_the_layout_is_the_heads_alone(eight_devices, q_shape, kv_heads, layout):
    """`launch_layout` is the rule, `_prepare` and ``attention.plan`` ask it: by
    rows where a head is whole lane tiles AND the query heads are grouped; a
    head narrower than the lanes keeps the transposed layout (and refuses the
    other by name)."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    k_shape = q_shape[:2] + (kv_heads, q_shape[3])
    assert pf.launch_layout(q_shape, k_shape) == layout
    made = attn_mod.plan(q_shape, k_shape, "cpu", "pallas", 2)
    assert made.route == "kernel" and made.layout("flash") == layout
    if q_shape[1] > 2048:
        return
    q, k = (jnp.zeros(s, jnp.bfloat16) for s in (q_shape, k_shape))
    prepared = pf._prepare(q, k, k, True, None, None, None, None, None, None, None,
                           None, True)
    assert prepared[0].layout == layout
    B, S, H, D = q_shape
    assert prepared[1].shape == ((B, 1, S, H * D) if layout == "rows"
                                 else (B * kv_heads, H // kv_heads, S, D))
    assert prepared[2].shape == (B * kv_heads, S, D)
    if q_shape[3] % 128:
        assert _transposes(lambda q, k: flash_attention_kernel(q, k, k, interpret=True),
                           q, k).count(q.size) >= 2            # q in, o out
        with pytest.raises(ValueError, match="rows"):
            flash_attention_kernel(q, k, k, interpret=True, layout="rows")
