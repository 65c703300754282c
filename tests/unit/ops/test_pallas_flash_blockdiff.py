"""The block-diffusion mask (``flash_*_blockdiff``: a clean and a noised copy
of every row; the sdar-30b-a3b cell): the old `test_pallas_flash.py`'s section
of that name, `blockdiff_attention`'s kernel route against the dense mask and
what the one launch under it multiplies."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.pallas_flash import (
    MASK_VALUE, flash_attention_with_lse)
from tests.unit.ops.flash_cases import FP32_TOL, GRAD_TOL, out_and_grads


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
    kernel = lambda q, k, v: attn_mod.blockdiff_attention(q, k, v, b, doc)
    (got, got_g), (want, want_g) = out_and_grads(
        (kernel, lambda q, k, v: _dense_blockdiff(q, k, v, doc, b)), w, q, k, v)
    np.testing.assert_allclose(got, want, **FP32_TOL)
    assert float(jnp.sum(got * w)) == pytest.approx(float(jnp.sum(want * w)), rel=1e-5)
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
