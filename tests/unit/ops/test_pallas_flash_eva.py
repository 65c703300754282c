"""EVA's two launches (``flash_*_eva_far`` over a row's summaries under a
q-block's limit, ``flash_*_eva_local`` a window a row; `attention.eva_attention`,
the evabyte-6.5b cell): the old `test_pallas_flash.py`'s section of that
name."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.pallas_flash import (
    MASK_VALUE, flash_attention_with_lse)
from tests.unit.ops.flash_cases import FP32_TOL, GRAD_TOL, out_and_grads


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
    first = np.arange(L) < window
    w = jnp.asarray(np.random.default_rng(1).normal(size=q.shape), jnp.float32)
    u = jnp.asarray(np.random.default_rng(2).normal(size=(2, q.shape[2], L)), jnp.float32)
    scalar = lambda pair: (jnp.sum(pair[0] * w)
                           + jnp.sum(jnp.where(first[None, None], 0.0, pair[1]) * u))
    ((out, lse), got_g), ((want, want_lse), want_g) = out_and_grads(
        (kernel, dense), scalar, q, k, v)
    np.testing.assert_allclose(out, want, **FP32_TOL)
    assert not np.asarray(out)[:, first].any()
    assert (np.asarray(lse)[:, :, first] < MASK_VALUE / 2).all()
    np.testing.assert_allclose(np.asarray(lse)[:, :, ~first],
                               np.asarray(want_lse)[:, :, ~first], **FP32_TOL)
    assert u.shape == lse.shape
    for a, c in zip(got_g, want_g):
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
