"""A static window's cut grids (``flash_*_window``; the old
`test_pallas_flash.py`'s two tests of them): which blocks the cut grids visit
against the mask itself, and that a Python int on the training call is in the
config, the kernels' names and the tiles. The kernels' numerics under a static
window are `test_pallas_flash_parity.py`'s ``static_window*`` cases."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.pallas_flash import flash_attention_kernel
from tests.unit.ops.flash_cases import _qkv


@pytest.mark.parametrize("tile,window,nq,nk,steps", [
    # (k-steps a q-block, q-steps a k-block) of the cut grids. The cell's tiles
    # and window over 8 and 6 blocks of its 32 and 16: the dense mask below is
    # positions squared, and a row of 8 blocks already has q-blocks that reach
    # their whole window, q-blocks the row's start cuts short and k-blocks the
    # row's end does, which is all the count is made of
    ((512, 512), 2048, 8, 8, (5, 5)),        # the cell's forward: 5 k-blocks a q-block
    ((1024, 1024), 2048, 6, 6, (3, 3)),      # its backward: 3
    ((512, 512), 4096, 10, 10, (9, 9)),      # the SmallThinker cell's forward: 9
    ((1024, 1024), 4096, 6, 6, (5, 5)),      # its backward: 5
    ((512, 512), 2049, 8, 8, (5, 5)),
    ((512, 512), 2050, 8, 8, (6, 6)),        # one key past a block's edge
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
    i, j = np.ogrid[:nq * bq, :nk * bk]
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
