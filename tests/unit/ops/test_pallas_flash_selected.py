"""A learned selection's operand (``flash_*_dsa``; PRs 48 and 50, the
keye-vl2-30b-a3b cell): the old `test_pallas_flash.py`'s section of that name,
the pair reading the packed bits against the masked softmax, and the launch's
names, tiles and refusals."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


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
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
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
