"""The two-width flash pair (PR 55; ``FlashConfig.v_dim``): queries and keys of
one width, values, the output and their gradients of another (latent attention:
192 and 128), in interpret mode on the CPU against ``jax.nn`` attention in all
four outputs, with and without packed documents, with dq added to in place and
summed; the plan, the tag, the tile table and the VMEM count at 192 / 128, and
that a launch of ONE width is the launch it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import attention, pallas_flash as pf

F32 = jnp.float32


def plain(q, k, v, ids):
    """``jax.nn.softmax`` attention over [B, S, H, D] operands, causal and
    inside a document (``jax.nn.dot_product_attention`` takes one width)."""
    S, D = q.shape[1], q.shape[3]
    seen = jnp.tril(jnp.ones((S, S), bool))[None, None]
    if ids is not None:
        seen = seen & (ids[:, :, None] == ids[:, None, :])[:, None]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.fixture(scope="module")
def operands():
    B, S, H, D, Dv = 2, 256, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k = (jax.random.normal(kk, (B, S, H, D), F32) for kk in ks[:2])
    v, w = (jax.random.normal(kk, (B, S, H, Dv), F32) for kk in ks[2:4])
    ids = jnp.cumsum(jax.random.bernoulli(ks[4], 0.02, (B, S)), axis=1).astype(jnp.int32)
    return q, k, v, w, ids


@pytest.mark.parametrize("documents", [False, True], ids=["one-document", "packed"])
@pytest.mark.parametrize("tile,mode", [((64, 64), "summed"), ((32, 32), "in_place"),
                                       ((256, 256), "one_block")])
def test_the_two_width_pair_is_plain_attention(operands, documents, tile, mode):
    q, k, v, w, ids = operands
    ids = ids if documents else None
    S = q.shape[1]
    assert pf.dq_mode(S, S, pf.FlashTiles(tile, tile)) == mode

    def kernel(q, k, v):
        out = pf.flash_attention_kernel(q, k, v, causal=True, segment_ids=ids,
                                        block_q=tile[0], block_k=tile[1])
        assert out.shape == v.shape
        return jnp.sum(out * w), out

    def reference(q, k, v):
        out = plain(q, k, v, ids)
        return jnp.sum(out * w), out

    (_, got), got_g = jax.value_and_grad(kernel, (0, 1, 2), has_aux=True)(q, k, v)
    (_, want), want_g = jax.value_and_grad(reference, (0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), got_g, want_g):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=name)


def test_the_launches_carry_their_own_names(operands):
    q, k, v, _, ids = operands
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(pf.flash_attention_kernel(
        q, k, v, causal=True, segment_ids=ids))))(q))
    assert "flash_fwd_mla" in text and "flash_bwd_mla" in text
    assert "attn_o_mla" in text and "attn_lse_mla" in text
    one = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(pf.flash_attention_kernel(
        q, k, k, causal=True))))(q))
    assert "_mla" not in one and "mla" in pf.TAGS
    with pytest.raises(ValueError, match="tagged 'mla'"):
        pf.flash_attention_with_lse(q, k, v, tag="eva_local")


def test_the_xla_routes_take_two_widths(operands, monkeypatch):
    q, k, v, _, ids = operands
    want = plain(q, k, v, ids)
    np.testing.assert_allclose(attention._xla_attention(q, k, v, True, None, ids), want, atol=2e-5)
    np.testing.assert_allclose(attention._xla_attention_chunked(
        q, k, v, True, None, ids, chunk=64), want, atol=2e-5)
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    np.testing.assert_allclose(attention.flash_attention(q, k, v, segment_ids=ids), want,
                               atol=2e-5)


@pytest.mark.parametrize("backend,mode,rows,route", [
    ("tpu", "", 8192, "kernel"), ("tpu", "", 4096, "kernel"), ("tpu", "", 128, "xla"),
    ("tpu", "xla", 8192, "xla_chunked"), ("cpu", "", 8192, "xla"), ("cpu", "pallas", 256, "kernel")])
def test_the_plan_of_a_two_width_call(backend, mode, rows, route):
    shape = (1, rows, 32, 192)
    made = attention.plan(shape, shape, backend, mode, 2, v_dim=128)
    assert made.route == route
    if route == "kernel":
        (at,) = made.launches
        # 192 is one and a half lane tiles and the heads are not grouped: by heads
        assert (at.tag, at.layout) == ("mla", "heads")
        assert made.dq("mla") == ("in_place" if rows > 4096 else "summed" if rows > 1024
                                  else "one_block")
        assert made.dq("flash") is None
        if backend == "tpu":
            assert at.tiles == pf.FlashTiles((512, 512), (1024, 1024))
    # a width the lanes cannot hold is refused to the kernel, as it was
    assert attention.plan((1, 8192, 32, 192), (1, 8192, 32, 192), "tpu", "", 2).route != "kernel"
    assert not pf.folds(shape, shape) and pf.folds(shape, shape, 128)
    assert not pf.folds((1, 8192, 32, 160), (1, 8192, 32, 160), 128)


def test_vmem_is_reckoned_at_both_widths():
    """By hand at 192 / 128 in bfloat16: a 192-wide block takes 256 lanes."""
    fwd = pf.tile_vmem_bytes((512, 512), 192, 2, backward=False, v_dim=128)
    assert fwd == 2 * 4 * 512 * 512 + 2 * 2 * (512 + 512) * (256 + 128) + 4 * 512 * (128 + 256)
    bwd = pf.tile_vmem_bytes((1024, 1024), 192, 2, backward=True, v_dim=128)
    assert bwd == (2 * 4 * 1024 * 1024 + 2 * 2 * (1024 * (512 + 128) + 1024 * (512 + 256))
                   + 4 * (1024 * (256 + 128) + 1024 * 256))
    # the backward at the target tile fills the compiler's own default to the byte
    assert fwd < pf.VMEM_BUDGET and bwd == pf.VMEM_BUDGET
    tiles = pf.launch_tiles(8192, 8192, 192, 2, v_dim=128)
    assert tiles == pf.FlashTiles((512, 512), (1024, 1024), None)
    # in float32 it does not fit, and the q tile steps down
    assert pf.launch_tiles(8192, 8192, 192, 4, v_dim=128).bwd[0] < 1024
    # no other mask takes a second width
    assert pf.launch_tiles(8192, 4096, 192, 2, blockdiff=4, v_dim=128) is None
    assert pf.launch_tiles(8192, 8192, 192, 2, selected=True, v_dim=128) is None


@pytest.mark.parametrize("head_dim", [64, 128, 256])
@pytest.mark.parametrize("tile", [(512, 512), (1024, 1024), (512, 1024)])
def test_a_launch_of_one_width_counts_what_it_counted(head_dim, tile):
    """The estimate of every standing launch, as PR 25 wrote it."""
    bq, bk = tile
    d = max(head_dim, 128)
    for backward in (False, True):
        rows = (3 * bq + 4 * bk) if backward else (2 * bq + 2 * bk)
        acc = (2 * bk * d if backward else bq * (d + 2 * 128)) * 4
        assert pf.tile_vmem_bytes(tile, head_dim, 2, backward=backward) == (
            2 * 4 * bq * bk + 2 * 2 * d * rows + acc)
