"""Differential attention's two-width flash pair (PR 57; the tag ``"diff"``):
keys of one width, a pair's two value heads side by side at twice it (64 / 128
on the chip; 16 / 32 here), grouped query heads, in interpret mode on the CPU
against ``jax.nn`` attention in all four outputs: under a static window and
none, with packed documents, with keys and values of another layer's making, dq
added to in place and summed; the plan, the names and the VMEM count at 64 /
128."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import attention, pallas_flash as pf

F32 = jnp.float32


def plain(q, k, v, ids, window):
    """``jax.nn.softmax`` attention over [B, S, H, D] operands with grouped
    query heads: causal, inside a document, among the ``window`` latest keys."""
    S, D = q.shape[1], q.shape[3]
    g = q.shape[2] // k.shape[2]
    at = jnp.arange(S)
    seen = (at[None, :] <= at[:, None])
    if window:
        seen = seen & (at[:, None] - at[None, :] < window)
    seen = seen[None, None]
    if ids is not None:
        seen = seen & (ids[:, :, None] == ids[:, None, :])[:, None]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, g, axis=2)) * D ** -0.5
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, jnp.repeat(v, g, axis=2))


@pytest.fixture(scope="module")
def operands():
    B, S, H, kvH, D = 2, 256, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, S, H, D), F32)
    k = jax.random.normal(ks[1], (B, S, kvH, D), F32)
    v = jax.random.normal(ks[2], (B, S, kvH, 2 * D), F32)
    w = jax.random.normal(ks[3], (B, S, H, 2 * D), F32)
    ids = jnp.cumsum(jax.random.bernoulli(ks[4], 0.02, (B, S)), axis=1).astype(jnp.int32)
    return q, k, v, w, ids


@pytest.mark.parametrize("window,documents,tile,mode", [
    (48, True, (64, 64), "summed"), (None, True, (32, 32), "in_place"),
    (48, False, (32, 32), "in_place"), (None, False, (64, 64), "summed")],
    ids=["window-packed-summed", "full-packed-in_place", "window-one-document-in_place",
         "full-one-document-summed"])
def test_the_pair_at_twice_the_keys_width_is_plain_attention(operands, documents, window,
                                                             tile, mode):
    q, k, v, w, ids = operands
    ids = ids if documents else None
    if window is None:
        assert pf.dq_mode(256, 256, pf.FlashTiles(tile, tile)) == mode

    def kernel(q, k, v):
        out = pf.flash_attention_kernel(q, k, v, causal=True, segment_ids=ids, window=window,
                                        block_q=tile[0], block_k=tile[1], tag="diff")
        assert out.shape == w.shape
        return jnp.sum(out * w), out

    def reference(q, k, v):
        out = plain(q, k, v, ids, window)
        return jnp.sum(out * w), out

    (_, got), got_g = jax.value_and_grad(kernel, (0, 1, 2), has_aux=True)(q, k, v)
    (_, want), want_g = jax.value_and_grad(reference, (0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), got_g, want_g):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=name)


def test_two_layers_share_one_layers_keys_and_values(operands, monkeypatch):
    """Queries of two layers over ONE layer's k and v (a cross-decoder): each
    reader's launch is plain attention, and k and v collect both gradients."""
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    q, k, v, w, ids = operands
    q2 = q[::-1] * 0.5

    def readers(fn):
        return lambda k, v: jnp.sum(fn(q, k, v) * w) + jnp.sum(fn(q2, k, v) * w)

    got = jax.grad(readers(lambda q, k, v: attention.flash_attention(
        q, k, v, segment_ids=ids, tag="diff")), (0, 1))(k, v)
    want = jax.grad(readers(lambda q, k, v: plain(q, k, v, ids, None)), (0, 1))(k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_the_launches_carry_their_own_names(operands):
    q, k, v, _, ids = operands
    def text(**kw):
        return str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(pf.flash_attention_kernel(
            q, k, v, causal=True, segment_ids=ids, **kw))))(q))
    full, cut = text(tag="diff"), text(tag="diff", window=48)
    assert "flash_fwd_diff" in full and "flash_bwd_diff" in full and "_window" not in full
    assert "flash_fwd_diff_window" in cut and "flash_bwd_diff_window" in cut
    assert "attn_o_diff" in full and "attn_lse_diff" in full
    # untagged, the two-width launch is latent attention's, as it was
    assert "flash_fwd_mla" in text() and "diff" in pf.TAGS and "_diff" not in text()
    with pytest.raises(ValueError, match="tagged 'mla' or 'diff'"):
        pf.flash_attention_with_lse(q, k, v, tag="eva_far")


@pytest.mark.parametrize("backend,mode,window,route", [
    ("tpu", "", None, "kernel"), ("tpu", "", 512, "kernel"), ("tpu", "xla", None, "xla_chunked"),
    ("cpu", "", 512, "xla"), ("cpu", "pallas", 512, "kernel")])
def test_the_plan_of_a_half_of_the_pairs(backend, mode, window, route):
    """The cell's launch: 20 query heads over 10 key heads of 64, values of 128,
    16,384 rows."""
    q, k = (1, 16384, 20, 64), (1, 16384, 10, 64)
    made = attention.plan(q, k, backend, mode, 2, window=window, v_dim=128, tag="diff")
    assert made.route == route
    if route == "kernel":
        (at,) = made.launches
        # keys of half a lane tile: by heads
        assert (at.tag, at.layout, at.window) == ("diff", "heads", window)
        assert made.dq("diff") == ("summed" if window else "in_place")
        assert made.dq("mla") is None and made.dq("flash") is None
    assert attention.plan(q, k, "tpu", "", 2, v_dim=128).launches[0].tag == "mla"
    assert pf.folds(q, k, 128)


def test_vmem_counts_the_padding_of_a_key_block_of_64():
    """By hand at 64 / 128 in bfloat16: a 64-wide block takes a whole lane tile."""
    fwd = pf.tile_vmem_bytes((512, 512), 64, 2, backward=False, v_dim=128)
    assert fwd == 2 * 4 * 512 * 512 + 2 * 2 * (512 + 512) * (128 + 128) + 4 * 512 * (128 + 256)
    bwd = pf.tile_vmem_bytes((1024, 1024), 64, 2, backward=True, v_dim=128)
    assert bwd == (2 * 4 * 1024 * 1024 + 2 * 2 * (1024 * (256 + 128) + 1024 * (256 + 256))
                   + 4 * (1024 * (128 + 128) + 1024 * 128))
    assert fwd < bwd < pf.VMEM_BUDGET
    assert pf.launch_tiles(16384, 16384, 64, 2, v_dim=128) == pf.FlashTiles(
        (512, 512), (1024, 1024), None)
