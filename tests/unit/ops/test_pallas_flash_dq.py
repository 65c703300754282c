"""dq where a q-block meets several k-blocks (PR 44): one float32 array a launch
that the pairs which run add to in place (``dq_mode`` ``in_place``). The old
`test_pallas_flash.py`'s section of that name: `dq_mode` over every cell's
tiles, dq, dk and dv against the dense masks, the smallest grids that read a
tile again, a q-block nothing reaches, one k-block bit for bit against the
recorded parent, and the cells' backward from the jaxpr alone."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import _xla_attention
from deepspeed_tpu.ops.transformer.pallas_flash import (
    flash_attention_kernel, flash_attention_with_lse)
from tests.unit.ops.flash_cases import FP32_TOL, GRAD_TOL, _packed_ids, out_and_grads


@pytest.mark.parametrize("sq,sk,window,cell,want", [
    (1024, 1024, None, "gpt2-large.train.seq1k", "one_block"),
    (4096, 4096, None, "olmoe-1b-7b.train.seq4k", "summed"),
    (5120, 5120, None, "the first length past DQ_SUMMED_PARTIALS k-blocks", "in_place"),
    (8192, 8192, None, "instella-moe-16b-a3b.train.seq8k", "in_place"),
    (16384, 16384, None, "trinity-mini.train.seq16k, the full layer", "in_place"),
    (16384, 16384, 2048, "trinity-mini.train.seq16k, a sliding layer", "summed"),
    # (a q-block of 1024 meets 5 k-blocks under 4096: past DQ_SUMMED_PARTIALS)
    (16384, 16384, 4096, "smallthinker-21b-a3b.train.win16k, a windowed layer", "in_place"),
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
    (got, got_g), (want, want_g) = out_and_grads((kernel, reference), w, q, k, v)
    np.testing.assert_allclose(got, want, **FP32_TOL)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, **GRAD_TOL)


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
    (_, got_g), (_, want_g) = out_and_grads((kernel, reference), None, q, k, v)
    for got, want in zip(got_g, want_g):
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
    out, (dq, dk, dv) = out_and_grads(kernel, None, q, k, v)
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
