"""The table of documents (PR 41): a tile whose keys all lie in other documents
is skipped. The old `test_pallas_flash.py`'s section of that name: forward and
gradients against the dense mask under every grid, NaN in a block no query is
in, the sentinel row, a launch's operands, and `tiles_run` against a
brute-force count."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import _xla_attention
from deepspeed_tpu.ops.transformer.pallas_flash import (
    MASK_VALUE, flash_attention_kernel, flash_attention_with_lse,
    merge_partials)
from tests.unit.ops.flash_cases import (DOC_TILE, FP32_TOL, GRAD_TOL, _packed_ids,
                                         _qkv, out_and_grads)


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
        w = jnp.where(keyed, w, 0.0)
    (got, got_g), (want, want_g) = out_and_grads((kernel, reference), w, q, k, v)
    if mask == "ring_hop":
        assert not np.asarray(jnp.where(keyed, 0.0, got)).any()
    np.testing.assert_allclose(got * w, want * w, **FP32_TOL)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, **GRAD_TOL)


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
    (out, got), (out_ref, want) = out_and_grads((kernel, reference), None, q, k, v)
    np.testing.assert_allclose(out, out_ref, **FP32_TOL)
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
