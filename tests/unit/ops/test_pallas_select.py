"""The learned selection as one Pallas launch (``pallas_select``; PR 52), on the
CPU in interpret mode: the launch's bits against ``pack_selection(select_topk(
...))`` on the launch's OWN float32 scores, byte for byte, and against the XLA
form (``attention.dsa_select_xla``, whose scores sum the heads in XLA's order)
up to pairs at a row's threshold; over packed documents with a boundary inside
a tile, rows where no query has a threshold, forced ties with exact zeros among
them (the prefix path), a document that starts on a plane's edge; the tile the
launch takes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import attention, pallas_flash
from deepspeed_tpu.ops.transformer import pallas_select as ps

F32 = jnp.float32
J, D, TOPK = 4, 64, 192
TILE = (128, 256)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@functools.partial(jax.jit, static_argnames=("topk",))
def launch(q_idx, k_idx, w, docs, topk=TOPK):
    return ps.select(q_idx, k_idx, w, docs, topk, TILE, scores=True, interpret=True)


def documents(*cuts_a_row, length=1024):
    """A row's documents from where each starts: int32 ``[rows, length]``."""
    return jnp.asarray(np.stack([
        (np.arange(length)[None, :] >= np.asarray(cuts)[:, None]).sum(0) - 1
        for cuts in cuts_a_row]), jnp.int32)


def operands(B, L, seed=0, whole=False, dtype=F32):
    """(q_idx, k_idx, w); ``whole``: small whole numbers, a third of the
    queries' weights zero: products and sums are exact in any order, scores tie
    by the hundred and whole rows score exactly 0.0."""
    key = jax.random.PRNGKey(seed)
    draw = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape, F32)
    q_idx, k_idx, w = draw(0, (B, L, J, D)), draw(1, (B, L, D)), draw(2, (B, L, J)) * 0.1
    if whole:
        q_idx, k_idx, w = jnp.round(q_idx * 0.6), jnp.round(k_idx * 0.6), jnp.round(w * 10)
        w = w.at[:, ::3].set(0.0)
    return q_idx.astype(dtype), k_idx.astype(dtype), w


CASES = {
    # two rows, each its own documents; 300 and 600 fall inside a 256-key tile
    "boundary_inside_a_tile": dict(docs=documents((0, 300, 600), (0, 37, 700))),
    # no document longer than topk: not one query has a threshold
    "no_threshold_anywhere": dict(docs=documents(range(0, 1024, 160), range(0, 1024, 192))),
    # more equals than places at the threshold, zeros among them
    "ties_and_exact_zeros": dict(docs=documents((0, 300), (0,)), whole=True),
    # a document from a plane's first query, and from a group's (a row of two)
    "document_on_a_planes_edge": dict(docs=documents((0, 128, 640), (0, 384))),
    "two_groups": dict(docs=documents((0, 1024, 1500), length=2048)),
    "bf16_operands": dict(docs=documents((0, 500), (0, 300, 600)), dtype=jnp.bfloat16),
    "topk_over_the_row": dict(docs=documents((0,), (0, 700)), topk=4096),
}


@pytest.mark.parametrize("case", CASES)
def test_the_launch_is_pack_of_select_topk_on_its_own_scores(case):
    spec = dict(CASES[case])
    docs, topk = spec.pop("docs"), spec.pop("topk", TOPK)
    B, L = docs.shape
    q_idx, k_idx, w = operands(B, L, **spec)
    bits, scores = launch(q_idx, k_idx, w, docs, topk=topk)
    seen = attention.causal_in_document(jnp.arange(L), docs, docs)
    assert bits.dtype == jnp.int8 and bits.shape == (B, attention.packed_rows(L), L)
    want = attention.pack_selection(attention.select_topk(scores, seen, topk))
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(want))
    picked, seen = np.asarray(attention.unpack_selection(bits, L)), np.asarray(seen)
    np.testing.assert_array_equal(picked.sum(-1), np.minimum(seen.sum(-1), topk))
    assert not (picked & ~seen).any()
    if case == "no_threshold_anywhere":
        np.testing.assert_array_equal(picked, seen)
    # against the XLA form: its scores sum the heads in another order, so a
    # pair may change sides only where the row's threshold lies between its
    # two scores
    theirs = np.asarray(attention.unpack_selection(
        jax.jit(attention.dsa_select_xla, static_argnums=4)(q_idx, k_idx, w, docs, topk), L))
    mine = np.where(seen, np.asarray(scores), -np.inf)
    ref = np.asarray(attention.index_scores(q_idx, k_idx, w))
    # (float32 steps at the terms' own size: the heads' order, no more)
    drift = float(np.abs(np.where(seen, np.asarray(scores) - ref, 0.0)).max())
    assert drift <= 4 * float(np.finfo(np.float32).eps) * float(np.abs(ref[seen]).max())
    kth = -np.partition(-mine, min(topk, L) - 1, axis=-1)[..., min(topk, L) - 1]
    if spec.get("whole"):
        assert drift == 0
        # the prefix path ran: some thresholded row has more equals than places
        at = kth[..., None]
        crowded = ((mine == at).sum(-1) > topk - (mine > at).sum(-1)) & (seen.sum(-1) > topk)
        assert crowded.any() and (mine == 0).any()
    b, t, s = np.nonzero(picked != theirs)
    assert (np.abs(mine[b, t, s] - kth[b, t]) <= 2 * drift).all()
    assert len(b) <= (0 if drift == 0 else B * L // 100)


@pytest.mark.parametrize("length,compiled,block_k,want", [
    (16384, True, None, (128, 512)), (2048, True, None, (128, 512)),
    (1024, True, None, (128, 512)), (1024, False, 256, (128, 256)),
    (16384, True, 1024, (128, 1024)),
    (1536, True, None, None),           # not whole groups of 1,024
    (512, True, None, None), (512, False, None, None),  # a short row's plane is 64 queries
    (2048, True, 192, None),            # the slot is not whole lanes
    (3072, True, None, (128, 512)), (5120, True, None, (128, 512)),
])
def test_choose_tile(length, compiled, block_k, want):
    assert ps.choose_tile(length, compiled, block_k) == want


def test_the_tiles_a_launch_runs_are_the_flash_tables():
    """``pallas_indexer_kl.tiles_of`` at the launch's tile counts every (plane,
    key block); `pallas_flash.tiles_run` the ones its loop lets through (the
    statistic a step leaves as ``select_tiles``); `attention.visible_counts`
    the keys a query sees (``select_rows``: those with more than ``topk``)."""
    from deepspeed_tpu.ops.transformer import pallas_indexer_kl
    docs = documents((0, 300, 600), (0,))
    run = int(pallas_flash.tiles_run(docs, docs, TILE)[1])
    assert pallas_indexer_kl.tiles_of(*docs.shape, TILE) == 2 * 8 * 4
    seen = attention.causal_in_document(jnp.arange(1024), docs, docs)
    np.testing.assert_array_equal(np.asarray(attention.visible_counts(docs)),
                                  np.asarray(seen).sum(-1))
    # the second row is one document: the causal half, 8 planes over 4 blocks
    one = sum((p * 128 + 127) // 256 + 1 for p in range(8))
    assert one < run < 2 * one
    assert ps.vmem_bytes((128, 512), 16384, 16, 64, 2) < pallas_flash.VMEM_CAP
