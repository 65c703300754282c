"""The layout of a learned selection's operand (``attention.pack_selection`` /
``unpack_selection``; PR 50): bits along the query axis in planes of 128
queries, stated once and read by ``dsa_select``, the flash pair's two
orientations, the KL pair and the XLA routes. Round trips bit for bit over
lengths and tile sides, the transposed readers' block against the ``[q, k]``
block, and ``dsa_select`` against ``pack(select_topk(...))`` under documents."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import attention

KEYS = 40       # the key axis is not packed: any width


def picks(length, seed=0, rows=2):
    """bool ``[rows, length, KEYS]``: random picks, with an EMPTY and a FULL
    query row among them and a ragged share row by row."""
    rng = np.random.default_rng(seed)
    picked = rng.random((rows, length, KEYS)) < rng.random((rows, length, 1))
    picked[0, 0] = False
    picked[-1, length - 1] = True
    picked[0, length // 2] = True
    return jnp.asarray(picked)


@pytest.mark.parametrize("length", [
    16384,                          # the keye-vl2-30b-a3b cell's row
    64,                             # the tiny preset's
    8, 13, 100, 128, 1000,          # shorter than a group of 1,024: one ragged group
    1024, 2048, 1536, 1100,         # whole groups, and a last group that is not
])
def test_round_trip_bit_for_bit(length):
    picked = picks(length, seed=length, rows=1 if length > 4096 else 2)
    packed = attention.pack_selection(picked)
    rows = attention.packed_rows(length)
    assert packed.dtype == jnp.int8 and packed.shape == picked.shape[:1] + (rows, KEYS)
    # a bit a pair: L / 8 rows where the row is whole groups or one short group
    assert rows == -(-length // 8) or (length > 1024 and length % 1024 and rows > length // 8)
    np.testing.assert_array_equal(
        np.asarray(attention.unpack_selection(packed, length)), np.asarray(picked))
    # the transposed readers' operand is the packed array's transpose
    np.testing.assert_array_equal(
        np.asarray(attention.unpack_selection(jnp.swapaxes(packed, 1, 2), length, axis=-1)),
        np.asarray(jnp.swapaxes(picked, 1, 2)))


def test_the_layout_is_planes_of_128_queries():
    """Bit ``j`` of packed row ``r`` of group ``g`` is query ``g x 1024 + j x
    128 + r``: said by hand for one pick a plane."""
    length = 2048
    for t in (0, 127, 128, 1023, 1024, 1024 + 5 * 128 + 7, 2047):
        one = np.zeros((1, length, 1), bool)
        one[0, t, 0] = True
        packed = np.asarray(attention.pack_selection(jnp.asarray(one))).view(np.uint8)
        g, j, r = t // 1024, (t % 1024) // 128, t % 128
        assert packed.shape == (1, 256, 1)
        assert packed[0, g * 128 + r, 0] == 1 << j and packed.sum() == 1 << j


TILES = [(16384, n) for n in (256, 512, 1024, 128, 2048)] + [
    (64, 16), (64, 32), (64, 64), (256, 32), (256, 64), (256, 128), (256, 256),
    (1536, 512), (2048, 1024), (2048, 2048)]


@pytest.mark.parametrize("length,n", TILES, ids=lambda v: str(v))
def test_a_tile_unpacks_to_its_queries_in_both_orientations(length, n):
    """What a kernel's tile reads: the packed block `selection_tile` names for
    q-block ``i`` (the rows a BlockSpec of that shape and index ``i // shared``
    fetches), unpacked under a TRACED ``i``, is the tile's queries; and the
    transposed readers' block of the transposed operand is that tile
    transposed."""
    picked = picks(length, seed=n, rows=1)
    packed = attention.pack_selection(picked)
    rows, shared = attention.selection_tile(length, n)
    assert rows == max(n // 8, attention.selection_plane(length))
    blocks = list(range(length // n))
    blocks = blocks if len(blocks) <= 8 else blocks[:3] + blocks[len(blocks) // 2:][:3] + blocks[-2:]
    unpack = jax.jit(lambda block, i, axis: attention.unpack_selection(
        block, length, (n, i), axis=axis), static_argnums=2)
    for i in blocks:
        at = (i // shared) * rows
        block = packed[:, at:at + rows]
        want = np.asarray(picked[:, i * n:(i + 1) * n])
        np.testing.assert_array_equal(np.asarray(unpack(block, jnp.int32(i), 1)), want)
        np.testing.assert_array_equal(
            np.asarray(unpack(jnp.swapaxes(block, 1, 2), jnp.int32(i), 2)),
            want.swapaxes(1, 2))


@pytest.mark.parametrize("length,n,compiled,want", [
    (16384, 512, True, (128, 2)), (16384, 1024, True, (128, 1)), (16384, 256, True, (128, 4)),
    (16384, 2048, True, (256, 1)), (16384, 128, True, (128, 8)),
    (16384, 64, True, None),            # under a plane
    (16384, 1536, True, None),          # neither whole planes of a group nor whole groups
    (512, 256, True, None),             # a short row's plane is 64 queries: not the chip's lanes
    (512, 256, False, (64, 2)), (64, 16, False, (8, 4)), (64, 8, False, (8, 8)),
    (64, 4, False, None), (100, 13, False, (13, 8)), (100, 26, False, (13, 4)),
])
def test_selection_tile_table(length, n, compiled, want):
    assert attention.selection_tile(length, n, compiled) == want


@pytest.mark.parametrize("length,cuts,topk", [
    (64, (0, 20, 40), 8),           # the tiny preset's row: one block, one ragged group
    (1024, (0, 100, 700), 48),      # two blocks of 512 packed as one group
    (2048, (0, 1500), 64),          # two groups
    (1536, (0, 37), 32),            # whole blocks, a last group that is not: packed whole
    (200, (0, 3), 300),             # topk over the row: every visible key
])
def test_dsa_select_is_pack_of_select_topk_under_documents(length, cuts, topk):
    """`dsa_select`'s operand is ``pack_selection`` of THE definition's picks
    over the whole row at once, bit for bit: rows with fewer visible keys than
    ``topk`` pick them all, none outside its document or ahead."""
    J, d, B = 2, 8, 2
    key = jax.random.PRNGKey(length)
    draw = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    q_idx, k_idx, w = draw(0, (B, length, J, d)), draw(1, (B, length, d)), draw(2, (B, length, J))
    docs = jnp.asarray((np.arange(length)[None, :] >= np.asarray(cuts)[:, None]).sum(0) - 1,
                       jnp.int32)[None].repeat(B, 0)
    seen = attention.causal_in_document(jnp.arange(length), docs, docs)
    want, got = jax.jit(lambda: (
        attention.select_topk(attention.index_scores(q_idx, k_idx, w), seen, topk),
        attention.dsa_select(q_idx, k_idx, w, docs, topk)))()
    assert got.dtype == jnp.int8 and got.shape == (B, attention.packed_rows(length), length)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(attention.pack_selection(want)))
    picked = np.asarray(attention.unpack_selection(got, length))
    np.testing.assert_array_equal(picked.sum(-1), np.minimum(np.asarray(seen).sum(-1), topk))
    assert not (picked & ~np.asarray(seen)).any()


@pytest.mark.parametrize("backend,form", [("tpu", "kernel"), ("cpu", "bisection")])
def test_the_records_carry_the_selections_form_and_counts(monkeypatch, backend, form):
    """``attention_records`` says which form a step of 2,048-token rows traces
    (`attention.select_launch`), and ``traced_rows_records`` hands on, beside
    ``kl_tiles``, the launch's ``select_tiles`` ``[run, of]`` and
    ``select_rows`` ``[thresholded, of]`` from a step's statistics."""
    from deepspeed_tpu.models import keye_vl2_model
    model = keye_vl2_model("keye-vl2-tiny", dtype=jnp.float32, max_seq_len=2048)
    dsa = model.attention_records()[0]["dsa"]
    assert dsa["select"] is None and dsa["select_tiles"] is None and dsa["select_rows"] is None
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setenv("DSTPU_ATTN", "")
    dsa = model.attention_records(1, 2048)[0]["dsa"]
    assert dsa["select"] == form and dsa["select_tiles"] is None
    stats = {"dsa_kl_tiles": np.asarray([5, 64]), "dsa_select_tiles": np.asarray([30, 64]),
             "dsa_select_rows": np.asarray([1200, 2048]), "attn_selected_share": 0.5}
    assert model.traced_rows_records(stats) == {"dsa": {
        "kl_tiles": [5, 64], "select_tiles": [30, 64], "select_rows": [1200, 2048]}}
    assert model.traced_rows_records({"dsa_kl_tiles": np.asarray([5, 64])}) == {
        "dsa": {"kl_tiles": [5, 64]}}
    assert model.traced_rows_records({"attn_selected_share": 0.5}) == {}
