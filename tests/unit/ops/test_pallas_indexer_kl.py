"""The indexer's KL as a Pallas pair (``pallas_indexer_kl``; PR 49), on the CPU
in interpret mode: the value and its three gradients against
``attention._kl_rows`` under ``jax.value_and_grad`` in float32 at ``highest``,
over one document, packed documents (q-blocks whose earlier k-blocks are all
skipped), rows with fewer visible keys than ``topk``, an ``lse`` that leaves
``sum p != 1``, a picked pair whose ``p`` underflows to 0, bf16 operands; the
route ``attention.kl_launch`` takes; the tiles a launch runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import attention, pallas_flash
from deepspeed_tpu.ops.transformer import pallas_indexer_kl as kl

F32 = jnp.float32


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def operands(L=64, H=4, kvH=2, D=16, J=2, d=8, topk=16, cuts=(0,), dtype=F32,
             rows=2, seed=0, lse_shift=0.0, far_key=None):
    """(q_idx, k_idx, w, q, k, lse, picked, documents, scale): the selection is
    ``dsa_select``'s own, the ``lse`` the selected attention's (+ ``lse_shift``:
    a row's p then sums to ``exp(-shift)``); ``far_key``: a key whose logits
    lie so far under every query's lse that its p underflows to 0 while the
    indexer picks it."""
    key = jax.random.PRNGKey(seed)
    draw = lambda i, shape, dt=dtype: jax.random.normal(
        jax.random.fold_in(key, i), shape, F32).astype(dt)
    q, k, v = draw(0, (rows, L, H, D)), draw(1, (rows, L, kvH, D)), draw(2, (rows, L, kvH, D))
    q_idx, k_idx = draw(3, (rows, L, J, d)), draw(4, (rows, L, d))
    w = jnp.abs(draw(5, (rows, L, J), F32)) * 0.3
    if far_key is not None:
        # every query's index score for this key is the row's largest (picked
        # wherever visible); its main logits are 200 under the rest
        q = jnp.abs(q)
        k = k.at[:, far_key].set(-200.0 * D ** 0.5 / jnp.sum(jnp.abs(q), -1).min())
        q_idx, k_idx = jnp.abs(q_idx), k_idx.at[:, far_key].set(10.0)
    documents = jnp.asarray(
        (np.arange(L)[None, :] >= np.asarray(cuts)[:, None]).sum(0) - 1, jnp.int32)[None]
    documents = jnp.repeat(documents, rows, axis=0)
    picked = attention.dsa_select(q_idx, k_idx, w, documents, topk)
    scale = D ** -0.5
    _, lse = attention._xla_selected_attention(q, k, v, unpacked(picked), scale)
    return q_idx, k_idx, w, q, k, lse + lse_shift, picked, documents, scale


def unpacked(picked):
    """The packed operand of whole rows as bool ``[rows, L, L]``."""
    return attention.unpack_selection(picked, picked.shape[-1])


def reference(q_idx, k_idx, w, q, k, lse, picked, documents, scale):
    up = lambda a: a.astype(F32)
    f = lambda a, b, c: attention._kl_rows(a, c, up(q), lse, unpacked(picked), b, up(k), scale)
    return jax.value_and_grad(f, argnums=(0, 1, 2))(up(q_idx), up(k_idx), up(w))


def forward_rows(args, tile):
    cfg = kl._config(args[0], args[3], args[4], args[8], args[7], tile, None)
    return kl._fwd_call(cfg, *args[:8])


def terms(args, tile) -> float:
    """``sum_t |A| + |C| + |P x lse_I|``: the size of what a row's KL is the
    sum of."""
    rows = forward_rows(args, tile)
    row = lambda name: rows[:, kl.ROWS[name]]
    return float(jnp.sum(jnp.abs(row("A")) + jnp.abs(row("C"))
                         + jnp.abs(row("P") * row("lse_I"))))


CASES = {
    "one_document": dict(),
    "packed_documents": dict(cuts=(0, 20, 40)),
    "a_document_a_block": dict(cuts=(0, 16, 32, 48)),
    "fewer_visible_than_topk": dict(topk=48, cuts=(0, 8, 30)),
    "every_visible_key_picked": dict(topk=64),
    "lse_leaves_p_short_of_one": dict(lse_shift=0.37),
    "lse_leaves_p_over_one": dict(lse_shift=-0.2, cuts=(0, 24)),
    "a_picked_p_underflows": dict(far_key=3, topk=8),
    "grouped_heads_wide": dict(H=8, kvH=2, J=4, d=16, seed=3),
    "one_row": dict(rows=1, cuts=(0, 40), seed=5),
}


def _cases_and_tiles():
    """Every case at 16 x 16 (a row of 64 is 4 x 4 tiles), and the packed
    documents under the other shapes of tile: wide, tall, the whole row."""
    every = [(case, (16, 16)) for case in CASES]
    return every + [("packed_documents", tile) for tile in ((32, 16), (16, 32), (64, 64))]


@pytest.mark.parametrize("case,tile", _cases_and_tiles(),
                         ids=lambda v: v if isinstance(v, str) else "%dx%d" % v)
def test_value_and_gradients_match_kl_rows(case, tile):
    args = operands(**CASES[case])
    want, grads = reference(*args)
    got, (dq, dk, dw) = kl.value_and_gradients(*args, tile)
    # (1e-5 of the value; where a shifted lse makes a row's three terms cancel,
    # 1e-7 of their absolute sum: float32 rounding of the terms themselves)
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-7 * terms(args, tile))
    assert float(kl.value(*args, tile)) == float(got)
    for name, a, b in zip(("dq_idx", "dk_idx", "dw"), (dq, dk, dw), grads):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * float(jnp.max(jnp.abs(b))), name


def test_the_underflow_case_holds_a_picked_pair_with_p_zero():
    """What ``a_picked_p_underflows`` is there for: a picked pair whose ``p``
    is exactly 0 (the guard ``p > 0``), in rows whose other picks are not."""
    q_idx, k_idx, w, q, k, lse, picked, _, scale = operands(**CASES["a_picked_p_underflows"])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2)) * scale
    p = jnp.mean(jnp.exp(logits - lse[..., None]), axis=1)
    assert bool(jnp.any(unpacked(picked) & (p == 0)))
    assert bool(jnp.all(jnp.sum(jnp.where(unpacked(picked), p, 0.0), -1) > 0.5))


def test_a_shifted_lse_leaves_the_rows_sum_off_one():
    """The gradient is ``P x exp(I - lse_I) - p`` and not ``r - p``: with the
    row's ``P`` off 1 the two differ, and the pair follows autodiff."""
    args = operands(**CASES["lse_leaves_p_short_of_one"])
    rows = forward_rows(args, (16, 16))
    np.testing.assert_allclose(rows[:, kl.ROWS["P"]], np.exp(-0.37), rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
@pytest.mark.parametrize("cuts", [(0,), (0, 20, 40)], ids=["one_document", "packed"])
def test_bf16_operands(dtype, cuts):
    """bf16 operands: the same products as the XLA form's (operands' dtype,
    float32 sums), so the value agrees to float32 rounding and a gradient to
    one rounding of its dtype."""
    args = operands(dtype=dtype, cuts=cuts)
    q_idx, k_idx, w, q, k, lse, picked, documents, scale = args
    f = lambda a, b, c: attention._kl_rows(a, c, q, lse, unpacked(picked), b, k, scale)
    want, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(q_idx, k_idx, w)
    got, (dq, dk, dw) = kl.value_and_gradients(*args, (16, 16))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert dq.dtype == dk.dtype == dtype and dw.dtype == F32
    for a, b in zip((dq, dk, dw), grads):
        a, b = a.astype(F32), b.astype(F32)
        assert float(jnp.max(jnp.abs(a - b))) <= 2 ** -7 * float(jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("documents", [True, False], ids=["table", "position_alone"])
def test_a_tile_in_another_document_is_skipped(documents):
    """The table of documents decides which tiles run: mark every pair of the
    second document's queries with the first document's keys as picked (whole
    tiles no selection could hold: the pairs are not visible). Handed the
    documents the launches skip those tiles and give the sound selection's
    value and gradients; by position alone they run them, and the value moves."""
    args = list(operands(cuts=(0, 32), topk=64, rows=1))
    q_idx, k_idx, w, q, k, lse, picked, docs, scale = args
    want, grads = reference(*args)
    unsound = attention.pack_selection(unpacked(picked).at[:, 32:, :32].set(True))
    got, (dq, dk, dw) = kl.value_and_gradients(
        q_idx, k_idx, w, q, k, lse, unsound, docs if documents else None, scale, (16, 16))
    if not documents:
        assert abs(float(got) - float(want)) > 1e-2 * abs(float(want))
        return
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip((dq, dk, dw), grads):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * float(jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("cuts,tile,run", [
    ((0,), (16, 16), 10),             # the causal half of 4 x 4, diagonal in
    ((0, 16, 32, 48), (16, 16), 4),   # a document a block: the diagonal alone
    ((0, 32), (16, 16), 6),
    ((0, 20, 40), (32, 32), 3),
])
def test_tiles_run_is_the_kernels_own_count(cuts, tile, run):
    """``pallas_flash.tiles_run`` at the pair's tile is what a launch runs:
    count the tiles whose p the forward adds to ``P`` by making every other
    tile's contribution impossible to miss (a selection of ALL pairs, visible
    or not, and ``documents`` handed to the launch)."""
    q_idx, k_idx, w, q, k, lse, picked, docs, scale = operands(cuts=cuts, rows=1)
    counted = pallas_flash.tiles_run(docs, docs, tile)
    assert int(counted[1]) == run and kl.tiles_of(1, 64, tile) == (64 // tile[0]) * (64 // tile[1])
    everything = jnp.full_like(picked, -1)      # every bit set
    cfg = kl._config(q_idx, q, k, scale, docs, tile, None)
    rows = kl._fwd_call(cfg, q_idx, k_idx, w, q, k, jnp.zeros_like(lse), everything, docs)
    # with lse 0 and every pair "picked" a tile that runs adds its p > 0 to P
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, q.shape[2] // k.shape[2], axis=2))
    p = jnp.mean(jnp.exp(logits * scale), axis=1)[0]
    nq, nk = 64 // tile[0], 64 // tile[1]
    by_tile = p.reshape(nq, tile[0], nk, tile[1]).sum(axis=(1, 3))
    want = sum(float(by_tile[i, j]) for i in range(nq) for j in range(nk)
               if _runs(cuts, tile, i, j))
    assert float(jnp.sum(rows[:, kl.ROWS["P"]])) == pytest.approx(want, rel=1e-5)
    assert sum(_runs(cuts, tile, i, j) for i in range(nq) for j in range(nk)) == run


def _runs(cuts, tile, i, j):
    """By hand: q-block i has a row at or after k-block j's first key whose
    document's range meets the k-block's."""
    doc = (np.arange(64)[None, :] >= np.asarray(cuts)[:, None]).sum(0) - 1
    q_docs, k_docs = doc[i * tile[0]:(i + 1) * tile[0]], doc[j * tile[1]:(j + 1) * tile[1]]
    return bool((i + 1) * tile[0] - 1 >= j * tile[1]
                and k_docs.max() >= q_docs.min() and k_docs.min() <= q_docs.max())


@pytest.mark.parametrize("length,compiled,want", [
    (16384, True, (256, 256)), (8192, True, (256, 256)), (1280, True, (256, 256)),
    # on the chip a bit plane of the operand is 128 lanes: rows over 1,016
    (512, True, None), (384, True, None), (128, True, None), (512, False, (256, 256)),
    (640, True, None),          # only 128 and 640 divide it: a side over the cap
    (200, True, None), (64, False, (64, 64)), (64, True, None), (96, False, (96, 96)),
])
def test_choose_tile(length, compiled, want):
    assert kl.choose_tile(length, compiled) == want


@pytest.mark.parametrize("backend,mode,length,want", [
    ("tpu", "", 16384, "kernel"), ("tpu", "", 1024, "kernel"),
    ("tpu", "", 512, "xla"),            # the operand's planes are 128 lanes on the chip
    ("tpu", "xla", 16384, "xla_chunked"), ("tpu", "xla", 512, "xla"),
    ("tpu", "", 128, "xla"),            # under the flash pair's crossover
    ("cpu", "", 16384, "xla"), ("cpu", "pallas", 64, "kernel"),
    ("cpu", "pallas", 200, "xla"),      # no tile divides it
])
def test_the_kl_follows_the_selected_calls_plan(monkeypatch, backend, mode, length, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    made = attention.plan((1, length, 32, 128), (1, length, 4, 128), backend, mode,
                          selected=2048)
    route, tile = attention.kl_launch(made, length)
    assert route == want and (tile is not None) == (want == "kernel")


@pytest.mark.parametrize("mode", ["pallas", "xla"])
def test_indexer_kl_takes_its_route_and_names_its_gradients(monkeypatch, mode):
    """``attention.indexer_kl`` under both routes: the same value and
    gradients (the custom_vjp's backward scales what the forward kept), the
    kernel route's program holding both launches, the XLA route's neither."""
    monkeypatch.setenv("DSTPU_ATTN", mode)
    args = operands(cuts=(0, 20, 40))
    want, grads = reference(*args)
    fn = jax.value_and_grad(
        lambda a, b, c: 0.5 * attention.indexer_kl(a, b, c, *args[3:]), argnums=(0, 1, 2))
    got, got_grads = fn(*args[:3])
    assert float(got) == pytest.approx(0.5 * float(want), rel=1e-5)
    for a, b in zip(got_grads, grads):
        assert float(jnp.max(jnp.abs(a - 0.5 * b))) <= 2e-4 * float(jnp.max(jnp.abs(b)))
    jaxpr = str(jax.make_jaxpr(fn)(*args[:3]))
    assert ("indexer_kl_fwd" in jaxpr and "indexer_kl_bwd" in jaxpr) == (mode == "pallas")
    for name in ("indexer_kl_dq", "indexer_kl_dk", "indexer_kl_dw"):
        assert name in jaxpr
