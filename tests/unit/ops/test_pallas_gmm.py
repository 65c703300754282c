"""The in-repo Pallas grouped matmul (ops/transformer/pallas_gmm.py) against
``jax.lax.ragged_dot`` and its ``jax.grad``: the three products (forward, row
gradient, weight gradient) across loads, on the CPU through
``pl.pallas_call(interpret=True)``, the same program the chip compiles.

Documented tolerances:
- float32: the forward and the row gradient never cut the contracted axis,
  so each output element is one dot product of the same operands in both
  programs, summed in whatever order the backend's matmul takes: bit-for-bit
  at a contraction of 64, rtol 2e-5 and atol 3e-5 of values of order 1-10 at
  256-1408. The weight gradient sums over row tiles in another order than
  ``ragged_dot`` does: rtol 2e-5, atol 1e-4 of values up to some 60.
- bf16 operands, float32 accumulation, results rounded to bf16 once in both
  programs: one bf16 step of the value, rtol 1.6e-2 (2^-6), atol 2e-2.

Rows past ``sum(group_sizes)``: the kernel's contract is exact zeros there
(forward and row gradient) and no part in the weight gradient, whatever the
rows hold; ``ragged_dot`` on the CPU happens to give zeros too, on a TPU it
leaves them uninitialised. The reference is therefore masked.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer import pallas_gmm as G

F32_TOL = dict(rtol=2e-5, atol=3e-5)
F32_DW_TOL = dict(rtol=2e-5, atol=1e-4)
BF16_TOL = dict(rtol=1.6e-2, atol=2e-2)

M, GROUPS = 256, 8


def zipf(m, g, seed=0):
    """Uneven sizes [g] summing to m: shares 1/rank, ranks shuffled."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, g + 1)
    sizes = np.floor(p / p.sum() * m).astype(np.int64)
    sizes[0] += m - sizes.sum()
    return rng.permutation(sizes)


LOADS = {
    "even": np.full(GROUPS, M // GROUPS),
    "zipf": zipf(M, GROUPS),
    "one_takes_all": np.eye(GROUPS, dtype=np.int64)[5] * M,
    "an_empty_group": np.array([40, 0, 72, 9, 0, 100, 3, 32]),
    "sums_under_m": np.array([40, 0, 50, 9, 0, 60, 3, 0]),
    "nothing": np.zeros(GROUPS, np.int64),
}


def operands(m, k, n, g, dtype, seed=0):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.normal(size=(m, k)), dtype)
    stack = jnp.asarray(rng.normal(size=(g, k, n)) * 0.2, dtype)
    d_out = jnp.asarray(rng.normal(size=(m, n)), dtype)
    return rows, stack, d_out


def both(rows, stack, d_out, sizes, tiles=None):
    """((out, d_rows, d_stack) of the kernel, the same of ``ragged_dot``)."""
    sizes = jnp.asarray(sizes, jnp.int32)
    live = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]

    def kernel(a, w):
        return G.kernel_grouped_matmul(a, w, sizes, tiles=tiles)

    def reference(a, w):
        return jnp.where(live, jax.lax.ragged_dot(a, w, sizes), 0)

    out = []
    for fn in (kernel, reference):
        y, vjp = jax.vjp(fn, rows, stack)
        out.append((y,) + vjp(d_out))
    return out


def assert_close(got, want, tol, dw_tol=None):
    for name, a, b in zip(G.KINDS, got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        t = dw_tol if (dw_tol and name == "weight_gradient") else tol
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   err_msg=name, **t)


@pytest.mark.parametrize("load", sorted(LOADS))
def test_three_products_match_ragged_dot_float32(load):
    got, want = both(*operands(M, 64, 96, GROUPS, jnp.float32), LOADS[load])
    assert_close(got, want, F32_TOL, F32_DW_TOL)


@pytest.mark.parametrize("load", ["zipf", "an_empty_group", "sums_under_m"])
def test_three_products_match_ragged_dot_bf16(load):
    got, want = both(*operands(M, 128, 128, GROUPS, jnp.bfloat16), LOADS[load])
    assert_close(got, want, BF16_TOL)


@pytest.mark.parametrize("k,n,g", [
    (256, 1408, 4),     # the Instella cell's width: 11 x 128, whole or 128
    (1408, 256, 4),     # and back
    (256, 1024, 8),     # the OLMoE cell's
    (1024, 256, 8),
])
def test_cell_widths(k, n, g):
    sizes = zipf(512, g, seed=1)
    got, want = both(*operands(512, k, n, g, jnp.float32), sizes)
    assert_close(got, want, F32_TOL, F32_DW_TOL)


def test_rows_that_no_power_of_two_divides():
    """144 rows (48 tokens x 3): interpret mode takes them as one tile, which
    128-row parts do not divide, so the shared tile is multiplied whole."""
    sizes = zipf(144, 8, seed=2)
    assert G.choose_tiles(144, 64, 96, 8, 4, compiled=False).fwd == (144, 96)
    got, want = both(*operands(144, 64, 96, 8, jnp.float32), sizes)
    assert_close(got, want, F32_TOL, F32_DW_TOL)


def test_an_empty_groups_slab_is_exactly_zero_and_rows_past_are_zero():
    """The stated contract, on operands whose unused rows hold NaN: a group
    without rows gets a slab of zeros, the rows past the groups' sum come
    out as zeros and take no part in any product."""
    rows, stack, d_out = operands(M, 64, 96, GROUPS, jnp.float32)
    sizes = LOADS["sums_under_m"]
    total = int(sizes.sum())
    rows = rows.at[total:].set(jnp.nan)
    d_out = d_out.at[total:].set(jnp.nan)
    (out, d_rows, d_stack), _ = both(rows, stack, d_out, sizes)
    assert not np.any(np.asarray(out[total:])) and not np.any(np.asarray(d_rows[total:]))
    assert np.all(np.isfinite(np.asarray(out))) and np.all(np.isfinite(np.asarray(d_stack)))
    for group in np.flatnonzero(sizes == 0):
        assert not np.any(np.asarray(d_stack[group])), group
    # and the rest is what the clean operands give
    clean = both(*operands(M, 64, 96, GROUPS, jnp.float32), sizes)[1]
    np.testing.assert_allclose(np.asarray(d_stack), np.asarray(clean[2]), **F32_DW_TOL)


@pytest.mark.parametrize("tiles", [
    G.GmmTiles((8, 96), (8, 64), (8, 64, 96)),          # a group inside one tile
    G.GmmTiles((128, 96), (128, 64), (128, 64, 96)),    # every tile shared
    G.GmmTiles((256, 96), (256, 64), (256, 64, 96)),    # one tile, in two parts
    G.GmmTiles((32, 32), (64, 16), (16, 32, 32)),       # the widths cut
])
def test_any_legal_tiles_give_the_same_products(tiles):
    got, want = both(*operands(M, 64, 96, GROUPS, jnp.float32), LOADS["zipf"], tiles)
    assert_close(got, want, F32_TOL, F32_DW_TOL)


def test_residuals_are_the_operands():
    """What a remat policy sees of a call: nothing is saved but the three
    operands (the names ``wi_gate``, ``wi_up``, ``wo`` stay the layer's)."""
    rows, stack, _ = operands(M, 64, 96, GROUPS, jnp.float32)
    sizes = jnp.asarray(LOADS["zipf"], jnp.int32)
    _, res = G._kernel_fwd(G._Config(G.choose_tiles(M, 64, 96, GROUPS, 4, compiled=False),
                                     True), rows, stack, sizes)
    assert [r.shape for r in res] == [rows.shape, stack.shape, sizes.shape]
    assert res[0] is rows and res[1] is stack


def test_the_schedule_visits_every_shared_tile_once_a_group():
    sizes = jnp.asarray([40, 0, 50, 9, 0, 60, 3, 0], jnp.int32)
    starts, ends, group_of, tile_of, count = G._visits(sizes, 256, 32, tail=True,
                                                       empty=False)
    n = int(count[0])
    visits = list(zip(np.asarray(group_of)[:n].tolist(), np.asarray(tile_of)[:n].tolist()))
    # rows 0-39 group 0, 40-89 group 2, 90-98 group 3, 99-158 group 5,
    # 159-161 group 6, 162-255 past the groups (index 8)
    assert visits == [(0, 0), (0, 1), (2, 1), (2, 2), (3, 2), (3, 3), (5, 3), (5, 4),
                      (6, 4), (6, 5), (8, 5), (8, 6), (8, 7)]
    assert np.asarray(group_of).shape == (256 // 32 + 8,)
    # the steps past the last visit repeat it
    assert set(zip(np.asarray(group_of)[n:].tolist(),
                   np.asarray(tile_of)[n:].tolist())) == {(8, 7)}
    # the weight gradient's: no tail, an empty group once
    _, _, group_of, tile_of, count = G._visits(sizes, 256, 32, tail=False, empty=True)
    n = int(count[0])
    assert np.asarray(group_of)[:n].tolist() == [0, 0, 1, 2, 2, 3, 3, 4, 5, 5, 6, 6, 7]
    assert int(np.asarray(tile_of)[:n].max()) <= 256 // 32 - 1


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("m,k,n,g,dtype,backend,devices,route", [
    (32768, 2048, 1024, 64, BF16, "tpu", 1, "kernel"),   # the OLMoE cell, up
    (32768, 1024, 2048, 64, BF16, "tpu", 1, "kernel"),   # and down
    (36864, 2048, 1408, 8, BF16, "tpu", 1, "kernel"),    # the Instella cell, up
    (36864, 1408, 2048, 8, BF16, "tpu", 1, "kernel"),    # and down
    (36864, 2048, 1408, 8, F32, "tpu", 1, "kernel"),
    (36864, 2048, 1408, 8, BF16, "tpu", 4, "xla"),       # a live mesh: GSPMD's
    (36864, 2048, 1408, 8, BF16, "cpu", 1, "xla"),       # the tests' program
    (32768, 2048, 1024, 64, BF16, "cpu", 8, "xla"),
    (36864, 2048, 1400, 8, BF16, "tpu", 1, "xla"),       # a width off the lanes
    (36864, 2000, 1408, 8, BF16, "tpu", 1, "xla"),
    (36000, 2048, 1408, 8, BF16, "tpu", 1, "xla"),       # no 128-multiple row tile
    (36864, 2048, 1408, 8, jnp.int8, "tpu", 1, "xla"),
    (36864, 2048, 1408, 8, jnp.float8_e4m3fn, "tpu", 1, "xla"),
    (256, 128, 128, 4, BF16, "tpu", 1, "kernel"),        # small and legal
    (32768, 131072, 1024, 8, BF16, "tpu", 1, "xla"),     # no block fits VMEM whole-k
])
def test_route_table(m, k, n, g, dtype, backend, devices, route):
    """``choose_route`` is the whole decision of ``grouped_matmul``, a pure
    function: the TPU's rows are checked here on the CPU."""
    assert G.choose_route(m, k, n, g, dtype, backend, devices) == route


@pytest.mark.parametrize("m,k,n,g,itemsize,compiled,want", [
    # both cells, both ways: rows 256, every width whole
    (32768, 2048, 1024, 64, 2, True, ((256, 1024), (256, 2048), (256, 2048, 1024))),
    (32768, 1024, 2048, 64, 2, True, ((256, 2048), (256, 1024), (256, 1024, 2048))),
    (36864, 2048, 1408, 8, 2, True, ((256, 1408), (256, 2048), (256, 2048, 1408))),
    (36864, 1408, 2048, 8, 2, True, ((256, 2048), (256, 1408), (256, 1408, 2048))),
    # the groups do not move the choice
    (36864, 2048, 1408, 64, 2, True, ((256, 1408), (256, 2048), (256, 2048, 1408))),
    # rows: the largest 128-multiple divisor up to 256 (36992 = 289 x 128)
    (1280, 512, 512, 4, 2, True, ((256, 512), (256, 512), (256, 512, 512))),
    (36992, 512, 512, 4, 2, True, ((128, 512), (128, 512), (128, 512, 512))),
    # blocks over the budget are cut on an output axis: float32's weight
    # gradient slab on k; 1408 = 11 x 128 admits only the whole of it or 128
    (36864, 2048, 1408, 8, 4, True, ((256, 1408), (256, 2048), (256, 1024, 1408))),
    (36864, 8192, 1408, 8, 2, True, ((256, 128), (256, 4096), (256, 2048, 1408))),
    # no legal tiling on the chip
    (36000, 2048, 1408, 8, 2, True, None),
    (36864, 2048, 1400, 8, 2, True, None),
    (36864, 131072, 1024, 8, 2, True, None),
    # interpret mode: any width, power-of-two rows
    (256, 64, 96, 8, 4, False, ((256, 96), (256, 64), (256, 64, 96))),
    (96, 64, 96, 8, 4, False, ((96, 96), (96, 64), (96, 64, 96))),
    (36000, 64, 96, 8, 4, False, ((32, 96), (32, 64), (32, 64, 96))),
])
def test_tile_table(m, k, n, g, itemsize, compiled, want):
    """``choose_tiles`` is a pure function of the shape: ((rows, n) forward,
    (rows, k) row gradient, (rows, k, n) weight gradient) or None."""
    tiles = G.choose_tiles(m, k, n, g, itemsize, compiled=compiled)
    assert (tiles and (tiles.fwd, tiles.dlhs, tiles.dw)) == want
    if tiles:
        need = max(G.rows_vmem_bytes(tiles.fwd[0], k, tiles.fwd[1], itemsize),
                   G.rows_vmem_bytes(tiles.dlhs[0], n, tiles.dlhs[1], itemsize),
                   G.weights_vmem_bytes(*tiles.dw, itemsize))
        assert need <= G.VMEM_BUDGET and need < tiles.vmem_limit_bytes <= G.VMEM_CAP


def test_the_route_reads_no_environment_and_no_model():
    import inspect
    import re
    src = inspect.getsource(G)
    assert not re.findall(r"os\.environ|getenv|DSTPU_", src)
    assert not re.findall(r"olmoe|instella|deepseek", src, flags=re.I)


def test_grouped_matmul_on_the_cpu_is_ragged_dot(monkeypatch):
    rows, stack, _ = operands(M, 64, 96, GROUPS, jnp.float32)
    sizes = jnp.asarray(LOADS["zipf"], jnp.int32)
    jaxpr = str(jax.make_jaxpr(lambda a, w: G.grouped_matmul(a, w, sizes))(rows, stack))
    assert "ragged_dot" in jaxpr and "pallas_call" not in jaxpr
    monkeypatch.setattr(G, "choose_route", lambda *a: "kernel")
    jaxpr = str(jax.make_jaxpr(lambda a, w: G.grouped_matmul(a, w, sizes))(rows, stack))
    assert "pallas_call" in jaxpr and "ragged_dot" not in jaxpr
