"""The selective scan (PR 57; ``ops/transformer/pallas_scan.py``) on the CPU: the
XLA route (a scan over chunks of an associative scan) against the recurrence a
token at a time in the forward and every gradient, with and without document
resets and with a chunk that does not divide the row; the Pallas pair in
interpret mode against that route; the route, the tile and the VMEM count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import pallas_scan as ps

F32 = jnp.float32
NAMES = ("a", "dt_raw", "A", "B", "C", "D", "dt_bias")


def operands(rows, channels, states, resets, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 9)
    first = (jax.random.uniform(k[7], (rows,)) < (0.1 if resets else 0.0))
    return (jax.random.normal(k[0], (rows, channels), F32),
            jax.random.normal(k[1], (rows, channels), F32) - 1.0,
            -jnp.exp(jax.random.normal(k[2], (channels, states)) * 0.5),
            jax.random.normal(k[3], (rows, states), F32),
            jax.random.normal(k[4], (rows, states), F32),
            jax.random.normal(k[5], (channels,)), jax.random.normal(k[6], (channels,)) * 0.5,
            first.astype(jnp.int32).at[0].set(1)), jax.random.normal(k[8], (rows, channels))


def both(fn, args, w):
    out = fn(*args)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a, args[7]) * w), argnums=tuple(range(7)))(*args[:7])
    return out, grads


def agree(got, want, what):
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, err_msg=what)
    for name, g, h in zip(NAMES, got[1], want[1]):
        assert g.shape == h.shape and g.dtype == h.dtype, name
        np.testing.assert_allclose(g, h, atol=2e-5 * max(1.0, float(jnp.abs(h).max())),
                                   err_msg=f"{what}: d{name}")


@pytest.mark.parametrize("rows,chunk,resets", [(64, 16, True), (37, 8, False)],
                         ids=["whole-chunks-packed", "a-partial-chunk-one-document"])
def test_the_chunked_route_is_the_recurrence(rows, chunk, resets):
    args, w = operands(rows, 32, 4, resets)
    want = both(ps.scan_by_token, args, w)
    agree(both(lambda *a: ps.scan_xla(*a, chunk=chunk), args, w), want, "scan_xla")


@pytest.mark.parametrize("rows,chunk,states,resets", [(64, 16, 8, False), (37, 8, 4, True)],
                         ids=["whole-chunks-one-document", "a-partial-chunk-packed"])
def test_the_kernel_pair_is_the_chunked_route(rows, chunk, states, resets):
    """Interpret mode: two tiles of 128 channels, the chunks' entry states
    carried across grid steps, the backward walking the chunks in reverse."""
    args, w = operands(rows, 256, states, resets, seed=1)
    want = both(ps.scan_by_token, args, w)
    agree(both(lambda *a: ps.scan_kernel(*a, chunk=chunk, tile=128), args, w), want, "kernel")


def test_the_launches_carry_their_own_names_and_residuals():
    args, _ = operands(32, 128, 8, True)
    text = str(jax.make_jaxpr(jax.grad(lambda a: jnp.sum(ps.scan_kernel(
        a, *args[1:], chunk=16))))(args[0]))
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text
    assert "ssm_m" in text and "ssm_state" in text
    # no value of rows x channels x states anywhere: the state lives in chunks
    assert "f32[32,128,8]" not in text and "f32[32,8,128]" not in text
    xla = str(jax.make_jaxpr(jax.grad(lambda a: jnp.sum(ps.scan_xla(
        a, *args[1:], chunk=16))))(args[0]))
    assert "pallas_call" not in xla and "f32[32,128,8]" not in xla


@pytest.mark.parametrize("backend,devices,states,channels,route", [
    ("tpu", 1, 16, 5120, "kernel"), ("cpu", 1, 16, 5120, "xla"), ("tpu", 4, 16, 5120, "xla"),
    ("tpu", 1, 4, 5120, "xla"), ("tpu", 1, 16, 96, "xla"), ("tpu", 1, 16, 128, "kernel")])
def test_the_route_is_a_function_of_what_a_call_can_observe(backend, devices, states,
                                                            channels, route):
    assert ps.choose_route(16384, channels, states, backend, devices) == route


def test_tiles_and_vmem_by_hand():
    """At the cell's 5120 channels of 16 states in chunks of 128: tiles of 1024."""
    assert ps.choose_tile(5120) == 1024 and ps.choose_tile(384) == 384
    assert ps.choose_tile(640) == 640 and ps.choose_tile(1152) == 384
    assert ps.choose_tile(2176) == 128 and ps.choose_tile(96) is None
    assert ps.CHUNK % ps.UNROLL == 0
    rows, stacked = 128 * 512, (2 * 16 + 8) * 128 * 4
    small = 6 * 16 * 512 * 4 + 8 * 512 * 4
    assert ps.tile_vmem_bytes(128, 512, 16, backward=False) == (
        2 * (3 * rows * 2 + stacked) + small + 4 * rows * 4)
    bwd = ps.tile_vmem_bytes(128, 512, 16, backward=True)
    assert bwd == (2 * (5 * rows * 2 + 2 * stacked) + small + 5 * rows * 4
                   + 2 * 128 * 16 * 512 * 4)
    assert 9 * 2 ** 20 < bwd < 12 * 2 ** 20 < ps.VMEM_CAP
    assert 20 * 2 ** 20 < ps.tile_vmem_bytes(128, 1024, 16, backward=True) < 24 * 2 ** 20
