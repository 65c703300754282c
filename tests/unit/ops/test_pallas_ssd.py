"""The state-space-duality core (PR 65; ``ops/transformer/pallas_ssd.py``) on the
CPU: the XLA route (the chunked form under a scan over chunks) and the Pallas
pair in interpret mode against the recurrence a token at a time, in the forward
and every gradient, at a tolerance a bfloat16 carried state fails; no gradient
across a document's start; the route, the tile and the launches' names.

Shapes by grid steps: the smallest case that crosses a chunk border, has a
document border inside a chunk and one ON a chunk's first row, two tiles of
heads, two heads to a lane block and two groups (40 rows in chunks of 16: three
grid steps a tile, the last chunk partial)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import pallas_ssd as ssd

F32 = jnp.float32
NAMES = ("a", "dt", "A", "B", "C", "D")
ROWS, HEADS, HEAD, STATES, GROUPS, CHUNK = 40, 4, 64, 16, 2, 16
#: a row's first position, a border inside a chunk, one on a chunk's first row,
#: two in a row
FIRSTS = (0, 5, 16, 21, 22, 37)
#: float32 against float32: a state carried in bfloat16 is 4e-3 off (below)
TOLERANCE = 2e-5


def operands(seed=0, firsts=FIRSTS):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    first = jnp.zeros((ROWS,), jnp.int32).at[jnp.asarray(firsts)].set(1)
    return (jax.random.normal(k[0], (ROWS, HEADS * HEAD), F32),
            jax.nn.softplus(jax.random.normal(k[1], (ROWS, HEADS)) - 1.0),
            -jnp.exp(jax.random.uniform(k[2], (HEADS,), minval=0.0, maxval=2.0)),
            jax.random.normal(k[3], (ROWS, GROUPS * STATES), F32) * 0.5,
            jax.random.normal(k[4], (ROWS, GROUPS * STATES), F32) * 0.5,
            jax.random.normal(k[5], (HEADS,)), first), jax.random.normal(k[6], (ROWS, HEADS * HEAD))


def out_and_grads(fn, args, w):
    """The output and every gradient from ONE jitted program."""
    def both(*x):
        m, pull = jax.vjp(lambda *x: fn(*x, args[6], GROUPS), *x)
        return m, pull(w.astype(m.dtype))
    return jax.jit(both)(*args[:6])


ROUTES = {
    "by_token": ssd.ssd_by_token,
    "xla": lambda *a: ssd.ssd_xla(*a, chunk=CHUNK),
    "kernel": lambda *a: ssd.ssd_kernel(*a, chunk=CHUNK, tile=2, interpret=True),
}


@pytest.fixture(scope="module")
def results():
    args, w = operands()
    return {name: out_and_grads(fn, args, w) for name, fn in ROUTES.items()}


def agree(got, want, what, tolerance=TOLERANCE):
    np.testing.assert_allclose(got[0], want[0], atol=tolerance * float(jnp.abs(want[0]).max()),
                               err_msg=what)
    for name, g, h in zip(NAMES, got[1], want[1]):
        assert g.shape == h.shape and g.dtype == h.dtype, name
        np.testing.assert_allclose(g, h, atol=tolerance * max(1.0, float(jnp.abs(h).max())),
                                   err_msg=f"{what}: d{name}")


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_a_route_is_the_recurrence_forward_and_in_every_gradient(results, route):
    agree(results[route], results["by_token"], route)


def test_a_bfloat16_carried_state_fails_the_tolerance(results):
    """What the tolerance is for: the recurrence with its state rounded to
    bfloat16 after every token is two orders over it."""
    args, _ = operands()
    a, dt, A, B, C, D, first = args
    heads = lambda x: jnp.repeat(x.reshape(GROUPS, STATES), HEADS // GROUPS, axis=0)

    def step(h, xs):
        a, dt, B, C, first = xs
        a = a.reshape(HEADS, HEAD)
        h = jnp.where(first > 0, 0.0, h)
        h = jnp.exp(dt * A)[:, None, None] * h + (dt[:, None] * a)[:, :, None] * heads(B)[:, None]
        h = h.astype(jnp.bfloat16).astype(F32)
        return h, (jnp.einsum("hpn,hn->hp", h, heads(C)) + D[:, None] * a).reshape(-1)
    _, low = jax.jit(lambda: jax.lax.scan(
        step, jnp.zeros((HEADS, HEAD, STATES), F32), (a, dt, B, C, first)))()
    want = results["by_token"][0]
    off = float(jnp.abs(low - want).max() / jnp.abs(want).max())
    assert off > 50 * TOLERANCE, off
    for route in ("xla", "kernel"):
        got = float(jnp.abs(results[route][0] - want).max() / jnp.abs(want).max())
        assert got < TOLERANCE < off, (route, got)


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_no_gradient_crosses_a_documents_start(route):
    """The loss reads rows 22.. alone (a document that starts at 22, inside the
    second chunk): nothing before row 22 has a gradient, in any operand a row
    has, and the rows from 22 on do."""
    args, _ = operands(seed=3)
    w = jnp.zeros((ROWS, HEADS * HEAD)).at[22:].set(1.0)
    _, (da, ddt, _, dB, dC, _) = out_and_grads(ROUTES[route], args, w)
    for name, g in (("a", da), ("dt", ddt), ("B", dB), ("C", dC)):
        assert not np.any(np.asarray(g[:22])), name
        assert np.any(np.asarray(g[22:])), name


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_strong_decays_across_a_documents_start_stay_finite(route):
    """dt 1 under A -16: a chunk's cumulative log-decay reaches -250, and a
    document's restarts at its first row, so the difference a masked-away pair
    would take is +200: every exponent is clamped before it is taken (the chip's
    first limits run read NaN in eight seeds of ten without it; these tests'
    other operands decay too gently to overflow). The decays' gradient, which
    this case makes 0 against large terms that cancel, holds the common
    tolerance: the kernel sums each pair's flow into its query's row and out of
    its key's in float32 (a difference of row sums through ``m`` read 3e-4 here)."""
    (a, _, _, B, C, D, first), w = operands(seed=5)
    args = (a, jnp.full((ROWS, HEADS), 1.0), jnp.full((HEADS,), -16.0), B, C, D, first)
    got = out_and_grads(ROUTES[route], args, w)
    for x in (got[0],) + tuple(got[1]):
        assert bool(jnp.all(jnp.isfinite(x)))
    agree(got, out_and_grads(ROUTES["by_token"], args, w), route)


def test_the_launches_carry_their_own_names_and_residuals():
    args, _ = operands()
    text = str(jax.make_jaxpr(jax.grad(lambda a: jnp.sum(ssd.ssd_kernel(
        a, *args[1:], GROUPS, chunk=CHUNK, tile=2, interpret=True))))(args[0]))
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert "ssd_m" in text and "ssd_state" in text
    # neither the states a token nor a decay mask a head and chunk in the program
    assert f"f32[{ROWS},{HEADS},{HEAD},{STATES}]" not in text
    assert f"f32[{HEADS},{CHUNK},{CHUNK}]" not in text
    xla = str(jax.make_jaxpr(jax.grad(lambda a: jnp.sum(ssd.ssd_xla(
        a, *args[1:], GROUPS, chunk=CHUNK))))(args[0]))
    assert "pallas_call" not in xla and f"f32[{ROWS},{HEADS},{HEAD},{STATES}]" not in xla


@pytest.mark.parametrize("backend,devices,heads,head,states,groups,route", [
    ("tpu", 1, 64, 64, 128, 1, "kernel"), ("cpu", 1, 64, 64, 128, 1, "xla"),
    ("tpu", 4, 64, 64, 128, 1, "xla"), ("tpu", 1, 64, 64, 16, 1, "xla"),
    ("tpu", 1, 64, 64, 128, 8, "kernel"), ("tpu", 1, 64, 64, 128, 64, "xla"),
    ("tpu", 1, 64, 48, 128, 1, "xla"), ("tpu", 1, 24, 128, 128, 1, "kernel"),
    ("tpu", 1, 24, 256, 128, 1, "xla")])
def test_the_route_is_a_function_of_what_a_call_can_observe(backend, devices, heads, head,
                                                            states, groups, route):
    assert ssd.choose_route(32768, heads, head, states, groups, backend, devices) == route


def test_tiles_by_hand():
    """Whole groups' heads, whole 128-lane blocks: 16 of the cell's 64 heads of
    64; a group of 8 is one tile; a group of ONE head of 64 fills no block."""
    assert ssd.choose_tile(64, 64, 1) == ssd.TILE_HEADS == 16
    assert ssd.choose_tile(64, 64, 8) == 8 and ssd.choose_tile(64, 64, 16) == 4
    assert ssd.choose_tile(64, 64, 64) is None and ssd.choose_tile(64, 48, 1) is None
    assert ssd.choose_tile(24, 128, 1) == 12 and ssd.choose_tile(6, 128, 2) == 3
    assert ssd.xla_chunk(256) == ssd.XLA_CHUNK and ssd.xla_chunk(16) == 16
    fwd = ssd.tile_vmem_bytes(256, 16, 64, 128, backward=False)
    bwd = ssd.tile_vmem_bytes(256, 16, 64, 128, backward=True)
    assert 4 * 2 ** 20 < fwd < bwd < 32 * 2 ** 20 < ssd.VMEM_CAP
    with pytest.raises(ValueError, match="no tile of heads"):
        ssd.ssd_kernel(*operands()[0][:6], operands()[0][6], GROUPS, tile=3, interpret=True)
