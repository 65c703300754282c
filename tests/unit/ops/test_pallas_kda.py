"""The Kimi Delta Attention core (PR 68; ``ops/transformer/pallas_kda.py``) on the
CPU: the XLA route (the chunked form under a scan over chunks) at two chunk sizes
and the Pallas pair in interpret mode against the recurrence a token at a time,
in the forward and every gradient, at a tolerance a bfloat16 carried state fails;
a decay strong enough that ``exp(-G)`` over a chunk overflows float32; no gradient
across a document's start; the route and the launches' names.

Shapes by grid steps: the smallest case that crosses a chunk's and a span's
border, has a document border inside a sub-block, one on a chunk's first row and
two in a row, and two heads (40 rows in chunks of 16, sub-blocks of 8, spans of
32: two grid steps a head, the last chunk partial)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import pallas_kda as kda

F32 = jnp.float32
NAMES = ("q", "k", "v", "g", "beta")
ROWS, HEADS, HEAD = 40, 2, 16
#: a row's first position, a border inside a sub-block, one on a chunk's first
#: row, two in a row
FIRSTS = (0, 5, 16, 21, 22, 37)
#: float32 against float32: a state carried in bfloat16 is 3e-3 off (below)
TOLERANCE = 2e-5


def operands(strength=1.0, firsts=FIRSTS, seed=0):
    """``strength``: the log-decays' size a token (30: -30 to -100 a token and
    channel, -500 and more over a chunk of 16, far past float32's exp(88))."""
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    first = jnp.zeros((ROWS,), jnp.int32).at[jnp.asarray(firsts)].set(1)
    wide = lambda key: jax.random.normal(key, (ROWS, HEADS * HEAD), F32)
    g = -strength * jax.nn.softplus(wide(k[3]))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (ROWS, HEADS)))
    return (wide(k[0]), wide(k[1]), wide(k[2]), g, beta, first), wide(k[6])


def out_and_grads(fn, args, w):
    """The output and every gradient from ONE jitted program."""
    def both(*x):
        o, pull = jax.vjp(lambda *x: fn(*x, args[5]), *x)
        return o, pull(w.astype(o.dtype))
    return jax.jit(both)(*args[:5])


ROUTES = {
    "by_token": kda.kda_by_token,
    "xla16": lambda *a: kda.kda_xla(*a, chunk=16, sub=8),
    "xla32": lambda *a: kda.kda_xla(*a, chunk=32, sub=8),
    "kernel": lambda *a: kda.kda_kernel(*a, chunk=16, sub=8, span=32, interpret=True),
}


@pytest.fixture(scope="module", params=[1.0, 30.0], ids=["gentle", "strong"])
def results(request):
    args, w = operands(request.param)
    return {name: out_and_grads(fn, args, w) for name, fn in ROUTES.items()}


def agree(got, want, what, tolerance=TOLERANCE):
    np.testing.assert_allclose(got[0], want[0], atol=tolerance * float(jnp.abs(want[0]).max()),
                               err_msg=what)
    for name, g, h in zip(NAMES, got[1], want[1]):
        assert g.shape == h.shape and g.dtype == h.dtype, name
        assert bool(jnp.isfinite(g).all()), f"{what}: d{name}"
        np.testing.assert_allclose(g, h, atol=tolerance * max(1.0, float(jnp.abs(h).max())),
                                   err_msg=f"{what}: d{name}")


@pytest.mark.parametrize("route", ["xla16", "xla32", "kernel"])
def test_a_route_is_the_recurrence_forward_and_in_every_gradient(results, route):
    """Two chunk sizes of the XLA route and the kernel pair: the mathematics does
    not depend on the chunk, under gentle decays and under decays whose sum over a
    chunk no float32 ``exp(-G)`` could hold."""
    agree(results[route], results["by_token"], route)


def test_a_naive_fold_of_the_decay_overflows_where_the_routes_do_not():
    """What the sub-blocks' reference points are for: ``exp(G_t) exp(-G_s)`` over a
    chunk of the strong case is ``0 x inf``."""
    (_, _, _, g, _, _), _ = operands(30.0)
    G = jnp.cumsum(g[:16], axis=0)
    assert float(G[-1].min()) < -400.0
    assert not bool(jnp.isfinite(jnp.exp(G[-1]) * jnp.exp(-G[-1])).all())


def test_a_bfloat16_carried_state_fails_the_tolerance(results):
    """What the tolerance is for: the recurrence with its state rounded to bfloat16
    after every token is two orders over it."""
    args, _ = operands(1.0)
    q, k, v, g, beta, first = args
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + kda.NORM_EPS)

    def step(S, xs):
        q, k, v, g, b, first = xs
        q, k, g, v = (x.reshape(HEADS, HEAD) for x in (q, k, g, v))
        q, k = unit(q) * HEAD ** -0.5, unit(k)
        S = jnp.exp(g)[:, :, None] * jnp.where(first > 0, 0.0, S)
        S = S + b[:, None, None] * k[:, :, None] * (v - jnp.einsum("hk,hkv->hv", k, S))[:, None, :]
        S = S.astype(jnp.bfloat16).astype(F32)
        return S, jnp.einsum("hk,hkv->hv", q, S).reshape(-1)
    _, low = jax.lax.scan(step, jnp.zeros((HEADS, HEAD, HEAD), F32), (q, k, v, g, beta, first))
    want = results["by_token"][0]
    assert float(jnp.abs(low - want).max()) > 50 * TOLERANCE * float(jnp.abs(want).max())


@pytest.mark.parametrize("route", ["xla16", "kernel"])
def test_no_gradient_crosses_a_documents_start(route):
    """A weight on the rows of the document that starts at row 21 alone: every
    gradient is EXACTLY zero on the rows before it (a reset is written into the
    masks: not even a rounding's gradient crosses), and the output there does not
    change with what stands in front."""
    args, _ = operands(1.0, firsts=(0, 5, 21))
    w = jnp.zeros((ROWS, HEADS * HEAD), F32).at[21:].set(1.0)
    o, grads = out_and_grads(ROUTES[route], args, w)
    for name, g in zip(NAMES, grads):
        assert float(jnp.abs(g[:21]).max()) == 0.0, name
        assert float(jnp.abs(g[21:]).max()) > 0.0, name
    other = tuple(a.at[:21].multiply(-2.0) if a.dtype == F32 and a.ndim == 2 and a is not args[3]
                  else a for a in args)
    o2, _ = out_and_grads(ROUTES[route], other, w)
    np.testing.assert_array_equal(o[21:], o2[21:])


@pytest.mark.parametrize("route", ["xla16", "xla32", "kernel"])
def test_keys_that_resemble_one_another_stay_finite(route):
    """What the solve's order is for: every row's key a common vector and a little
    noise, ``beta`` near 1, next to no decay (a frequent token repeated; SiLU
    outputs share a direction). The inverse's nilpotent series over a whole chunk
    has terms of 1e30 there and read NaN in the chip's first window; by blocks the
    routes stay at the recurrence (the system itself is ill-conditioned: 2e-3)."""
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    common = jnp.tile(jax.random.normal(k[0], (1, HEADS * HEAD)), (ROWS, 1))
    keys = common + 0.05 * jax.random.normal(k[1], (ROWS, HEADS * HEAD))
    args = (keys, keys, jax.random.normal(k[2], (ROWS, HEADS * HEAD)),
            jnp.full((ROWS, HEADS * HEAD), -1e-4), jnp.full((ROWS, HEADS), 0.999),
            jnp.zeros((ROWS,), jnp.int32).at[0].set(1))
    w = jnp.ones((ROWS, HEADS * HEAD), F32)
    agree(out_and_grads(ROUTES[route], args, w), out_and_grads(kda.kda_by_token, args, w),
          route, tolerance=2e-3)


def test_the_route_is_a_pure_function_and_the_launches_are_named():
    assert kda.choose_route(32768, 32, 128, 128, "tpu", 1) == "kernel"
    assert kda.choose_route(32768, 32, 128, 128, "tpu", 4) == "xla"     # a mesh
    assert kda.choose_route(32768, 32, 128, 128, "cpu", 1) == "xla"
    assert kda.choose_route(64, 2, 16, 16, "tpu", 1) == "xla"           # no whole lane tile
    # the XLA route's chunk from a sequence's rows: a short one still crosses chunks
    assert [kda.xla_chunk(r) for r in (32768, 256, 255, 64, 8)] == [
        (64, 16), (64, 16), (32, 16), (16, 8), (8, 4)]
    args, w = operands()
    text = jax.jit(lambda *a: jax.vjp(
        lambda *x: kda.kda_kernel(*x, a[5], chunk=16, sub=8, span=32, interpret=True),
        *a[:5])[1](a[6])).lower(*args, w).as_text(debug_info=True)
    assert "kda_fwd" in text and "kda_bwd" in text
    with pytest.raises(ValueError, match="whole chunks"):
        kda.kda_kernel(*args, chunk=16, sub=8, span=40, interpret=True)
