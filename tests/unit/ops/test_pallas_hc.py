"""The streams' coefficients in one pass (``pallas_hc``; PR 56), on the CPU: the
factored product ``rsqrt(mean(x^2) + eps) (x Phi)`` and its hand-written
backward, on the XLA route and on the kernel route in interpret mode, against
the plain formula ``(x rsqrt(mean(x^2) + eps)) Phi`` at ``HIGHEST`` under plain
autodiff: ``m`` and, through the model's ``_hc_coefficients``, ``pre``, ``post``,
``res`` and the gradients to X, ``phi``, ``alpha`` and ``bias``; on float32 and
bfloat16 streams, a float32 and a bfloat16 Phi, rows that are a multiple of
the tile and rows that are not; the route by backend / type / shape / devices
and the records saying so; and the scope ``hc/coeff`` on the forward's and the
backward's operations."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import models
from deepspeed_tpu.ops.transformer import pallas_hc

F32, BF16 = jnp.float32, jnp.bfloat16
EPS = 1e-6


def plain(x, phi):
    x = x.astype(F32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS)
    return jnp.einsum("bsk,kc->cbs", x, phi.astype(F32), precision=jax.lax.Precision.HIGHEST)


def operands(seq, K, x_dtype, phi_dtype, seed=0):
    """(x [2, seq, K], phi [K, 24], a cotangent for m)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = (jax.random.normal(ks[0], (2, seq, K), F32) * 3.0).astype(x_dtype)
    phi = (jax.random.normal(ks[1], (K, 24), F32) * 0.05).astype(phi_dtype)
    return x, phi, jax.random.normal(ks[2], (24, 2, seq), F32)


def close(got, want, ulps=0.0):
    """Within 2e-6 of ``want``'s largest element, plus ``ulps`` of the result's
    own type at each element (a gradient leaves in the streams' or Phi's type)."""
    got32, want = np.asarray(got.astype(F32)), np.asarray(want.astype(F32))
    room = 2e-6 * np.abs(want).max() + ulps * float(jnp.finfo(got.dtype).eps) * np.abs(want)
    return bool((np.abs(got32 - want) <= room).all())


# (seq, route, tiles): rows of 64 in tiles of 32 (two row tiles, two tiles of
# K); 40 is no multiple of 16: one tile of 40 in interpret mode, XLA else
CASES = [(64, "xla", None), (40, "xla", None),
         (64, "kernel", pallas_hc.Tiles(32, 128, 1 << 20)), (40, "kernel", None)]


@pytest.mark.parametrize("phi_dtype", [F32, BF16], ids=["phi32", "phi16"])
@pytest.mark.parametrize("x_dtype", [F32, BF16], ids=["x32", "x16"])
@pytest.mark.parametrize("seq,route,tiles", CASES,
                         ids=[f"{r}-{route}" for r, route, _ in CASES])
def test_the_factored_product_is_the_plain_formula(seq, route, tiles, x_dtype, phi_dtype):
    if route == "kernel" and x_dtype != BF16:
        with pytest.raises(NotImplementedError, match="bfloat16"):
            pallas_hc.coeff_product(*operands(seq, 256, x_dtype, phi_dtype)[:2], EPS,
                                    "kernel")
        return
    x, phi, w = operands(seq, 256, x_dtype, phi_dtype)
    ours = lambda x, phi: pallas_hc.coeff_product(x, phi, EPS, route, tiles=tiles)
    assert ours(x, phi).shape == (24, 2, seq) and ours(x, phi).dtype == F32
    assert close(ours(x, phi), plain(x, phi))
    got = jax.grad(lambda x, phi: jnp.sum(w * ours(x, phi)), (0, 1))(x, phi)
    want = jax.grad(lambda x, phi: jnp.sum(w * plain(x, phi)), (0, 1))(x, phi)
    for g, wnt in zip(got, want):
        assert g.dtype == wnt.dtype and g.shape == wnt.shape
        assert close(g, wnt, ulps=0.0 if g.dtype == F32 else 1.0)


def test_a_float32_operand_is_not_rounded_to_one_bfloat16():
    """Phi and ``s g`` enter the one-pass products as three parts each: a Phi
    whose bfloat16 rounding is 0.4 % off still gives the float32 result."""
    x, phi, w = operands(64, 256, BF16, F32, seed=3)
    rounded = plain(x, phi.astype(BF16))
    assert not close(rounded, plain(x, phi))
    for route in ("xla", "kernel"):
        assert close(pallas_hc.coeff_product(x, phi, EPS, route), plain(x, phi))


def test_bfloat16_streams_at_a_size_the_cpus_dot_refuses():
    """Under ``jit`` at 256 x 256 the CPU's dot has no bf16 x bf16 -> f32: the
    XLA route's products go in as float32 there, forward and backward."""
    x, phi, w = operands(256, 256, BF16, BF16, seed=5)
    f = lambda x, phi: jnp.sum(w * pallas_hc.coeff_product(x, phi, EPS, "xla"))
    want = jax.grad(lambda x, phi: jnp.sum(w * plain(x, phi)), (0, 1))(x, phi)
    for g, wnt in zip(jax.jit(jax.grad(f, (0, 1)))(x, phi), want):
        assert close(g, wnt, ulps=1.0)


@pytest.fixture(scope="module", params=[F32, BF16], ids=["streams32", "streams16"])
def model(request):
    return models.xing4_model("xing4-tiny", dtype=request.param, remat=False)


def layer(model, phi_dtype, seed=0, tokens=(2, 20)):
    """(hc, X) with coefficients visibly off a fresh layer's; 40 positions."""
    n, H = model.config.residual_streams, model.config.hidden_size
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    hc = {"phi": (jax.random.normal(ks[0], (n * H, n * (n + 2)), F32) * 0.05).astype(phi_dtype),
          "bias": jax.random.normal(ks[1], (n * (n + 2),), F32),
          "alpha": jnp.asarray([0.5, 0.7, 0.3], F32)}
    return hc, (jax.random.normal(ks[2], tokens + (n * H,), F32) * 2.0).astype(model.config.dtype)


@pytest.mark.parametrize("phi_dtype", [F32, BF16], ids=["phi32", "phi16"])
def test_the_models_coefficients_and_their_gradients(model, phi_dtype):
    """``_hc_coefficients`` (the shipped route) against the plain formula in
    front of the model's own sigmoids and Sinkhorn rounds."""
    hc, X = layer(model, phi_dtype)

    def reference(hc, X):
        return model._hc_mixes(hc, plain(X, hc["phi"]))

    got, want = model._hc_coefficients(hc, X), reference(hc, X)
    for g, w in zip(got, want):
        assert g.shape == w.shape and close(g, w)
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    weights = [jax.random.normal(k, w.shape, F32) for k, w in zip(ks, want)]
    loss = lambda f: lambda hc, X: sum(jnp.sum(w * o) for w, o in zip(weights, f(hc, X)))
    got = jax.grad(loss(model._hc_coefficients), (0, 1))(hc, X)
    want = jax.grad(loss(reference), (0, 1))(hc, X)
    for name in ("phi", "alpha", "bias"):
        assert np.abs(np.asarray(want[0][name], np.float32)).max() > 1e-3
        assert close(got[0][name], want[0][name],
                     ulps=0.0 if got[0][name].dtype == F32 else 1.0), name
    assert close(got[1], want[1], ulps=0.0 if X.dtype == F32 else 1.0)


@pytest.mark.parametrize("seq,K,dtype,backend,devices,route,tm", [
    (8192, 14336, BF16, "tpu", 1, "kernel", 256),     # the cell
    (8192, 14336, BF16, "cpu", 1, "xla", None),       # no chip
    (8192, 14336, F32, "tpu", 1, "xla", None),        # an fp32 job: HIGHEST stays
    (8192, 14336, jnp.float16, "tpu", 1, "xla", None),
    (8192, 14336, BF16, "tpu", 4, "xla", None),       # a partitioned program
    (8192, 14400, BF16, "tpu", 1, "xla", None),       # K off the lanes
    (8200, 14336, BF16, "tpu", 1, "xla", None),       # a row off the tile
    (384, 256, BF16, "tpu", 1, "kernel", 128),
    (640, 1024, BF16, "tpu", 1, "kernel", 128),
])
def test_route_table(seq, K, dtype, backend, devices, route, tm):
    assert pallas_hc.choose_route(seq, K, dtype, backend, devices) == route
    tiles = pallas_hc.choose_tiles(seq, K)
    if route == "kernel":
        assert tiles.tm == tm and seq % tiles.tm == 0 and K % tiles.tk == 0
        assert tiles.tk % 128 == 0 and tiles.vmem_limit_bytes <= pallas_hc.VMEM_CAP


def test_the_records_say_the_route(monkeypatch):
    """``attention_records`` carries the route and the row tile the step's
    sub-layers take: XLA here, the kernel where the backend is a TPU and the
    streams are bfloat16 at whole tiles."""
    m16 = models.xing4_model("xing4-tiny", dtype=BF16, remat=False)
    assert m16.attention_records()[0]["hc"]["route"] is None        # no shape yet
    assert m16.attention_records(2, 64)[0]["hc"]["route"] == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hc = m16.attention_records(2, 128)[0]["hc"]
    assert (hc["route"], hc["tile_rows"]) == ("kernel", 128) == m16._hc_route(128, BF16)
    assert m16.attention_records(2, 64)[0]["hc"]["route"] == "xla"      # rows of 64
    m32 = models.xing4_model("xing4-tiny", dtype=F32, remat=False)
    hc = m32.attention_records(2, 64)[0]["hc"]
    assert (hc["route"], hc["tile_rows"]) == ("xla", None)


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_forward_and_backward_carry_the_scope(route, monkeypatch):
    """Every product of the pass, forward and backward, has ``hc/coeff`` in its
    ``op_name``: the readers of ``train_hc_coeff_ms`` and ``train_hc_ms`` find
    an operation by nothing else."""
    model = models.xing4_model("xing4-tiny", dtype=BF16, remat=False)
    monkeypatch.setattr(type(model), "_hc_route", lambda self, seq, dtype: (route, None))
    hc, X = layer(model, BF16, tokens=(2, 32))

    def loss(hc, X):
        pre, post, res = model._hc_coefficients(hc, X)
        return jnp.sum(pre) + jnp.sum(post * post) + jnp.sum(res * res)

    text = jax.jit(jax.grad(loss, (0, 1))).lower(hc, X).as_text(debug_info=True)
    names = set(re.findall(r'loc\("(jit\(loss\)/[^"]*)"', text))
    products = {n for n in names if "dot_general" in n}
    forward = {n for n in products if "/jvp(hc)/coeff/" in n}
    backward = {n for n in products if "/transpose(jvp(hc))/coeff/" in n}
    assert forward and backward and products == forward | backward
    assert any("rsqrt" in n and "/jvp(hc)/coeff/" in n for n in names)
