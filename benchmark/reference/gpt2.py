"""GPT-2 in plain ``jax.numpy``: forward pass, loss and gradient norm.

Written from the published description (Radford et al. 2019, and OpenAI's
``gpt-2/src/model.py``): learned token and position embeddings, pre-LN
blocks of causal multi-head attention and a 4x MLP with the tanh GELU, a
final LayerNorm and the tied embedding as output head. float32 throughout,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching tricks. It imports nothing from ``deepspeed_tpu``.

Departures, each noted where it is made:

- ``make_weights`` draws random weights from a seed (the benchmark serves
  random weights); normal(0, 0.02), residual projections scaled by
  1/sqrt(2 L) as in the GPT-2 paper, small non-zero biases and LayerNorm
  gains near 1 so that no term of the mathematics can be dropped unseen.
  The query and key projections are drawn wider, so that attention scores
  have a standard deviation of about 4 (peaked heads, as trained models
  have), and the attention output projection twice as wide as the MLP's:
  with the paper's 0.02 every softmax is nearly uniform, averages hundreds
  of keys and hides the precision of the cached keys and values.
- ``loss_and_gradient`` wraps each block in ``jax.checkpoint`` so that the
  float32 gradient of a published-width model fits beside nothing else on
  one 16 GB chip. Recomputation repeats the same float32 operations, so
  the numbers are those of the plain backward pass.
- ``control`` lets the harness compute this same reference in a LOWER
  precision than a configuration states, as the control that ``correct``
  has to reject: ``"fp8"`` rounds every matmul operand to float8_e4m3fn
  under a per-tensor scale (what an fp8 matmul path would do). The
  reference proper is ``control=None``.

Weights are one flat dict; per-layer arrays are stacked on a leading layer
axis. Names are this file's own::

    wte [V,H]  wpe [P,H]  lnf_g lnf_b [H]
    ln1_g ln1_b ln2_g ln2_b [L,H]
    wq wk wv wo [L,H,H]   bq bk bv bo [L,H]
    w_in [L,H,4H] b_in [L,4H]   w_out [L,4H,H] b_out [L,H]
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

Weights = Dict[str, jax.Array]

LN_EPS = 1e-5  # config.json: layer_norm_epsilon


def sizes(config: dict) -> dict:
    """The published keys this file reads, under short names."""
    return dict(V=int(config["vocab_size"]), P=int(config["n_positions"]),
                H=int(config["n_embd"]), L=int(config["n_layer"]),
                nh=int(config["n_head"]))


def key_of(seed: int):
    """PRNG key of a seed of any size: the bits above 31 are folded in,
    not dropped."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(key, config: dict, dtype=jnp.bfloat16) -> Weights:
    """Random weights from ``key_of(seed)`` in the dtype they are served
    in. Pure and jittable with the key traced: the harness calls it once,
    on the device, and one compiled program serves every seed."""
    s = sizes(config)
    V, P, H, L = s["V"], s["P"], s["H"], s["L"]
    keys = iter(jax.random.split(key, 24))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dtype)

    resid = 0.02 / math.sqrt(2 * L)
    qk = math.sqrt(4.0 / H)   # scores q.k/sqrt(hd) then have std 4
    w: Weights = {
        "wte": normal((V, H), 0.02), "wpe": normal((P, H), 0.01),
        "lnf_g": 1.0 + normal((H,), 0.05), "lnf_b": normal((H,), 0.02),
        "ln1_g": 1.0 + normal((L, H), 0.05), "ln1_b": normal((L, H), 0.02),
        "ln2_g": 1.0 + normal((L, H), 0.05), "ln2_b": normal((L, H), 0.02),
        "wq": normal((L, H, H), qk), "bq": normal((L, H), 0.02),
        "wk": normal((L, H, H), qk), "bk": normal((L, H), 0.02),
        "wv": normal((L, H, H), 0.02), "bv": normal((L, H), 0.02),
        "wo": normal((L, H, H), 2 * resid), "bo": normal((L, H), 0.02),
        "w_in": normal((L, H, 4 * H), 0.02), "b_in": normal((L, 4 * H), 0.02),
        "w_out": normal((L, 4 * H, H), resid), "b_out": normal((L, H), 0.02),
    }
    return w


def layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def gelu_new(x):
    """GPT-2's GELU: 0.5 x (1 + tanh(sqrt(2/pi) (x + 0.044715 x^3)))."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def rounded(t, control):
    """The control's rounding of one matmul operand (identity for the
    reference proper). Values stay float32; only their precision drops."""
    if control is None:
        return t
    if control != "fp8":
        raise ValueError(f"unknown control {control!r}")
    scale = jnp.max(jnp.abs(t)) / 448.0 + 1e-30  # e4m3fn's largest
    low = (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    # straight through: the backward pass sees the rounded VALUES and is
    # itself computed in float32, the kindest form of a low-precision path
    return t + jax.lax.stop_gradient(low - t)


def block(x, lw, nh: int, control=None):
    """One pre-LN block. x [B,S,H]; lw: this layer's slice of the weights."""
    B, S, H = x.shape
    hd = H // nh
    r = lambda t: rounded(t, control)
    h = r(layer_norm(x, lw["ln1_g"], lw["ln1_b"]))
    q = (h @ r(lw["wq"]) + lw["bq"]).reshape(B, S, nh, hd)
    k = (h @ r(lw["wk"]) + lw["bk"]).reshape(B, S, nh, hd)
    v = (h @ r(lw["wv"]) + lw["bv"]).reshape(B, S, nh, hd)
    scores = jnp.einsum("bqnd,bknd->bnqk", r(q), r(k)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("bnqk,bknd->bqnd", r(probs), r(v)).reshape(B, S, H)
    x = x + r(a) @ r(lw["wo"]) + lw["bo"]
    h = r(layer_norm(x, lw["ln2_g"], lw["ln2_b"]))
    m = r(gelu_new(h @ r(lw["w_in"]) + lw["b_in"]))
    return x + m @ r(lw["w_out"]) + lw["b_out"]


_LAYER_KEYS = ("ln1_g", "ln1_b", "ln2_g", "ln2_b", "wq", "bq", "wk", "bk",
               "wv", "bv", "wo", "bo", "w_in", "b_in", "w_out", "b_out")


def _cast(w: Weights, dtype) -> Weights:
    return {k: v.astype(dtype) for k, v in w.items()}


def forward(w: Weights, ids, nh: int, *, control=None,
            checkpoint: bool = False):
    """float32 logits [B,S,V] of token ids [B,S]."""
    with jax.default_matmul_precision("highest"):
        w = _cast(w, jnp.float32)
        S = ids.shape[1]
        x = w["wte"][ids] + w["wpe"][:S][None]
        layers = {k: w[k] for k in _LAYER_KEYS}
        fn = (lambda x, lw: (block(x, lw, nh, control), None))
        if checkpoint:  # departure: memory only, same arithmetic
            fn = jax.checkpoint(fn)
        x, _ = jax.lax.scan(fn, x, layers)
        x = rounded(layer_norm(x, w["lnf_g"], w["lnf_b"]), control)
        return x @ rounded(w["wte"], control).T


def next_token_loss(w: Weights, ids, nh: int, **kw):
    """Mean cross-entropy of predicting ids[:, 1:] from ids[:, :-1]."""
    logits = forward(w, ids, nh, **kw)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


def loss_and_gradient(w: Weights, ids, nh: int, *, control=None):
    """(loss, l2 norm of the gradient over every weight, sign of every
    gradient element as int8 under the weights' names). The output head is
    tied, so ``wte`` gets one gradient from both of its uses. The signs
    are the direction of steepest descent weight by weight: what a first
    optimizer step has to follow (an eighth of the gradient's bytes, so
    they can wait on the device while the program takes its step)."""
    w32 = _cast(w, jnp.float32)
    loss, g = jax.value_and_grad(
        lambda p: next_token_loss(p, ids, nh, control=control, checkpoint=True))(w32)
    sq = sum(jnp.sum(jnp.square(v)) for v in g.values())
    return loss, jnp.sqrt(sq), {k: jnp.sign(v).astype(jnp.int8) for k, v in g.items()}
