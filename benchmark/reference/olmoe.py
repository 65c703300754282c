"""OLMoE in plain ``jax.numpy``: forward pass, loss (with both router
losses) and gradient norm, read from a configuration file with Hugging
Face's key names (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``num_experts``, ``num_experts_per_tok``, ``norm_topk_prob``,
``rms_norm_eps``, ``rope_theta``, ``vocab_size``).

Written from the published description (Muennighoff et al. 2024, "OLMoE:
Open Mixture-of-Experts Language Models", arXiv:2409.02060, and Hugging
Face's ``modeling_olmoe.py``): token embedding, no position table; blocks
of RMSNorm -> causal attention with QK-norm (an RMSNorm with its own gain
over the WHOLE projected query vector ``[heads x head_dim]`` and key
vector, before the head split and the rotary embedding) and rotary
positions ("rotate half") -> RMSNorm -> a sparse mixture of experts; a
final RMSNorm and an output head of its own. No biases anywhere.

The mixture of experts, per token: router logits ``h @ W_r`` over all
``num_experts``; a softmax over all of them; the ``num_experts_per_tok``
largest probabilities (the lowest index wins a tie), used AS THEY ARE
when ``norm_topk_prob`` is false (OLMoE) and divided by their sum when
true; the output is the weighted sum of the chosen experts' gated SiLU
MLPs ``down(silu(gate(h)) * up(h))``. No token is dropped and there is
no capacity. It is computed the obvious way: every expert on every
token, under the 0/1 mask of the tokens that chose it, in passes of a
few experts so that a published-width layer fits beside its weights.
Nothing is sorted, gathered or grouped.

The two router losses of the paper (section 3), per layer over the
``T`` tokens of the batch: the load-balancing loss ``E x sum_e f_e x
P_e`` with ``f_e`` the share of the ``T x k`` assignments that chose
expert ``e`` and ``P_e`` the mean router probability of ``e``, and the
router z-loss ``mean_t logsumexp(logits_t)^2``. The objective is the
next-token cross-entropy plus ``(c_b x sum_l balance_l + c_z x sum_l
z_l) / L``; the coefficients come from the configuration file
(``assumed.router_aux_loss_coef``, ``assumed.router_z_loss_coef``).
Hugging Face's ``load_balancing_loss_func`` pools the tokens of all
layers before the product; the paper and this file take it per layer.

float32 throughout, ``jax.default_matmul_precision("highest")``, no
kernels, no cache. It imports nothing of the program under test and
nothing of the benchmark. It exports what every reference file exports
(benchmark/reference/gpt2.py lists them). Departures are that file's, for
the same reasons: random weights from a seed, norm gains near 1 and not
at 1 (the QK-norm gains near 2, so that attention scores have a standard
deviation of about 4: peaked heads, as trained models have; with QK-norm
the projections' scale no longer sets it), ``jax.checkpoint`` around
each block and each pass of experts in ``loss_and_gradient`` (memory
only), and the ``fp8`` control that rounds every matmul operand, the
router's too, to float8_e4m3fn.

Weights are one flat dict, per-layer arrays stacked on a leading axis
(hd = hidden_size / num_attention_heads, I = intermediate_size, the width
of ONE expert, E = num_experts)::

    embed [V,H]  head [H,V]  norm_f [H]  norm1 norm2 [L,H]
    wq [L,H,nh*hd]  wk wv [L,H,nkv*hd]  wo [L,nh*hd,H]
    q_norm [L,nh*hd]  k_norm [L,nkv*hd]
    router [L,H,E]  w_gate w_up [L,E,H,I]  w_down [L,E,I,H]
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

Weights = Dict[str, jax.Array]

#: the largest [experts of a pass, tokens, I] float32 intermediate, in elements
PASS_ELEMENTS = 2 ** 26


def sizes(config: dict) -> dict:
    """The published keys this file reads, under short names."""
    assumed = config.get("assumed", {})
    s = dict(V=int(config["vocab_size"]), H=int(config["hidden_size"]),
             L=int(config["num_hidden_layers"]), I=int(config["intermediate_size"]),
             E=int(config["num_experts"]), k=int(config["num_experts_per_tok"]),
             renorm=bool(config["norm_topk_prob"]),
             nh=int(config["num_attention_heads"]),
             nkv=int(config["num_key_value_heads"]),
             eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
             c_balance=float(assumed["router_aux_loss_coef"]),
             c_z=float(assumed["router_z_loss_coef"]))
    s["hd"] = s["H"] // s["nh"]
    return s


def key_of(seed: int):
    """PRNG key of a seed of any size: the bits above 31 are folded in,
    not dropped."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(key, config: dict, dtype=jnp.bfloat16) -> Weights:
    """Random weights from ``key_of(seed)`` in the dtype they are trained
    from. Pure and jittable with the key traced."""
    s = sizes(config)
    V, H, L, I, E = s["V"], s["H"], s["L"], s["I"], s["E"]
    q_out, kv_out = s["nh"] * s["hd"], s["nkv"] * s["hd"]
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dtype)

    resid = 0.02 / math.sqrt(2 * L)
    return {
        "embed": normal((V, H), 0.02), "head": normal((H, V), 0.02),
        "norm_f": 1.0 + normal((H,), 0.05),
        "norm1": 1.0 + normal((L, H), 0.05), "norm2": 1.0 + normal((L, H), 0.05),
        "wq": normal((L, H, q_out), 0.02), "wk": normal((L, H, kv_out), 0.02),
        "wv": normal((L, H, kv_out), 0.02), "wo": normal((L, q_out, H), 2 * resid),
        # gains near 2: scores q.k/sqrt(hd) then have a standard deviation of 4
        "q_norm": 2.0 + normal((L, q_out), 0.05),
        "k_norm": 2.0 + normal((L, kv_out), 0.05),
        "router": normal((L, H, E), 0.02),
        "w_gate": normal((L, E, H, I), 0.02), "w_up": normal((L, E, H, I), 0.02),
        "w_down": normal((L, E, I, H), resid),
    }


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rotate(x, theta):
    """Rotary positions 0..S-1 on x [B,S,n,hd]: the pair (i, i + hd/2) is
    turned by the angle position x theta^(-2i/hd)."""
    S, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs     # [S, half]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


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


def route(h, w_router, s: dict, control=None):
    """h [T,H] -> (weight [T,E]: the routing weight of each token for each
    expert, 0 where it did not choose it; balance loss; z-loss)."""
    T, E, k = h.shape[0], s["E"], s["k"]
    logits = rounded(h, control) @ rounded(w_router, control)          # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(probs, k)           # the lowest index wins a tie
    if s["renorm"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(chosen, E, dtype=jnp.float32)             # [T,k,E]
    weight = jnp.einsum("tk,tke->te", top, onehot)
    f = jnp.sum(onehot, axis=(0, 1)) / (T * k)     # share of the T x k assignments
    p = jnp.mean(probs, axis=0)
    balance = E * jnp.sum(f * p)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return weight, balance, z


def experts(h, weight, lw, s: dict, control=None, checkpoint: bool = False):
    """sum_e weight[:, e] x down_e(silu(gate_e(h)) x up_e(h)): every expert
    on every token under the mask, a few experts a pass."""
    T, E = h.shape[0], s["E"]
    per = max(1, min(E, PASS_ELEMENTS // (T * s["I"])))
    while E % per:
        per -= 1
    r = lambda t: rounded(t, control)

    def one_pass(acc, xs):
        wg, wu, wd, w = xs                       # [per,H,I] [per,H,I] [per,I,H] [per,T]
        mid = r(jax.nn.silu(jnp.einsum("th,ehf->etf", r(h), r(wg)))
                * jnp.einsum("th,ehf->etf", r(h), r(wu)))
        y = jnp.einsum("etf,efh->eth", mid, r(wd))
        return acc + jnp.einsum("eth,et->th", y, w), None

    if checkpoint:  # departure: memory only, same arithmetic
        one_pass = jax.checkpoint(one_pass)
    group = lambda a: a.reshape((E // per, per) + a.shape[1:])
    out, _ = jax.lax.scan(one_pass, jnp.zeros_like(h),
                          (group(lw["w_gate"]), group(lw["w_up"]),
                           group(lw["w_down"]), group(weight.T)))
    return out


def block(x, lw, s: dict, control=None, checkpoint: bool = False):
    """One pre-norm block. x [B,S,H]; lw: this layer's slice of the weights.
    Returns (x', balance loss, z-loss) of the layer."""
    B, S, H = x.shape
    nh, nkv, hd = s["nh"], s["nkv"], s["hd"]
    r = lambda t: rounded(t, control)
    h = r(rms_norm(x, lw["norm1"], s["eps"]))
    # QK-norm: over the whole projected vector, before the head split and rope
    q = rms_norm(h @ r(lw["wq"]), lw["q_norm"], s["eps"])
    k = rms_norm(h @ r(lw["wk"]), lw["k_norm"], s["eps"])
    q = rotate(q.reshape(B, S, nh, hd), s["theta"])
    k = rotate(k.reshape(B, S, nkv, hd), s["theta"])
    v = (h @ r(lw["wv"])).reshape(B, S, nkv, hd)
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqnd,bknd->bnqk", r(q), r(k)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("bnqk,bknd->bqnd", r(probs), r(v)).reshape(B, S, nh * hd)
    x = x + r(a) @ r(lw["wo"])
    h = rms_norm(x, lw["norm2"], s["eps"]).reshape(B * S, H)
    weight, balance, z = route(h, lw["router"], s, control)
    m = experts(h, weight, lw, s, control, checkpoint)
    return x + m.reshape(B, S, H), balance, z


_LAYER_KEYS = ("norm1", "norm2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
               "router", "w_gate", "w_up", "w_down")


def _cast(w: Weights, dtype) -> Weights:
    return {k: v.astype(dtype) for k, v in w.items()}


def forward_with_router_losses(w: Weights, ids, config: dict, *, control=None,
                               checkpoint: bool = False):
    """(float32 logits [B,S,V], per-layer balance losses [L], z-losses [L])."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        w = _cast(w, jnp.float32)
        x = w["embed"][ids]
        layers = {k: w[k] for k in _LAYER_KEYS}

        def fn(x, lw):
            x, balance, z = block(x, lw, s, control, checkpoint)
            return x, (balance, z)
        if checkpoint:  # departure: memory only, same arithmetic
            fn = jax.checkpoint(fn)
        x, (balance, z) = jax.lax.scan(fn, x, layers)
        x = rounded(rms_norm(x, w["norm_f"], s["eps"]), control)
        return x @ rounded(w["head"], control), balance, z


def forward(w: Weights, ids, config: dict, **kw):
    """float32 logits [B,S,V] of token ids [B,S]."""
    return forward_with_router_losses(w, ids, config, **kw)[0]


def next_token_loss(w: Weights, ids, config: dict, **kw):
    """The training objective: mean cross-entropy of predicting ids[:, 1:]
    from ids[:, :-1], plus both router losses, each under its coefficient
    and averaged over the layers."""
    s = sizes(config)
    logits, balance, z = forward_with_router_losses(w, ids, config, **kw)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return (jnp.mean(nll)
            + (s["c_balance"] * jnp.sum(balance) + s["c_z"] * jnp.sum(z)) / s["L"])


def loss_and_gradient(w: Weights, ids, config: dict, *, control=None):
    """(loss, l2 norm of the gradient over every weight, sign of every
    gradient element as int8 under the weights' names)."""
    w32 = _cast(w, jnp.float32)
    loss, g = jax.value_and_grad(
        lambda p: next_token_loss(p, ids, config, control=control, checkpoint=True))(w32)
    sq = sum(jnp.sum(jnp.square(v)) for v in g.values())
    return loss, jnp.sqrt(sq), {k: jnp.sign(v).astype(jnp.int8) for k, v in g.items()}


def matmul_params(config: dict) -> int:
    """Parameters that multiply each token: the attention kernels, the
    router, the ``num_experts_per_tok`` experts a token is routed to (NOT
    the ``num_experts`` that exist) and the output head. The embedding is
    a lookup; the norm gains, QK-norm's among them, are scalings: not
    counted."""
    s = sizes(config)
    attn = s["H"] * (s["nh"] + 2 * s["nkv"]) * s["hd"] + s["nh"] * s["hd"] * s["H"]
    moe = s["H"] * s["E"] + s["k"] * 3 * s["H"] * s["I"]
    return s["L"] * (attn + moe) + s["H"] * s["V"]


def train_flops_per_token(config: dict, seq: int) -> float:
    """FLOPs one trained token REQUIRES at sequence length ``seq`` (the
    contract is benchmark/reference/gpt2.py's): 6 per matmul parameter a
    token meets, plus QK^T and PV of every query head over the causal half
    of the sequence, 6 L (nh hd) S. OLMoE-1B-7B at 2 layers and S = 4096:
    6 x (2 x (16,777,216 + 131,072 + 50,331,648) + 103,022,592)
    + 6 x 2 x 2048 x 4096 = 1.525 GFLOP."""
    s = sizes(config)
    return 6.0 * matmul_params(config) + 6.0 * s["L"] * s["nh"] * s["hd"] * seq
