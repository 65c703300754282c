"""EvaByte (``model_type`` ``evabyte``; EvaByte/EvaByte, 6.5 B, byte-level) in
plain ``jax.numpy``: forward pass, the eight-head next-byte loss and its
gradient, read from a configuration file with Hugging Face's key names.

Written from the configuration's keys and the published method (EVA,
"Efficient Attention via Control Variates", arXiv:2302.04542: an exact set of
nearby keys and one control-variate summary a chunk of the far ones under ONE
normaliser); what neither settles is marked + and stands in the file's
``assumed`` in the same words. H ``hidden_size``, nh heads of hd = H / nh with
as many key heads, W ``window_size``, c ``chunk_size`` (c divides W), P
``num_pred_heads``, V ``vocab_size``; no bias on any matmul. A row holds L
byte ids x.

1. ``h = E[x]`` (no scaling).
2. Layer: ``h = h + EVA(N_1(h))``, ``h = h + W_down(silu(W_gate n) * W_up n)``
   with ``n = N_2(h)``; ``N(x) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + g)``
   (``norm_add_unit_offset``). (``fp32_skip_add``: the sum in float32; all of
   this file is float32.)
3. ``EVA(u)``: ``q, k, v = u Wq, u Wk, u Wv`` per head; rotary positions on q
   and k by position i (theta ``rope_theta``, all hd dims, the pair (j, j +
   hd/2) turned by ``i x theta^(-2j/hd)`` +). Two learned vectors a head and
   layer, ``phi`` and ``mu`` in R^hd (+ the released model's ``adaptive_phi``
   and ``adaptive_mu_k``).
   Summaries (+): for every chunk g (positions g c .. g c + c - 1)
   ``w_j = softmax over j in g of (k_j . phi)``, ``kbar_g = sum_j w_j k_j +
   mu``, ``vbar_g = sum_j w_j v_j``.
   What query i sees (``visible``): the exact key j iff ``j // W == i // W``
   and ``j <= i``; the summary g iff ``(g c) // W < i // W`` (+ every chunk of
   every window that is complete before i's own; windows and chunks are
   counted from the ROW's start; no document ids enter).
   ONE softmax over both:
   ``o_i = (sum_j e^{s q_i.k_j} v_j + sum_g e^{s q_i.kbar_g} vbar_g)
         / (sum_j e^{s q_i.k_j} + sum_g e^{s q_i.kbar_g})``, s = hd^-1/2;
   ``EVA(u) = o Wo``.
4. ``logits_i = N_f(h_i) W_head`` viewed ``[P, V]``: head m predicts byte
   ``x_{i+1+m}`` (+). ``loss = mean over m of the mean over the positions i
   with i + 1 + m < L of CE(logits_i[m], x_{i+1+m})`` (+ equal weights).

float32 throughout, ``jax.default_matmul_precision("highest")``, no kernels,
no cache. It imports nothing of the program under test and nothing of the
benchmark, and exports what every reference file exports
(benchmark/reference/gpt2.py lists them; ``forward`` is the first head's
logits), ``forward_heads``, ``eva_pairs`` and ``attention_pair_flops``. Departures: random weights from a seed (norm gains
g near 0, the query and key projections wide enough that scores have a
standard deviation near 4, ``phi`` wide enough that a chunk's softmax is not
flat and ``mu`` a visible part of a summary key, residual projections at
GPT-2's 1/sqrt(2 L), the embedding at unit RMS); memory only: a layer's heads
a group at a time, blocks of
queries against their own window's keys and the row's summaries (the mask is
still built from positions over what is scored), rows in blocks through the
MLP and the head's loss, ``jax.checkpoint`` around layers and blocks, and in
``loss_and_gradient`` the gradient taken a layer at a time from the last to
the first (so that one layer's float32 weights and gradient are held, not
all: the numbers are ``jax.grad(next_token_loss)``'s); and the ``fp8``
control, which rounds every matmul operand to float8_e4m3fn.

Weights are one flat dict, per-layer arrays stacked on a leading axis (n =
layers, I = ``intermediate_size``)::

    embed [V,H]  head [H,P*V]  norm_f [H]
    norm1 norm2 [n,H]  wq wk wv wo [n,H,H]  phi mu [n,nh,hd]
    w_gate w_up [n,H,I]  w_down [n,I,H]
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

Weights = Dict[str, jax.Array]

#: queries of a block of the attention scores; rows of a block through the
#: MLP and through the head's loss; the most elements of one ``[rows, heads x
#: hd]`` projection before a layer's heads are taken a group at a time
#: (memory only)
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
HEAD_GROUP_ELEMENTS = 2 ** 25


def sizes(config: dict) -> dict:
    """The published keys this file reads, under short names."""
    nh = int(config["num_attention_heads"])
    if (config.get("attention_class", "eva") != "eva" or config.get("num_chunks") is not None
            or int(config.get("num_key_value_heads", nh)) != nh
            or config.get("rope_scaling") is not None
            or config.get("hidden_act", "silu") != "silu" or config.get("attention_bias")
            or config.get("tie_word_embeddings")
            or not config.get("norm_add_unit_offset", True)):
        raise ValueError("EVA attention by chunk size, as many key heads as query "
                         "heads, plain rope, SiLU, no bias, an untied head, "
                         "norms with a unit offset")
    W, c = int(config["window_size"]), int(config["chunk_size"])
    if W % c:
        raise ValueError(f"a window ({W}) of whole chunks ({c})")
    H = int(config["hidden_size"])
    return dict(V=int(config["vocab_size"]), H=H, L=int(config["num_hidden_layers"]),
                I=int(config["intermediate_size"]), nh=nh, hd=H // nh, W=W, c=c,
                P=int(config.get("num_pred_heads", 1)),
                eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]))


def key_of(seed: int):
    """PRNG key of a seed of any size: the bits above 31 are folded in,
    not dropped."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(key, config: dict, dtype=jnp.bfloat16) -> Weights:
    """Random weights from ``key_of(seed)`` in the dtype they are trained
    from. Pure and jittable with the key traced."""
    s = sizes(config)
    H, V, I, n, nh, hd, P = s["H"], s["V"], s["I"], s["L"], s["nh"], s["hd"], s["P"]
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    resid = 0.02 / math.sqrt(2 * n)
    return {
        "embed": normal((V, H), 1.0), "head": normal((H, P * V), 0.02),
        "norm_f": normal((H,), 0.05),
        "norm1": normal((n, H), 0.05), "norm2": normal((n, H), 0.05),
        # a normed row has unit RMS: q and k elements near 2, scores near 4
        "wq": normal((n, H, H), 2.0 / math.sqrt(H)),
        "wk": normal((n, H, H), 2.0 / math.sqrt(H)),
        "wv": normal((n, H, H), 0.02), "wo": normal((n, H, H), resid),
        # k . phi near 1.5: a chunk's softmax leans on a few of its keys
        "phi": normal((n, nh, hd), 1.5 / (2.0 * math.sqrt(hd))),
        "mu": normal((n, nh, hd), 0.5),
        "w_gate": normal((n, H, I), 0.02), "w_up": normal((n, H, I), 0.02),
        "w_down": normal((n, I, H), resid),
    }


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + g)


def rotate(x, at, theta):
    """Rotary positions ``at`` [S] on x [B,S,n,hd]: the pair (j, j + hd/2)
    is turned by the angle position x theta^(-2j/hd)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = at.astype(jnp.float32)[:, None] * freqs               # [S, half]
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


def in_blocks(fn, x, block: int, checkpoint: bool):
    """``fn`` over the leading axis of ``x`` (an array or a tuple of them)
    in blocks (memory only: the same arithmetic, a block's intermediates
    at a time)."""
    n = jax.tree.leaves(x)[0].shape[0]
    if not checkpoint or n <= block or n % block:
        return fn(x)
    split = lambda a: a.reshape((n // block, block) + a.shape[1:])
    out = jax.lax.map(jax.checkpoint(fn), jax.tree.map(split, x))
    return out.reshape((n,) + out.shape[2:])


def visible(q_at, k_at, k_summary, W: int, c: int):
    """Equation 3's mask, densely: whether each key is visible to each query
    (broadcasting). ``k_at``: an exact key's position, a summary's chunk;
    ``k_summary``: which of the two the key is."""
    exact = (k_at // W == q_at // W) & (k_at <= q_at)
    far = (k_at * c) // W < q_at // W
    return jnp.where(k_summary, far, exact)


def summaries(k, v, phi, mu, c: int, control=None):
    """Equation 3's summaries: k, v [B,S,nh,hd] -> kbar, vbar [B,S/c,nh,hd]."""
    B, S, nh, hd = k.shape
    kc, vc = (t[:, :S // c * c].reshape(B, S // c, c, nh, hd) for t in (k, v))
    w = jax.nn.softmax(jnp.einsum("bgchd,hd->bgch", rounded(kc, control),
                                  rounded(phi, control)), axis=2)
    wr = rounded(w, control)
    return (jnp.einsum("bgch,bgchd->bghd", wr, rounded(kc, control)) + mu,
            jnp.einsum("bgch,bgchd->bghd", wr, rounded(vc, control)))


def attention(x, lw, s: dict, control=None, checkpoint: bool = False):
    """The attention sub-block's ``o Wo`` on the normed input x [B,S,H]: every
    head at once, or (memory only: a head reads no other head, and ``o Wo`` is
    the sum over the heads of each head's part) a group of heads at a time."""
    B, S, H = x.shape
    nh, hd = s["nh"], s["hd"]
    r = lambda t: rounded(t, control)
    h = r(x)
    wq, wk, wv, wo = (r(lw[name]) for name in ("wq", "wk", "wv", "wo"))
    groups = next(g for g in range(1, nh + 1) if nh % g == 0 and (
        not checkpoint or g == nh or B * S * (nh // g) * hd <= HEAD_GROUP_ELEMENTS))
    if groups == 1:
        return heads_attention(h, wq, wk, wv, wo, lw["phi"], lw["mu"], s, control, checkpoint)
    wide = nh // groups * hd
    columns = lambda w: w.reshape(H, groups, wide).swapaxes(0, 1)
    by_group = lambda a: a.reshape((groups, nh // groups) + a.shape[1:])

    def one_group(acc, ws):
        return acc + heads_attention(h, *ws, s, control, checkpoint), None
    acc, _ = jax.lax.scan(jax.checkpoint(one_group), jnp.zeros_like(x),
                          (columns(wq), columns(wk), columns(wv),
                           wo.reshape(groups, wide, H), by_group(lw["phi"]),
                           by_group(lw["mu"])))
    return acc


def heads_attention(h, wq, wk, wv, wo, phi, mu, s: dict, control=None,
                    checkpoint: bool = False):
    """Some heads' part of ``o Wo``: h [B,S,H] (rounded under the control, as
    the weights are), wq wk wv [H, n hd], wo [n hd, H], phi mu [n, hd]."""
    B, S, _ = h.shape
    hd, W, c = s["hd"], s["W"], s["c"]
    nh = wq.shape[1] // hd
    r = lambda t: rounded(t, control)
    at = jnp.arange(S)
    q, k, v = ((h @ w).reshape(B, S, nh, hd) for w in (wq, wk, wv))
    q, k = rotate(q, at, s["theta"]), rotate(k, at, s["theta"])
    kbar, vbar = summaries(k, v, phi, mu, c, control)
    G = kbar.shape[1]
    kr, vr, kbr, vbr = r(k), r(v), r(kbar), r(vbar)

    def scores_to_values(qb, q_at, kb, vb, k_at, k_summary):
        """A block of queries [B,n,nh,hd] against the keys kb [B,m,nh,hd]
        (exact ones and summaries side by side): one softmax a query over
        what ``visible`` leaves."""
        sc = jnp.einsum("bqhd,bkhd->bhqk", r(qb), kb) * hd ** -0.5
        seen = visible(q_at[:, None], k_at[None, :], k_summary[None, :], W, c)
        sc = jnp.where(seen[None, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", r(jax.nn.softmax(sc, axis=-1)), vb)

    n, span = QUERY_BLOCK, min(W, S)
    is_summary = lambda m: jnp.arange(m + G) >= m
    if not (checkpoint and S > n and span % n == 0 and S % span == 0):
        cat = lambda exact, far: jnp.concatenate([exact, far], axis=1)
        a = scores_to_values(q, at, cat(kr, kbr), cat(vr, vbr),
                             jnp.concatenate([at, jnp.arange(G)]), is_summary(S))
    else:
        # a block of n queries lies in one window: it is scored against that
        # window's exact keys and every summary of the row
        def block(qs):
            qb, p = qs                                   # p: its first position
            first = p // span * span
            cat = lambda exact, far: jnp.concatenate(
                [jax.lax.dynamic_slice_in_dim(exact, first, span, 1), far], axis=1)
            return scores_to_values(
                qb, p + jnp.arange(n), cat(kr, kbr), cat(vr, vbr),
                jnp.concatenate([first + jnp.arange(span), jnp.arange(G)]),
                is_summary(span))
        a = jax.lax.map(jax.checkpoint(block),
                        (q.reshape(B, S // n, n, nh, hd).swapaxes(0, 1),
                         jnp.arange(0, S, n)))
        a = a.swapaxes(0, 1)
    return r(a.reshape(B, S, nh * hd)) @ wo


def mlp(n2d, lw, control=None, checkpoint: bool = False):
    """The gated SiLU MLP over rows n2d [T,H], a block of rows at a time."""
    r = lambda t: rounded(t, control)
    wg, wu, wd = r(lw["w_gate"]), r(lw["w_up"]), r(lw["w_down"])

    def rows(h):
        h = r(h)
        return r(jax.nn.silu(h @ wg) * (h @ wu)) @ wd
    return in_blocks(rows, n2d, TOKEN_BLOCK, checkpoint)


def layer(x, lw, s: dict, control=None, checkpoint: bool = False):
    """One layer on x [B,S,H]; lw: this layer's slice."""
    B, S, H = x.shape
    ck = jax.checkpoint if checkpoint else (lambda f: f)   # memory only
    x = x + ck(lambda x: attention(rms_norm(x, lw["norm1"], s["eps"]), lw, s,
                                   control, checkpoint))(x)
    return x + ck(lambda x: mlp(rms_norm(x, lw["norm2"], s["eps"]).reshape(B * S, H),
                                lw, control, checkpoint))(x).reshape(B, S, H)


_LAYER_KEYS = ("norm1", "norm2", "wq", "wk", "wv", "wo", "phi", "mu",
               "w_gate", "w_up", "w_down")
_HEAD_KEYS = ("norm_f", "head")


def _f32(w: Weights, keys, i=None) -> Weights:
    take = (lambda a: a) if i is None else (lambda a: a[i])
    return {k: take(w[k]).astype(jnp.float32) for k in keys}


def stream(w: Weights, ids, config: dict, *, control=None, checkpoint: bool = False):
    """The stream after the last layer [B,S,H] (equations 1-3)."""
    s = sizes(config)
    x = w["embed"].astype(jnp.float32)[ids]
    for i in range(s["L"]):
        fn = lambda x, lw: layer(x, lw, s, control, checkpoint)
        if checkpoint:  # departure: memory only, same arithmetic
            fn = jax.checkpoint(fn)
        x = fn(x, _f32(w, _LAYER_KEYS, i))
    return x


def head_logits(hw: Weights, x, s: dict, control=None):
    """Equation 4's logits, ``[..., P, V]``; hw: ``norm_f`` and ``head``."""
    flat = rounded(rms_norm(x, hw["norm_f"], s["eps"]), control) @ rounded(hw["head"], control)
    return flat.reshape(flat.shape[:-1] + (s["P"], s["V"]))


def forward_heads(w: Weights, ids, config: dict, *, control=None,
                  checkpoint: bool = False):
    """float32 logits [B,S,P,V]: head m at position i predicts byte i + 1 + m."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        x = stream(w, ids, config, control=control, checkpoint=checkpoint)
        return head_logits(_f32(w, _HEAD_KEYS), x, s, control)


def forward(w: Weights, ids, config: dict, *, control=None, checkpoint: bool = False):
    """The contract's logits [B,S,V]: the FIRST head's, position i's next
    byte (what plain generation reads; ``forward_heads`` has all P)."""
    return forward_heads(w, ids, config, control=control, checkpoint=checkpoint)[:, :, 0]


def head_loss(hw: Weights, x, ids, s: dict, control=None, checkpoint: bool = False):
    """Equation 4's loss from the final stream x [B,S,H]."""
    B, S, H = x.shape
    P = s["P"]
    # position i's P targets, x_{i+1} .. x_{i+P}; past the row's end: none
    at = jnp.arange(S)[:, None] + 1 + jnp.arange(P)[None, :]            # [S,P]
    has = at < S
    targets = jnp.take(ids, jnp.minimum(at, S - 1), axis=1)             # [B,S,P]
    weight = has / jnp.maximum(jnp.sum(has, axis=0), 1) / (B * P)       # [S,P]
    weight = jnp.broadcast_to(weight[None], (B, S, P))

    def weighted_nll(block):
        xb, tb, wb = block
        logp = jax.nn.log_softmax(head_logits(hw, xb, s, control), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0] * wb,
                        axis=-1)
    total = in_blocks(weighted_nll, (x.reshape(-1, H), targets.reshape(-1, P),
                                     weight.reshape(-1, P)), TOKEN_BLOCK, checkpoint)
    return jnp.sum(total)


def next_token_loss(w: Weights, ids, config: dict, *, control=None,
                    checkpoint: bool = False):
    """Equation 4's loss: the mean over the P heads of each head's mean
    cross-entropy over the positions that have its target."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        x = stream(w, ids, config, control=control, checkpoint=checkpoint)
        return head_loss(_f32(w, _HEAD_KEYS), x, ids, s, control, checkpoint)


def loss_and_gradient(w: Weights, ids, config: dict, *, control=None):
    """(loss, l2 norm of the gradient over every weight, sign of every
    gradient element as int8 under the weights' names). The gradient of
    ``next_token_loss`` by the chain rule a layer at a time, last to first
    (memory only: one layer's float32 weights and gradient at a time)."""
    s = sizes(config)
    signs, sq = {}, []

    def keep(g: Weights, i=None):
        for name, v in g.items():
            sq.append(jnp.sum(jnp.square(v)))
            signs.setdefault(name, {})[i] = jnp.sign(v).astype(jnp.int8)

    with jax.default_matmul_precision("highest"):
        embed = w["embed"].astype(jnp.float32)
        x, embedded = jax.vjp(lambda e: e[ids], embed)
        inputs = []
        for i in range(s["L"]):
            inputs.append(x)
            x = layer(x, _f32(w, _LAYER_KEYS, i), s, control, True)
        loss, (g_head, dx) = jax.value_and_grad(
            lambda hw, x: head_loss(hw, x, ids, s, control, True), argnums=(0, 1))(
                _f32(w, _HEAD_KEYS), x)
        keep(g_head)
        for i in reversed(range(s["L"])):
            _, back = jax.vjp(lambda x, lw: layer(x, lw, s, control, True),
                              inputs[i], _f32(w, _LAYER_KEYS, i))
            dx, g = back(dx)
            keep(g, i)
        keep({"embed": embedded(dx)[0]})
    out = {name: (by[None] if None in by else jnp.stack([by[i] for i in range(s["L"])]))
           for name, by in signs.items()}
    return loss, jnp.sqrt(sum(sq)), out


def eva_pairs(config: dict, seq: int) -> dict:
    """The (query, key) pairs ONE head has to multiply over a row of
    ``seq`` positions, exact integers: ``exact`` the (query, exact key) pairs
    (a query at offset r of its window sees r + 1), ``summary`` the (query,
    summary) pairs (a query of window w sees the w W / c summaries of the
    windows before it). Nothing masked is counted. At 32,768 under W 2048, c
    16: 16 x 2048 x 2049 / 2 = 33,570,816 and 2048 x 128 x 120 = 31,457,280."""
    s = sizes(config)
    W, per = s["W"], s["W"] // s["c"]
    whole, rest = divmod(int(seq), W)
    exact = whole * W * (W + 1) // 2 + rest * (rest + 1) // 2
    summary = W * per * whole * (whole - 1) // 2 + rest * per * whole
    return {"exact": exact, "summary": summary}


def attention_pair_flops(config: dict) -> dict:
    """FLOPs ONE visible (query, key) pair of ONE head costs each kernel of
    the attention core: the forward's two matmuls (QK^T, PV), 4 hd; the
    fused backward's five (the scores again, dV, dP, dK, dQ), 10 hd
    (``attn_eva_roofline`` multiplies them by the pairs that exist)."""
    s = sizes(config)
    return {"forward": 4.0 * s["hd"], "backward": 10.0 * s["hd"], "heads": s["nh"]}


def train_flops_per_token(config: dict, seq: int) -> float:
    """FLOPs one trained token REQUIRES at sequence length ``seq`` (the
    contract is benchmark/reference/gpt2.py's): 6 per matmul parameter (the
    four attention projections and the gated MLP of every layer, the head of
    P x V outputs once), plus QK^T and PV of every head over the REAL pairs
    of a row (``eva_pairs``: exact keys and summaries, nothing masked), 12
    hd a pair, plus the summaries' own products (k . phi and the two weighted
    sums: 6 hd a key and head forward, 18 trained). The embedding is a
    lookup and the norm gains are scalings: not counted."""
    s = sizes(config)
    H, I, nh, hd = s["H"], s["I"], s["nh"], s["hd"]
    params = s["L"] * (4 * H * H + 3 * H * I) + H * s["P"] * s["V"]
    pairs = eva_pairs(config, seq)
    per_token = (pairs["exact"] + pairs["summary"]) / float(seq)
    return 6.0 * params + s["L"] * nh * hd * (12.0 * per_token + 18.0)
