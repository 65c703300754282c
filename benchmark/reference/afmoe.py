"""Arcee's Trinity-Mini (``model_type`` ``afmoe``) in plain ``jax.numpy``:
forward pass, training loss and gradient, read from a configuration file
with Hugging Face's key names, as ONE chip's share of a deployment in which
several chips share each layer.

Written from the configuration's keys and, for the four things it has no key
for (marked +, the file's ``assumed`` says the same), from the published
``afmoe`` modelling code. ``N_*`` is RMSNorm (eps ``rms_norm_eps``) with a
gain; no bias on any matmul; H ``hidden_size``, nh query heads over nkv key
heads of hd = ``head_dim`` (nh x hd is NOT H).

1. Embedding: ``x = Emb[token] * sqrt(H)`` (+ ``mup_enabled`` scales the
   embedding's output and nothing else).
2. Attention sub-block: ``u = N_in(x)``; ``q = u Wq`` [nh x hd], ``k = u Wk``,
   ``v = u Wv`` [nkv x hd], ``g = u Wg`` [nh x hd] (+ the gate);
   ``q, k = N_q(q), N_k(k)`` per head, one gain of hd for all heads (+);
   on a ``sliding_attention`` layer ONLY, rotary positions on q and k (theta
   ``rope_theta``, the pair (i, i + hd/2) turned by position x
   theta^(-2i/hd)); a ``full_attention`` layer has no positional term (+).
   ``a = softmax(q k^T / sqrt(hd) + mask) v`` with key head ``h // (nh / nkv)``
   for query head h; the mask is causal, inside a packed document (a
   document ends WITH its separator token; ``assumed.separator``, none = a
   row is one document), and on a sliding layer ``i - j < sliding_window``.
   ``y = (a * sigmoid(g)) Wo``; ``x = x + N_post_attn(y)`` (+ sandwich: the
   branch's OUTPUT is normed before it is added).
3. MLP sub-block: ``x = x + N_post_mlp(F(N_pre_mlp(x)))``. The first
   ``num_dense_layers`` layers: ``F`` = ``down(silu(gate(u)) * up(u))`` of
   ``intermediate_size``. The others: ``s = sigmoid(u Wr)`` over all
   PUBLISHED experts; chosen = the ``num_experts_per_tok`` largest of ``s + b``
   (the lowest index wins a tie); ``w_e = s_e / (sum_chosen s + 1e-20) x
   route_scale`` (``route_norm``); ``F(u) = Shared(u) + sum over (chosen AND
   held) w_e E_e(u)``, ``E_e`` a gated SiLU MLP of ``moe_intermediate_size``,
   ``Shared`` one of ``num_shared_experts`` times that width, unweighted.
   ``b`` gets no gradient (``stop_gradient``): after a step load moves it by
   ``load_balance_coeff``, up for an expert that drew fewer assignments than
   the mean and down for one that drew more (``bias_after_step``). No
   auxiliary loss: the configuration has no coefficient for one.
4. ``logits = N_f(x) W_head``, untied; the loss is the mean next-token
   cross-entropy over the row's positions that have a next token.

THE SHARE. The file's ``share`` block says how many chips share a layer and
what was published; ``num_experts`` and ``vocab_size`` of the file are what
THIS chip holds (rank ``assumed.share_rank``, 0 unless given: experts
``rank x held .. (rank + 1) x held - 1``). The router keeps its published
width; the held experts are computed the obvious way, every one of them on
every token under the mask of chosen AND held, a few a pass; what the absent
experts would add is left out and that partial result goes on to the next
layer; nothing stands in for the other chips. The vocabulary is the file's:
embedding, head and loss are over the slice. ``layer_types`` is read from
its start, ``num_hidden_layers`` entries (a cut in depth keeps the published
list whole). Without a ``share`` block every expert is held.

float32 throughout, ``jax.default_matmul_precision("highest")``, no
kernels, no cache. It imports nothing of the program under test and nothing
of the benchmark, and exports what every reference file exports
(benchmark/reference/gpt2.py lists them) and ``expert_product_flops_per_row``.
Departures: random weights from a seed (norm gains near 1, the two
post-norms' near 1/sqrt(2 L): the scale GPT-2's initialisation gives a
residual branch, which such a norm erases from the projection in front of
it; the embedding drawn at H^(-1/2) under ``mup_enabled``, so that times
sqrt(H) the stream starts at unit RMS at any width; the QK-norm gains near 2 so that heads are peaked as trained ones are, a
small random router bias so that it decides some choices); memory only: ``jax.checkpoint``
around layers, passes of experts and blocks of queries, tokens in blocks
through the MLPs and the head's loss, and a sliding layer's block of queries
multiplies the ``sliding_window`` + block keys it can see and no others (a
whole ``[32, 16384, 16384]`` float32 score tensor is 34 GB); and the ``fp8``
control, which rounds every matmul operand to float8_e4m3fn.

Weights are one flat dict. Per-layer arrays are stacked on a leading axis,
under two prefixes: ``d_`` the leading dense layers [D, ..], none the expert
layers [L - D, ..] (I = moe_intermediate_size, E = published experts, Eh =
held, ns = shared experts)::

    embed [V,H]  head [H,V]  norm_f [H]
    <p>norm1 <p>norm1_post <p>norm2 <p>norm2_post [n,H]
    <p>wq <p>wg [n,H,nh*hd]  <p>wk <p>wv [n,H,nkv*hd]  <p>wo [n,nh*hd,H]
    <p>q_norm <p>k_norm [n,hd]
    d_gate d_up [D,H,F]  d_down [D,F,H]
    router [n,H,E]  router_bias [n,E]  w_gate w_up [n,Eh,H,I]
    w_down [n,Eh,I,H]  s_gate s_up [n,H,ns*I]  s_down [n,ns*I,H]
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

Weights = Dict[str, jax.Array]

#: the largest [experts of a pass, tokens, I] float32 intermediate, in elements
PASS_ELEMENTS = 2 ** 26
#: queries of a block of the attention scores; tokens of a block through an
#: MLP or the head (memory only)
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
SLIDING = "sliding_attention"


def sizes(config: dict) -> dict:
    """The published keys this file reads, under short names."""
    a = config.get("assumed", {})
    share = config.get("share")
    held = int(config["num_experts"])
    published = int(share["published"].get("num_experts", held)) if share else held
    rank = int(a.get("share_rank", 0))
    L = int(config["num_hidden_layers"])
    kinds = tuple(config["layer_types"])[:L]
    groups = ("n_group", "topk_group", "num_expert_groups", "num_limited_groups")
    if any(config.get(k, 1) != 1 for k in groups):
        raise ValueError("one group of experts")
    if (config.get("rope_scaling") is not None or config.get("hidden_act", "silu") != "silu"
            or config.get("score_func", "sigmoid") != "sigmoid" or len(kinds) != L):
        raise ValueError("plain rope, SiLU, sigmoid scores, a kind for every layer")
    sep = a.get("separator")
    return dict(
        V=int(config["vocab_size"]), H=int(config["hidden_size"]), L=L,
        D=int(config["num_dense_layers"]), F=int(config["intermediate_size"]),
        I=int(config["moe_intermediate_size"]), E=published, Eh=held, lo=rank * held,
        k=int(config["num_experts_per_tok"]), ns=int(config["num_shared_experts"]),
        renorm=bool(config["route_norm"]), scale=float(config["route_scale"]),
        step=float(config["load_balance_coeff"]),
        nh=int(config["num_attention_heads"]), nkv=int(config["num_key_value_heads"]),
        hd=int(config["head_dim"]), eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]), W=int(config["sliding_window"]),
        sliding=tuple(kind == SLIDING for kind in kinds),
        mup=bool(config.get("mup_enabled")), sep=None if sep is None else int(sep))


def key_of(seed: int):
    """PRNG key of a seed of any size: the bits above 31 are folded in,
    not dropped."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _attention_shapes(s: dict) -> dict:
    H, q, kv = s["H"], s["nh"] * s["hd"], s["nkv"] * s["hd"]
    return {"wq": (H, q), "wk": (H, kv), "wv": (H, kv), "wg": (H, q), "wo": (q, H)}


def make_weights(key, config: dict, dtype=jnp.bfloat16) -> Weights:
    """Random weights from ``key_of(seed)`` in the dtype they are trained
    from. Pure and jittable with the key traced."""
    s = sizes(config)
    H, V, I, E, Eh, F = s["H"], s["V"], s["I"], s["E"], s["Eh"], s["F"]
    keys = iter(jax.random.split(key, 64))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    resid = 0.02 / math.sqrt(2 * s["L"])
    # under mup the stream starts at unit RMS at any width: 0.0221 at 2048
    w = {"embed": normal((V, H), H ** -0.5 if s["mup"] else 0.02),
         "head": normal((H, V), 0.02),
         "norm_f": 1.0 + normal((H,), 0.05)}
    for p, n, experts in (("d_", s["D"], False), ("", s["L"] - s["D"], True)):
        if not n:
            continue
        for name, shape in _attention_shapes(s).items():
            w[p + name] = normal((n,) + shape, 2 * resid if name == "wo" else 0.02)
        for name in ("norm1", "norm2"):
            w[p + name] = 1.0 + normal((n, H), 0.05)
        # a post-norm erases the scale of the projection in front of it, so
        # the residual branches' 1/sqrt(2 L) stands in its gain
        for name in ("norm1_post", "norm2_post"):
            w[p + name] = (1.0 + normal((n, H), 0.05)) / math.sqrt(2 * s["L"])
        # gains near 2: scores then have a standard deviation near 4
        w.update({p + "q_norm": 2.0 + normal((n, s["hd"]), 0.05),
                  p + "k_norm": 2.0 + normal((n, s["hd"]), 0.05)})
        if not experts:
            w.update({p + "gate": normal((n, H, F), 0.02), p + "up": normal((n, H, F), 0.02),
                      p + "down": normal((n, F, H), resid)})
            continue
        w.update({"router": normal((n, H, E), 0.02), "router_bias": normal((n, E), 0.05),
                  "w_gate": normal((n, Eh, H, I), 0.02), "w_up": normal((n, Eh, H, I), 0.02),
                  "w_down": normal((n, Eh, I, H), resid),
                  "s_gate": normal((n, H, s["ns"] * I), 0.02),
                  "s_up": normal((n, H, s["ns"] * I), 0.02),
                  "s_down": normal((n, s["ns"] * I, H), resid)})
    return w


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


def documents(ids, s: dict):
    """Each position's document, [B,S]: the separators before it (a
    separator ends its own document); one document a row without one."""
    if s["sep"] is None:
        return jnp.zeros(ids.shape, jnp.int32)
    ends = (ids == s["sep"]).astype(jnp.int32)
    return jnp.cumsum(ends, axis=1) - ends


def attention(x, doc, lw, s: dict, sliding: bool, control=None, checkpoint: bool = False):
    """The attention sub-block's ``y`` on the normed input x [B,S,H]
    (equation 2, before ``N_post_attn``); doc [B,S] from ``documents``."""
    B, S, _ = x.shape
    nh, nkv, hd, W = s["nh"], s["nkv"], s["hd"], s["W"]
    G = nh // nkv
    r = lambda t: rounded(t, control)
    h = r(x)
    q = rms_norm((h @ r(lw["wq"])).reshape(B, S, nh, hd), lw["q_norm"], s["eps"])
    k = rms_norm((h @ r(lw["wk"])).reshape(B, S, nkv, hd), lw["k_norm"], s["eps"])
    v = (h @ r(lw["wv"])).reshape(B, S, nkv, hd)
    if sliding:
        q, k = rotate(q, s["theta"]), rotate(k, s["theta"])
    q = q.reshape(B, S, nkv, G, hd)
    kr, vr = r(k), r(v)

    def scores_to_values(qb, q_at, q_doc, kb, vb, k_at, k_doc):
        """One block of queries [B,n,nkv,G,hd] at positions q_at [n] against
        the keys kb [B,m,nkv,hd] at positions k_at [m] (below 0: padding)."""
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", r(qb), kb) * hd ** -0.5
        seen = (k_at[None, :] >= 0) & (k_at[None, :] <= q_at[:, None])
        if sliding:
            seen &= q_at[:, None] - k_at[None, :] < W
        seen = seen[None] & (q_doc[:, :, None] == k_doc[:, None, :])       # [B,n,m]
        sc = jnp.where(seen[:, None, None], sc, -jnp.inf)
        return jnp.einsum("bhgqk,bkhd->bqhgd", r(jax.nn.softmax(sc, axis=-1)), vb)

    at = jnp.arange(S)
    n = QUERY_BLOCK

    def in_blocks_of_queries(keys_of):
        """``scores_to_values`` a block of ``n`` queries at a time;
        ``keys_of(p)``: the keys, values, positions and documents the block
        from position ``p`` is given."""
        def block(qs):
            qb, p = qs
            return scores_to_values(qb, p + jnp.arange(n),
                                    jax.lax.dynamic_slice_in_dim(doc, p, n, 1), *keys_of(p))
        a = jax.lax.map(jax.checkpoint(block),
                        (q.reshape(B, S // n, n, nkv, G, hd).swapaxes(0, 1),
                         jnp.arange(0, S, n)))
        return a.swapaxes(0, 1)

    if not (checkpoint and S > n and S % n == 0):
        a = scores_to_values(q, at, doc, kr, vr, at, doc)
    elif sliding and W + n < S:
        # a block of queries from position p sees the keys p - W + 1 .. p + n - 1:
        # W + n of them from p - W, the front padded
        pad = lambda t, fill: jnp.pad(t, ((0, 0), (W, 0)) + ((0, 0),) * (t.ndim - 2),
                                      constant_values=fill)
        kp, vp, dp, ap = pad(kr, 0.0), pad(vr, 0.0), pad(doc, -1), jnp.arange(-W, S)
        take = lambda t, p, axis: jax.lax.dynamic_slice_in_dim(t, p, W + n, axis)
        a = in_blocks_of_queries(
            lambda p: (take(kp, p, 1), take(vp, p, 1), take(ap, p, 0), take(dp, p, 1)))
    else:
        a = in_blocks_of_queries(lambda p: (kr, vr, at, doc))
    a = a.reshape(B, S, nh * hd) * jax.nn.sigmoid(h @ r(lw["wg"]))
    return r(a) @ r(lw["wo"])


def gated_mlp(h, wg, wu, wd, control=None):
    r = lambda t: rounded(t, control)
    return r(jax.nn.silu(r(h) @ r(wg)) * (r(h) @ r(wu))) @ r(wd)


def route(h, w_router, bias, s: dict, control=None):
    """h [T,H] -> (weight [T,E]: each token's routing weight for each
    PUBLISHED expert, 0 where it did not choose it; assignments per
    expert [E])."""
    score = jax.nn.sigmoid(rounded(h, control) @ rounded(w_router, control))
    _, chosen = jax.lax.top_k(score + jax.lax.stop_gradient(bias), s["k"])
    top = jnp.take_along_axis(score, chosen, axis=-1)
    if s["renorm"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    onehot = jax.nn.one_hot(chosen, s["E"], dtype=jnp.float32)              # [T,k,E]
    return (jnp.einsum("tk,tke->te", top * s["scale"], onehot),
            jnp.sum(onehot, axis=(0, 1)))


def held_experts(h, weight, lw, s: dict, control=None, checkpoint: bool = False):
    """sum over the HELD experts e of weight[:, e] x E_e(h): every held
    expert on every token under the mask, a few a pass."""
    T, Eh = h.shape[0], s["Eh"]
    per = max(1, min(Eh, PASS_ELEMENTS // (T * s["I"])))
    while Eh % per:
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
    group = lambda a: a.reshape((Eh // per, per) + a.shape[1:])
    held = weight[:, s["lo"]:s["lo"] + Eh]
    out, _ = jax.lax.scan(one_pass, jnp.zeros_like(h),
                          (group(lw["w_gate"]), group(lw["w_up"]),
                           group(lw["w_down"]), group(held.T)))
    return out


def layer(x, doc, lw, s: dict, sliding: bool, control=None, checkpoint: bool = False):
    """One layer on the stream x [B,S,H]; lw: this layer's slice, prefix
    stripped. Returns (x', assignments per published expert [E])."""
    B, S, H = x.shape
    y = attention(rms_norm(x, lw["norm1"], s["eps"]), doc, lw, s, sliding, control,
                  checkpoint)
    x = x + rms_norm(y, lw["norm1_post"], s["eps"])
    h = rms_norm(x, lw["norm2"], s["eps"]).reshape(B * S, H)
    if "router" not in lw:
        m = in_blocks(lambda t: gated_mlp(t, lw["gate"], lw["up"], lw["down"], control),
                      h, TOKEN_BLOCK, checkpoint)
        load = jnp.zeros((s["E"],))
    else:
        weight, load = route(h, lw["router"], lw["router_bias"], s, control)
        m = held_experts(h, weight, lw, s, control, checkpoint)
        m = m + in_blocks(lambda t: gated_mlp(t, lw["s_gate"], lw["s_up"], lw["s_down"],
                                              control), h, TOKEN_BLOCK, checkpoint)
    return x + rms_norm(m.reshape(B, S, H), lw["norm2_post"], s["eps"]), load


_ATTENTION_KEYS = ("norm1", "norm1_post", "norm2", "norm2_post", "wq", "wk", "wv", "wg",
                   "wo", "q_norm", "k_norm")
_DENSE_KEYS = _ATTENTION_KEYS + ("gate", "up", "down")
_EXPERT_KEYS = _ATTENTION_KEYS + ("router", "router_bias", "w_gate", "w_up", "w_down",
                                  "s_gate", "s_up", "s_down")


def _cast(w: Weights, dtype) -> Weights:
    return {k: v.astype(dtype) for k, v in w.items()}


def stream_and_load(w: Weights, ids, config: dict, *, control=None,
                    checkpoint: bool = False):
    """(the final stream [B,S,H], assignments per published expert of the
    expert layers [L-D, E]). The layers run one after another, each as its
    own kind (``layer_types``)."""
    s = sizes(config)
    x = w["embed"][ids] * (math.sqrt(s["H"]) if s["mup"] else 1.0)
    doc = documents(ids, s)
    loads = []
    for i, sliding in enumerate(s["sliding"]):
        dense = i < s["D"]
        prefix, at, keys = ("d_", i, _DENSE_KEYS) if dense else ("", i - s["D"], _EXPERT_KEYS)

        def fn(x, lw, sliding=sliding):
            return layer(x, doc, lw, s, sliding, control, checkpoint)
        if checkpoint:  # departure: memory only, same arithmetic
            fn = jax.checkpoint(fn)
        x, load = fn(x, {k: w[prefix + k][at] for k in keys})
        if not dense:
            loads.append(load)
    return x, jnp.stack(loads)


def head_logits(w: Weights, x, s: dict, control=None):
    return rounded(rms_norm(x, w["norm_f"], s["eps"]), control) @ rounded(w["head"], control)


def forward(w: Weights, ids, config: dict, *, control=None, checkpoint: bool = False):
    """float32 logits [B,S,V] of token ids [B,S]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        w = _cast(w, jnp.float32)
        x = stream_and_load(w, ids, config, control=control, checkpoint=checkpoint)[0]
        return head_logits(w, x, s, control)


def next_token_loss(w: Weights, ids, config: dict, *, control=None,
                    checkpoint: bool = False):
    """The training objective (equation 4): the mean cross-entropy at
    predicting the next token, over the positions that have one."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        w = _cast(w, jnp.float32)
        x = stream_and_load(w, ids, config, control=control, checkpoint=checkpoint)[0]
        B, S, H = x.shape
        targets = jnp.roll(ids, -1, axis=1)
        valid = jnp.broadcast_to(jnp.arange(S) < S - 1, (B, S))

        def nll(block):
            xb, tb, vb = block
            logp = jax.nn.log_softmax(head_logits(w, xb, s, control), axis=-1)
            return jnp.where(vb, -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0], 0.0)
        total = in_blocks(nll, (x.reshape(-1, H), targets.reshape(-1), valid.reshape(-1)),
                          TOKEN_BLOCK, checkpoint)
        return jnp.sum(total) / (B * (S - 1))


def router_load(w: Weights, ids, config: dict):
    """Assignments each published expert drew, [expert layers, E]: what
    moves the router's bias after the step."""
    with jax.default_matmul_precision("highest"):
        return stream_and_load(_cast(w, jnp.float32), ids, config)[1]


def bias_after_step(bias, load, config: dict):
    """The router's bias after a step that drew ``load`` [.., E]: up by
    ``load_balance_coeff`` for an expert under the mean load, down for one
    over it (equation 3)."""
    mean = jnp.mean(load, axis=-1, keepdims=True)
    return bias + sizes(config)["step"] * jnp.sign(mean - load)


def loss_and_gradient(w: Weights, ids, config: dict, *, control=None):
    """(loss, l2 norm of the gradient over every weight, sign of every
    gradient element as int8 under the weights' names). The router's bias
    enters under ``stop_gradient``: its signs are exactly 0."""
    w32 = _cast(w, jnp.float32)
    loss, g = jax.value_and_grad(
        lambda p: next_token_loss(p, ids, config, control=control, checkpoint=True))(w32)
    sq = sum(jnp.sum(jnp.square(v)) for v in g.values())
    return loss, jnp.sqrt(sq), {k: jnp.sign(v).astype(jnp.int8) for k, v in g.items()}


def matmul_params(config: dict) -> float:
    """Parameters that multiply each token HERE: the attention kernels (the
    gate's too), a dense layer's MLP, the router, the shared expert, the
    routed experts at ``num_experts_per_tok x held / published`` a token
    (a token's chosen experts that live on other chips multiply it there,
    not here), and the head. The embedding is a lookup and the norm gains
    are scalings: not counted."""
    s = sizes(config)
    H, I = s["H"], s["I"]
    attn = sum(a * b for a, b in _attention_shapes(s).values())
    routed = s["k"] * s["Eh"] / s["E"] * 3 * H * I
    expert_layer = attn + H * s["E"] + 3 * H * s["ns"] * I + routed
    return s["D"] * (attn + 3 * H * s["F"]) + (s["L"] - s["D"]) * expert_layer + H * s["V"]


def attention_keys_per_token(config: dict, seq: int) -> float:
    """The keys a token's query meets, summed over the layers and averaged
    over the positions of a row of ``seq`` that is one document: ``position
    + 1`` on a full layer, ``min(position + 1, sliding_window)`` on a
    sliding one."""
    s = sizes(config)
    full = (seq + 1) / 2
    w = min(s["W"], seq)
    slide = (w * (w + 1) / 2 + (seq - w) * w) / seq
    return sum(slide if sliding else full for sliding in s["sliding"])


def train_flops_per_token(config: dict, seq: int) -> float:
    """FLOPs one trained token REQUIRES of this chip at sequence length
    ``seq`` (the contract is benchmark/reference/gpt2.py's): 6 per matmul
    parameter a token meets here (``matmul_params``), plus QK^T and PV of
    every query head over the keys a causal row gives it
    (``attention_keys_per_token``: packed documents hide more, which is
    traffic's and not counted), 12 nh hd a key."""
    s = sizes(config)
    return (6.0 * matmul_params(config)
            + 12.0 * s["nh"] * s["hd"] * attention_keys_per_token(config, seq))


def expert_product_flops_per_row(config: dict) -> float:
    """FLOPs ONE product of a routed expert's MLP costs ONE routed row
    (the contract is benchmark/reference/olmoe.py's): 2 x 2048 x 1024."""
    s = sizes(config)
    return 2.0 * s["H"] * s["I"]


def window_pairs(document_lengths, config: dict) -> int:
    """The (query, key) pairs ONE query head of a sliding layer has to
    multiply over documents of the given lengths (each packed whole into a
    row): causal AND inside the window AND inside the document, a document
    of n tokens ``m (m + 1) / 2 + (n - m) m`` with ``m = min(n,
    sliding_window)``. Exact integers: what the mask of equation 2 leaves."""
    W = sizes(config)["W"]
    total = 0
    for n in document_lengths:
        m = min(int(n), W)
        total += m * (m + 1) // 2 + (int(n) - m) * m
    return total


def attention_pair_flops(config: dict) -> dict:
    """FLOPs ONE visible (query, key) pair of ONE query head costs each
    kernel of the attention core: the forward's two matmuls (QK^T, PV), 4
    hd; the fused backward's five (the scores again, dV, dP, dK, dQ), 10
    hd (``attn_window_roofline`` multiplies them by the pairs that exist)."""
    hd = sizes(config)["hd"]
    return {"forward": 4.0 * hd, "backward": 10.0 * hd, "heads": sizes(config)["nh"]}
