"""PowerInfer's SmallThinker-21B-A3B (``model_type`` ``smallthinker``,
arXiv:2507.20984) in plain ``jax.numpy``: forward pass, training loss and
gradient, read from a configuration file with Hugging Face's key names, as ONE
chip's share of a deployment in which several chips share each layer.

Written from the configuration's keys and, for what it has no key for (marked
+, the file's ``assumed`` says the same), from the published modelling code
and llama.cpp's graph of it. ``N_*`` is RMSNorm (eps ``rms_norm_eps``) with a
gain; no bias on any matmul (+); H ``hidden_size``, nh query heads over nkv key
heads of hd = ``head_dim`` (nh x hd is NOT H; nh / nkv = 7 as published).
Every layer is alike but for its attention's kind; there is no dense layer
and no shared expert. With ``x`` a layer's input, the stream as it stands:

1. Embedding: ``x = Emb[token]``.
2. The router, FIRST (+ "router placed before attention"): ``r = x W_r``
   [H -> E] over all PUBLISHED experts, from ``x`` UN-NORMED and before
   attention; chosen = the ``moe_num_active_primary_experts`` largest of
   ``r`` (the lowest index wins a tie); ``p`` = softmax over the chosen
   logits (``moe_primary_router_apply_softmax``; with ``norm_topk_prob`` the
   softmax over all E renormalised over the chosen is the same numbers).
3. Attention: ``u = N_1(x)``; ``q = u Wq`` [nh x hd], ``k = u Wk``, ``v = u
   Wv`` [nkv x hd]; no QK-norm (+), no gate (+). On a layer whose
   ``sliding_window_layout`` entry is 1 (``rope_layout`` is 1 there and only
   there), rotary positions on q and k (theta ``rope_theta``, the pair (i, i +
   hd/2) turned by position x theta^(-2i/hd): rotate-half over the whole head
   (+)) and the mask ``i - j < sliding_window_size``; a layer whose entry is 0
   (every fourth, from layer 0) attends the whole row with NO positional term.
   ``a = softmax(q k^T / sqrt(hd) + mask) v`` with key head ``h // (nh / nkv)``
   for query head h; the mask is causal and inside a packed document (a
   document ends WITH its separator token; ``assumed.separator``, none = a row
   is one document). ``h = x + a Wo``.
4. Experts: ``u = N_2(h)``; ``y = h + sum over (chosen AND held) p_e E_e(u)``,
   ``E_e(u) = (relu(u Wg_e) * (u Wu_e)) Wd_e`` of ``moe_ffn_hidden_size`` (the
   gate is a ReLU: sparse ReGLU). No auxiliary loss: the configuration has no
   coefficient for one. ``described_as`` in the catalog speaks of "primary +
   secondary experts": the configuration has primary experts alone, and the
   configuration is what is computed.
5. ``logits = N_f(x) W_head``, untied; the loss is the mean next-token
   cross-entropy over the row's positions that have a next token.

THE SHARE. The file's ``share`` block says how many chips share a layer and
what was published; ``moe_num_primary_experts`` and ``vocab_size`` of the file
are what THIS chip holds (rank ``assumed.share_rank``, 0 unless given: experts
``rank x held .. (rank + 1) x held - 1``). The router keeps its published
width; the held experts are computed the obvious way, every one of them on
every token under the mask of chosen AND held, a few a pass; what the absent
experts would add is left out and that partial result goes on to the next
layer; nothing stands in for the other chips. The vocabulary is the file's:
embedding, head and loss are over the slice. ``sliding_window_layout`` and
``rope_layout`` are read from their start, ``num_hidden_layers`` entries (a
cut in depth keeps the published lists whole). Without a ``share`` block every
expert is held.

float32 throughout, ``jax.default_matmul_precision("highest")``, no kernels,
no cache. It imports nothing of the program under test and nothing of the
benchmark, and exports what every reference file exports
(benchmark/reference/gpt2.py lists them), ``expert_product_flops_per_row``,
``window_pairs`` and ``attention_pair_flops``. Departures: random weights from
a seed (norm gains near 1; the embedding at unit RMS, because the router reads
the stream UN-NORMED: at GPT-2's 0.02 every logit would lie within 0.03 of
every other and the top-k would be decided by rounding, where a trained
stream's logits decide; queries and keys drawn wide, sqrt(4 / H), so that
scores have a standard deviation of about 4 without a QK-norm to give it;
every other projection at 1 / sqrt(fan-in), the two that add to the stream at
1 / sqrt(2 L) of that); memory only:
``jax.checkpoint`` around layers, passes of experts and blocks of queries,
tokens in blocks through the head's loss, and a windowed layer's block of
queries multiplies the ``sliding_window_size`` + block keys it can see and no
others; and the ``fp8`` control, which rounds every matmul operand to
float8_e4m3fn.

Weights are one flat dict, per-layer arrays stacked on a leading axis (I =
moe_ffn_hidden_size, E = published experts, Eh = held)::

    embed [V,H]  head [H,V]  norm_f [H]
    norm1 norm2 [L,H]
    wq [L,H,nh*hd]  wk wv [L,H,nkv*hd]  wo [L,nh*hd,H]
    router [L,H,E]  w_gate w_up [L,Eh,H,I]  w_down [L,Eh,I,H]
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

Weights = Dict[str, jax.Array]

#: the largest [experts of a pass, tokens, I] float32 intermediate, in elements
PASS_ELEMENTS = 2 ** 26
#: queries of a block of the attention scores; tokens of a block through the
#: head (memory only)
QUERY_BLOCK = 128
TOKEN_BLOCK = 2048


def sizes(config: dict) -> dict:
    """The published keys this file reads, under short names."""
    a = config.get("assumed", {})
    share = config.get("share")
    held = int(config["moe_num_primary_experts"])
    published = (int(share["published"].get("moe_num_primary_experts", held))
                 if share else held)
    rank = int(a.get("share_rank", 0))
    L = int(config["num_hidden_layers"])
    windowed = tuple(int(w) for w in config["sliding_window_layout"])[:L]
    rope = tuple(int(w) for w in config.get("rope_layout", windowed))[:L]
    if (config.get("rope_scaling") is not None or len(windowed) != L or rope != windowed
            or not config.get("moe_primary_router_apply_softmax", True)
            or not config.get("norm_topk_prob", True)
            or config.get("tie_word_embeddings", False)):
        raise ValueError("plain rope on the windowed layers and only there, a kind for "
                         "every layer, a softmax over the chosen logits, an untied head")
    sep = a.get("separator")
    return dict(
        V=int(config["vocab_size"]), H=int(config["hidden_size"]), L=L,
        I=int(config["moe_ffn_hidden_size"]), E=published, Eh=held, lo=rank * held,
        k=int(config["moe_num_active_primary_experts"]),
        nh=int(config["num_attention_heads"]), nkv=int(config["num_key_value_heads"]),
        hd=int(config["head_dim"]), eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]), W=int(config["sliding_window_size"]),
        windowed=tuple(bool(w) for w in windowed), sep=None if sep is None else int(sep))


def key_of(seed: int):
    """PRNG key of a seed of any size: the bits above 31 are folded in,
    not dropped."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _attention_shapes(s: dict) -> dict:
    H, q, kv = s["H"], s["nh"] * s["hd"], s["nkv"] * s["hd"]
    return {"wq": (H, q), "wk": (H, kv), "wv": (H, kv), "wo": (q, H)}


def make_weights(key, config: dict, dtype=jnp.bfloat16) -> Weights:
    """Random weights from ``key_of(seed)`` in the dtype they are trained
    from. Pure and jittable with the key traced."""
    s = sizes(config)
    H, V, I, E, Eh, L = s["H"], s["V"], s["I"], s["E"], s["Eh"], s["L"]
    keys = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    # every projection keeps its input's scale at any width (1 / sqrt(fan-in)),
    # the two that add to the stream 1 / sqrt(2 L) of that: GPT-2's rule for a
    # residual branch on a stream of unit RMS
    resid = 1.0 / math.sqrt(2 * L)
    wide = math.sqrt(4.0 / H)       # scores of standard deviation 4, no QK-norm
    # the embedding at unit RMS: the router reads the stream un-normed, and
    # H^(-1/2) a weight then gives its logits a standard deviation of 1
    w = {"embed": normal((V, H), 1.0), "head": normal((H, V), 0.02),
         "norm_f": 1.0 + normal((H,), 0.05),
         "norm1": 1.0 + normal((L, H), 0.05), "norm2": 1.0 + normal((L, H), 0.05),
         "router": normal((L, H, E), H ** -0.5),
         "w_gate": normal((L, Eh, H, I), H ** -0.5), "w_up": normal((L, Eh, H, I), H ** -0.5),
         # (twice: half a ReLU's products are 0 and a token's weights sum to 1)
         "w_down": normal((L, Eh, I, H), 2 * resid * I ** -0.5)}
    std = {"wq": wide, "wk": wide, "wv": H ** -0.5,
           "wo": resid * (s["nh"] * s["hd"]) ** -0.5}
    for name, shape in _attention_shapes(s).items():
        w[name] = normal((L,) + shape, std[name])
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


def attention(x, doc, lw, s: dict, windowed: bool, control=None, checkpoint: bool = False):
    """The attention branch ``a Wo`` on the normed input x [B,S,H] (equation
    3); doc [B,S] from ``documents``."""
    B, S, _ = x.shape
    nh, nkv, hd, W = s["nh"], s["nkv"], s["hd"], s["W"]
    G = nh // nkv
    r = lambda t: rounded(t, control)
    h = r(x)
    q = (h @ r(lw["wq"])).reshape(B, S, nh, hd)
    k = (h @ r(lw["wk"])).reshape(B, S, nkv, hd)
    v = (h @ r(lw["wv"])).reshape(B, S, nkv, hd)
    if windowed:
        q, k = rotate(q, s["theta"]), rotate(k, s["theta"])
    q = q.reshape(B, S, nkv, G, hd)
    kr, vr = r(k), r(v)

    def scores_to_values(qb, q_at, q_doc, kb, vb, k_at, k_doc):
        """One block of queries [B,n,nkv,G,hd] at positions q_at [n] against
        the keys kb [B,m,nkv,hd] at positions k_at [m] (below 0: padding)."""
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", r(qb), kb) * hd ** -0.5
        seen = (k_at[None, :] >= 0) & (k_at[None, :] <= q_at[:, None])
        if windowed:
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
    elif windowed and W + n < S:
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
    return r(a.reshape(B, S, nh * hd)) @ r(lw["wo"])


def route(x, w_router, s: dict, control=None):
    """The block's un-normed input x [T,H] -> (weight [T,E]: each token's
    routing weight for each PUBLISHED expert, 0 where it did not choose it;
    assignments per expert [E])."""
    logits = rounded(x, control) @ rounded(w_router, control)
    top, chosen = jax.lax.top_k(logits, s["k"])
    p = jax.nn.softmax(top, axis=-1)
    onehot = jax.nn.one_hot(chosen, s["E"], dtype=jnp.float32)              # [T,k,E]
    return jnp.einsum("tk,tke->te", p, onehot), jnp.sum(onehot, axis=(0, 1))


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
        mid = r(jax.nn.relu(jnp.einsum("th,ehf->etf", r(h), r(wg)))
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


def layer(x, doc, lw, s: dict, windowed: bool, control=None, checkpoint: bool = False):
    """One layer on the stream x [B,S,H]; lw: this layer's slice. Returns
    (x', assignments per published expert [E]). The routing is made FIRST,
    from ``x`` as it came in."""
    B, S, H = x.shape
    weight, load = route(x.reshape(B * S, H), lw["router"], s, control)
    h = x + attention(rms_norm(x, lw["norm1"], s["eps"]), doc, lw, s, windowed, control,
                      checkpoint)
    u = rms_norm(h, lw["norm2"], s["eps"]).reshape(B * S, H)
    return h + held_experts(u, weight, lw, s, control, checkpoint).reshape(B, S, H), load


_LAYER_KEYS = ("norm1", "norm2", "wq", "wk", "wv", "wo", "router", "w_gate", "w_up", "w_down")


def _cast(w: Weights, dtype) -> Weights:
    return {k: v.astype(dtype) for k, v in w.items()}


def stream_and_load(w: Weights, ids, config: dict, *, control=None,
                    checkpoint: bool = False):
    """(the final stream [B,S,H], assignments per published expert [L, E]).
    The layers run one after another, each as its own kind
    (``sliding_window_layout``)."""
    s = sizes(config)
    x = w["embed"][ids]
    doc = documents(ids, s)
    loads = []
    for i, windowed in enumerate(s["windowed"]):
        def fn(x, lw, windowed=windowed):
            return layer(x, doc, lw, s, windowed, control, checkpoint)
        if checkpoint:  # departure: memory only, same arithmetic
            fn = jax.checkpoint(fn)
        x, load = fn(x, {k: w[k][i] for k in _LAYER_KEYS})
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
    """The training objective (equation 5): the mean cross-entropy at
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
    """Assignments each published expert drew, [L, E]."""
    with jax.default_matmul_precision("highest"):
        return stream_and_load(_cast(w, jnp.float32), ids, config)[1]


def loss_and_gradient(w: Weights, ids, config: dict, *, control=None):
    """(loss, l2 norm of the gradient over every weight, sign of every
    gradient element as int8 under the weights' names). One jitted program
    (called op by op, the checkpointed layers and passes would each compile
    alone, again at every call)."""
    def compute(w, ids):
        loss, g = jax.value_and_grad(
            lambda p: next_token_loss(p, ids, config, control=control, checkpoint=True))(
                _cast(w, jnp.float32))
        sq = sum(jnp.sum(jnp.square(v)) for v in g.values())
        return loss, jnp.sqrt(sq), {k: jnp.sign(v).astype(jnp.int8) for k, v in g.items()}
    return jax.jit(compute)(w, ids)


def matmul_params(config: dict) -> float:
    """Parameters that multiply each token HERE: the attention kernels, the
    router, the routed experts at ``moe_num_active_primary_experts x held /
    published`` a token (a token's chosen experts that live on other chips
    multiply it there, not here), and the head. The embedding is a lookup and
    the norm gains are scalings: not counted."""
    s = sizes(config)
    H, I = s["H"], s["I"]
    attn = sum(a * b for a, b in _attention_shapes(s).values())
    routed = s["k"] * s["Eh"] / s["E"] * 3 * H * I
    return s["L"] * (attn + H * s["E"] + routed) + H * s["V"]


def attention_keys_per_token(config: dict, seq: int) -> float:
    """The keys a token's query meets, summed over the layers and averaged
    over the positions of a row of ``seq`` that is one document: ``position
    + 1`` on a full layer, ``min(position + 1, sliding_window_size)`` on a
    windowed one."""
    s = sizes(config)
    full = (seq + 1) / 2
    w = min(s["W"], seq)
    slide = (w * (w + 1) / 2 + (seq - w) * w) / seq
    return sum(slide if windowed else full for windowed in s["windowed"])


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
    (the contract is benchmark/reference/olmoe.py's): 2 x 2560 x 768."""
    s = sizes(config)
    return 2.0 * s["H"] * s["I"]


def window_pairs(document_lengths, config: dict) -> int:
    """The (query, key) pairs ONE query head of a windowed layer has to
    multiply over documents of the given lengths (each packed whole into a
    row): causal AND inside the window AND inside the document, a document
    of n tokens ``m (m + 1) / 2 + (n - m) m`` with ``m = min(n,
    sliding_window_size)``. Exact integers: what the mask of equation 3
    leaves."""
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
