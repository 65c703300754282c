"""JetLM's SDAR-30B-A3B-Chat (``model_type`` ``sdar_moe``) in plain
``jax.numpy``, trained as a BLOCK-DIFFUSION model: ``next_token_loss`` is the
contract's name for the scalar that ``loss_and_gradient`` differentiates, and
here it is the weighted DENOISING loss over a clean and a noised copy of every
row, the noise drawn from ``ids``; ``forward`` is the FIRST DENOISING PASS of
every block. Read from a configuration file with Hugging Face's key names, as
ONE chip's share of a deployment in which several chips share each layer.

Written from the configuration's keys; what it has no key for is marked + and
stands in the file's ``assumed`` in the same words. ``N_*`` is RMSNorm (eps
``rms_norm_eps``) with a gain; no bias on any matmul; H ``hidden_size``, nh
query heads over nkv key heads of hd = ``head_dim`` (nh x hd is NOT H). A row
holds L data tokens ``x_0``; b the block length (+ 4); ``blk(i) = i // b``
counted from the row's start (+); ``doc(i)`` the packed document of position
``i``, from the CLEAN ids (a document ends WITH its separator token,
``assumed.separator``; none = a row is one document).

1. Noise (+ all of it: the published block-diffusion recipe of BD3-LM,
   arXiv:2503.09573, in its vectorised form). Every block B of a row draws
   ``t_B ~ U[1e-3, 1]``; every token of the block becomes the mask token
   independently with probability ``t_B`` (linear schedule, ``alpha_t = 1 -
   t``), giving ``x_t``. The mask token's id is ``vocab_size`` (+): the
   embedding has ``vocab_size + 1`` rows, the head and the loss ``vocab_size``.
   The draws are a pure function of ``ids`` (+): the key is
   ``fold_in(PRNGKey(noise_seed), f(ids))``, ``f`` the sum over the flattened
   ids of ``id x (2 x index + 1)`` in uint32 arithmetic, shifted right one
   bit; ``t`` and the Bernoulli mask are two ``jax.random.uniform`` draws
   from its two splits (``noise``).
2. The network runs on BOTH copies, ``2 L`` rows of activations for ``L``
   tokens: the clean stream (embeddings of ``x_0``) and the noised stream
   (embeddings of ``x_t``), the same position ``i`` for both copies of token
   ``i``, the same weights, every layer.
3. The layer (the family's autoregressive parent's): ``u = N_in(x)``; ``q = u
   Wq`` [nh x hd], ``k = u Wk``, ``v = u Wv`` [nkv x hd]; ``q, k = N_q(q),
   N_k(k)`` per head, one gain of hd for all heads (+); rotary positions on q
   and k by position ``i`` (theta ``rope_theta``, the pair (j, j + hd/2) turned
   by ``i x theta^(-2j/hd)``); ``a = softmax(q k^T / sqrt(hd) + mask) v`` with
   key head ``h // (nh / nkv)``, ONE softmax a query over the union of what it
   sees (``visible``):
     a clean query i sees the clean key j iff doc(j) = doc(i) and blk(j) <= blk(i);
     a noised query i sees the clean key j iff doc(j) = doc(i) and blk(j) < blk(i),
       and the noised key j iff doc(j) = doc(i) and blk(j) = blk(i);
     a clean query sees no noised key.
   ``x = x + a Wo``; ``u = N_post(x)``; ``p = softmax(u Wr)`` in float32 over
   all PUBLISHED experts; chosen = the ``num_experts_per_tok`` largest (the
   lowest index wins a tie); ``w_e = p_e / sum_chosen p`` (``norm_topk_prob``);
   ``x = x + sum over (chosen AND held) w_e E_e(u)``, ``E_e`` a gated SiLU MLP
   of ``moe_intermediate_size``. No shared expert; auxiliary loss 0 (+).
4. ``logits_i = N_f(n_i) W_head`` on the NOISED stream only, float32,
   UNSHIFTED (+): position i's logits predict token i.
   ``loss = (1 / (rows x L)) sum over masked i of (1 / t_blk(i)) x
   CE(logits_i, x_0[i])``.

``forward(w, ids, cfg)`` is what generation computes when it opens a block:
the noised stream with every token masked (``t = 1``, no draw), so position
i's logits depend on the clean tokens of strictly earlier blocks alone.

THE SHARE. The file's ``share`` block says how many chips share a layer and
what was published; ``num_experts`` and ``vocab_size`` of the file are what
THIS chip holds (rank ``assumed.share_rank``, 0 unless given: experts ``rank
x held .. (rank + 1) x held - 1``). The router keeps its published width; the
held experts are computed the obvious way, every one of them on every row of
both streams under the mask of chosen AND held, a few a pass; what the absent
experts would add is left out and that partial result goes on to the next
layer; nothing stands in for the other chips. The vocabulary is the file's:
embedding (and the mask row), head and loss are over the slice. Without a
``share`` block every expert is held.

float32 throughout, ``jax.default_matmul_precision("highest")``, no kernels,
no cache. It imports nothing of the program under test and nothing of the
benchmark, and exports what every reference file exports
(benchmark/reference/gpt2.py lists them), ``expert_product_flops_per_row``,
``attention_pair_flops`` and ``kernel_pairs``. Departures: random weights from
a seed (norm gains near 1, the QK-norm gains near 2 so that heads are peaked
as trained ones are, residual projections at GPT-2's 1/sqrt(2 L), the
embedding at unit RMS: at GPT-2's 0.02 the stream after one layer is the mean
of what attention saw, a quarter of all rows are the one mask token, and every
row of a deep layer chose the same eight experts); memory
only: ``jax.checkpoint`` around layers, passes of experts and blocks of
queries, rows in blocks through the head's loss, and a block of queries is
scored against the clean keys and the noised keys of its OWN positions alone
(no other noised key is visible to it; the mask is still built densely from
``doc``, ``blk`` and the half over what is scored); and the ``fp8`` control,
which rounds every matmul operand to float8_e4m3fn.

Weights are one flat dict, per-layer arrays stacked on a leading axis (n =
layers, I = moe_intermediate_size, E = published experts, Eh = held)::

    embed [V+1,H]  head [H,V]  norm_f [H]
    norm1 norm2 [n,H]  q_norm k_norm [n,hd]
    wq [n,H,nh*hd]  wk wv [n,H,nkv*hd]  wo [n,nh*hd,H]
    router [n,H,E]  w_gate w_up [n,Eh,H,I]  w_down [n,Eh,I,H]
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

Weights = Dict[str, jax.Array]

#: the largest [experts of a pass, rows, I] float32 intermediate, in elements
PASS_ELEMENTS = 2 ** 26
#: queries of a block of the attention scores (a multiple of every block
#: length); rows of a block through the head's loss (memory only)
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
T_MIN = 1e-3


def sizes(config: dict) -> dict:
    """The published keys this file reads, under short names."""
    a = config.get("assumed", {})
    share = config.get("share")
    held = int(config["num_experts"])
    published = int(share["published"].get("num_experts", held)) if share else held
    if (config.get("rope_scaling") is not None or config.get("hidden_act", "silu") != "silu"
            or config.get("use_sliding_window") or config.get("mlp_only_layers")
            or config.get("decoder_sparse_step", 1) != 1 or config.get("attention_bias")
            or config.get("tie_word_embeddings")):
        raise ValueError("plain rope, SiLU, no window, no bias, an untied head, "
                         "every layer an expert layer")
    sep = a.get("separator")
    V, b = int(config["vocab_size"]), int(a.get("block_length", 4))
    if b < 1 or b & (b - 1) or int(a.get("mask_token_id", V)) != V:
        raise ValueError("a block length that is a power of two; the mask "
                         "token one row past the vocabulary")
    return dict(
        V=V, H=int(config["hidden_size"]), L=int(config["num_hidden_layers"]),
        I=int(config["moe_intermediate_size"]), E=published, Eh=held,
        lo=int(a.get("share_rank", 0)) * held, k=int(config["num_experts_per_tok"]),
        renorm=bool(config.get("norm_topk_prob", True)),
        nh=int(config["num_attention_heads"]), nkv=int(config["num_key_value_heads"]),
        hd=int(config["head_dim"]), eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]), b=b, mask=V,
        noise_seed=int(a.get("noise_seed", 0)), sep=None if sep is None else int(sep))


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
    H, V, I, E, Eh, n = s["H"], s["V"], s["I"], s["E"], s["Eh"], s["L"]
    keys = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    resid = 0.02 / math.sqrt(2 * n)
    # the embedding at unit RMS: a row's own token then outweighs what the
    # first attention outputs add (0.3 an element at these scales)
    w = {"embed": normal((V + 1, H), 1.0), "head": normal((H, V), 0.02),
         "norm_f": 1.0 + normal((H,), 0.05),
         "norm1": 1.0 + normal((n, H), 0.05), "norm2": 1.0 + normal((n, H), 0.05),
         # gains near 2: scores then have a standard deviation near 4
         "q_norm": 2.0 + normal((n, s["hd"]), 0.05),
         "k_norm": 2.0 + normal((n, s["hd"]), 0.05),
         "router": normal((n, H, E), 0.02),
         "w_gate": normal((n, Eh, H, I), 0.02), "w_up": normal((n, Eh, H, I), 0.02),
         "w_down": normal((n, Eh, I, H), resid)}
    for name, shape in _attention_shapes(s).items():
        w[name] = normal((n,) + shape, resid if name == "wo" else 0.02)
    return w


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


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


def documents(ids, s: dict):
    """Each position's document, [B,L]: the separators before it (a
    separator ends its own document); one document a row without one."""
    if s["sep"] is None:
        return jnp.zeros(ids.shape, jnp.int32)
    ends = (ids == s["sep"]).astype(jnp.int32)
    return jnp.cumsum(ends, axis=1) - ends


def noise_key(ids, s: dict):
    """Equation 1's key: a pure function of the batch's ids."""
    flat = ids.reshape(-1).astype(jnp.uint32)
    odd = 2 * jnp.arange(flat.size, dtype=jnp.uint32) + 1
    return jax.random.fold_in(jax.random.PRNGKey(s["noise_seed"]),
                              jnp.sum(flat * odd, dtype=jnp.uint32) >> 1)


def noise(ids, s: dict):
    """(x_t [B,L]: ``ids`` with each block's tokens masked with its own
    probability; each position's loss weight [B,L]: 1 / t where it was
    masked, 0 elsewhere). Equation 1."""
    B, L = ids.shape
    key_t, key_mask = jax.random.split(noise_key(ids, s))
    t = jax.random.uniform(key_t, (B, L // s["b"]), jnp.float32, minval=T_MIN, maxval=1.0)
    t = jnp.repeat(t, s["b"], axis=1)
    masked = jax.random.uniform(key_mask, (B, L), jnp.float32) < t
    return (jnp.where(masked, jnp.asarray(s["mask"], ids.dtype), ids),
            jnp.where(masked, 1.0 / t, 0.0))


def visible(q_noised, q_at, q_doc, k_noised, k_at, k_doc, b: int):
    """Equation 3's mask, densely: whether each key is visible to each
    query (broadcasting), from the half, the position's block and the
    document of either."""
    qb, kb = q_at // b, k_at // b
    seen = jnp.where(k_noised, q_noised & (kb == qb), jnp.where(q_noised, kb < qb, kb <= qb))
    return seen & (q_doc == k_doc)


def attention(x, doc, lw, s: dict, control=None, checkpoint: bool = False):
    """The attention sub-block's ``a Wo`` on the normed input x [B,2L,H]:
    the clean stream's rows, then the noised stream's; doc [B,L] from
    ``documents``."""
    B, S, _ = x.shape
    L, nh, nkv, hd, b = S // 2, s["nh"], s["nkv"], s["hd"], s["b"]
    G = nh // nkv
    r = lambda t: rounded(t, control)
    h = r(x)
    at = jnp.arange(S) % L                     # one position for both copies
    noised = jnp.arange(S) >= L
    doc2 = jnp.concatenate([doc, doc], axis=1)                             # [B,2L]
    q = rms_norm((h @ r(lw["wq"])).reshape(B, S, nh, hd), lw["q_norm"], s["eps"])
    k = rms_norm((h @ r(lw["wk"])).reshape(B, S, nkv, hd), lw["k_norm"], s["eps"])
    v = (h @ r(lw["wv"])).reshape(B, S, nkv, hd)
    q, k = rotate(q, at, s["theta"]), rotate(k, at, s["theta"])
    q = q.reshape(B, S, nkv, G, hd)
    kr, vr = r(k), r(v)

    def scores_to_values(qb, q_noised, q_at, q_doc, kb, vb, k_noised, k_at, k_doc):
        """A block of queries [B,n,nkv,G,hd] against the keys kb [B,m,nkv,hd]:
        one softmax a query over what ``visible`` leaves."""
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", r(qb), kb) * hd ** -0.5
        seen = visible(q_noised[None, :, None], q_at[None, :, None], q_doc[:, :, None],
                       k_noised[None, None, :], k_at[None, None, :], k_doc[:, None, :], b)
        sc = jnp.where(seen[:, None, None], sc, -jnp.inf)
        return jnp.einsum("bhgqk,bkhd->bqhgd", r(jax.nn.softmax(sc, axis=-1)), vb)

    n = QUERY_BLOCK
    if not (checkpoint and L > n and L % n == 0):
        a = scores_to_values(q, noised, at, doc2, kr, vr, noised, at, doc2)
    else:
        # a block of n queries (n a multiple of b, so whole blocks of
        # positions) is scored against the L clean keys and the n noised
        # keys of its OWN positions: every other noised key is hidden from it
        take = lambda t, p, axis: jax.lax.dynamic_slice_in_dim(t, p, n, axis)

        def block(qs):
            qb, p = qs                                   # p: its first ROW of 2L
            own = L + p % L                              # the noised keys' first row
            cat = lambda whole, axis: jnp.concatenate(
                [jax.lax.slice_in_dim(whole, 0, L, axis=axis), take(whole, own, axis)], axis)
            return scores_to_values(
                qb, take(noised, p, 0), take(at, p, 0), take(doc2, p, 1),
                cat(kr, 1), cat(vr, 1), cat(noised, 0), cat(at, 0), cat(doc2, 1))
        a = jax.lax.map(jax.checkpoint(block),
                        (q.reshape(B, S // n, n, nkv, G, hd).swapaxes(0, 1),
                         jnp.arange(0, S, n)))
        a = a.swapaxes(0, 1)
    return r(a.reshape(B, S, nh * hd)) @ r(lw["wo"])


def route(h, w_router, s: dict, control=None):
    """h [T,H] -> (weight [T,E]: each row's routing weight for each
    PUBLISHED expert, 0 where it did not choose it; assignments per
    expert [E]). Float32 softmax over all experts."""
    p = jax.nn.softmax(rounded(h, control) @ rounded(w_router, control), axis=-1)
    top, chosen = jax.lax.top_k(p, s["k"])
    if s["renorm"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(chosen, s["E"], dtype=jnp.float32)              # [T,k,E]
    return jnp.einsum("tk,tke->te", top, onehot), jnp.sum(onehot, axis=(0, 1))


def held_experts(h, weight, lw, s: dict, control=None, checkpoint: bool = False):
    """sum over the HELD experts e of weight[:, e] x E_e(h): every held
    expert on every row under the mask, a few a pass."""
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


def layer(x, doc, lw, s: dict, control=None, checkpoint: bool = False):
    """One layer on both streams x [B,2L,H]; lw: this layer's slice.
    Returns (x', assignments per published expert [E])."""
    B, S, H = x.shape
    x = x + attention(rms_norm(x, lw["norm1"], s["eps"]), doc, lw, s, control, checkpoint)
    h = rms_norm(x, lw["norm2"], s["eps"]).reshape(B * S, H)
    weight, load = route(h, lw["router"], s, control)
    m = held_experts(h, weight, lw, s, control, checkpoint)
    return x + m.reshape(B, S, H), load


_LAYER_KEYS = ("norm1", "norm2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
               "router", "w_gate", "w_up", "w_down")


def _cast(w: Weights, dtype) -> Weights:
    return {k: v.astype(dtype) for k, v in w.items()}


def stream_and_load(w: Weights, ids, noised_ids, config: dict, *, control=None,
                    checkpoint: bool = False):
    """(the NOISED stream after the last layer [B,L,H], assignments per
    published expert [layers, E] over both streams' rows). Equation 2: the
    clean copy's rows, then the noised copy's, through every layer."""
    s = sizes(config)
    L = ids.shape[1]
    if L % s["b"]:
        raise ValueError(f"a row of {L} tokens is no multiple of the block length")
    x = w["embed"][jnp.concatenate([ids, noised_ids], axis=1)]
    doc = documents(ids, s)
    loads = []
    for i in range(s["L"]):
        fn = lambda x, lw: layer(x, doc, lw, s, control, checkpoint)
        if checkpoint:  # departure: memory only, same arithmetic
            fn = jax.checkpoint(fn)
        x, load = fn(x, {k: w[k][i] for k in _LAYER_KEYS})
        loads.append(load)
    return x[:, L:], jnp.stack(loads)


def head_logits(w: Weights, x, s: dict, control=None):
    return rounded(rms_norm(x, w["norm_f"], s["eps"]), control) @ rounded(w["head"], control)


def forward(w: Weights, ids, config: dict, *, control=None, checkpoint: bool = False):
    """float32 logits [B,L,V] of the FIRST DENOISING PASS of every block:
    the noised stream all mask tokens, so position i's logits read the clean
    tokens of strictly earlier blocks alone."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        w = _cast(w, jnp.float32)
        x = stream_and_load(w, ids, jnp.full_like(ids, s["mask"]), config,
                            control=control, checkpoint=checkpoint)[0]
        return head_logits(w, x, s, control)


def next_token_loss(w: Weights, ids, config: dict, *, control=None,
                    checkpoint: bool = False):
    """The training objective under the contract's name: equation 4's
    weighted denoising loss, the noise drawn from ``ids`` (equation 1)."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        w = _cast(w, jnp.float32)
        noised_ids, weights = noise(ids, s)
        x = stream_and_load(w, ids, noised_ids, config, control=control,
                            checkpoint=checkpoint)[0]
        B, L, H = x.shape

        def weighted_nll(block):
            xb, tb, wb = block
            logp = jax.nn.log_softmax(head_logits(w, xb, s, control), axis=-1)
            return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0] * wb
        total = in_blocks(weighted_nll, (x.reshape(-1, H), ids.reshape(-1),
                                         weights.reshape(-1)), TOKEN_BLOCK, checkpoint)
        return jnp.sum(total) / (B * L)


def router_load(w: Weights, ids, config: dict):
    """Assignments each published expert drew from both streams' rows under
    the step's own noise, [layers, E]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        return stream_and_load(_cast(w, jnp.float32), ids, noise(ids, s)[0], config)[1]


def loss_and_gradient(w: Weights, ids, config: dict, *, control=None):
    """(loss, l2 norm of the gradient over every weight, sign of every
    gradient element as int8 under the weights' names)."""
    w32 = _cast(w, jnp.float32)
    loss, g = jax.value_and_grad(
        lambda p: next_token_loss(p, ids, config, control=control, checkpoint=True))(w32)
    sq = sum(jnp.sum(jnp.square(v)) for v in g.values())
    return loss, jnp.sqrt(sq), {k: jnp.sign(v).astype(jnp.int8) for k, v in g.items()}


def matmul_params_a_row(config: dict) -> float:
    """Parameters that multiply each ROW of a layer HERE (a data token is
    two rows): the attention kernels, the router, and the routed experts at
    ``num_experts_per_tok x held / published`` a row (a row's chosen experts
    that live on other chips multiply it there, not here)."""
    s = sizes(config)
    attn = sum(a * b for a, b in _attention_shapes(s).values())
    return attn + s["H"] * s["E"] + s["k"] * s["Eh"] / s["E"] * 3 * s["H"] * s["I"]


def attention_keys_per_token(config: dict, seq: int) -> float:
    """The keys a DATA token's two queries meet in one layer, averaged over
    the positions of a row of ``seq`` that is one document: the clean query
    of block B sees (B + 1) b clean keys, the noised one B b clean keys and
    its block's b noised ones: over the row ``seq x (seq + b)`` pairs
    (against ``2 seq^2`` had the concatenation been run causally)."""
    return float(seq + sizes(config)["b"])


def train_flops_per_token(config: dict, seq: int) -> float:
    """FLOPs one trained DATA token REQUIRES of this chip at sequence length
    ``seq`` (the contract is benchmark/reference/gpt2.py's): 6 per matmul
    parameter its TWO rows meet in every layer (``matmul_params_a_row``) and
    the head's once (the noised copy alone is read out), plus QK^T and PV of
    every query head over the keys its two queries see
    (``attention_keys_per_token``: packed documents hide more, which is
    traffic's and not counted), 12 nh hd a key. The embedding is a lookup
    and the norm gains are scalings: not counted."""
    s = sizes(config)
    params = 2 * s["L"] * matmul_params_a_row(config) + s["H"] * s["V"]
    return (6.0 * params + 12.0 * s["nh"] * s["hd"] * s["L"]
            * attention_keys_per_token(config, seq))


def expert_product_flops_per_row(config: dict) -> float:
    """FLOPs ONE product of a routed expert's MLP costs ONE routed row
    (the contract is benchmark/reference/olmoe.py's): 2 x 2048 x 768."""
    s = sizes(config)
    return 2.0 * s["H"] * s["I"]


def kernel_pairs(pieces, config: dict) -> int:
    """The (query, CLEAN key) pairs ONE query head has to multiply over one
    row's pieces of documents, ``pieces`` = (first position, length) of each
    in the row (blocks are counted from the ROW's start, so where a piece
    lies matters): a clean query i of a piece from s to e sees the clean
    keys s .. min(e, (i | (b - 1)) + 1) - 1, a noised one s .. (i - i % b) -
    1. Exact integers: the clean-key part of equation 3's mask, which is
    what a blockwise kernel over the clean keys has to multiply; the noised
    keys of a query's own block (at most b a query) are not in it."""
    b = sizes(config)["b"]
    total = 0
    for start, length in pieces:
        s, e = int(start), int(start) + int(length)
        i = np.arange(s, e, dtype=np.int64)
        clean = np.minimum(e, (i | (b - 1)) + 1) - s
        noised = np.maximum(0, (i - i % b) - s)
        total += int(clean.sum() + noised.sum())
    return total


def attention_pair_flops(config: dict) -> dict:
    """FLOPs ONE visible (query, key) pair of ONE query head costs each
    kernel of the attention core: the forward's two matmuls (QK^T, PV), 4
    hd; the fused backward's five (the scores again, dV, dP, dK, dQ), 10
    hd (``attn_blockdiff_roofline`` multiplies them by the pairs that exist)."""
    s = sizes(config)
    return {"forward": 4.0 * s["hd"], "backward": 10.0 * s["hd"], "heads": s["nh"]}
