"""Kimi Linear (``model_type`` ``kimi_linear``; moonshotai/Kimi-Linear-48B-A3B, report
arXiv:2510.26692) in plain ``jax.numpy``: forward pass, next-token loss and its
gradient, read from a configuration file with Hugging Face's key names.

Written from the configuration's keys and the report's equations; the published
modelling code (``modeling_kimi.py``: ``KimiDeltaAttention``, ``KimiMLAAttention``,
``KimiSparseMoeBlock``) and ``fla``'s ``kda`` are not in this machine, so what is
written from memory of them or settled by neither is marked + and stands in the
file's ``assumed`` in the same words. C ``hidden_size``, F ``intermediate_size``, I
``moe_intermediate_size``, E ``num_experts`` published and Eh held, k
``num_experts_per_token``; ``linear_attn_config``: H ``num_heads`` heads with keys
and values of d ``head_dim`` (D = H d), taps ``short_conv_kernel_size``, r the
low-rank gates' rank+ (d); nh ``num_attention_heads`` of nope + rope
(``qk_nope_head_dim`` + ``qk_rope_head_dim``) with values of v ``v_head_dim`` over a
compressed vector of rank ``kv_lora_rank``; V ``vocab_size``, L
``num_hidden_layers``, eps ``rms_norm_eps``. A row holds S token ids x.

1. ``h = E[x]``; no positional term anywhere (``mla_use_nope``).
2. A layer l: ``h = h + Mixer_l(RMSNorm(h))``, ``h = h + FFN_l(RMSNorm(h))``;
   ``RMSNorm`` has a gain.
3. Which mixer: layer l (1-indexed) is KDA where ``kda_layers`` lists it, latent
   attention where ``full_attn_layers`` does; a depth below the lists' length
   takes their entries up to it+.
4. KDA: ``q, k, v = silu(conv(u W_{q,k,v}))``+, ``conv(a)_t = sum_j w_j a_{t - (taps -
   1 - j)}`` a channel, no bias+, over the taps whose token lies in t's document+;
   q and k divided by their L2 norm a head (``x / sqrt(sum x^2 + 1e-6)``+), q times
   ``d^-1/2``; ``g_t = -exp(A_log) softplus((u W_fa) W_fb + dt_b)`` a channel (``A_log``
   a head's scalar, ``dt_b`` a channel's), ``beta_t = sigmoid(u W_beta)`` a head;
   the state a head ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
   v_t^T`` ``[d, d]``, ``S`` = 0 before a document's first token+, ``o_t = S_t^T q_t``;
   ``y = (RMSNorm_d(o) * sigmoid((u W_ga) W_gb)) W_o``+: the norm over a head's
   values, one learned gain of d.
5. Latent attention: ``q = u W_q`` as nh heads of nope + rope; ``[c, k_r] = u
   W_kva`` (rank, rope), ``[k_n, v] = RMSNorm(c) W_kvb`` as nh heads of nope and v;
   a head's key ``[k_n, k_r]`` (``k_r`` shared by the heads, NOT turned), ``softmax(q
   k^T (nope + rope)^-1/2)`` over the keys at or before the query in its
   document+, then ``W_o``.
6. FFN: layers ``l < first_k_dense_replace`` ``(silu(u W_gate) * (u W_up)) W_down``
   of F; the others ``s = sigmoid(u W_r)``, the k largest of ``s + b`` chosen (``b``
   moves by load and has no gradient; one group: a plain top-k), the chosen ``s``
   over their sum (``moe_renormalize``) times ``routed_scaling_factor``, the
   weighted sum of the chosen experts (gated SiLU of I), plus ``num_shared_experts``
   shared experts as one MLP on every token.
7. After the last layer an RMSNorm, then the untied head; the loss is the mean
   cross-entropy of position i's logits against ``x_{i+1}``.

**A chip's share** (the file's ``share`` block): this chip holds experts ``lo ..
lo + Eh`` of a router of E (``assumed.share_rank`` x Eh) and a slice of the
vocabulary's rows. What the absent experts would add is LEFT OUT and that
partial result goes on to the next layer; embedding, head and loss are over the
slice. Nothing stands in for the other chips. Without a ``share`` block every
expert is held.

It imports nothing of the program under test and nothing of the benchmark, and
exports what every reference file exports (benchmark/reference/gpt2.py lists
them), ``kda_flops_per_row`` / ``kda_bytes_per_row`` for the roofline reader,
``mla_pairs`` / ``mla_pair_flops`` and ``expert_product_flops_per_row``. Departures:
random weights from a seed (`make_weights`); memory only: the recurrence a token
at a time (as the recurrence: NOT the chunked form a program may use) inside
blocks of `SCAN_BLOCK` tokens, each made again in its backward; blocks of
`QUERY_BLOCK` queries against the row's keys; rows in blocks through the MLPs and
the head; passes of experts; ``jax.checkpoint`` around them; the gradient a layer
at a time from the last to the first (`loss_and_gradient`); and the controls:
``fp8`` rounds every matmul operand to float8_e4m3fn, ``bf16_state`` the
recurrence's carried state to bfloat16 after every token.

Weights are one flat dict. The stack is cut into STRETCHES of consecutive layers
of one mixer and one FFN, ``r0``, ``r1``, ... (five layers: ``r0`` the dense KDA
layer, ``r1`` two KDA expert layers, ``r2`` the latent expert layer, ``r3`` a KDA
expert layer), a stretch's layers stacked on a leading axis. Every stretch
``norm1 norm2 [n, C]``; a dense one ``w_gate w_up [n, C, F]``, ``w_down [n, F, C]``; an
expert one ``router [n, C, E]``, ``router_bias [n, E]``, ``e_gate e_up [n, Eh, C, I]``,
``e_down [n, Eh, I, C]``, ``s_gate s_up [n, C, ns I]``, ``s_down [n, ns I, C]``; a KDA one
``wq wk wv [n, C, D]``, ``conv_q conv_k conv_v [n, taps, D]``, ``w_fa w_ga [n, C, r]``,
``w_fb w_gb [n, r, D]``, ``dt_b [n, D]``, ``A_log [n, H]``, ``w_beta [n, C, H]``, ``norm_o
[n, d]``, ``wo [n, D, C]``; a latent one ``wq [n, C, nh (nope + rope)]``, ``wkva [n, C,
rank + rope]``, ``kv_norm [n, rank]``, ``wkvb [n, rank, nh (nope + v)]``, ``wo [n, nh v,
C]``; and ``embed [V, C]``, ``head [C, V]``, ``norm_f [C]``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

Weights = Dict[str, jax.Array]

#: tokens of a block of the recurrence and blocks of a block of blocks (each made
#: again in its backward: a block's tokens hold a few states each, ``[H, d, d]``),
#: heads of a group of a KDA layer's heads, queries of a block of the attention
#: scores, rows of a block through an MLP and the head's loss, and the largest
#: [experts of a pass, tokens, I] float32 intermediate in elements (memory only)
SCAN_BLOCK = 32
SCAN_BLOCKS = 32
HEAD_GROUP = 8
QUERY_BLOCK = 128
TOKEN_BLOCK = 2048
PASS_ELEMENTS = 2 ** 26


def sizes(config: dict) -> dict:
    """The published keys this file reads, under short names."""
    a, lin = config.get("assumed", {}), config["linear_attn_config"]
    share = config.get("share")
    held = int(config["num_experts"])
    published = int(share["published"].get("num_experts", held)) if share else held
    L = int(config["num_hidden_layers"])
    kda, full = list(lin["kda_layers"]), list(lin["full_attn_layers"])
    if (config.get("hidden_act", "silu") != "silu" or config.get("tie_word_embeddings")
            or not config.get("mla_use_nope") or config.get("q_lora_rank") is not None
            or config.get("rope_scaling") is not None
            or int(config.get("num_nextn_predict_layers", 0))
            or (config.get("num_expert_group", 1), config.get("topk_group", 1)) != (1, 1)
            or config.get("moe_router_activation_func") != "sigmoid"
            or sorted(kda + full) != list(range(1, len(kda) + len(full) + 1))
            or not 0 < L <= len(kda) + len(full)):
        raise ValueError("SiLU, an untied head, latent attention without positions or "
                         "query compression, no prediction module, one group of experts "
                         "under sigmoid scores, every layer in exactly one of the two lists")
    d, H = int(lin["head_dim"]), int(lin["num_heads"])
    D0 = int(config["first_k_dense_replace"])
    s = dict(
        V=int(config["vocab_size"]), C=int(config["hidden_size"]), L=L, D0=D0,
        F=int(config["intermediate_size"]), I=int(config["moe_intermediate_size"]),
        E=published, Eh=held, lo=int(a.get("share_rank", 0)) * held,
        k=int(config["num_experts_per_token"]), ns=int(config["num_shared_experts"]),
        renorm=bool(config["moe_renormalize"]), scale=float(config["routed_scaling_factor"]),
        H=H, d=d, D=H * d, taps=int(lin["short_conv_kernel_size"]),
        r=d,
        nh=int(config["num_attention_heads"]), rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]), rope=int(config["qk_rope_head_dim"]),
        v=int(config["v_head_dim"]), eps=float(config["rms_norm_eps"]),
        kinds=tuple(("kda" if l in kda else "latent", "dense" if l <= D0 else "experts")
                    for l in range(1, L + 1)),
        sep=a.get("separator"))
    s["hd"] = s["nope"] + s["rope"]
    return s


def stretches(s: dict) -> List[Tuple[str, Tuple[str, str], int]]:
    """The stack as consecutive layers of one mixer and one FFN: (name, (mixer,
    ffn), layers)."""
    out: List[Tuple[str, Tuple[str, str], int]] = []
    for kind in s["kinds"]:
        if out and out[-1][1] == kind:
            out[-1] = (out[-1][0], kind, out[-1][2] + 1)
        else:
            out.append((f"r{len(out)}", kind, 1))
    return out


def key_of(seed: int):
    """PRNG key of a seed of any size: the bits above 31 are folded in,
    not dropped."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _shapes(kind: Tuple[str, str], s: dict) -> Dict[str, tuple]:
    """A layer's matrices by name (the norms, the convolutions and the small
    arrays apart): what `make_weights` draws and `matmul_params` counts."""
    C, D, r = s["C"], s["D"], s["r"]
    mixer = ({"wq": (C, D), "wk": (C, D), "wv": (C, D), "w_fa": (C, r), "w_fb": (r, D),
              "w_ga": (C, r), "w_gb": (r, D), "w_beta": (C, s["H"]), "wo": (D, C)}
             if kind[0] == "kda" else
             {"wq": (C, s["nh"] * s["hd"]), "wkva": (C, s["rank"] + s["rope"]),
              "wkvb": (s["rank"], s["nh"] * (s["nope"] + s["v"])), "wo": (s["nh"] * s["v"], C)})
    ffn = ({"w_gate": (C, s["F"]), "w_up": (C, s["F"]), "w_down": (s["F"], C)}
           if kind[1] == "dense" else
           {"router": (C, s["E"]), "e_gate": (s["Eh"], C, s["I"]), "e_up": (s["Eh"], C, s["I"]),
            "e_down": (s["Eh"], s["I"], C), "s_gate": (C, s["ns"] * s["I"]),
            "s_up": (C, s["ns"] * s["I"]), "s_down": (s["ns"] * s["I"], C)})
    return {**mixer, **ffn}


def make_weights(key, config: dict, dtype=jnp.bfloat16) -> Weights:
    """Random weights from ``key_of(seed)`` in the dtype they are trained from.
    Pure and jittable with the key traced. KDA's small arrays as Mamba-2's
    initialisers draw them+: ``A_log`` the log of a uniform draw in [1, 16], ``dt_b``
    the inverse softplus of a log-uniform draw in [1e-3, 1e-1]; the convolutions
    uniform in +-1/sqrt(taps); the decay's low-rank pair wide enough that a
    token's log-decays differ by channel (a deviation near 1 in front of the
    softplus); latent attention's query and key projections wide enough that
    scores have a deviation near 3 (peaked heads); residual projections at
    GPT-2's 1/sqrt(2 L); norm gains near 1 and a small random router bias so that
    no term can be dropped unseen."""
    s = sizes(config)
    C = s["C"]
    keys = iter(jax.random.split(key, 64 + 48 * len(stretches(s))))

    def normal(shape, std, mean=0.0):
        return (mean + jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    resid = 0.02 / math.sqrt(2 * s["L"])
    # a normed row has unit RMS: q . k / sqrt(hd) has a deviation near 3
    wide = {"wq": math.sqrt(3.0 / C), "wkva": math.sqrt(3.0 / C), "wkvb": s["rank"] ** -0.5,
            "w_fa": C ** -0.5, "w_fb": s["r"] ** -0.5}
    w: Weights = {"embed": normal((s["V"], C), 0.02), "head": normal((C, s["V"]), 0.02),
                  "norm_f": normal((C,), 0.05, 1.0)}
    for name, kind, n in stretches(s):
        p = lambda leaf: f"{name}.{leaf}"
        w.update({p("norm1"): normal((n, C), 0.05, 1.0), p("norm2"): normal((n, C), 0.05, 1.0)})
        for leaf, shape in _shapes(kind, s).items():
            std = resid if leaf in ("wo", "w_down", "e_down", "s_down") else 0.02
            if kind[0] == "latent" or leaf in ("w_fa", "w_fb"):
                std = wide.get(leaf, std)
            w[p(leaf)] = normal((n,) + shape, std)
        if kind[0] == "kda":
            dt = jnp.exp(uniform((n, s["D"]), math.log(1e-3), math.log(1e-1)))
            bound = s["taps"] ** -0.5
            w.update({
                **{p("conv_" + a): uniform((n, s["taps"], s["D"]), -bound, bound).astype(dtype)
                   for a in "qkv"},
                p("dt_b"): (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                p("A_log"): jnp.log(uniform((n, s["H"]), 1.0, 16.0)).astype(dtype),
                p("norm_o"): normal((n, s["d"]), 0.05, 1.0)})
        else:
            w[p("kv_norm")] = normal((n, s["rank"]), 0.05, 1.0)
        if kind[1] == "experts":
            w[p("router_bias")] = normal((n, s["E"]), 0.05)
    return w


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rounded(t, control):
    """The ``fp8`` control's rounding of one matmul operand (identity for the
    reference proper and the other control). Values stay float32."""
    if control != "fp8":
        if control not in (None, "bf16_state"):
            raise ValueError(f"unknown control {control!r}")
        return t
    scale = jnp.max(jnp.abs(t)) / 448.0 + 1e-30  # e4m3fn's largest
    low = (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    # straight through: the backward pass sees the rounded VALUES and is
    # itself computed in float32, the kindest form of a low-precision path
    return t + jax.lax.stop_gradient(low - t)


def in_blocks(fn, x, block: int, checkpoint: bool):
    """``fn`` over the leading axis of ``x`` (an array or a tuple of them) in
    blocks (memory only: the same arithmetic, a block's intermediates at a time)."""
    n = jax.tree.leaves(x)[0].shape[0]
    if not checkpoint or n <= block or n % block:
        return fn(x)
    split = lambda a: a.reshape((n // block, block) + a.shape[1:])
    out = jax.lax.map(jax.checkpoint(fn), jax.tree.map(split, x))
    return jax.tree.map(lambda o: o.reshape((n,) + o.shape[2:]), out)


def documents(ids, s: dict):
    """Each position's document in a packed row [B, S]: the separators before
    it (a separator ends its own document); one document without a separator."""
    if s["sep"] is None:
        return jnp.zeros(ids.shape, jnp.int32)
    ends = (ids == s["sep"]).astype(jnp.int32)
    return jnp.cumsum(ends, axis=1) - ends


def first_of_document(doc):
    """[B, S] bool: a row's first position or a document's first token."""
    before = jnp.concatenate([jnp.full_like(doc[:, :1], -1), doc[:, :-1]], axis=1)
    return doc != before


def short_conv(a, doc, conv):
    """Equation 4's convolution over a [B, S, channels], literally: tap j
    multiplies the token ``taps - 1 - j`` back, where that token is in the same
    document."""
    B, S, _ = a.shape
    taps = conv.shape[0]
    out = jnp.zeros_like(a)
    for j in range(taps):
        back = taps - 1 - j
        if back >= S:
            continue
        # the token ``back`` before t, and its document (none before the row)
        shifted = jnp.pad(a[:, :S - back], ((0, 0), (back, 0), (0, 0)))
        its_doc = jnp.pad(doc[:, :S - back], ((0, 0), (back, 0)), constant_values=-1)
        out = out + jnp.where((doc == its_doc)[..., None], shifted, 0.0) * conv[j]
    return out


def _token(S, xs, low: bool = False):
    """One token of equation 4's recurrence on the state ``[H, d, d]`` (keys x
    values)."""
    q, k, v, g, beta, first = xs                    # [H, d] x 4, [H], []
    S = jnp.exp(g)[:, :, None] * jnp.where(first, 0.0, S)
    S = S + beta[:, None, None] * k[:, :, None] * (
        v - jnp.einsum("hk,hkv->hv", k, S))[:, None, :]
    if low:
        # (``reduce_precision``: a cast there and back is one XLA may drop)
        S = S + jax.lax.stop_gradient(jax.lax.reduce_precision(S, 8, 7) - S)
    return S, jnp.einsum("hk,hkv->hv", q, S)


def _token_low(S, xs):
    return _token(S, xs, low=True)


def _tokens(S, xs):
    return jax.lax.scan(_token, S, xs)


def _tokens_low(S, xs):
    return jax.lax.scan(_token_low, S, xs)


def recurrence(q, k, v, g, beta, first, control=None, checkpoint: bool = False):
    """Equation 4's recurrence over ONE row, a token at a time: q, k, v, g ``[S, H,
    d]``, beta ``[S, H]``, first ``[S]`` -> o ``[S, H, d]``; ``control`` ``bf16_state``
    rounds the carried state to bfloat16 after every token. Memory only: blocks
    of `SCAN_BLOCK` tokens inside blocks of `SCAN_BLOCKS` blocks, each made again
    in its backward."""
    block = _tokens_low if control == "bf16_state" else _tokens
    S, H, d = q.shape
    xs = (q, k, v, g, beta, first)
    start = jnp.zeros((H, d, d), jnp.float32)
    outer = SCAN_BLOCK * SCAN_BLOCKS
    if not checkpoint or S <= SCAN_BLOCK or S % SCAN_BLOCK:
        return block(start, xs)[1]
    split = lambda n: lambda t: t.reshape((t.shape[0] // n, n) + t.shape[1:])
    blocks = lambda S0, xs: jax.lax.scan(jax.checkpoint(block), S0, xs)
    if S % outer:
        _, o = blocks(start, jax.tree.map(split(SCAN_BLOCK), xs))
        return o.reshape((S,) + o.shape[2:])
    _, o = jax.lax.scan(jax.checkpoint(blocks), start,
                        jax.tree.map(split(SCAN_BLOCKS), jax.tree.map(split(SCAN_BLOCK), xs)))
    return o.reshape((S,) + o.shape[3:])


def kda_mixer(u, doc, lw, s: dict, control=None, checkpoint: bool = False):
    """Equation 4 on the normed input u [B, S, C]. Memory only: a long row takes
    its heads in groups of `HEAD_GROUP` (a head reads no other head: the
    projections' columns, the convolutions' channels and the recurrence of a
    group at a time, a group made again in its backward)."""
    B, S, _ = u.shape
    H, d = s["H"], s["d"]
    r = lambda t: rounded(t, control)
    ck = jax.checkpoint if checkpoint else (lambda f: f)   # memory only
    grouped = checkpoint and H % HEAD_GROUP == 0 and H > HEAD_GROUP
    n = H // HEAD_GROUP if grouped else 1
    hg = H // n

    def unit(x):
        return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    def low(u, a, b):
        return r(r(u) @ r(a)) @ r(b)

    def heads_of(u, gw):
        """A group's heads: gw its columns of every array -> the gated, normed
        output ``[B, S, hg d]``."""
        heads = lambda t: t.reshape(B, S, hg, d)

        def made(w, conv):
            return heads(jax.nn.silu(short_conv(r(u) @ r(w), doc, conv)))
        q = unit(made(gw["wq"], gw["conv_q"])) * d ** -0.5
        k = unit(made(gw["wk"], gw["conv_k"]))
        v = made(gw["wv"], gw["conv_v"])
        g = -jnp.exp(gw["A_log"])[:, None] * heads(
            jax.nn.softplus(low(u, lw["w_fa"], gw["w_fb"]) + gw["dt_b"]))
        beta = jax.nn.sigmoid(r(u) @ r(gw["w_beta"]))
        one_row = lambda *xs: recurrence(*xs, control, checkpoint)
        o = jax.vmap(one_row)(q, k, v, g, beta, first_of_document(doc))
        gate = jax.nn.sigmoid(low(u, lw["w_ga"], gw["w_gb"]))
        return (rms_norm(o, lw["norm_o"], s["eps"]) * heads(gate)).reshape(B, S, hg * d)

    # a group's columns of the arrays whose last axis runs over the heads
    columns = lambda a: jnp.moveaxis(a.reshape(a.shape[:-1] + (n, a.shape[-1] // n)), -2, 0)
    gw = {name: columns(lw[name]) for name in (
        "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_fb", "w_gb", "dt_b", "A_log",
        "w_beta")}
    if not grouped:
        out = heads_of(u, {name: a[0] for name, a in gw.items()})
    else:
        out = jax.lax.map(ck(lambda gw: heads_of(u, gw)), gw)           # [n, B, S, hg d]
        out = jnp.moveaxis(out, 0, 2).reshape(B, S, H * d)
    return r(out) @ r(lw["wo"])


def latent_attention(u, doc, lw, s: dict, control=None, checkpoint: bool = False):
    """Equation 5 on the normed input u [B, S, C]. Memory only: a block of
    queries at a time against the row's keys."""
    B, S, _ = u.shape
    nh, hd, nope, vd, rank = s["nh"], s["hd"], s["nope"], s["v"], s["rank"]
    r = lambda t: rounded(t, control)
    q = (r(u) @ r(lw["wq"])).reshape(B, S, nh, hd)
    kva = r(u) @ r(lw["wkva"])
    kv = (r(rms_norm(kva[..., :rank], lw["kv_norm"], s["eps"])) @ r(lw["wkvb"])).reshape(
        B, S, nh, nope + vd)
    # the shared part of the keys beside each head's own, unturned
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        kva[:, :, None, rank:], (B, S, nh, s["rope"]))], axis=-1)
    kr, vr = r(k), r(kv[..., nope:])
    at = jnp.arange(S)

    def block(qs):
        qb, q_at = qs                                    # [n, B, nh, hd], [n]
        sc = jnp.einsum("qbnd,bknd->bnqk", r(qb), kr) * hd ** -0.5
        seen = ((at[None, None, :] <= q_at[None, :, None])
                & (doc[:, None, :] == jnp.take(doc, q_at, axis=1)[:, :, None]))
        sc = jnp.where(seen[:, None], sc, -jnp.inf)
        return jnp.einsum("bnqk,bknd->qbnd", r(jax.nn.softmax(sc, axis=-1)), vr)

    out = in_blocks(block, (q.swapaxes(0, 1), at), QUERY_BLOCK, checkpoint)
    return r(out.swapaxes(0, 1).reshape(B, S, nh * vd)) @ r(lw["wo"])


def gated_mlp(h, wg, wu, wd, control=None):
    r = lambda t: rounded(t, control)
    return r(jax.nn.silu(r(h) @ r(wg)) * (r(h) @ r(wu))) @ r(wd)


def route(h, w_router, bias, s: dict, control=None):
    """h [T, C] -> (weight [T, E]: each token's routing weight for each PUBLISHED
    expert, 0 where it did not choose it; assignments per expert [E])."""
    E, k = s["E"], s["k"]
    score = jax.nn.sigmoid(rounded(h, control) @ rounded(w_router, control))
    _, chosen = jax.lax.top_k(score + jax.lax.stop_gradient(bias), k)
    top = jnp.take_along_axis(score, chosen, axis=-1)
    if s["renorm"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    onehot = jax.nn.one_hot(chosen, E, dtype=jnp.float32)              # [T, k, E]
    return (jnp.einsum("tk,tke->te", top * s["scale"], onehot),
            jnp.sum(onehot, axis=(0, 1)))


def held_experts(h, weight, lw, s: dict, control=None, checkpoint: bool = False):
    """sum over the HELD experts e of weight[:, e] x E_e(h): every held expert on
    every token under the mask, a few a pass."""
    T, Eh = h.shape[0], s["Eh"]
    per = max(1, min(Eh, PASS_ELEMENTS // (T * s["I"])))
    while Eh % per:
        per -= 1
    r = lambda t: rounded(t, control)

    def one_pass(acc, xs):
        wg, wu, wd, w = xs                       # [per,C,I] [per,C,I] [per,I,C] [per,T]
        mid = r(jax.nn.silu(jnp.einsum("th,ehf->etf", r(h), r(wg)))
                * jnp.einsum("th,ehf->etf", r(h), r(wu)))
        y = jnp.einsum("etf,efh->eth", mid, r(wd))
        return acc + jnp.einsum("eth,et->th", y, w), None

    if checkpoint:  # departure: memory only, same arithmetic
        one_pass = jax.checkpoint(one_pass)
    group = lambda a: a.reshape((Eh // per, per) + a.shape[1:])
    held = weight[:, s["lo"]:s["lo"] + Eh]
    out, _ = jax.lax.scan(one_pass, jnp.zeros_like(h),
                          (group(lw["e_gate"]), group(lw["e_up"]),
                           group(lw["e_down"]), group(held.T)))
    return out


def ffn(h, lw, kind: str, s: dict, control=None, checkpoint: bool = False,
        shared: bool = True):
    """Equation 6 over rows h [T, C]; ``shared`` False leaves the shared expert
    out (a second chip's share of a layer: the shared expert counts once)."""
    rows = lambda wg, wu, wd: in_blocks(
        lambda t: gated_mlp(t, lw[wg], lw[wu], lw[wd], control), h, TOKEN_BLOCK, checkpoint)
    if kind == "dense":
        return rows("w_gate", "w_up", "w_down")
    weight, _ = route(h, lw["router"], lw["router_bias"], s, control)
    out = held_experts(h, weight, lw, s, control, checkpoint)
    return out + rows("s_gate", "s_up", "s_down") if shared else out


def layer(x, doc, lw, kind: Tuple[str, str], s: dict, control=None,
          checkpoint: bool = False):
    """One layer of ``kind`` on x [B, S, C] (equation 2)."""
    B, S, C = x.shape
    ck = jax.checkpoint if checkpoint else (lambda f: f)   # memory only
    mixer = kda_mixer if kind[0] == "kda" else latent_attention
    x = x + ck(lambda x, lw: mixer(
        rms_norm(x, lw["norm1"], s["eps"]), doc, lw, s, control, checkpoint))(x, lw)
    return x + ck(lambda x, lw: ffn(
        rms_norm(x, lw["norm2"], s["eps"]).reshape(B * S, C),
        lw, kind[1], s, control, checkpoint))(x, lw).reshape(B, S, C)


def _stack(w: Weights, name: str) -> Weights:
    """A stretch's stacked weights under the bare names (as they are held)."""
    prefix = name + "."
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def _f32(lw: Weights) -> Weights:
    return {k: v.astype(jnp.float32) for k, v in lw.items()}


@functools.lru_cache(maxsize=None)
def _stretch(kind: Tuple[str, str], sizes_key, control, checkpoint: bool):
    """A stretch's layers one after another -> (the stream after them, each
    layer's input). One jitted function a kind and size (a caller outside any
    ``jit`` then compiles a stretch's scan once a shape, not once a call)."""
    s = dict(sizes_key)

    def run(x, doc, stack):
        return jax.lax.scan(
            lambda x, lw: (layer(x, doc, _f32(lw), kind, s, control, checkpoint), x), x, stack)
    return jax.jit(run)


def _key(s: dict):
    return tuple(sorted(s.items()))


def stream(w: Weights, ids, config: dict, *, control=None, checkpoint: bool = False):
    """The stream after the last layer [B, S, C] (equations 1-6)."""
    s = sizes(config)
    doc = documents(ids, s)
    x = w["embed"].astype(jnp.float32)[ids]
    for name, kind, _ in stretches(s):
        x, _ = _stretch(kind, _key(s), control, checkpoint)(x, doc, _stack(w, name))
    return x


def head_logits(hw: Weights, x, s: dict, control=None):
    """Equation 7's logits; hw: ``norm_f`` and ``head``."""
    return rounded(rms_norm(x, hw["norm_f"], s["eps"]), control) @ rounded(hw["head"], control)


def _head(w: Weights) -> Weights:
    return {k: w[k].astype(jnp.float32) for k in ("norm_f", "head")}


def forward(w: Weights, ids, config: dict, *, control=None, checkpoint: bool = False):
    """float32 logits [B, S, V]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        x = stream(w, ids, config, control=control, checkpoint=checkpoint)
        return head_logits(_head(w), x, s, control)


def head_loss(hw: Weights, x, ids, s: dict, control=None, checkpoint: bool = False):
    """Equation 7's loss from the final stream x [B, S, C]."""
    B, S, C = x.shape
    targets = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
    weight = jnp.broadcast_to((jnp.arange(S) < S - 1) / (B * (S - 1.0)), (B, S))

    def weighted_nll(block):
        xb, tb, wb = block
        logp = jax.nn.log_softmax(head_logits(hw, xb, s, control), axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0] * wb
    total = in_blocks(weighted_nll, (x.reshape(-1, C), targets.reshape(-1),
                                     weight.reshape(-1)), TOKEN_BLOCK, checkpoint)
    return jnp.sum(total)


def next_token_loss(w: Weights, ids, config: dict, *, control=None,
                    checkpoint: bool = False):
    """Equation 7's loss."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        x = stream(w, ids, config, control=control, checkpoint=checkpoint)
        return head_loss(_head(w), x, ids, s, control, checkpoint)


def loss_and_gradient(w: Weights, ids, config: dict, *, control=None):
    """(loss, l2 norm of the gradient over every weight, sign of every gradient
    element as int8 under the weights' names). The gradient of
    ``next_token_loss`` by the chain rule a layer at a time, last to first, a
    ``lax.scan`` a stretch each way (memory only: one layer's float32 weights and
    gradient at a time, and the compiler is not free to run every layer's
    forward first). The router's bias enters under ``stop_gradient``: its signs
    are exactly 0."""
    s = sizes(config)
    doc = documents(ids, s)
    # (memory only, and only where a row is longer than a block)
    blocks = ids.shape[1] > QUERY_BLOCK
    signs, sq = {}, []
    sign = lambda g: jnp.sign(g).astype(jnp.int8)
    with jax.default_matmul_precision("highest"):
        x, embedded = jax.vjp(lambda e: e[ids], w["embed"].astype(jnp.float32))
        inputs = []
        for name, kind, _ in stretches(s):
            x, x_in = _stretch(kind, _key(s), control, blocks)(x, doc, _stack(w, name))
            inputs.append(x_in)
        loss, (g_head, dx) = jax.value_and_grad(
            lambda hw, x: head_loss(hw, x, ids, s, control, blocks), argnums=(0, 1))(_head(w), x)
        for leaf, g in g_head.items():
            signs[leaf] = sign(g)
            sq.append(jnp.sum(jnp.square(g)))
        for (name, kind, _), x_in in reversed(list(zip(stretches(s), inputs))):
            def back_one(dx, xs):
                lw, x_l = xs
                _, back = jax.vjp(
                    lambda x, lw: layer(x, doc, lw, kind, s, control, blocks), x_l, _f32(lw))
                dx, g = back(dx)
                return dx, (jax.tree.map(sign, g),
                            sum(jnp.sum(jnp.square(v)) for v in g.values()))
            dx, (sg, total) = jax.lax.scan(back_one, dx, (_stack(w, name), x_in), reverse=True)
            signs.update({f"{name}.{leaf}": v for leaf, v in sg.items()})
            sq.append(jnp.sum(total))
        d_embed = embedded(dx)[0]
        signs["embed"] = sign(d_embed)
        sq.append(jnp.sum(jnp.square(d_embed)))
    return loss, jnp.sqrt(sum(sq)), signs


# -- what the step requires, for the share of peak and the roofline ------------

def matmul_params(config: dict) -> float:
    """Parameters that multiply each token HERE: every layer's mixer matrices, a
    dense layer's MLP, an expert layer's router, its shared expert and the routed
    experts at ``num_experts_per_token x held / published`` a token (a token's
    chosen experts that live on other chips multiply it there, not here), and the
    head once. The embedding is a lookup; norms, convolutions and the small
    arrays are not counted."""
    s = sizes(config)
    total = float(s["C"] * s["V"])
    for kind in s["kinds"]:
        for leaf, shape in _shapes(kind, s).items():
            count = math.prod(shape)
            if leaf in ("e_gate", "e_up", "e_down"):     # Eh x one expert's matrix
                count = count / s["Eh"] * s["k"] * s["Eh"] / s["E"]
            total += count
    return total


def kda_flops_per_row(config: dict) -> dict:
    """Operations ONE token costs ONE KDA layer's recurrence whatever implements
    it, over the H x d x d state elements: forward the decay (1), ``k^T S`` and its
    sum (2), the rank-1 write (``k (x) u`` and the sum, 2) and the read-out (``q^T
    S`` and its sum, 2): 7 an element; backward the state made again without its
    read-out (5), and for each of the four products its two cotangents (the
    read-out's dq and dS, the write's dk and du, ``k^T S``'s dk and dS, 4 each; the
    decay's dg and dS, 1 + 1): 14, so 19 an element. A chunk size appears in
    neither."""
    s = sizes(config)
    cells = s["H"] * s["d"] * s["d"]
    return {"forward": 7.0 * cells, "backward": 19.0 * cells}


def kda_bytes_per_row(config: dict, itemsize: int = 2) -> dict:
    """Bytes ONE token's recurrence MUST move in ONE layer whatever implements it:
    forward it reads q, k, v (D each) at ``itemsize``, ``g`` (D, float32) and
    ``beta`` (H, float32) and writes o (D); backward it reads those and ``do`` and
    writes dq, dk, dv (D each), ``dg`` (D, float32) and ``d beta`` (H, float32)."""
    s = sizes(config)
    D, H = s["D"], s["H"]
    return {"forward": itemsize * 4 * D + 4 * D + 4 * H,
            "backward": itemsize * 8 * D + 8 * D + 8 * H}


def mla_pairs(doc_lens) -> int:
    """The (query, key) pairs that exist under causal AND same document in a
    row whose pieces of documents have the lengths ``doc_lens``. Exact integers."""
    return sum(int(n) * (int(n) + 1) // 2 for n in doc_lens)


attention_pairs = mla_pairs


def mla_pair_flops(config: dict) -> dict:
    """FLOPs ONE (query, key) pair of ONE head costs each kernel of the
    two-width attention core: the forward's QK^T at the keys' width and PV at
    the values', 2 hd + 2 v (640 at 192 / 128); the fused backward's five
    matmuls, the scores again, dK and dQ at the keys' width, dV and dP at the
    values', 6 hd + 4 v (1,664)."""
    s = sizes(config)
    return {"forward": 2.0 * s["hd"] + 2.0 * s["v"],
            "backward": 6.0 * s["hd"] + 4.0 * s["v"], "heads": s["nh"]}


attention_pair_flops = mla_pair_flops


def expert_product_flops_per_row(config: dict) -> float:
    """FLOPs ONE product of a routed expert's MLP costs ONE routed row (the
    contract is benchmark/reference/olmoe.py's): 2 x 2304 x 1024."""
    s = sizes(config)
    return 2.0 * s["C"] * s["I"]


def train_flops_per_token(config: dict, seq: int) -> float:
    """FLOPs one trained token REQUIRES of this chip at sequence length ``seq``
    (the contract is benchmark/reference/gpt2.py's), a row taken as one
    document: 6 per matmul parameter (`matmul_params`); each KDA layer's
    recurrence, forward and backward (`kda_flops_per_row`); each latent layer's
    causal pairs at `mla_pair_flops`' forward and backward a pair and head.
    Packed documents hide more, which is traffic's and not counted. The
    convolutions, the norms and the gates are not counted."""
    s = sizes(config)
    kda, pair = kda_flops_per_row(config), mla_pair_flops(config)
    mixers = [kind[0] for kind in s["kinds"]]
    return (6.0 * matmul_params(config)
            + mixers.count("kda") * (kda["forward"] + kda["backward"])
            + mixers.count("latent") * (pair["forward"] + pair["backward"]) * pair["heads"]
            * mla_pairs([seq]) / float(seq))
