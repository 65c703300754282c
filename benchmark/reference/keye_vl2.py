"""Kwai-Keye's Keye-VL-2.0-30B-A3B (``model_type`` ``KeyeVL2``), the LANGUAGE
MODEL of the checkpoint, in plain ``jax.numpy``: a rotary MoE decoder whose
every layer attends a LEARNED SELECTION of keys (DeepSeek Sparse Attention:
the "lightning indexer" and the token selection of the DeepSeek-V3.2-Exp
report, DeepSeek-AI 2025), trained by the report's SPARSE-TRAINING stage:
``next_token_loss`` is ``L_LM + L_I``. Read from a configuration file with
Hugging Face's key names, as ONE chip's share of a deployment in which several
chips share each layer. The vision tower is not built: a batch is token ids.

What ``train_flops_per_token`` counts, up front: the MAIN attention over the
SELECTED pairs (a query's ``min(visible, topk)`` keys), the indexer's scores
over the VISIBLE pairs (every one has to be scored before one is picked), the
indexer's KL over the selected pairs, and the KL's target (every main head's
score once more) over the selected pairs.

Written from the configuration's keys; what it has no key for is marked + and
stands in the file's ``assumed`` in the same words. ``N_*`` is RMSNorm (eps
``rms_norm_eps``) with a gain; no bias on any matmul; H ``hidden_size``, nh
query heads over nkv key heads of hd = ``head_dim``; ``doc(i)`` the packed
document of position i (a document ends WITH its separator token,
``assumed.separator``; none = a row is one document); ``visible(t, s)`` = ``s
<= t and doc(s) = doc(t)``.

1. Main projections (+: the Qwen3-MoE family's, which the widths are): ``u =
   N_in(x)``; ``q = u Wq`` [nh x hd], ``k = u Wk``, ``v = u Wv`` [nkv x hd];
   ``q, k = N_q(q), N_k(k)`` per head, one gain of hd for all heads (+).
   Rope by sections (+: Qwen2-VL's layout of ``rope_scaling.mrope_section``
   [16, 24, 24]): hd / 2 frequency pairs ``theta^(-i / (hd/2))``, the pair
   (i, i + hd/2); pair i is turned by the position stream of its section
   (pairs 0-15 temporal, 16-39 height, 40-63 width). A text token's three
   positions are all its index in the row: plain rope.
2. The indexer (sizes from ``sa_config``; J = ``indexer_num_heads``, d =
   ``indexer_head_dim``, ONE key head). With ``xs = stop_gradient(u)`` (+: this
   model has no q-LoRA, so the indexer's queries are projected from the layer's
   input): ``qI[t] = xs_t W_qI`` [J x d]; ``kI[s] = LayerNorm(xs_s W_kI)`` [d]
   (+: gain and bias, eps 1e-6); rope on qI and kI over all d dims by the
   temporal stream (+); ``w[t] = xs_t W_w`` [J] times ``J^-1/2 d^-1/2``.
   ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])`` for visible s, minus
   infinity elsewhere.
3. The selection: ``S_t`` = the ``topk`` visible s of largest ``I[t, s]``, ties
   to the lower s (``jax.lax.top_k`` per query over the masked scores); every
   visible s where there are ``topk`` or fewer. (+: ``q_chunk_size`` /
   ``kv_chunk_size`` are the tiles the released code scores in, with no effect
   on ``S_t``; its Hadamard rotation and fp8 of qI, kI are left out.)
4. ``a[t, h] = sum over s in S_t of softmax over S_t of (q[t, h] . k[s, g(h)] /
   sqrt(hd)) v[s, g(h)]``, key head ``g(h) = h // (nh / nkv)``: one selection
   for all heads. ``x = x + a Wo``.
5. ``u = N_post(x)``; ``p = softmax(u Wr)`` in float32 over all PUBLISHED
   experts; chosen = the ``num_experts_per_tok`` largest; ``w_e = p_e /
   sum_chosen p``; ``x = x + sum over (chosen AND held) w_e E_e(u)``, ``E_e`` a
   gated SiLU MLP of ``moe_intermediate_size``. No shared expert; router
   auxiliary loss 0 (+).
6. ``L_LM`` = next-token cross-entropy of ``N_f(x) W_head``, float32, over the
   positions that have a next token, through the selected keys alone.
   ``L_I = (1 / (rows x L)) sum over layers and t of KL(p_t || softmax over S_t
   of I[t, .])``, ``p_t[s] = (1 / nh) sum over heads of the main attention's
   own probability`` under stop_gradient (+: the published sparse-training
   objective; coefficient 1, one optimizer, one rate). ``L_LM`` gives NO
   gradient to W_qI, W_kI, W_w or the LayerNorm; ``L_I`` gives none to anything
   else.

THE SHARE: as benchmark/reference/sdar_moe.py states it (``share`` block,
``num_experts`` and ``vocab_size`` of the file are what THIS chip holds, the
router keeps its published width, what the absent experts would add is left
out).

float32 throughout, ``jax.default_matmul_precision("highest")``, no kernels.
It imports nothing of the program under test and nothing of the benchmark, and
exports what every reference file exports (benchmark/reference/gpt2.py lists
them), ``expert_product_flops_per_row``, ``attention_pair_flops``,
``dsa_pairs`` and ``selection``. Departures: random weights from a seed (norm
gains near 1, the QK-norm gains near 2, residual projections at GPT-2's
1/sqrt(2 L), the embedding at unit RMS as the SDAR file argues it); memory
only: ``jax.checkpoint`` around passes of experts and blocks of queries, the
gradient a layer at a time from the last to the first; and the ``fp8`` control,
which rounds every matmul operand to float8_e4m3fn.

Weights are one flat dict, per-layer arrays stacked on a leading axis (n =
layers, I = moe_intermediate_size, E = published experts, Eh = held)::

    embed [V,H]  head [H,V]  norm_f [H]
    norm1 norm2 [n,H]  q_norm k_norm [n,hd]
    wq [n,H,nh*hd]  wk wv [n,H,nkv*hd]  wo [n,nh*hd,H]
    idx_wq [n,H,J*d]  idx_wk [n,H,d]  idx_ww [n,H,J]  idx_ln_g idx_ln_b [n,d]
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
#: queries of a block of the attention scores; rows of a block through the
#: head's loss (memory only)
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
INDEXER_LN_EPS = 1e-6


def sizes(config: dict) -> dict:
    """The published keys this file reads, under short names."""
    a = config.get("assumed", {})
    share = config.get("share")
    held = int(config["num_experts"])
    published = int(share["published"].get("num_experts", held)) if share else held
    scaling = config.get("rope_scaling") or {}
    sections = tuple(int(n) for n in scaling.get("mrope_section") or ())
    sa = config["sa_config"]
    hd = int(config["head_dim"])
    if (scaling.get("rope_type", "default") != "default" or len(sections) != 3
            or sum(sections) != hd // 2 or config.get("hidden_act", "silu") != "silu"
            or config.get("use_sliding_window") or config.get("mlp_only_layers")
            or config.get("decoder_sparse_step", 1) != 1 or config.get("attention_bias")
            or config.get("tie_word_embeddings")
            or int(sa.get("indexer_num_kv_heads", 1)) != 1):
        raise ValueError("default rope by three sections that add up to head_dim / "
                         "2, SiLU, no window, no bias, an untied head, every layer "
                         "an expert layer, one indexer key head")
    sep = a.get("separator")
    return dict(
        V=int(config["vocab_size"]), H=int(config["hidden_size"]),
        L=int(config["num_hidden_layers"]), I=int(config["moe_intermediate_size"]),
        E=published, Eh=held, lo=int(a.get("share_rank", 0)) * held,
        k=int(config["num_experts_per_tok"]),
        renorm=bool(config.get("norm_topk_prob", True)),
        nh=int(config["num_attention_heads"]), nkv=int(config["num_key_value_heads"]),
        hd=hd, eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        sections=sections, J=int(sa["indexer_num_heads"]), d=int(sa["indexer_head_dim"]),
        topk=int(sa["topk"]), sep=None if sep is None else int(sep))


def key_of(seed: int):
    """PRNG key of a seed of any size: the bits above 31 are folded in,
    not dropped."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _attention_shapes(s: dict) -> dict:
    H, q, kv = s["H"], s["nh"] * s["hd"], s["nkv"] * s["hd"]
    return {"wq": (H, q), "wk": (H, kv), "wv": (H, kv), "wo": (q, H)}


def _indexer_shapes(s: dict) -> dict:
    H = s["H"]
    return {"idx_wq": (H, s["J"] * s["d"]), "idx_wk": (H, s["d"]), "idx_ww": (H, s["J"])}


def make_weights(key, config: dict, dtype=jnp.bfloat16) -> Weights:
    """Random weights from ``key_of(seed)`` in the dtype they are trained
    from. Pure and jittable with the key traced."""
    s = sizes(config)
    H, V, I, E, Eh, n = s["H"], s["V"], s["I"], s["E"], s["Eh"], s["L"]
    keys = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    resid = 0.02 / math.sqrt(2 * n)
    w = {"embed": normal((V, H), 1.0), "head": normal((H, V), 0.02),
         "norm_f": 1.0 + normal((H,), 0.05),
         "norm1": 1.0 + normal((n, H), 0.05), "norm2": 1.0 + normal((n, H), 0.05),
         # gains near 2: scores then have a standard deviation near 4
         "q_norm": 2.0 + normal((n, s["hd"]), 0.05),
         "k_norm": 2.0 + normal((n, s["hd"]), 0.05),
         "idx_ln_g": 1.0 + normal((n, s["d"]), 0.05), "idx_ln_b": normal((n, s["d"]), 0.05),
         "router": normal((n, H, E), 0.02),
         "w_gate": normal((n, Eh, H, I), 0.02), "w_up": normal((n, Eh, H, I), 0.02),
         "w_down": normal((n, Eh, I, H), resid)}
    for name, shape in {**_attention_shapes(s), **_indexer_shapes(s)}.items():
        w[name] = normal((n,) + shape, resid if name == "wo" else 0.02)
    return w


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def rotate(x, at, theta):
    """Rotary positions on x [B,S,n,hd]: ``at`` [B,S,hd/2] gives each
    frequency pair's own position; the pair (j, j + hd/2) is turned by the
    angle ``at[.., j] x theta^(-j/(hd/2))``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = (at.astype(jnp.float32) * freqs)[:, :, None, :]         # [B,S,1,half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def by_sections(positions, sections):
    """positions [3,B,S] -> [B,S,hd/2]: pair i's position is its section's
    stream's (equation 1)."""
    stream = np.repeat(np.arange(3), sections)
    return jnp.moveaxis(positions[stream], 0, -1)


def text_positions(ids):
    """A text token's three positions: all its index in the row, [3,B,S]."""
    return jnp.broadcast_to(jnp.arange(ids.shape[1]), (3,) + ids.shape)


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


def index_scores(qI, kI, wI, q_at, q_doc, k_doc):
    """Equation 2's ``I`` for a block of queries: qI [B,n,J,d], kI [B,S,d], wI
    [B,n,J]; q_at [n] the queries' positions, q_doc [B,n], k_doc [B,S]. Minus
    infinity where s is not visible to t."""
    dots = jnp.einsum("btjd,bsd->btjs", qI, kI)
    scores = jnp.sum(jax.nn.relu(dots) * wI[..., None], axis=2)
    seen = ((jnp.arange(k_doc.shape[1])[None, None, :] <= q_at[None, :, None])
            & (q_doc[:, :, None] == k_doc[:, None, :]))
    return jnp.where(seen, scores, -jnp.inf)


def select(scores, topk: int):
    """Equation 3: bool [B,n,S], True for the ``topk`` largest finite scores
    of a query (``jax.lax.top_k``: the lower index wins a tie), every finite
    one where there are fewer."""
    B, n, S = scores.shape
    vals, at = jax.lax.top_k(scores, min(topk, S))
    rows = jnp.arange(B)[:, None, None], jnp.arange(n)[None, :, None]
    return jnp.zeros((B, n, S), bool).at[rows[0], rows[1], at].set(vals > -jnp.inf)


def attention(x, doc, positions, lw, s: dict, control=None, checkpoint: bool = False,
              give_selection: bool = False):
    """The attention sub-block on the normed input x [B,L,H] -> (``a Wo``, this
    layer's ``sum_t KL`` of equation 6). ``give_selection``: the selection
    instead, bool [B,L,L]."""
    B, L, _ = x.shape
    nh, nkv, hd, J, d = s["nh"], s["nkv"], s["hd"], s["J"], s["d"]
    G = nh // nkv
    r = lambda t: rounded(t, control)
    h = r(x)
    q = rms_norm((h @ r(lw["wq"])).reshape(B, L, nh, hd), lw["q_norm"], s["eps"])
    k = rms_norm((h @ r(lw["wk"])).reshape(B, L, nkv, hd), lw["k_norm"], s["eps"])
    v = (h @ r(lw["wv"])).reshape(B, L, nkv, hd)
    at = by_sections(positions, s["sections"])
    q, k = rotate(q, at, s["theta"]), rotate(k, at, s["theta"])
    q = q.reshape(B, L, nkv, G, hd)
    kr, vr = r(k), r(v)
    # the indexer reads the layer's input as data
    hs = jax.lax.stop_gradient(h)
    temporal = jnp.broadcast_to(positions[0][..., None], (B, L, d // 2))
    qI = rotate((hs @ r(lw["idx_wq"])).reshape(B, L, J, d), temporal, s["theta"])
    kI = layer_norm(hs @ r(lw["idx_wk"]), lw["idx_ln_g"], lw["idx_ln_b"], INDEXER_LN_EPS)
    kI = rotate(kI[:, :, None, :], temporal, s["theta"])[:, :, 0, :]
    wI = (hs @ r(lw["idx_ww"])) * (J ** -0.5 * d ** -0.5)
    qI, kI = r(qI), r(kI)

    def block(xs):
        qb, qIb, wIb, q_at, q_doc = xs                 # a block of n queries
        scores = index_scores(qIb, kI, wIb, q_at, q_doc, doc)
        picked = select(jax.lax.stop_gradient(scores), s["topk"])
        if give_selection:
            return picked
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", r(qb), kr) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(picked[:, None, None], sc, -jnp.inf), axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", r(probs), vr)
        # equation 6's KL: the target is the heads' mean probability, as data
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=(1, 2)))        # [B,n,L]
        log_r = jax.nn.log_softmax(jnp.where(picked, scores, -jnp.inf), axis=-1)
        on = picked & (target > 0)
        kl = jnp.sum(jnp.where(on, target * (jnp.log(jnp.where(on, target, 1.0))
                                             - jnp.where(on, log_r, 0.0)), 0.0))
        return out, kl

    n = QUERY_BLOCK if (checkpoint and L > QUERY_BLOCK and L % QUERY_BLOCK == 0) else L
    rows = lambda t: jnp.moveaxis(t.reshape((B, L // n, n) + t.shape[2:]), 1, 0)
    xs = (rows(q), rows(qI), rows(wI), jnp.arange(L).reshape(L // n, n), rows(doc))
    got = jax.lax.map(jax.checkpoint(block) if checkpoint else block, xs)
    whole = lambda t: jnp.moveaxis(t, 0, 1).reshape((B, L) + t.shape[3:])
    if give_selection:
        return whole(got)
    a, kl = got
    return r(whole(a).reshape(B, L, nh * hd)) @ r(lw["wo"]), jnp.sum(kl)


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


def layer(x, doc, positions, lw, s: dict, control=None, checkpoint: bool = False):
    """One layer on x [B,L,H]; lw: this layer's slice. Returns (x', this
    layer's sum_t KL, assignments per published expert [E])."""
    B, L, H = x.shape
    a, kl = attention(rms_norm(x, lw["norm1"], s["eps"]), doc, positions, lw, s,
                      control, checkpoint)
    x = x + a
    h = rms_norm(x, lw["norm2"], s["eps"]).reshape(B * L, H)
    weight, load = route(h, lw["router"], s, control)
    m = held_experts(h, weight, lw, s, control, checkpoint)
    return x + m.reshape(B, L, H), kl, load


_LAYER_KEYS = ("norm1", "norm2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
               "idx_wq", "idx_wk", "idx_ww", "idx_ln_g", "idx_ln_b",
               "router", "w_gate", "w_up", "w_down")
_HEAD_KEYS = ("norm_f", "head")


def _f32(w: Weights, keys, i=None) -> Weights:
    """The weights under ``keys`` (layer ``i``'s slice of them) in float32."""
    return {k: (w[k] if i is None else w[k][i]).astype(jnp.float32) for k in keys}


def stream(w: Weights, ids, config: dict, *, positions=None, control=None,
           checkpoint: bool = False):
    """(the stream after the last layer [B,L,H], sum over layers and t of the
    KL, assignments per published expert [layers, E])."""
    s = sizes(config)
    positions = text_positions(ids) if positions is None else positions
    x = w["embed"].astype(jnp.float32)[ids]
    doc = documents(ids, s)
    kl, loads = 0.0, []
    for i in range(s["L"]):
        x, kl_i, load = layer(x, doc, positions, _f32(w, _LAYER_KEYS, i), s, control,
                              checkpoint)
        kl = kl + kl_i
        loads.append(load)
    return x, kl, jnp.stack(loads)


def head_logits(hw: Weights, x, s: dict, control=None):
    return rounded(rms_norm(x, hw["norm_f"], s["eps"]), control) @ rounded(hw["head"], control)


def head_loss(hw: Weights, x, ids, s: dict, control=None, checkpoint: bool = False):
    """L_LM: the mean next-token cross-entropy over the positions that have a
    next token (a row's last has none)."""
    B, L, H = x.shape
    targets = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
    has = jnp.broadcast_to(jnp.arange(L) < L - 1, (B, L)).astype(jnp.float32)

    def nll(block):
        xb, tb, mb = block
        logp = jax.nn.log_softmax(head_logits(hw, xb, s, control), axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0] * mb
    total = in_blocks(nll, (x.reshape(-1, H), targets.reshape(-1), has.reshape(-1)),
                      TOKEN_BLOCK, checkpoint)
    return jnp.sum(total) / jnp.sum(has)


def forward(w: Weights, ids, config: dict, *, positions=None, control=None,
            checkpoint: bool = False):
    """float32 logits [B,L,V]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        x = stream(w, ids, config, positions=positions, control=control,
                   checkpoint=checkpoint)[0]
        return head_logits(_f32(w, _HEAD_KEYS), x, s, control)


def loss_terms(w: Weights, ids, config: dict, *, positions=None, control=None,
               checkpoint: bool = False):
    """(L_LM, L_I) of equation 6."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        x, kl, _ = stream(w, ids, config, positions=positions, control=control,
                          checkpoint=checkpoint)
        return (head_loss(_f32(w, _HEAD_KEYS), x, ids, s, control, checkpoint),
                kl / ids.size)


def next_token_loss(w: Weights, ids, config: dict, *, positions=None, control=None,
                    checkpoint: bool = False):
    """The training objective under the contract's name: L_LM + L_I."""
    return sum(loss_terms(w, ids, config, positions=positions, control=control,
                          checkpoint=checkpoint))


def selection(w: Weights, ids, config: dict, layer_index: int = 0, *,
              positions=None) -> jax.Array:
    """Layer ``layer_index``'s selection for ``ids``, bool [B,L,L] (what the
    program's is compared with; the earlier layers run in full)."""
    s = sizes(config)
    positions = text_positions(ids) if positions is None else positions
    with jax.default_matmul_precision("highest"):
        x = w["embed"].astype(jnp.float32)[ids]
        doc = documents(ids, s)
        for i in range(layer_index):
            x = layer(x, doc, positions, _f32(w, _LAYER_KEYS, i), s, None, True)[0]
        lw = _f32(w, _LAYER_KEYS, layer_index)
        return attention(rms_norm(x, lw["norm1"], s["eps"]), doc, positions, lw, s,
                         None, True, give_selection=True)


def router_load(w: Weights, ids, config: dict):
    """Assignments each published expert drew, [layers, E]."""
    with jax.default_matmul_precision("highest"):
        return stream(w, ids, config)[2]


def loss_and_gradient(w: Weights, ids, config: dict, *, control=None):
    """(loss, l2 norm of the gradient over every weight, sign of every
    gradient element as int8 under the weights' names). The gradient of
    ``next_token_loss`` by the chain rule a layer at a time, last to first, each
    pass a ``lax.scan`` over the layers (memory only: one layer's float32
    weights, intermediates and gradient at a time; unrolled, a compiler is free
    to hold several layers' and at eight layers did); a layer's KL enters with
    the cotangent ``1 / (rows x L)``."""
    s = sizes(config)
    f32 = lambda tree: {k: v.astype(jnp.float32) for k, v in tree.items()}
    signs_of = lambda g: {k: jnp.sign(v).astype(jnp.int8) for k, v in g.items()}
    squares = lambda g: sum(jnp.sum(jnp.square(v)) for v in g.values())
    with jax.default_matmul_precision("highest"):
        positions = text_positions(ids)
        doc = documents(ids, s)
        one = lambda x, lw: layer(x, doc, positions, lw, s, control, True)[:2]
        layers = {k: w[k] for k in _LAYER_KEYS}
        x, embedded = jax.vjp(lambda e: e[ids], w["embed"].astype(jnp.float32))

        def forth(x, lw):
            out, kl = one(x, f32(lw))
            return out, (x, kl)
        x, (inputs, kl) = jax.lax.scan(forth, x, layers)
        lm, (g_head, dx) = jax.value_and_grad(
            lambda hw, x: head_loss(hw, x, ids, s, control, True), argnums=(0, 1))(
                _f32(w, _HEAD_KEYS), x)

        def back(dx, xs):
            lw, x_in = xs
            _, pull = jax.vjp(one, x_in, f32(lw))
            dx, g = pull((dx, jnp.asarray(1.0 / ids.size, jnp.float32)))
            return dx, (signs_of(g), squares(g))
        dx, (signs, sq) = jax.lax.scan(back, dx, (layers, inputs), reverse=True)
        g_embed = {"embed": embedded(dx)[0]}
    total = jnp.sum(sq) + squares(g_head) + squares(g_embed)
    return (lm + jnp.sum(kl) / ids.size, jnp.sqrt(total),
            {**signs, **signs_of(g_head), **signs_of(g_embed)})


def matmul_params_a_row(config: dict) -> float:
    """Parameters that multiply each row of a layer HERE: the attention
    kernels, the indexer's three projections, the router, and the routed
    experts at ``num_experts_per_tok x held / published`` a row."""
    s = sizes(config)
    attn = sum(a * b for a, b in {**_attention_shapes(s), **_indexer_shapes(s)}.values())
    return attn + s["H"] * s["E"] + s["k"] * s["Eh"] / s["E"] * 3 * s["H"] * s["I"]


def dsa_pairs(doc_lens, topk: int) -> int:
    """The SELECTED (query, key) pairs of a row whose pieces of documents have
    the lengths ``doc_lens``: a query with v visible keys picks ``min(v,
    topk)``, so a piece of n positions holds ``sum over v = 1..n of min(v,
    topk)``. Exact integers."""
    total = 0
    for n in doc_lens:
        n, full = int(n), min(int(n), int(topk))
        total += full * (full + 1) // 2 + (n - full) * int(topk)
    return total


def train_flops_per_token(config: dict, seq: int) -> float:
    """FLOPs one trained token REQUIRES of this chip at sequence length ``seq``
    (the contract is benchmark/reference/gpt2.py's), a row taken as one
    document: 6 per matmul parameter it meets in every layer
    (``matmul_params_a_row``) and the head's; the main attention's QK^T and PV
    over the SELECTED keys, 12 nh hd a key (forward 4, backward 8); the
    indexer's scores over the VISIBLE keys, 2 J d a key (scored forward, to be
    picked from), and forward and backward again over the selected keys for the
    KL, 6 J d a key; the KL's target, every main head's score once more over
    the selected keys, 2 nh hd a key. Packed documents hide more, which is
    traffic's and not counted. The embedding is a lookup and the norm gains
    are scalings: not counted."""
    s = sizes(config)
    params = s["L"] * matmul_params_a_row(config) + s["H"] * s["V"]
    selected = dsa_pairs([seq], s["topk"]) / seq
    visible = (seq + 1) / 2
    attn = ((12.0 + 2.0) * s["nh"] * s["hd"] * selected
            + 2.0 * s["J"] * s["d"] * visible + 6.0 * s["J"] * s["d"] * selected)
    return 6.0 * params + s["L"] * attn


def expert_product_flops_per_row(config: dict) -> float:
    """FLOPs ONE product of a routed expert's MLP costs ONE routed row
    (the contract is benchmark/reference/olmoe.py's): 2 x 2048 x 768."""
    s = sizes(config)
    return 2.0 * s["H"] * s["I"]


def attention_pair_flops(config: dict) -> dict:
    """FLOPs ONE selected (query, key) pair of ONE query head costs each kernel
    of the attention core: the forward's two matmuls (QK^T, PV), 4 hd; the
    fused backward's five (the scores again, dV, dP, dK, dQ), 10 hd
    (``attn_dsa_roofline`` multiplies them by the pairs that exist)."""
    s = sizes(config)
    return {"forward": 4.0 * s["hd"], "backward": 10.0 * s["hd"], "heads": s["nh"]}
