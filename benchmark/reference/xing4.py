"""Xing4.0-29B-A4B (``model_type`` ``xing4_0``) in plain ``jax.numpy``: forward
pass, training loss and gradient, read from a configuration file with Hugging
Face's key names, as ONE chip's share of a deployment in which several chips
share each layer.

Written from the published descriptions: Hyper-Connections (arXiv:2409.19606)
and manifold-constrained hyper-connections (mHC, arXiv:2512.24880) for the
residual streams, DeepSeek-V2 (arXiv:2405.04434: latent attention with a
compressed query), the DeepSeek-V3 report (arXiv:2412.19437: the sigmoid router
with its correction bias, a shared expert, the sequence-wise balance loss,
multi-token prediction) and YaRN (arXiv:2309.00071). RMSNorm everywhere, eps
``rms_norm_eps``; no bias on any matmul. What the configuration does not state
is in the file's ``assumed``.

1. Streams. The residual is ``X`` in R^(n x C), n = ``hc_mult``, C the hidden
   size; ``vec(X)`` in R^(nC) is the streams side by side. The stack starts
   from n copies of the embedding and ends in the streams' sum, which the
   final norm reads.
2. A sub-layer ``F`` (attention, a dense MLP or an expert MLP, each with
   ``Phi`` [nC, n^2 + 2n], a bias ``b`` and three scalars ``alpha`` of its own),
   a position at a time: ``r = rsqrt(mean(vec(X)^2) + hc_eps)``; ``m = (vec(X)
   r) Phi``, split ``m_pre`` [n], ``m_post`` [n], ``m_res`` [n x n] (row-major);
   ``H_pre = sigmoid(alpha_pre m_pre + b_pre)``; ``H_post = 2 sigmoid(alpha_post
   m_post + b_post)``; ``M_0 = exp(clamp(alpha_res m_res + b_res,
   mhc_h_res_clamp_min, mhc_h_res_clamp_max))`` and ``hc_sinkhorn_iters`` times:
   every row over its sum + ``hc_eps``, then every column over its sum +
   ``hc_eps``; ``H_res`` is the last. ``u = sum_i H_pre[i] X[i]``; ``y =
   F(RMSNorm(u))``; ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``.
3. Attention. ``c_q = RMSNorm(h W_qa)`` [``q_lora_rank``], ``q = c_q W_qb`` in
   heads of ``qk_nope_head_dim + qk_rope_head_dim``; ``[c_kv; k_r] = h W_kva``
   (``kv_lora_rank`` and one rotary key the heads share); ``[k_nope; v] =
   RMSNorm(c_kv) W_kvb``, ``v`` of ``v_head_dim``; ``k = [k_nope; k_r]``. Rotary
   on the last ``qk_rope_head_dim`` of q and k, interleaved pairs (2i, 2i + 1),
   YaRN's frequencies (benchmark/reference/instella_moe.py has the formula);
   scores scaled ``(nope + rope)^(-1/2) x (0.1 mscale_all_dim ln(factor) +
   1)^2``; a query sees the keys at or before it IN ITS OWN DOCUMENT (a
   document ends with its separator, ``assumed.separator``); the heads'
   outputs, ``v_head_dim`` wide, side by side through ``W_o``.
4. The first ``first_k_dense_replace`` layers' MLP: ``down(silu(gate(x)) *
   up(x))`` of ``intermediate_size``. The other layers: the sigmoid router over
   all PUBLISHED experts, ``num_experts_per_tok`` largest of score + bias, the
   chosen scores over their sum, times ``routed_scaling_factor``; ``y = sum
   over (chosen AND held) g_e E_e(x) + Shared(x)``; the sequence-wise balance
   loss (benchmark/reference/instella_moe.py, equation 4, to the letter).
5. One prediction module: ``h' = [RMSNorm_h(final stream sum); RMSNorm_e(Emb(
   t_(i+1)))] M``, copied into n streams, one expert layer of kind 2-4, the
   streams summed, a norm of its own, the shared embedding and head. ``loss =
   CE(main, t_(i+1)) + lambda CE(module, t_(i+2)) + alpha x (the balance
   losses' sum)``.

THE SHARE is benchmark/reference/instella_moe.py's: the file's
``n_routed_experts`` and ``vocab_size`` are what THIS chip holds, the router
keeps its published width, what the absent experts would add is left out.

float32 throughout, ``jax.default_matmul_precision("highest")``, no kernels.
It imports nothing of the program under test and nothing of the benchmark, and
exports what every reference file exports (benchmark/reference/gpt2.py lists
them), ``expert_product_flops_per_row``, ``mla_pairs`` and ``mla_pair_flops``.
Departures: a layer's weights are cast to float32 as the layer runs and its
gradient leaves in the weights' own dtype (memory only, ``loss_and_gradient``);
random weights from a seed (mHC's ``alpha`` near 0.5 and ``b``
drawn so that ``H_res`` is visibly not the identity, 2 on its diagonal and
normal(0, 1) elsewhere, and ``H_pre`` not uniform, normal(0, 1)); memory only:
``jax.checkpoint`` around layers, passes of experts and blocks of queries,
tokens in blocks through the MLPs, the mixings and the head's loss; and the
``fp8`` control, which rounds every matmul operand to float8_e4m3fn.

Weights are one flat dict, per-layer arrays stacked on a leading axis under the
prefixes ``d_`` (leading dense layers), none (expert layers), ``m_`` (the
module's layer); nh heads, hd = nope + rope, qr / kr the two ranks, K = n^2 +
2n; ``a_`` the attention sub-layer's coefficients, ``f_`` the MLP's::

    embed [V,H]  head [H,V]  norm_f [H]
    m_norm_h m_norm_e m_norm_f [H]  m_merge [2H,H]
    <p>norm1 <p>norm2 [l,H]  <p>wqa [l,H,qr]  <p>q_norm [l,qr]  <p>wqb [l,qr,nh*hd]
    <p>wkva [l,H,kr+rope]  <p>kv_norm [l,kr]  <p>wkvb [l,kr,nh*(nope+v)]
    <p>wo [l,nh*v,H]  <p>a_phi <p>f_phi [l,nH,K]  <p>a_b <p>f_b [l,K]
    <p>a_alpha <p>f_alpha [l,3]
    d_gate d_up [D,H,F]  d_down [D,F,H]
    <p>router [l,H,E]  <p>router_bias [l,E]  <p>w_gate <p>w_up [l,Eh,H,I]
    <p>w_down [l,Eh,I,H]  <p>s_gate <p>s_up [l,H,ns*I]  <p>s_down [l,ns*I,H]
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
#: MLP, a mixing or the head (memory only)
QUERY_BLOCK = 512
TOKEN_BLOCK = 2048


def sizes(config: dict) -> dict:
    """The published keys this file reads, under short names."""
    a = config.get("assumed", {})
    share = config.get("share")
    held = int(config["n_routed_experts"])
    published = int(share["published"].get("n_routed_experts", held)) if share else held
    rank = int(a.get("share_rank", 0))
    s = dict(
        V=int(config["vocab_size"]), H=int(config["hidden_size"]),
        L=int(config["num_hidden_layers"]), D=int(config["first_k_dense_replace"]),
        M=int(config["num_nextn_predict_layers"]), F=int(config["intermediate_size"]),
        I=int(config["moe_intermediate_size"]), E=published, Eh=held,
        lo=rank * held, k=int(config["num_experts_per_tok"]),
        ns=int(config["n_shared_experts"]), renorm=bool(config["norm_topk_prob"]),
        scale=float(config["routed_scaling_factor"]),
        nh=int(config["num_attention_heads"]), kr=int(config["kv_lora_rank"]),
        qr=int(config["q_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]), rope=int(config["qk_rope_head_dim"]),
        v=int(config["v_head_dim"]), eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]), yarn=config.get("rope_scaling"),
        n=int(config["hc_mult"]), iters=int(config["hc_sinkhorn_iters"]),
        hc_eps=float(config["hc_eps"]),
        clamp=(float(config["mhc_h_res_clamp_min"]), float(config["mhc_h_res_clamp_max"])),
        sep=a.get("separator"),
        alpha=float(a["seq_aux_alpha"]), lam=float(a["mtp_loss_lambda"]))
    s["hd"] = s["nope"] + s["rope"]
    s["K"] = s["n"] * (s["n"] + 2)
    if s["M"] not in (0, 1):
        raise ValueError("one prediction module at most")
    if (config.get("n_group", 1), config.get("topk_group", 1)) != (1, 1):
        raise ValueError("one group of experts")
    return s


def key_of(seed: int):
    """PRNG key of a seed of any size: the bits above 31 are folded in,
    not dropped."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _attention_shapes(s: dict) -> dict:
    H, nh, hd = s["H"], s["nh"], s["hd"]
    return {"wqa": (H, s["qr"]), "wqb": (s["qr"], nh * hd),
            "wkva": (H, s["kr"] + s["rope"]),
            "wkvb": (s["kr"], nh * (s["nope"] + s["v"])), "wo": (nh * s["v"], H)}


def make_weights(key, config: dict, dtype=jnp.bfloat16) -> Weights:
    """Random weights from ``key_of(seed)`` in the dtype they are trained
    from. Pure and jittable with the key traced."""
    s = sizes(config)
    H, V, I, E, Eh, F, n, K = (s[k] for k in ("H", "V", "I", "E", "Eh", "F", "n", "K"))
    keys = iter(jax.random.split(key, 128))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    resid = 0.02 / math.sqrt(2 * (s["L"] + s["M"]))
    w = {"embed": normal((V, H), 0.02), "head": normal((H, V), 0.02),
         "norm_f": 1.0 + normal((H,), 0.05)}
    if s["M"]:
        w.update({"m_norm_h": 1.0 + normal((H,), 0.05),
                  "m_norm_e": 1.0 + normal((H,), 0.05),
                  "m_norm_f": 1.0 + normal((H,), 0.05),
                  "m_merge": normal((2 * H, H), 0.02 / math.sqrt(2))})
    # the diagonal of m_res, row-major, after the 2n of m_pre and m_post
    diagonal = jnp.zeros((K,), jnp.float32).at[2 * n + jnp.arange(n) * (n + 1)].set(2.0)
    for p, layers, experts in (("d_", s["D"], False), ("", s["L"] - s["D"], True),
                               ("m_", s["M"], True)):
        if not layers:
            continue
        for name, shape in _attention_shapes(s).items():
            w[p + name] = normal((layers,) + shape, 2 * resid if name == "wo" else 0.02)
        w.update({p + "norm1": 1.0 + normal((layers, H), 0.05),
                  p + "norm2": 1.0 + normal((layers, H), 0.05),
                  p + "q_norm": 1.0 + normal((layers, s["qr"]), 0.05),
                  p + "kv_norm": 1.0 + normal((layers, s["kr"]), 0.05)})
        for sub in ("a_", "f_"):
            # vec(X) r has unit mean square over nC entries: m's standard
            # deviation is 0.02 sqrt(nC), 2.4 at 4 x 3584, times alpha
            w.update({p + sub + "phi": normal((layers, n * H, K), 0.02),
                      p + sub + "b": (normal((layers, K), 1.0).astype(jnp.float32)
                                      + diagonal).astype(dtype),
                      p + sub + "alpha": 0.5 + normal((layers, 3), 0.05)})
        if not experts:
            w.update({p + "gate": normal((layers, H, F), 0.02),
                      p + "up": normal((layers, H, F), 0.02),
                      p + "down": normal((layers, F, H), resid)})
            continue
        w.update({p + "router": normal((layers, H, E), 0.02),
                  p + "router_bias": normal((layers, E), 0.05),
                  p + "w_gate": normal((layers, Eh, H, I), 0.02),
                  p + "w_up": normal((layers, Eh, H, I), 0.02),
                  p + "w_down": normal((layers, Eh, I, H), resid),
                  p + "s_gate": normal((layers, H, s["ns"] * I), 0.02),
                  p + "s_up": normal((layers, H, s["ns"] * I), 0.02),
                  p + "s_down": normal((layers, s["ns"] * I, H), resid)})
    return w


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def yarn_band(d: int, theta: float, yarn: dict):
    """(low, high): the frequency indices between which YaRN blends."""
    at = lambda turns: (d * math.log(yarn["original_max_position_embeddings"]
                                     / (turns * 2 * math.pi)) / (2 * math.log(theta)))
    return (max(math.floor(at(yarn["beta_fast"])), 0),
            min(math.ceil(at(yarn["beta_slow"])), d - 1))


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 or not m else 0.1 * m * math.log(factor) + 1.0


def rotate(x, s: dict):
    """Rotary positions 0..S-1 on x [B,S,heads,rope]: the pair (2i, 2i+1) is
    turned by position x f'_i (YaRN's frequencies where the file scales)."""
    S, d = x.shape[1], x.shape[-1]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    freqs = s["theta"] ** (-2.0 * i / d)
    mult = 1.0
    yarn = s["yarn"]
    if yarn is not None:
        low, high = yarn_band(d, s["theta"], yarn)
        ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        freqs = freqs * (1.0 - ramp) + freqs / yarn["factor"] * ramp
        mult = (_mscale(yarn["factor"], yarn.get("mscale", 1))
                / _mscale(yarn["factor"], yarn.get("mscale_all_dim", 0)))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs         # [S, d/2]
    cos, sin = (jnp.cos(angles) * mult)[:, None, :], (jnp.sin(angles) * mult)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


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
    return jax.tree.map(lambda a: a.reshape((n,) + a.shape[2:]), out)


def documents(ids, s: dict):
    """Each position's document, [B,S]: the separators before it (a
    separator ends its own document); one document a row without one."""
    if s["sep"] is None:
        return jnp.zeros(ids.shape, jnp.int32)
    ends = (ids == s["sep"]).astype(jnp.int32)
    return jnp.cumsum(ends, axis=1) - ends


def doubly_stochastic(m, s: dict):
    """Equation 2's ``H_res`` from ``alpha_res m_res + b_res`` [.., n, n]."""
    def one_round(M, _):
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + s["hc_eps"])     # rows
        return M / (jnp.sum(M, axis=-2, keepdims=True) + s["hc_eps"]), None   # columns
    M, _ = jax.lax.scan(one_round, jnp.exp(jnp.clip(m, *s["clamp"])), None, length=s["iters"])
    return M


def coefficients(X, phi, b, alpha, s: dict, control=None):
    """Equation 2's (H_pre [T,n], H_post [T,n], H_res [T,n,n]) of X [T,n,C]."""
    T, n, C = X.shape
    vec = X.reshape(T, n * C)
    vec = vec * jax.lax.rsqrt(jnp.mean(jnp.square(vec), axis=-1, keepdims=True) + s["hc_eps"])
    m = rounded(vec, control) @ rounded(phi, control)
    pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    res = doubly_stochastic((alpha[2] * m[:, 2 * n:] + b[2 * n:]).reshape(T, n, n), s)
    return pre, post, res


def sublayer(X, lw, sub: str, F, s: dict, control=None, checkpoint: bool = False):
    """Equation 2 around ``F`` ([B,S,C] -> ([B,S,C], *more)) on X [B,S,n,C];
    ``sub``: ``"a_"`` or ``"f_"``, whose coefficients. -> (X', *more)."""
    B, S, n, C = X.shape
    flat = X.reshape(B * S, n, C)

    def read(x):
        pre, post, res = coefficients(x, lw[sub + "phi"], lw[sub + "b"],
                                      lw[sub + "alpha"], s, control)
        return jnp.einsum("ti,tic->tc", pre, x), post, res
    u, post, res = in_blocks(read, flat, TOKEN_BLOCK, checkpoint)
    y, *more = F(u.reshape(B, S, C))

    def write(xs):
        x, post, res, y = xs
        return jnp.einsum("tij,tjc->tic", res, x) + post[:, :, None] * y[:, None, :]
    out = in_blocks(write, (flat, post, res, y.reshape(B * S, C)), TOKEN_BLOCK, checkpoint)
    return (out.reshape(B, S, n, C), *more)


def attention(x, doc, lw, s: dict, control=None, checkpoint: bool = False):
    """The attention sub-layer's ``F`` on the normed input x [B,S,H]
    (equation 3); ``doc`` [B,S]: each position's document."""
    B, S, _ = x.shape
    nh, hd, nope, vd = s["nh"], s["hd"], s["nope"], s["v"]
    r = lambda t: rounded(t, control)
    h = r(x)
    c_q = rms_norm(h @ r(lw["wqa"]), lw["q_norm"], s["eps"])
    q = (r(c_q) @ r(lw["wqb"])).reshape(B, S, nh, hd)
    kva = h @ r(lw["wkva"])
    latent = rms_norm(kva[..., :s["kr"]], lw["kv_norm"], s["eps"])
    kv = (r(latent) @ r(lw["wkvb"])).reshape(B, S, nh, nope + vd)
    k_rope = jnp.broadcast_to(kva[:, :, None, s["kr"]:], (B, S, nh, s["rope"]))
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], s)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], rotate(k_rope, s)], axis=-1)
    v = kv[..., nope:]
    scale = hd ** -0.5
    if s["yarn"] is not None:
        scale *= _mscale(s["yarn"]["factor"], s["yarn"].get("mscale_all_dim", 0)) ** 2
    kr, vr = r(k), r(v)

    def queries(block):
        qb, at, qdoc = block                          # [B,qb,nh,hd], [qb], [B,qb]
        scores = jnp.einsum("bqnd,bknd->bnqk", r(qb), kr) * scale
        seen = ((at[:, None] >= jnp.arange(S)[None, :])[None]
                & (qdoc[:, :, None] == doc[:, None, :]))
        scores = jnp.where(seen[:, None], scores, -jnp.inf)
        return jnp.einsum("bnqk,bknd->bqnd", r(jax.nn.softmax(scores, axis=-1)), vr)

    if checkpoint and S > QUERY_BLOCK and S % QUERY_BLOCK == 0:
        nb = S // QUERY_BLOCK
        a = jax.lax.map(jax.checkpoint(queries),
                        (q.reshape(B, nb, QUERY_BLOCK, nh, hd).swapaxes(0, 1),
                         jnp.arange(S).reshape(nb, QUERY_BLOCK),
                         doc.reshape(B, nb, QUERY_BLOCK).swapaxes(0, 1)))
        a = a.swapaxes(0, 1).reshape(B, S, nh * vd)
    else:
        a = queries((q, jnp.arange(S), doc)).reshape(B, S, nh * vd)
    return r(a) @ r(lw["wo"])


def gated_mlp(h, wg, wu, wd, control=None):
    r = lambda t: rounded(t, control)
    return r(jax.nn.silu(r(h) @ r(wg)) * (r(h) @ r(wu))) @ r(wd)


def route(h, w_router, bias, s: dict, rows_per_seq: int, control=None):
    """h [T,H] (T = rows x S) -> (weight [T,E]: each token's routing weight
    for each PUBLISHED expert, 0 where it did not choose it; the
    sequence-wise balance loss; assignments per expert [E])."""
    E, k = s["E"], s["k"]
    score = jax.nn.sigmoid(rounded(h, control) @ rounded(w_router, control))
    _, chosen = jax.lax.top_k(score + jax.lax.stop_gradient(bias), k)
    top = jnp.take_along_axis(score, chosen, axis=-1)
    if s["renorm"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    onehot = jax.nn.one_hot(chosen, E, dtype=jnp.float32)              # [T,k,E]
    weight = jnp.einsum("tk,tke->te", top * s["scale"], onehot)
    per_row = lambda a: a.reshape((-1, rows_per_seq) + a.shape[1:])
    f = jnp.sum(per_row(onehot), axis=(1, 2)) * (E / (k * rows_per_seq))   # [rows,E]
    p = jnp.mean(per_row(score / jnp.sum(score, axis=-1, keepdims=True)), axis=1)
    return weight, jnp.mean(jnp.sum(f * p, axis=-1)), jnp.sum(onehot, axis=(0, 1))


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


def layer(X, doc, lw, s: dict, control=None, checkpoint: bool = False):
    """One layer on the streams X [B,S,n,C]. lw: this layer's slice, prefix
    stripped. Returns (X', balance loss, assignments per published expert)."""
    B, S, _, H = X.shape

    def attn(u):
        return (attention(rms_norm(u, lw["norm1"], s["eps"]), doc, lw, s, control,
                          checkpoint),)

    def mlp(u):
        h = rms_norm(u, lw["norm2"], s["eps"]).reshape(B * S, H)
        if "router" not in lw:
            m = in_blocks(lambda t: gated_mlp(t, lw["gate"], lw["up"], lw["down"], control),
                          h, TOKEN_BLOCK, checkpoint)
            return m.reshape(B, S, H), jnp.zeros(()), jnp.zeros((s["E"],))
        weight, balance, load = route(h, lw["router"], lw["router_bias"], s, S, control)
        m = held_experts(h, weight, lw, s, control, checkpoint)
        m = m + in_blocks(lambda t: gated_mlp(t, lw["s_gate"], lw["s_up"], lw["s_down"],
                                              control), h, TOKEN_BLOCK, checkpoint)
        return m.reshape(B, S, H), balance, load

    (X,) = sublayer(X, lw, "a_", attn, s, control, checkpoint)
    return sublayer(X, lw, "f_", mlp, s, control, checkpoint)


_ATTENTION_KEYS = ("norm1", "norm2", "wqa", "q_norm", "wqb", "wkva", "kv_norm", "wkvb",
                   "wo", "a_phi", "a_b", "a_alpha", "f_phi", "f_b", "f_alpha")
_DENSE_KEYS = _ATTENTION_KEYS + ("gate", "up", "down")
_EXPERT_KEYS = _ATTENTION_KEYS + ("router", "router_bias", "w_gate", "w_up", "w_down",
                                  "s_gate", "s_up", "s_down")


#: the weights that are not stacked over layers
_TOP = ("embed", "head", "norm_f", "m_norm_h", "m_norm_e", "m_norm_f", "m_merge")


@jax.custom_vjp
def float32_of(x, tally):
    """``x`` in float32. Memory only, for ``loss_and_gradient`` at the cell's
    size: in the backward pass the float32 gradient leaves in ``x``'s own dtype
    (a rounding, which keeps every sign) and its sum of squares is handed out
    in ``tally``'s place, so the gradient's norm is that of the float32
    gradient though no float32 gradient of every layer is ever whole."""
    return x.astype(jnp.float32)


float32_of.defvjp(
    lambda x, tally: (x.astype(jnp.float32), jnp.zeros((0,), x.dtype)),
    lambda like, ct: (ct.astype(like.dtype), jnp.sum(jnp.square(ct))))


def _cast(w: Weights, tally=0.0) -> Weights:
    """The weights outside the layers in float32; a layer's are cast where
    the layer runs (`_layers`), so that no float32 copy of every layer is ever
    whole in memory (memory only: the same float32 arithmetic on the same
    values)."""
    return {k: (float32_of(v, tally) if k in _TOP else v) for k, v in w.items()}


def _layers(X, doc, w, prefix, keys, s, control, checkpoint, tally=0.0):
    """The stacked layers under ``prefix``, one after another, each layer's
    weights cast to float32 as it runs."""
    def fn(X, lw):
        lw = {k: float32_of(v, tally) for k, v in lw.items()}
        X, balance, load = layer(X, doc, lw, s, control, checkpoint)
        return X, (balance, load)
    if checkpoint:  # departure: memory only, same arithmetic
        fn = jax.checkpoint(fn)
    return jax.lax.scan(fn, X, {k: w[prefix + k] for k in keys})


def copies(x, s: dict):
    """n copies of one stream [B,S,C] -> [B,S,n,C]."""
    return jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (s["n"], x.shape[2]))


def streams_and_losses(w: Weights, ids, config: dict, *, control=None,
                       checkpoint: bool = False, tally=0.0):
    """(the final streams' sum [B,S,H], the module's or None, balance losses
    of the expert layers and the module's [L-D+M], assignments per published
    expert of the same layers [L-D+M, E])."""
    s = sizes(config)
    doc = documents(ids, s)
    X = copies(w["embed"][ids], s)
    if s["D"]:
        X, _ = _layers(X, doc, w, "d_", _DENSE_KEYS, s, control, checkpoint, tally)
    X, (balance, load) = _layers(X, doc, w, "", _EXPERT_KEYS, s, control, checkpoint, tally)
    x = jnp.sum(X, axis=2)
    if not s["M"]:
        return x, None, balance, load
    nxt = w["embed"][jnp.roll(ids, -1, axis=1)]      # the row's last: its first
    merged = rounded(jnp.concatenate(
        [rms_norm(x, w["m_norm_h"], s["eps"]), rms_norm(nxt, w["m_norm_e"], s["eps"])],
        axis=-1), control) @ rounded(w["m_merge"], control)
    MX, (m_balance, m_load) = _layers(copies(merged, s), doc, w, "m_", _EXPERT_KEYS, s,
                                      control, checkpoint, tally)
    return (x, jnp.sum(MX, axis=2), jnp.concatenate([balance, m_balance]),
            jnp.concatenate([load, m_load]))


def head_logits(w: Weights, x, norm, s: dict, control=None):
    return rounded(rms_norm(x, norm, s["eps"]), control) @ rounded(w["head"], control)


def forward(w: Weights, ids, config: dict, *, control=None, checkpoint: bool = False):
    """float32 logits [B,S,V] of token ids [B,S] (the main head's)."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        w = _cast(w)
        x = streams_and_losses(w, ids, config, control=control, checkpoint=checkpoint)[0]
        return head_logits(w, x, w["norm_f"], s, control)


def _mean_nll(w, x, norm, ids, ahead: int, s, control, checkpoint):
    """Mean cross-entropy of the head over x [B,S,H] at predicting the token
    ``ahead`` positions on, over the positions that have one."""
    B, S, H = x.shape
    targets = jnp.roll(ids, -ahead, axis=1)
    valid = jnp.broadcast_to(jnp.arange(S) < S - ahead, (B, S))

    def nll(block):
        xb, tb, vb = block
        logp = jax.nn.log_softmax(head_logits(w, xb, norm, s, control), axis=-1)
        return jnp.where(vb, -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0], 0.0)
    total = in_blocks(nll, (x.reshape(-1, H), targets.reshape(-1), valid.reshape(-1)),
                      TOKEN_BLOCK, checkpoint)
    return jnp.sum(total) / (B * (S - ahead))


def next_token_loss(w: Weights, ids, config: dict, *, control=None,
                    checkpoint: bool = False, tally=0.0):
    """The training objective (equation 5). ``tally``: `float32_of`'s."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        w = _cast(w, tally)
        x, mx, balance, _ = streams_and_losses(w, ids, config, control=control,
                                               checkpoint=checkpoint, tally=tally)
        loss = _mean_nll(w, x, w["norm_f"], ids, 1, s, control, checkpoint)
        if mx is not None:
            loss = loss + s["lam"] * _mean_nll(w, mx, w["m_norm_f"], ids, 2, s, control,
                                               checkpoint)
        return loss + s["alpha"] * jnp.sum(balance)


def router_load(w: Weights, ids, config: dict):
    """Assignments each published expert drew, [expert layers (+ the
    module's), E]: what moves the correction bias after the step."""
    with jax.default_matmul_precision("highest"):
        return streams_and_losses(_cast(w), ids, config)[3]


def loss_and_gradient(w: Weights, ids, config: dict, *, control=None):
    """(loss, l2 norm of the gradient over every weight, sign of every
    gradient element as int8 under the weights' names). The correction
    bias enters under ``stop_gradient``: its signs are exactly 0. The
    gradient is taken with respect to the weights AS GIVEN (bfloat16 on the
    chip): every product and sum of the backward pass is float32, a weight's
    gradient is rounded to the weight's dtype as it leaves (memory only: a
    rounding keeps every sign), and the norm is the float32 gradient's
    (`float32_of`)."""
    loss, (g, sq) = jax.value_and_grad(
        lambda p, tally: next_token_loss(p, ids, config, control=control, checkpoint=True,
                                         tally=tally), argnums=(0, 1))(w, jnp.zeros(()))
    return loss, jnp.sqrt(sq), {k: jnp.sign(v).astype(jnp.int8) for k, v in g.items()}


def matmul_params(config: dict) -> float:
    """Parameters that multiply each token HERE (the contract is
    benchmark/reference/instella_moe.py's): the attention kernels, each
    sub-layer's ``Phi``, the dense layers' MLP, the router, the shared expert,
    the routed experts at ``num_experts_per_tok x held / published`` a token,
    the module's merge, and the head once for each loss."""
    s = sizes(config)
    H, I = s["H"], s["I"]
    attn = sum(a * b for a, b in _attention_shapes(s).values()) + 2 * s["n"] * H * s["K"]
    routed = s["k"] * s["Eh"] / s["E"] * 3 * H * I
    expert_layer = attn + H * s["E"] + 3 * H * s["ns"] * I + routed
    return (s["D"] * (attn + 3 * H * s["F"]) + (s["L"] - s["D"] + s["M"]) * expert_layer
            + s["M"] * 2 * H * H + (1 + s["M"]) * H * s["V"])


def mixing_flops_per_token(config: dict) -> float:
    """FLOPs the two mixings of ONE sub-layer cost one token, forward: the
    weighted read, 2 n C; the write-back, 2 n^2 C for ``H_res X`` and 2 n C for
    ``H_post y``: (2 n^2 + 4 n) C = 172,032 at n = 4, C = 3584. (The norm's
    statistics, the sigmoids and the Sinkhorn rounds over 16 numbers a
    position are not counted.)"""
    s = sizes(config)
    return (2.0 * s["n"] ** 2 + 4.0 * s["n"]) * s["H"]


def train_flops_per_token(config: dict, seq: int) -> float:
    """FLOPs one trained token REQUIRES of this chip at sequence length
    ``seq`` (the contract is benchmark/reference/gpt2.py's), a row taken as one
    document: 6 per matmul parameter a token meets here (``matmul_params``:
    ``Phi``'s product among them), three times ``mixing_flops_per_token`` for
    each of the 2 (L + M) sub-layers (forward, and twice that backward), plus
    the attention's pairs over the causal half of the sequence in every layer
    and the module: a pair and head costs 2 x (hd + v) forward (QK^T at the
    keys' width, PV at the values'), three times that trained: 3 (L + M) nh
    (hd + v) S. Packed documents hide more, which is traffic's and not
    counted."""
    s = sizes(config)
    layers = s["L"] + s["M"]
    return (6.0 * matmul_params(config)
            + 3.0 * 2 * layers * mixing_flops_per_token(config)
            + 3.0 * layers * s["nh"] * (s["hd"] + s["v"]) * seq)


def expert_product_flops_per_row(config: dict) -> float:
    """FLOPs ONE product of a routed expert's MLP costs ONE routed row
    (the contract is benchmark/reference/olmoe.py's): 2 x 3584 x 1024."""
    s = sizes(config)
    return 2.0 * s["H"] * s["I"]


def mla_pairs(doc_lens) -> int:
    """The (query, key) pairs that exist under causal AND same document in a
    row whose pieces of documents have the lengths ``doc_lens``: a piece of n
    positions holds n (n + 1) / 2. Exact integers."""
    return sum(int(n) * (int(n) + 1) // 2 for n in doc_lens)


def mla_pair_flops(config: dict) -> dict:
    """FLOPs ONE (query, key) pair of ONE head costs each kernel of the
    two-width attention core: the forward's QK^T at the keys' width and PV at
    the values', 2 hd + 2 v (640 at 192 / 128); the fused backward's five
    matmuls, the scores again, dK and dQ at the keys' width, dV and dP at the
    values', 6 hd + 4 v (1,664) (``attn_mla_roofline`` multiplies them by the
    pairs that exist)."""
    s = sizes(config)
    return {"forward": 2.0 * s["hd"] + 2.0 * s["v"],
            "backward": 6.0 * s["hd"] + 4.0 * s["v"], "heads": s["nh"]}
