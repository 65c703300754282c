"""Instella-MoE-16B-A3B (``model_type`` ``deepseek_v3``) in plain
``jax.numpy``: forward pass, training loss and gradient, read from a
configuration file with Hugging Face's key names, as ONE chip's share of a
deployment in which several chips share each layer.

Written from the published descriptions: DeepSeek-V2 (arXiv:2405.04434,
multi-head latent attention) and the DeepSeek-V3 report (arXiv:2412.19437:
the sigmoid router with its correction bias, shared experts, the
sequence-wise balance loss, multi-token prediction), YaRN (Peng et al. 2023,
arXiv:2309.00071), and for the three flags the configuration carries without
a formula: gated attention (Qiu et al. 2025, arXiv:2505.06708: a sigmoid gate
on the attention output), per-head QK-norm, and FarSkip-Collective (Dukler et
al., AMD, 2025). ``x`` is a sub-block's normed input; RMSNorm everywhere,
eps ``rms_norm_eps``; no bias on any matmul.

1. Stream (``farskip``). Sub-blocks f_1 .. f_2L alternate attention and MLP;
   r_0 is the embedding and r_(-1) := r_0;
   ``r_i = r_(i-1) + f_i(RMSNorm_i(r_(i-2)))``: a sub-block reads the stream
   as it stood before the sub-block in front of it. The final norm reads
   r_2L. With the flag off, ``r_i = r_(i-1) + f_i(RMSNorm_i(r_(i-1)))``.
2. Attention. ``q = x W_q`` in heads of ``qk_nope_head_dim +
   qk_rope_head_dim``; ``[c; k_r] = x W_kva`` (``kv_lora_rank`` and one
   rotary key shared by the heads); ``[k_nope_h; v_h]_h = RMSNorm_kv(c)
   W_kvb``; ``k_h = [k_nope_h; k_r]``. ``qk_layernorm``: RMSNorm over each
   head's whole query vector and key vector, one gain shared by the heads
   for each, before the rotary embedding. Rotary on the last
   ``qk_rope_head_dim`` of q_h and k_h, interleaved pairs (2i, 2i+1), YaRN
   frequencies ``f'_i = f_i (1 - ramp_i) + f_i / factor x ramp_i`` with
   ``f_i = theta^(-2i/d)``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``,
   ``low = floor(d ln(P / (beta_fast 2 pi)) / (2 ln theta))``, ``high =
   ceil(d ln(P / (beta_slow 2 pi)) / (2 ln theta))``, P the original
   positions; cos and sin scaled by mscale / mscale_all_dim's ratio (1
   here). Scores scaled by ``head^(-1/2) x m^2``, ``m = 0.1 mscale_all_dim
   ln(factor) + 1``; causal softmax; ``a = concat_h(softmax v_h)``.
   ``gated_attention``: ``y = (a * sigmoid(x W_g)) W_o``.
3. The first ``first_k_dense_replace`` layers' MLP: ``down(silu(gate(x)) *
   up(x))`` of ``intermediate_size``.
4. The other layers: ``s = sigmoid(x W_r)`` over all PUBLISHED experts;
   chosen = the ``num_experts_per_tok`` largest of ``s + b`` (one group; the
   lowest index wins a tie); ``g_e = s_e / (sum_chosen s + 1e-20) x
   routed_scaling_factor``; ``y = sum over (chosen AND held) g_e E_e(x) +
   Shared(x)``, ``E_e`` a gated SiLU MLP of ``moe_intermediate_size``,
   ``Shared`` one of ``n_shared_experts`` times that width, unweighted. ``b``
   gets no gradient (``stop_gradient``): load moves it after a step. The
   sequence-wise balance loss of a layer is the mean over the batch's rows of
   ``sum_e f_e P_e``, ``f_e = E / (k S) x #{t of the row: e chosen}``, ``P_e``
   the row's mean of ``s_e / sum_j s_j``, over all published experts.
5. One prediction module (report, section 2.2): ``h'_i = [RMSNorm_h(r_2L,i);
   RMSNorm_e(Emb(t_(i+1)))] M``; one layer of kind 4 on h' (its first two
   sub-blocks both read h'); a norm of its own; the shared embedding and
   head. ``loss = CE(main, t_(i+1)) + lambda CE(module, t_(i+2)) + alpha x
   (sum over the expert layers and the module's of their balance loss)``.

THE SHARE. The file's ``share`` block says how many chips share a layer and
what was published; ``n_routed_experts`` and ``vocab_size`` of the file are
what THIS chip holds (rank ``assumed.share_rank``, 0 unless given: experts
``rank x held .. (rank + 1) x held - 1``). The router keeps its published
width; the held experts are computed the obvious way, every one of them on
every token under the mask of chosen AND held, a few a pass; what the
absent experts would add is left out and that partial result goes on to the
next layer; nothing stands in for the other chips. The vocabulary is the
file's: embedding, head and loss are over the slice. Without a ``share``
block every expert is held.

float32 throughout, ``jax.default_matmul_precision("highest")``, no
kernels, no cache. It imports nothing of the program under test and nothing
of the benchmark, and exports what every reference file exports
(benchmark/reference/gpt2.py lists them) and ``expert_product_flops_per_row``.
Departures from the descriptions: random weights from a seed (norm gains near
1, the QK-norm gains near 2 so that heads are peaked as trained ones are, a
small random correction bias so that it decides some choices); the
coefficients alpha, gamma and lambda are ``assumed`` (the report's); the
row's last position has no next token and the module is given the row's
first there (its loss is masked, the causal mask keeps it from every other
position; it counts in the module's router statistics as one token of S);
memory only: ``jax.checkpoint`` around layers, passes of experts and blocks
of queries, tokens in blocks through the MLPs and the head's loss; and the
``fp8`` control, which rounds every matmul operand to float8_e4m3fn.

Weights are one flat dict. Per-layer arrays are stacked on a leading axis,
under three prefixes: ``d_`` the leading dense layers [D, ..], none the
expert layers [L - D, ..], ``m_`` the module's layer [1, ..] (nh heads, hd =
nope + rope, I = moe_intermediate_size, E = published experts, Eh = held)::

    embed [V,H]  head [H,V]  norm_f [H]
    m_norm_h m_norm_e m_norm_f [H]  m_merge [2H,H]
    <p>norm1 <p>norm2 [n,H]  <p>wq [n,H,nh*hd]  <p>wkva [n,H,rank+rope]
    <p>kv_norm [n,rank]  <p>wkvb [n,rank,nh*(nope+v)]  <p>wo [n,nh*v,H]
    <p>wg [n,H,nh*v]  <p>q_norm <p>k_norm [n,hd]
    d_gate d_up [D,H,F]  d_down [D,F,H]
    <p>router [n,H,E]  <p>router_bias [n,E]  <p>w_gate <p>w_up [n,Eh,H,I]
    <p>w_down [n,Eh,I,H]  <p>s_gate <p>s_up [n,H,ns*I]  <p>s_down [n,ns*I,H]
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
        nh=int(config["num_attention_heads"]), rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]), rope=int(config["qk_rope_head_dim"]),
        v=int(config["v_head_dim"]), eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]), yarn=config.get("rope_scaling"),
        farskip=bool(config["farskip"]), gated=bool(config["gated_attention"]),
        qk_norm=bool(config["qk_layernorm"]),
        alpha=float(a["seq_aux_alpha"]), lam=float(a["mtp_loss_lambda"]))
    s["hd"] = s["nope"] + s["rope"]
    if s["M"] not in (0, 1) or config.get("q_lora_rank") is not None:
        raise ValueError("one prediction module at most, no query compression")
    if (config.get("n_group", 1), config.get("topk_group", 1)) != (1, 1):
        raise ValueError("one group of experts")
    return s


def key_of(seed: int):
    """PRNG key of a seed of any size: the bits above 31 are folded in,
    not dropped."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _attention_shapes(s: dict) -> dict:
    H, nh, hd = s["H"], s["nh"], s["hd"]
    return {"wq": (H, nh * hd), "wkva": (H, s["rank"] + s["rope"]),
            "wkvb": (s["rank"], nh * (s["nope"] + s["v"])),
            "wo": (nh * s["v"], H), "wg": (H, nh * s["v"])}


def make_weights(key, config: dict, dtype=jnp.bfloat16) -> Weights:
    """Random weights from ``key_of(seed)`` in the dtype they are trained
    from. Pure and jittable with the key traced."""
    s = sizes(config)
    H, V, I, E, Eh, F = s["H"], s["V"], s["I"], s["E"], s["Eh"], s["F"]
    keys = iter(jax.random.split(key, 96))

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
    for p, n, experts in (("d_", s["D"], False), ("", s["L"] - s["D"], True),
                          ("m_", s["M"], True)):
        if not n:
            continue
        for name, shape in _attention_shapes(s).items():
            w[p + name] = normal((n,) + shape, 2 * resid if name == "wo" else 0.02)
        w.update({p + "norm1": 1.0 + normal((n, H), 0.05),
                  p + "norm2": 1.0 + normal((n, H), 0.05),
                  p + "kv_norm": 1.0 + normal((n, s["rank"]), 0.05),
                  # gains near 2: scores then have a standard deviation near 4
                  p + "q_norm": 2.0 + normal((n, s["hd"]), 0.05),
                  p + "k_norm": 2.0 + normal((n, s["hd"]), 0.05)})
        if not experts:
            w.update({p + "gate": normal((n, H, F), 0.02), p + "up": normal((n, H, F), 0.02),
                      p + "down": normal((n, F, H), resid)})
            continue
        w.update({p + "router": normal((n, H, E), 0.02),
                  p + "router_bias": normal((n, E), 0.05),
                  p + "w_gate": normal((n, Eh, H, I), 0.02),
                  p + "w_up": normal((n, Eh, H, I), 0.02),
                  p + "w_down": normal((n, Eh, I, H), resid),
                  p + "s_gate": normal((n, H, s["ns"] * I), 0.02),
                  p + "s_up": normal((n, H, s["ns"] * I), 0.02),
                  p + "s_down": normal((n, s["ns"] * I, H), resid)})
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
    """Rotary positions 0..S-1 on x [B,S,n,rope]: the pair (2i, 2i+1) is
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
    return out.reshape((n,) + out.shape[2:])


def attention(x, lw, s: dict, control=None, checkpoint: bool = False):
    """The attention sub-block on the normed input x [B,S,H] (equation 2)."""
    B, S, _ = x.shape
    nh, hd, nope, vd, rank = s["nh"], s["hd"], s["nope"], s["v"], s["rank"]
    r = lambda t: rounded(t, control)
    h = r(x)
    q = (h @ r(lw["wq"])).reshape(B, S, nh, hd)
    kva = h @ r(lw["wkva"])
    latent = rms_norm(kva[..., :rank], lw["kv_norm"], s["eps"])
    kv = (r(latent) @ r(lw["wkvb"])).reshape(B, S, nh, nope + vd)
    k_rope = jnp.broadcast_to(kva[:, :, None, rank:], (B, S, nh, s["rope"]))
    k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
    v = kv[..., nope:]
    if s["qk_norm"]:
        q, k = rms_norm(q, lw["q_norm"], s["eps"]), rms_norm(k, lw["k_norm"], s["eps"])
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], s)], axis=-1)
    k = jnp.concatenate([k[..., :nope], rotate(k[..., nope:], s)], axis=-1)
    scale = hd ** -0.5
    if s["yarn"] is not None:
        scale *= _mscale(s["yarn"]["factor"], s["yarn"].get("mscale_all_dim", 0)) ** 2
    kr, vr = r(k), r(v)

    def queries(block):
        qb, at = block                                    # [B,qb,nh,hd], [qb]
        scores = jnp.einsum("bqnd,bknd->bnqk", r(qb), kr) * scale
        scores = jnp.where(at[:, None] >= jnp.arange(S)[None, :], scores, -jnp.inf)
        return jnp.einsum("bnqk,bknd->bqnd", r(jax.nn.softmax(scores, axis=-1)), vr)

    if checkpoint and S > QUERY_BLOCK and S % QUERY_BLOCK == 0:
        n = S // QUERY_BLOCK
        a = jax.lax.map(jax.checkpoint(queries),
                        (q.reshape(B, n, QUERY_BLOCK, nh, hd).swapaxes(0, 1),
                         jnp.arange(S).reshape(n, QUERY_BLOCK)))
        a = a.swapaxes(0, 1).reshape(B, S, nh * vd)
    else:
        a = queries((q, jnp.arange(S))).reshape(B, S, nh * vd)
    if s["gated"]:
        a = a * jax.nn.sigmoid(h @ r(lw["wg"]))
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


def layer(streams, lw, s: dict, control=None, checkpoint: bool = False):
    """One layer on ``streams`` = (r_(i-1), r_(i-2)) (the same array twice
    without FarSkip's history). lw: this layer's slice, prefix stripped.
    Returns (streams', balance loss, assignments per published expert)."""
    near, far = streams
    B, S, H = near.shape
    read = far if s["farskip"] else near
    after = near + attention(rms_norm(read, lw["norm1"], s["eps"]), lw, s, control,
                             checkpoint)
    read = near if s["farskip"] else after
    h = rms_norm(read, lw["norm2"], s["eps"]).reshape(B * S, H)
    if "router" not in lw:
        m = in_blocks(lambda t: gated_mlp(t, lw["gate"], lw["up"], lw["down"], control),
                      h, TOKEN_BLOCK, checkpoint)
        balance, load = jnp.zeros(()), jnp.zeros((s["E"],))
    else:
        weight, balance, load = route(h, lw["router"], lw["router_bias"], s, S, control)
        m = held_experts(h, weight, lw, s, control, checkpoint)
        m = m + in_blocks(lambda t: gated_mlp(t, lw["s_gate"], lw["s_up"], lw["s_down"],
                                              control), h, TOKEN_BLOCK, checkpoint)
    return (after + m.reshape(B, S, H), after), balance, load


_ATTENTION_KEYS = ("norm1", "norm2", "wq", "wkva", "kv_norm", "wkvb", "wo", "wg",
                   "q_norm", "k_norm")
_DENSE_KEYS = _ATTENTION_KEYS + ("gate", "up", "down")
_EXPERT_KEYS = _ATTENTION_KEYS + ("router", "router_bias", "w_gate", "w_up", "w_down",
                                  "s_gate", "s_up", "s_down")


def _cast(w: Weights, dtype) -> Weights:
    return {k: v.astype(dtype) for k, v in w.items()}


def _layers(streams, w, prefix, keys, s, control, checkpoint):
    """The stacked layers under ``prefix``, one after another."""
    def fn(streams, lw):
        streams, balance, load = layer(streams, lw, s, control, checkpoint)
        return streams, (balance, load)
    if checkpoint:  # departure: memory only, same arithmetic
        fn = jax.checkpoint(fn)
    return jax.lax.scan(fn, streams, {k: w[prefix + k] for k in keys})


def streams_and_losses(w: Weights, ids, config: dict, *, control=None,
                       checkpoint: bool = False):
    """(the final stream r_2L [B,S,H], the module's output stream or None,
    balance losses of the expert layers and the module's [L-D+M],
    assignments per published expert of the same layers [L-D+M, E])."""
    s = sizes(config)
    x = w["embed"][ids]
    streams = (x, x)
    if s["D"]:
        streams, _ = _layers(streams, w, "d_", _DENSE_KEYS, s, control, checkpoint)
    streams, (balance, load) = _layers(streams, w, "", _EXPERT_KEYS, s, control, checkpoint)
    x = streams[0]
    if not s["M"]:
        return x, None, balance, load
    nxt = w["embed"][jnp.roll(ids, -1, axis=1)]      # the row's last: its first
    merged = rounded(jnp.concatenate(
        [rms_norm(x, w["m_norm_h"], s["eps"]), rms_norm(nxt, w["m_norm_e"], s["eps"])],
        axis=-1), control) @ rounded(w["m_merge"], control)
    streams, (m_balance, m_load) = _layers((merged, merged), w, "m_", _EXPERT_KEYS, s,
                                           control, checkpoint)
    return (x, streams[0], jnp.concatenate([balance, m_balance]),
            jnp.concatenate([load, m_load]))


def head_logits(w: Weights, x, norm, s: dict, control=None):
    return rounded(rms_norm(x, norm, s["eps"]), control) @ rounded(w["head"], control)


def forward(w: Weights, ids, config: dict, *, control=None, checkpoint: bool = False):
    """float32 logits [B,S,V] of token ids [B,S] (the main head's)."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        w = _cast(w, jnp.float32)
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
                    checkpoint: bool = False):
    """The training objective (equation 5)."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        w = _cast(w, jnp.float32)
        x, mx, balance, _ = streams_and_losses(w, ids, config, control=control,
                                               checkpoint=checkpoint)
        loss = _mean_nll(w, x, w["norm_f"], ids, 1, s, control, checkpoint)
        if mx is not None:
            loss = loss + s["lam"] * _mean_nll(w, mx, w["m_norm_f"], ids, 2, s, control,
                                               checkpoint)
        return loss + s["alpha"] * jnp.sum(balance)


def router_load(w: Weights, ids, config: dict):
    """Assignments each published expert drew, [expert layers (+ the
    module's), E]: what moves the correction bias after the step."""
    with jax.default_matmul_precision("highest"):
        return streams_and_losses(_cast(w, jnp.float32), ids, config)[3]


def loss_and_gradient(w: Weights, ids, config: dict, *, control=None):
    """(loss, l2 norm of the gradient over every weight, sign of every
    gradient element as int8 under the weights' names). The correction
    bias enters under ``stop_gradient``: its signs are exactly 0."""
    w32 = _cast(w, jnp.float32)
    loss, g = jax.value_and_grad(
        lambda p: next_token_loss(p, ids, config, control=control, checkpoint=True))(w32)
    sq = sum(jnp.sum(jnp.square(v)) for v in g.values())
    return loss, jnp.sqrt(sq), {k: jnp.sign(v).astype(jnp.int8) for k, v in g.items()}


def matmul_params(config: dict) -> float:
    """Parameters that multiply each token HERE: the attention kernels (the
    gate's too), the dense layer's MLP, the router, the shared expert, the
    routed experts at ``num_experts_per_tok x held / published`` a token
    (a token's chosen experts that live on other chips multiply it there,
    not here), the module's merge, and the head once for each loss. The
    embedding is a lookup and the norm gains are scalings: not counted."""
    s = sizes(config)
    H, I = s["H"], s["I"]
    attn = sum(a * b for a, b in _attention_shapes(s).values())
    routed = s["k"] * s["Eh"] / s["E"] * 3 * H * I
    expert_layer = attn + H * s["E"] + 3 * H * s["ns"] * I + routed
    return (s["D"] * (attn + 3 * H * s["F"]) + (s["L"] - s["D"] + s["M"]) * expert_layer
            + s["M"] * 2 * H * H + (1 + s["M"]) * H * s["V"])


def train_flops_per_token(config: dict, seq: int) -> float:
    """FLOPs one trained token REQUIRES of this chip at sequence length
    ``seq`` (the contract is benchmark/reference/gpt2.py's): 6 per matmul
    parameter a token meets here (``matmul_params``: the routed experts
    count at 6 x held / published a token, the share of a token's experts
    an even router sends here), plus QK^T and PV of every head over the
    causal half of the sequence in every layer and the module, 6 (L + M) nh
    hd S. The share at 8 of 64 experts, 6 layers and the module, S = 8192:
    6 x 393.9 M + 6 x 7 x 2048 x 8192 = 3.068 GFLOP."""
    s = sizes(config)
    return (6.0 * matmul_params(config)
            + 6.0 * (s["L"] + s["M"]) * s["nh"] * s["hd"] * seq)


def expert_product_flops_per_row(config: dict) -> float:
    """FLOPs ONE product of a routed expert's MLP costs ONE routed row
    (the contract is benchmark/reference/olmoe.py's): 2 x 2048 x 1408."""
    s = sizes(config)
    return 2.0 * s["H"] * s["I"]
