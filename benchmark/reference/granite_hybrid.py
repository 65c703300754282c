"""Granite 4.0-H (``model_type`` ``granitemoehybrid``; IBM's dense Mamba-2 /
attention hybrid, on Mamba-2 arXiv:2405.21060) in plain ``jax.numpy``: forward
pass, next-token loss and its gradient, read from a configuration file with
Hugging Face's key names.

Written from the configuration's keys and the paper's equations; what neither
settles is marked + and stands in the file's ``assumed`` in the same words. C
``hidden_size``, nh query and kvh key-value heads of hd = C / nh, I
``shared_intermediate_size``, H ``mamba_n_heads`` heads of P ``mamba_d_head``
channels (Di = H P) over N ``mamba_d_state`` states in G ``mamba_n_groups``
groups, taps ``mamba_d_conv``, V ``vocab_size``, L ``num_hidden_layers``, eps
``rms_norm_eps``; the four multipliers m_e ``embedding_multiplier``, m_r
``residual_multiplier``, m_a ``attention_multiplier``, m_l ``logits_scaling``. A
row holds S token ids x.

1. ``h = m_e E[x]``; no positional term anywhere (``position_embedding_type``
   ``nope``).
2. A layer l: ``h = h + m_r Mixer_l(RMSNorm(h))``, ``h = h + m_r MLP(RMSNorm(h))``;
   ``RMSNorm`` has a gain; ``MLP(u) = (silu(u W_gate) * (u W_up)) W_down`` (the
   published fused ``input_linear``'s two halves+), no bias.
3. Which mixer: ``layer_types[l]``, ``mamba`` or ``attention``; a depth below the
   list's length takes its first entries+.
4. Mamba-2 mixer: ``[z, xBC, dt_raw] = u W_in`` (Di, Di + 2 G N, H); ``xBC =
   silu(conv(xBC) + b_conv)``, ``conv(a)_t = sum_k w_k a_{t - (taps - 1 - k)}`` over
   the taps whose token lies in t's document+; ``[a, B, C] = xBC`` (a as H heads
   of P, B and C as G groups of N: head i reads group ``i // (H / G)``); ``dt =
   softplus(dt_raw + dt_bias)``, ``A = -exp(A_log)``, one scalar a head; ``h_t =
   exp(dt_t A) h_{t-1} + dt_t a_t (x) B_t`` ``[H, P, N]``, ``h`` = 0 before a
   document's first token+; ``m_t = h_t C_t + D a_t``; ``y = RMSNorm_g(m *
   silu(z)) W_out``: the gate goes in BEFORE the norm, whose statistic is over a
   group's Di / G channels and whose gain is learned.
5. Attention: ``q = u W_q`` as nh heads, ``k = u W_k``, ``v = u W_v`` as kvh heads
   (query head i reads key head ``i // (nh / kvh)``), no bias, no rotation;
   ``softmax(q k^T m_a)`` over the keys at or before the query in its document+,
   then ``W_o``.
6. After the last layer an RMSNorm, then the tied head: ``logits = (n E^T) / m_l``;
   the loss is the mean cross-entropy of position i's logits against ``x_{i+1}``.

It imports nothing of the program under test and nothing of the benchmark, and
exports what every reference file exports (benchmark/reference/gpt2.py lists
them), ``ssd_flops_per_row`` / ``ssd_bytes_per_row`` for the roofline reader and
``attention_pairs`` / ``attention_pair_flops``. Departures: random weights from a
seed (`make_weights`); memory only: the recurrence a token at a time (as the
recurrence: NOT the chunked form a program may use) inside blocks of
`SCAN_BLOCK` tokens, each made again in its backward; blocks of `QUERY_BLOCK`
queries against the row's keys; rows in blocks through the MLP and the head;
``jax.checkpoint`` around them; the gradient a layer at a time from the last to
the first (`loss_and_gradient`); and the controls: ``fp8`` rounds every matmul
operand to float8_e4m3fn, ``bf16_state`` the recurrence's carried state to
bfloat16 after every token.

Weights are one flat dict. The stack is cut into STRETCHES of consecutive
layers of one kind, ``r0``, ``r1``, ... (ten layers: ``r0`` five Mamba-2 layers,
``r1`` the attention layer, ``r2`` four Mamba-2 layers), a stretch's layers
stacked on a leading axis. Every stretch: ``norm1 norm2 [n, C]``, ``w_gate w_up
[n, C, I]``, ``w_down [n, I, C]``; a Mamba-2 stretch ``w_in [n, C, 2 Di + 2 G N +
H]``, ``conv [n, taps, Di + 2 G N]``, ``conv_b [n, Di + 2 G N]``, ``dt_b A_log D [n,
H]``, ``norm_g [n, Di]``, ``w_out [n, Di, C]``; an attention stretch ``wq [n, C, nh
hd]``, ``wk wv [n, C, kvh hd]``, ``wo [n, nh hd, C]``; and ``embed [V, C]``,
``norm_f [C]``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

Weights = Dict[str, jax.Array]

#: tokens of a block of the recurrence, queries of a block of the attention
#: scores, rows of a block through the MLP and the head's loss (memory only)
SCAN_BLOCK = 128
QUERY_BLOCK = 128
TOKEN_BLOCK = 2048

KINDS = ("mamba", "attention")


def sizes(config: dict) -> dict:
    """The published keys this file reads, under short names."""
    kinds = list(config["layer_types"])
    L = int(config["num_hidden_layers"])
    H, G = int(config["mamba_n_heads"]), int(config["mamba_n_groups"])
    if (config.get("hidden_act", "silu") != "silu" or not config.get("tie_word_embeddings")
            or int(config.get("num_local_experts", 0))
            or config.get("position_embedding_type", "nope") != "nope"
            or config.get("attention_bias") or config.get("mamba_proj_bias")
            or not config.get("mamba_conv_bias", True) or H % G or L > len(kinds)
            or any(kind not in KINDS for kind in kinds)):
        raise ValueError("SiLU, a tied head, no experts, no positional term, no bias "
                         "but the convolution's, groups that divide the heads, a list "
                         "of 'mamba' and 'attention' at least as long as the depth")
    C, nh = int(config["hidden_size"]), int(config["num_attention_heads"])
    return dict(V=int(config["vocab_size"]), C=C, L=L,
                I=int(config["shared_intermediate_size"]), nh=nh,
                kvh=int(config["num_key_value_heads"]), hd=C // nh,
                H=H, P=int(config["mamba_d_head"]), N=int(config["mamba_d_state"]), G=G,
                Di=H * int(config["mamba_d_head"]), taps=int(config["mamba_d_conv"]),
                eps=float(config["rms_norm_eps"]), kinds=tuple(kinds[:L]),
                m_e=float(config["embedding_multiplier"]),
                m_r=float(config["residual_multiplier"]),
                m_a=float(config["attention_multiplier"]),
                m_l=float(config["logits_scaling"]),
                sep=config.get("assumed", {}).get("separator"))


def stretches(s: dict) -> List[Tuple[str, str, int]]:
    """The stack as consecutive layers of one kind: (name, kind, layers)."""
    out: List[Tuple[str, str, int]] = []
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


def make_weights(key, config: dict, dtype=jnp.bfloat16) -> Weights:
    """Random weights from ``key_of(seed)`` in the dtype they are trained from.
    Pure and jittable with the key traced. The three head arrays as the published
    initialisers draw them: ``A_log`` the log of a uniform draw in [1, 16], ``D`` =
    1 (with a seeded jitter of 0.02, so that no array is the same under two
    seeds), ``dt``'s bias the inverse softplus of a log-uniform draw in [1e-3, 1e-1];
    the convolution uniform in +-1/sqrt(taps) with a small bias; the query and
    key projections wide enough that scores (under ``attention_multiplier``, not
    1/sqrt(hd)) have a standard deviation near 3 (peaked heads); residual
    projections at GPT-2's 1/sqrt(2 L); norm gains near 1 so that no term can be
    dropped unseen; the embedding at 0.02 (it is the head too)."""
    s = sizes(config)
    C, I, Di, H, hd = s["C"], s["I"], s["Di"], s["H"], s["hd"]
    conv = Di + 2 * s["G"] * s["N"]
    q_out, kv_out = s["nh"] * hd, s["kvh"] * hd
    keys = iter(jax.random.split(key, 64 + 32 * len(stretches(s))))

    def normal(shape, std, mean=0.0):
        return (mean + jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    resid = 0.02 / math.sqrt(2 * s["L"])
    # a normed row has unit RMS: q . k m_a has a deviation near 3
    qk = math.sqrt(3.0 / (s["m_a"] * math.sqrt(hd) * C))
    w: Weights = {"embed": normal((s["V"], C), 0.02), "norm_f": normal((C,), 0.05, 1.0)}
    for name, kind, n in stretches(s):
        p = lambda leaf: f"{name}.{leaf}"
        w.update({
            p("norm1"): normal((n, C), 0.05, 1.0), p("norm2"): normal((n, C), 0.05, 1.0),
            p("w_gate"): normal((n, C, I), 0.02), p("w_up"): normal((n, C, I), 0.02),
            p("w_down"): normal((n, I, C), resid)})
        if kind == "mamba":
            dt = jnp.exp(uniform((n, H), math.log(1e-3), math.log(1e-1)))
            w.update({
                p("w_in"): normal((n, C, Di + conv + H), 0.02),
                p("conv"): uniform((n, s["taps"], conv), -s["taps"] ** -0.5,
                                   s["taps"] ** -0.5).astype(dtype),
                p("conv_b"): normal((n, conv), 0.02),
                p("dt_b"): (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                p("A_log"): jnp.log(uniform((n, H), 1.0, 16.0)).astype(dtype),
                p("D"): normal((n, H), 0.02, 1.0),
                p("norm_g"): normal((n, Di), 0.05, 1.0),
                p("w_out"): normal((n, Di, C), resid)})
        else:
            w.update({
                p("wq"): normal((n, C, q_out), qk), p("wk"): normal((n, C, kv_out), qk),
                p("wv"): normal((n, C, kv_out), 0.02), p("wo"): normal((n, q_out, C), resid)})
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


def short_conv(a, doc, conv, conv_b):
    """Equation 4's convolution over a [B, S, channels], literally: tap k
    multiplies the token ``taps - 1 - k`` back, where that token is in the same
    document."""
    B, S, _ = a.shape
    taps = conv.shape[0]
    out = jnp.zeros_like(a) + conv_b
    for k in range(taps):
        back = taps - 1 - k
        if back >= S:
            continue
        # the token ``back`` before t, and its document (none before the row)
        shifted = jnp.pad(a[:, :S - back], ((0, 0), (back, 0), (0, 0)))
        its_doc = jnp.pad(doc[:, :S - back], ((0, 0), (back, 0)), constant_values=-1)
        out = out + jnp.where((doc == its_doc)[..., None], shifted, 0.0) * conv[k]
    return out


def _token(carry, xs, low: bool = False):
    """One token of equation 4's recurrence; the carry holds ``A`` and ``D``
    ``[G, H / G]`` beside the state ``[G, H / G, P, N]`` (a group's heads side by
    side: they read the same ``B_t`` and ``C_t``)."""
    h, A, D = carry
    a_t, dt_t, B_t, C_t, first_t = xs              # [Di], [H], [G, N], [G, N], []
    a_t, dt_t = a_t.reshape(h.shape[:3]), dt_t.reshape(A.shape)
    h = jnp.where(first_t, 0.0, h)
    h = (jnp.exp(dt_t * A)[:, :, None, None] * h
         + (dt_t[:, :, None] * a_t)[..., None] * B_t[:, None, None, :])
    if low:
        # (``reduce_precision``: a cast there and back is one XLA may drop)
        h = h + jax.lax.stop_gradient(jax.lax.reduce_precision(h, 8, 7) - h)
    m_t = jnp.einsum("ghpn,gn->ghp", h, C_t) + D[:, :, None] * a_t
    return (h, A, D), m_t.reshape(-1)


def _token_low(carry, xs):
    return _token(carry, xs, low=True)


def _tokens(carry, xs):
    return jax.lax.scan(_token, carry, xs)


def _tokens_low(carry, xs):
    return jax.lax.scan(_token_low, carry, xs)


def recurrence(a, dt, A, Bm, Cm, D, first, head_dim: int, control=None,
               checkpoint: bool = False):
    """Equation 4's recurrence over ONE row, a token at a time: a [S, H P], dt
    [S, H], A and D [H], Bm, Cm [S, G, N], first [S] -> m [S, H P]; ``control``
    ``bf16_state`` rounds the carried state to bfloat16 after every token. Memory
    only: blocks of `SCAN_BLOCK` tokens, each made again in its backward."""
    block = _tokens_low if control == "bf16_state" else _tokens
    S, (G, N), H = a.shape[0], Bm.shape[1:], dt.shape[1]
    xs = (a, dt, Bm, Cm, first)
    carry = (jnp.zeros((G, H // G, head_dim, N), jnp.float32),
             A.reshape(G, H // G), D.reshape(G, H // G))
    if not checkpoint or S <= SCAN_BLOCK or S % SCAN_BLOCK:
        return block(carry, xs)[1]
    split = lambda t: t.reshape((S // SCAN_BLOCK, SCAN_BLOCK) + t.shape[1:])
    _, m = jax.lax.scan(jax.checkpoint(block), carry, jax.tree.map(split, xs))
    return m.reshape((S,) + m.shape[2:])


def mamba_mixer(u, doc, lw, s: dict, control=None, checkpoint: bool = False):
    """Equation 4 on the normed input u [B, S, C]."""
    B, S, _ = u.shape
    Di, H, P, N, G = s["Di"], s["H"], s["P"], s["N"], s["G"]
    r = lambda t: rounded(t, control)
    ck = jax.checkpoint if checkpoint else (lambda f: f)   # memory only
    zxd = r(u) @ r(lw["w_in"])
    z, dt_raw = zxd[..., :Di], zxd[..., 2 * Di + 2 * G * N:]
    xbc = ck(lambda x, conv, conv_b: jax.nn.silu(short_conv(x, doc, conv, conv_b)))(
        zxd[..., Di:2 * Di + 2 * G * N], lw["conv"], lw["conv_b"])
    groups = lambda t: t.reshape(B, S, G, N)
    dt = jax.nn.softplus(dt_raw + lw["dt_b"])
    one_row = lambda a, dt, Bm, Cm, first: recurrence(
        a, dt, -jnp.exp(lw["A_log"]), Bm, Cm, lw["D"], first, P, control, checkpoint)
    m = jax.vmap(one_row)(xbc[..., :Di], dt, groups(xbc[..., Di:Di + G * N]),
                          groups(xbc[..., Di + G * N:]), first_of_document(doc))

    def gated_norm(m, z, gain):
        gated = (m * jax.nn.silu(z)).reshape(B, S, G, Di // G)
        gated = gated / jnp.sqrt(
            jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + s["eps"])
        return gated.reshape(B, S, Di) * gain
    return r(ck(gated_norm)(m, z, lw["norm_g"])) @ r(lw["w_out"])


def attention(u, doc, lw, s: dict, control=None, checkpoint: bool = False):
    """Equation 5 on the normed input u [B, S, C]. Memory only: a block of
    queries at a time against the row's keys."""
    B, S, _ = u.shape
    nh, kvh, hd = s["nh"], s["kvh"], s["hd"]
    g = nh // kvh
    r = lambda t: rounded(t, control)
    q = (r(u) @ r(lw["wq"])).reshape(B, S, kvh, g, hd)
    kr = r((r(u) @ r(lw["wk"])).reshape(B, S, kvh, hd))
    vr = r((r(u) @ r(lw["wv"])).reshape(B, S, kvh, hd))
    at = jnp.arange(S)

    def block(qs):
        qb, q_at = qs                                    # [n, B, kvh, g, hd], [n]
        sc = jnp.einsum("qbhgd,bkhd->bhgqk", r(qb), kr) * s["m_a"]
        seen = ((at[None, None, :] <= q_at[None, :, None])
                & (doc[:, None, :] == jnp.take(doc, q_at, axis=1)[:, :, None]))
        sc = jnp.where(seen[:, None, None], sc, -jnp.inf)
        return jnp.einsum("bhgqk,bkhd->qbhgd", r(jax.nn.softmax(sc, axis=-1)), vr)

    out = in_blocks(block, (q.swapaxes(0, 1), at), QUERY_BLOCK, checkpoint)
    return r(out.swapaxes(0, 1).reshape(B, S, nh * hd)) @ r(lw["wo"])


def mlp(n2d, lw, control=None, checkpoint: bool = False):
    """The gated SiLU MLP over rows n2d [T, C], a block of rows at a time."""
    r = lambda t: rounded(t, control)
    wg, wu, wd = r(lw["w_gate"]), r(lw["w_up"]), r(lw["w_down"])

    def rows(h):
        h = r(h)
        return r(jax.nn.silu(h @ wg) * (h @ wu)) @ wd
    return in_blocks(rows, n2d, TOKEN_BLOCK, checkpoint)


def layer(x, doc, lw, kind: str, s: dict, control=None, checkpoint: bool = False):
    """One layer of ``kind`` on x [B, S, C] (equation 2)."""
    B, S, C = x.shape
    ck = jax.checkpoint if checkpoint else (lambda f: f)   # memory only
    mixer = mamba_mixer if kind == "mamba" else attention
    x = x + s["m_r"] * ck(lambda x, lw: mixer(
        rms_norm(x, lw["norm1"], s["eps"]), doc, lw, s, control, checkpoint))(x, lw)
    return x + s["m_r"] * ck(lambda x, lw: mlp(
        rms_norm(x, lw["norm2"], s["eps"]).reshape(B * S, C),
        lw, control, checkpoint))(x, lw).reshape(B, S, C)


def _stack(w: Weights, name: str) -> Weights:
    """A stretch's stacked weights under the bare names (as they are held)."""
    prefix = name + "."
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def _f32(lw: Weights) -> Weights:
    return {k: v.astype(jnp.float32) for k, v in lw.items()}


@functools.lru_cache(maxsize=None)
def _stretch(kind: str, sizes_key, control, checkpoint: bool):
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
    """The stream after the last layer [B, S, C] (equations 1-5)."""
    s = sizes(config)
    doc = documents(ids, s)
    x = w["embed"].astype(jnp.float32)[ids] * s["m_e"]
    for name, kind, _ in stretches(s):
        x, _ = _stretch(kind, _key(s), control, checkpoint)(x, doc, _stack(w, name))
    return x


def head_logits(hw: Weights, x, s: dict, control=None):
    """Equation 6's logits; hw: ``norm_f`` and ``embed``."""
    n = rms_norm(x, hw["norm_f"], s["eps"])
    return rounded(n, control) @ rounded(hw["embed"], control).T / s["m_l"]


def forward(w: Weights, ids, config: dict, *, control=None, checkpoint: bool = False):
    """float32 logits [B, S, V]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        x = stream(w, ids, config, control=control, checkpoint=checkpoint)
        return head_logits(_head(w), x, s, control)


def _head(w: Weights) -> Weights:
    return {k: w[k].astype(jnp.float32) for k in ("norm_f", "embed")}


def head_loss(hw: Weights, x, ids, s: dict, control=None, checkpoint: bool = False):
    """Equation 6's loss from the final stream x [B, S, C]."""
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
    """Equation 6's loss."""
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
    forward first); the tied embedding collects the head's and the lookup's."""
    s = sizes(config)
    doc = documents(ids, s)
    # (memory only, and only where a row is longer than a block)
    blocks = ids.shape[1] > QUERY_BLOCK
    signs, sq = {}, []
    sign = lambda g: jnp.sign(g).astype(jnp.int8)
    with jax.default_matmul_precision("highest"):
        embed = w["embed"].astype(jnp.float32)
        x, embedded = jax.vjp(lambda e: e[ids] * s["m_e"], embed)
        inputs = []
        for name, kind, _ in stretches(s):
            x, x_in = _stretch(kind, _key(s), control, blocks)(x, doc, _stack(w, name))
            inputs.append(x_in)
        loss, (g_head, dx) = jax.value_and_grad(
            lambda hw, x: head_loss(hw, x, ids, s, control, blocks), argnums=(0, 1))(_head(w), x)
        d_embed = g_head.pop("embed")
        signs["norm_f"] = sign(g_head["norm_f"])
        sq.append(jnp.sum(jnp.square(g_head["norm_f"])))
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
        d_embed = d_embed + embedded(dx)[0]
        signs["embed"] = sign(d_embed)
        sq.append(jnp.sum(jnp.square(d_embed)))
    return loss, jnp.sqrt(sum(sq)), signs


# -- what the step requires, for the share of peak and the roofline ------------

def matmul_params(config: dict) -> float:
    """Parameters that multiply each token on this chip: every layer's MLP and
    mixer matrices (norms, the convolution and the three head arrays are not
    counted) and the tied head once."""
    s = sizes(config)
    C, I, Di = s["C"], s["I"], s["Di"]
    each = {"mamba": C * (2 * Di + 2 * s["G"] * s["N"] + s["H"]) + Di * C,
            "attention": 2 * C * s["nh"] * s["hd"] + 2 * C * s["kvh"] * s["hd"]}
    return float(sum(each[kind] + 3 * C * I for kind in s["kinds"]) + C * s["V"])


def ssd_flops_per_row(config: dict) -> dict:
    """Operations ONE token costs ONE Mamba-2 layer's recurrence whatever
    implements it, over the H x P x N state elements: forward the state's update
    (``(dt a) B`` and the sum, 2) and its read-out (``h C`` and its sum, 2): 4 an
    element; backward the state made again (2), ``dh += dm C`` (2), ``dh B`` for
    da (2), ``dh (dt a)`` for dB (2) and ``h dm`` for dC (2): 10 an element. A
    chunk size appears in neither."""
    s = sizes(config)
    cells = s["H"] * s["P"] * s["N"]
    return {"forward": 4.0 * cells, "backward": 10.0 * cells}


def ssd_bytes_per_row(config: dict, itemsize: int = 2) -> dict:
    """Bytes ONE token's recurrence MUST move in ONE layer whatever implements
    it: forward it reads ``a`` (Di) and ``B``, ``C`` (G N each) at ``itemsize`` and
    ``dt`` (H, float32) and writes ``m`` (Di); backward it reads those and ``dm``
    and writes ``da`` (Di), ``dB``, ``dC`` (G N each, float32), ``d dt`` and the
    decays' gradient (H each, float32)."""
    s = sizes(config)
    Di, shared, H = s["Di"], 2 * s["G"] * s["N"], s["H"]
    return {"forward": itemsize * (2 * Di + shared) + 4 * H,
            "backward": itemsize * (3 * Di + shared) + 4 * shared + 4 * 3 * H}


def attention_pairs(doc_lens) -> int:
    """The (query, key) pairs that exist under causal AND same document in a
    row whose pieces of documents have the lengths ``doc_lens``. Exact integers."""
    return sum(int(n) * (int(n) + 1) // 2 for n in doc_lens)


def attention_pair_flops(config: dict) -> dict:
    """FLOPs ONE (query, key) pair of ONE query head costs each kernel of the
    attention core: the forward's QK^T and PV (4 hd), the fused backward's five
    products (10 hd); ``heads``: the query heads."""
    s = sizes(config)
    return {"forward": 4.0 * s["hd"], "backward": 10.0 * s["hd"], "heads": s["nh"]}


def expert_product_flops_per_row(config: dict) -> float:
    """The contract asks every file whose configuration counts routed experts
    (``num_local_experts``, 0 here) what ONE product of the layer's MLP costs a
    row: with no routed part the shared MLP stands in the expert layer's place,
    and one of its three products is 2 C I."""
    s = sizes(config)
    return 2.0 * s["C"] * s["I"]


def train_flops_per_token(config: dict, seq: int) -> float:
    """FLOPs one trained token REQUIRES of this chip at sequence length ``seq``
    (the contract is benchmark/reference/gpt2.py's), a row taken as one
    document: 6 per matmul parameter (`matmul_params`); each Mamba-2 layer's
    recurrence, forward and backward (`ssd_flops_per_row`); each attention
    layer's causal pairs at `attention_pair_flops`' forward and backward a pair
    and query head. Packed documents hide more, which is traffic's and not
    counted. The convolution, the norms and the gates are not counted."""
    s = sizes(config)
    ssd, pair = ssd_flops_per_row(config), attention_pair_flops(config)
    n = {kind: s["kinds"].count(kind) for kind in KINDS}
    return (6.0 * matmul_params(config)
            + n["mamba"] * (ssd["forward"] + ssd["backward"])
            + n["attention"] * (pair["forward"] + pair["backward"]) * pair["heads"]
            * attention_pairs([seq]) / float(seq))
