"""Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``; the SambaY
decoder-hybrid-decoder of arXiv:2507.06607, on Samba arXiv:2406.07522, Mamba
arXiv:2312.00752, YOCO arXiv:2405.05254 and the Differential Transformer
arXiv:2410.05258) in plain ``jax.numpy``: forward pass, next-token loss and its
gradient, read from a configuration file with Hugging Face's key names.

Written from the configuration's keys and the papers' equations; what neither
settles is marked + and stands in the file's ``assumed`` in the same words. C
``hidden_size``, nh query and kvh key-value heads of hd = C / nh, I
``intermediate_size``, Di = 2 C scan channels+, N = 16 states a channel+, taps =
4+, R = ceil(C / 16) = the rank of dt+ (Mamba-1's defaults: the row has
``mb_per_layer`` alone), W ``sliding_window``, V ``vocab_size``, L
``num_hidden_layers``, eps ``layer_norm_eps``. A row holds S token ids x.

1. ``h = E[x]``; no positional term anywhere+.
2. A layer l: ``h = h + Mixer_l(LN(h))``, ``h = h + MLP(LN(h))``; ``LN`` has a
   gain and a bias; ``MLP(u) = (silu(u W_gate) * (u W_up)) W_down`` (the
   published fused ``gate_up`` matrix's two halves), no bias.
3. Which mixer+ (the released rule): l is a SCAN layer iff ``l % mb_per_layer
   == 0``, else attention, under the window W where ``l < L / 2`` and full from
   there. Layers ``l >= L / 2 + 2`` are the cross-decoder: its attention layers
   make queries alone and attend the keys and values of layer ``L / 2 + 1``;
   its scan-slot layers are gated memory units over the scan output of layer ``L
   / 2``. So the stack is three sections: ``L / 4`` pairs (scan, window), the pair
   (scan*, full) that hands its tensors on, ``L / 4 - 1`` pairs (memory unit,
   cross).
4. Scan mixer (Mamba-1): ``[a, z] = u W_in``; ``a = silu(conv(a) + b_conv)``,
   ``conv(a)_t = sum_k w_k a_{t - (taps - 1 - k)}`` over the taps whose token lies
   in t's document+; ``[r, B, C] = a W_x``; ``dt = softplus(r W_dt + b_dt)``; ``A =
   -exp(A_log)``; ``h_t = exp(dt_t A) * h_{t-1} + (dt_t a_t) (x) B_t``, ``h`` = 0
   before a document's first token+; ``m_t = h_t C_t + D * a_t``; ``y = (m *
   silu(z)) W_out``. ``m`` is the memory a cross-decoder's units read+.
5. Gated memory unit: ``y = (silu(u W_1) * m) W_2``.
6. Differential attention (every attention layer+): ``q = u W_q + b_q`` as nh
   heads, ``[k, v] = u W_kv + b_kv`` as kvh heads each; heads paired by parity+
   (``q1 = q[0::2]``, ``q2 = q[1::2]``, k and v alike; query head i of a half reads
   key head ``i // (nh / kvh)`` of that half); ``o_j = softmax(q_j k_j^T / sqrt(hd))
   [v1 | v2]`` for j = 1, 2 over the keys at or before the query, in its document
   and, under a window, among the W latest; ``lambda = exp(lq1 . lk1) - exp(lq2 .
   lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``+; ``o =
   RMSNorm_{2 hd}(o1 - lambda o2) * (1 - lambda_init)`` with one gain of 2 hd a
   layer, then ``o W_o + b_o``. A cross layer has ``W_q``, the four vectors, the
   norm's gain and ``W_o`` of its own.
7. After the last layer a LayerNorm, then the tied head ``E^T``; the loss is the
   mean cross-entropy of position i's logits against ``x_{i+1}``.

It imports nothing of the program under test and nothing of the benchmark, and
exports what every reference file exports (benchmark/reference/gpt2.py lists
them), ``scan_flops_per_row`` / ``scan_bytes_per_row`` and ``diff_pairs`` /
``diff_pair_flops`` for the roofline readers. Departures: random weights from a
seed (`make_weights`); memory only: the scan a token at a time inside blocks of
`SCAN_BLOCK` tokens, each made again in its backward; blocks of `QUERY_BLOCK`
queries against the row's keys; rows in blocks through the MLP and the head;
``jax.checkpoint`` around them; the gradient a layer at a time from the last to
the first (`loss_and_gradient`); and the controls: ``fp8`` rounds every matmul
operand to float8_e4m3fn, ``bf16_state`` the scan's carried state to bfloat16
after every token.

Weights are one flat dict, ``<kind>.<name>``, a kind's layers stacked on a
leading axis: ``ss`` the self-decoder's scan layers, ``sw`` its window layers,
``ms`` / ``mf`` the pair that hands on, ``cg`` / ``cx`` the cross-decoder's memory
units and cross layers. Every kind: ``norm1_g norm1_b norm2_g norm2_b [n, C]``,
``w_gate w_up [n, C, I]``, ``w_down [n, I, C]``; scan kinds ``w_in [n, C, 2 Di]``,
``conv [n, taps, Di]``, ``conv_b [n, Di]``, ``w_x [n, Di, R + 2 N]``, ``w_dt [n, R,
Di]``, ``dt_b [n, Di]``, ``A_log [n, Di, N]``, ``D [n, Di]``, ``w_out [n, Di, C]``;
attention kinds ``wq [n, C, nh hd]``, ``bq``, ``wkv [n, C, 2 kvh hd]``, ``bkv`` (not
``cx``), ``wo``, ``bo``, ``lam [n, 4, hd]``, ``subln [n, 2 hd]``; ``cg`` ``w1 [n, C, Di]``,
``w2 [n, Di, C]``; and ``embed [V, C]``, ``norm_f_g``, ``norm_f_b [C]``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

Weights = Dict[str, jax.Array]

#: tokens of a block of the scan, queries of a block of the attention scores,
#: rows of a block through the MLP and the head's loss (memory only)
SCAN_BLOCK = 128
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048

#: the kinds of layer in the stack's order of sections, and which mixer each has
KINDS = ("ss", "sw", "ms", "mf", "cg", "cx")
_SCAN, _ATTN = ("ss", "ms"), ("sw", "mf", "cx")


def sizes(config: dict) -> dict:
    """The published keys this file reads, under short names, and Mamba-1's
    defaults (``assumed``)."""
    if (config.get("hidden_act", "silu") != "silu" or not config.get("tie_word_embeddings")
            or config.get("mlp_bias") or config.get("lm_head_bias")
            or int(config["mb_per_layer"]) != 2 or int(config["num_hidden_layers"]) % 4
            or int(config["num_hidden_layers"]) < 8):
        raise ValueError("SiLU, a tied head, no MLP or head bias, a scan layer every "
                         "second layer, a depth that is a multiple of 4, at least 8")
    C, nh = int(config["hidden_size"]), int(config["num_attention_heads"])
    assumed = config.get("assumed", {})
    Di = int(assumed.get("d_inner", 2 * C))
    return dict(V=int(config["vocab_size"]), C=C, L=int(config["num_hidden_layers"]),
                I=int(config["intermediate_size"]), nh=nh,
                kvh=int(config["num_key_value_heads"]), hd=C // nh,
                W=int(config["sliding_window"]), eps=float(config["layer_norm_eps"]),
                Di=Di, N=int(assumed.get("d_state", 16)), taps=int(assumed.get("d_conv", 4)),
                R=int(assumed.get("dt_rank", -(-C // 16))),
                sep=assumed.get("separator"))


def counts(s: dict) -> Dict[str, int]:
    """How many layers each kind has (equation 3's three sections)."""
    quarter = s["L"] // 4
    return {"ss": quarter, "sw": quarter, "ms": 1, "mf": 1,
            "cg": quarter - 1, "cx": quarter - 1}


def depth_of(kind: str, i: int, s: dict) -> int:
    """The layer index l of the ``i``-th layer of ``kind``."""
    half = s["L"] // 2
    return {"ss": 2 * i, "sw": 2 * i + 1, "ms": half, "mf": half + 1,
            "cg": half + 2 + 2 * i, "cx": half + 3 + 2 * i}[kind]


def key_of(seed: int):
    """PRNG key of a seed of any size: the bits above 31 are folded in,
    not dropped."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(key, config: dict, dtype=jnp.bfloat16) -> Weights:
    """Random weights from ``key_of(seed)`` in the dtype they are trained from.
    Pure and jittable with the key traced. The scan's arrays as the published
    initialisers draw them: ``A_log`` = log(1 .. N) a channel, ``D`` = 1 (each with
    a seeded jitter of 0.02, so that no array is the same under two seeds), ``dt``'s
    bias the inverse softplus of a log-uniform draw in [1e-3, 1e-1], the
    convolution uniform in +-1/sqrt(taps); the lambda vectors normal(0, 0.1); the
    query and key projections wide enough that scores have a standard deviation
    near 3 (peaked heads); residual projections at GPT-2's 1/sqrt(2 L); norm gains
    near 1 and small biases so that no term can be dropped unseen; the embedding
    at 0.02 (it is the head too)."""
    s = sizes(config)
    C, I, Di, N, R, hd = s["C"], s["I"], s["Di"], s["N"], s["R"], s["hd"]
    q_out, kv_out = s["nh"] * hd, s["kvh"] * hd
    keys = iter(jax.random.split(key, 256))

    def normal(shape, std, mean=0.0):
        return (mean + jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    resid = 0.02 / math.sqrt(2 * s["L"])
    w: Weights = {"embed": normal((s["V"], C), 0.02), "norm_f_g": normal((C,), 0.05, 1.0),
                  "norm_f_b": normal((C,), 0.02)}
    for kind, n in counts(s).items():
        if not n:
            continue
        p = lambda name: f"{kind}.{name}"
        w.update({
            p("norm1_g"): normal((n, C), 0.05, 1.0), p("norm1_b"): normal((n, C), 0.02),
            p("norm2_g"): normal((n, C), 0.05, 1.0), p("norm2_b"): normal((n, C), 0.02),
            p("w_gate"): normal((n, C, I), 0.02), p("w_up"): normal((n, C, I), 0.02),
            p("w_down"): normal((n, I, C), resid)})
        if kind in _SCAN:
            dt = jnp.exp(uniform((n, Di), math.log(1e-3), math.log(1e-1)))
            w.update({
                p("w_in"): normal((n, C, 2 * Di), 0.02),
                p("conv"): uniform((n, s["taps"], Di), -s["taps"] ** -0.5,
                                   s["taps"] ** -0.5).astype(dtype),
                p("conv_b"): normal((n, Di), 0.02),
                p("w_x"): normal((n, Di, R + 2 * N), Di ** -0.5),
                p("w_dt"): normal((n, R, Di), R ** -0.5),
                p("dt_b"): (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                p("A_log"): normal((n, Di, N), 0.02,
                                   jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))),
                p("D"): normal((n, Di), 0.02, 1.0),
                p("w_out"): normal((n, Di, C), resid)})
        elif kind in _ATTN:
            # a normed row has unit RMS: q . k / sqrt(hd) has a deviation near 3
            w.update({
                p("wq"): normal((n, C, q_out), math.sqrt(3.0 / C)),
                p("bq"): normal((n, q_out), 0.02),
                p("wo"): normal((n, q_out, C), resid), p("bo"): normal((n, C), 0.02),
                p("lam"): normal((n, 4, hd), 0.1), p("subln"): normal((n, 2 * hd), 0.05, 1.0)})
            if kind != "cx":
                std = jnp.concatenate([
                    jnp.full((kv_out,), math.sqrt(3.0 / C)),
                    jnp.full((kv_out,), 0.02)])
                w[p("wkv")] = (jax.random.normal(next(keys), (n, C, 2 * kv_out), jnp.float32)
                               * std).astype(dtype)
                w[p("bkv")] = normal((n, 2 * kv_out), 0.02)
        else:
            w.update({p("w1"): normal((n, C, Di), 0.02), p("w2"): normal((n, Di, C), resid)})
    return w


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


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
    """Equation 4's convolution over a [B, S, Di], literally: tap k multiplies
    the token ``taps - 1 - k`` back, where that token is in the same document."""
    B, S, _ = a.shape
    taps = conv.shape[0]
    out = jnp.zeros_like(a) + conv_b
    at = jnp.arange(S)
    for k in range(taps):
        back = taps - 1 - k
        if back >= S:
            continue
        src = jnp.maximum(at - back, 0)
        seen = (at >= back)[None, :] & (doc == doc[:, src])
        out = out + jnp.where(seen[..., None], a[:, src], 0.0) * conv[k]
    return out


def _scan_step(carry, xs, low: bool = False):
    """One token of equation 4's recurrence; the carry holds ``A`` and ``D``
    beside the state (a function of the module, so that a caller outside any
    ``jit`` compiles the token loop once a shape, not once a call)."""
    h, A, D = carry
    a_t, dt_t, B_t, C_t, first_t = xs
    h = jnp.where(first_t, 0.0, h)
    h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * a_t)[:, None] * B_t[None, :]
    if low:
        # (``reduce_precision``: a cast there and back is one XLA may drop)
        h = h + jax.lax.stop_gradient(jax.lax.reduce_precision(h, 8, 7) - h)
    return (h, A, D), h @ C_t + D * a_t


def _scan_step_low(carry, xs):
    return _scan_step(carry, xs, low=True)


def _scan_block(carry, xs):
    return jax.lax.scan(_scan_step, carry, xs)


def _scan_block_low(carry, xs):
    return jax.lax.scan(_scan_step_low, carry, xs)


def selective_scan(a, dt, A, Bm, Cm, D, first, control=None, checkpoint: bool = False):
    """Equation 4's recurrence over ONE row, a token at a time: a, dt [S, Di],
    Bm, Cm [S, N], first [S] -> m [S, Di]; ``control`` ``bf16_state`` rounds the
    carried state to bfloat16 after every token. Memory only: blocks of
    `SCAN_BLOCK` tokens, each made again in its backward."""
    block = _scan_block_low if control == "bf16_state" else _scan_block
    S = a.shape[0]
    xs = (a, dt, Bm, Cm, first)
    carry = (jnp.zeros(A.shape, jnp.float32), A, jnp.broadcast_to(D, A.shape[:1]))
    if not checkpoint or S <= SCAN_BLOCK or S % SCAN_BLOCK:
        return block(carry, xs)[1]
    split = lambda t: t.reshape((S // SCAN_BLOCK, SCAN_BLOCK) + t.shape[1:])
    _, m = jax.lax.scan(jax.checkpoint(block), carry, jax.tree.map(split, xs))
    return m.reshape((S,) + m.shape[2:])


def scan_mixer(u, doc, lw, s: dict, control=None, checkpoint: bool = False):
    """Equation 4 on the normed input u [B, S, C] -> (y, the memory m)."""
    Di, N, R = s["Di"], s["N"], s["R"]
    r = lambda t: rounded(t, control)
    az = r(u) @ r(lw["w_in"])
    a = jax.nn.silu(short_conv(az[..., :Di], doc, lw["conv"], lw["conv_b"]))
    rbc = r(a) @ r(lw["w_x"])
    dt = jax.nn.softplus(r(rbc[..., :R]) @ r(lw["w_dt"]) + lw["dt_b"])
    one_row = lambda a, dt, Bm, Cm, first: selective_scan(
        a, dt, -jnp.exp(lw["A_log"]), Bm, Cm, lw["D"], first, control, checkpoint)
    m = jax.vmap(one_row)(a, dt, rbc[..., R:R + N], rbc[..., R + N:], first_of_document(doc))
    return r(m * jax.nn.silu(az[..., Di:])) @ r(lw["w_out"]), m


def memory_unit(u, m, lw, control=None):
    """Equation 5."""
    r = lambda t: rounded(t, control)
    return r(jax.nn.silu(r(u) @ r(lw["w1"])) * m) @ r(lw["w2"])


def visible(q_at, k_at, q_doc, k_doc, window: Optional[int]):
    """Equation 6's mask, densely (broadcasting): the key is at or before the
    query, in its document and, under a window, among the ``window`` latest."""
    seen = (k_at <= q_at) & (k_doc == q_doc)
    if window:
        seen = seen & (q_at - k_at < window)
    return seen


def attend(q, k, vv, doc, window, control=None, checkpoint: bool = False):
    """ONE softmax of the queries q [B, S, h, hd] over the keys k [B, S, kh,
    hd], multiplied into the values vv [B, S, kh, dv] -> [B, S, h, dv]; query
    head i reads key head ``i // (h / kh)``. Memory only: a block of queries at
    a time against the row's keys."""
    B, S, h, hd = q.shape
    g = h // k.shape[2]
    r = lambda t: rounded(t, control)
    kr, vr = r(jnp.repeat(k, g, axis=2)), r(jnp.repeat(vv, g, axis=2))
    at = jnp.arange(S)

    def block(qs):
        qb, q_at = qs                                    # [n, B, h, hd], [n]
        sc = jnp.einsum("qbhd,bkhd->bhqk", r(qb), kr) * hd ** -0.5
        seen = visible(q_at[None, :, None], at[None, None, :],
                       jnp.take(doc, q_at, axis=1)[:, :, None], doc[:, None, :], window)
        sc = jnp.where(seen[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->qbhd", r(jax.nn.softmax(sc, axis=-1)), vr)

    out = in_blocks(block, (q.swapaxes(0, 1), at), QUERY_BLOCK, checkpoint)
    return out.swapaxes(0, 1)


def diff_attention(u, doc, lw, s: dict, depth: int, window, kv=None, control=None,
                   checkpoint: bool = False):
    """Equation 6 on the normed input u -> (the branch's output, (k, v));
    ``kv``: another layer's keys and values (a cross layer)."""
    B, S, _ = u.shape
    nh, kvh, hd = s["nh"], s["kvh"], s["hd"]
    r = lambda t: rounded(t, control)
    q = (r(u) @ r(lw["wq"]) + lw["bq"]).reshape(B, S, nh, hd)
    if kv is None:
        made = r(u) @ r(lw["wkv"]) + lw["bkv"]
        kv = (made[..., :kvh * hd].reshape(B, S, kvh, hd),
              made[..., kvh * hd:].reshape(B, S, kvh, hd))
    k, v = kv
    values = jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], axis=-1)
    o1, o2 = (attend(q[:, :, i::2], k[:, :, i::2], values, doc, window, control, checkpoint)
              for i in (0, 1))
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = (jnp.exp(jnp.sum(lw["lam"][0] * lw["lam"][1]))
           - jnp.exp(jnp.sum(lw["lam"][2] * lw["lam"][3])) + lam_init)
    o = o1 - lam * o2
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + s["eps"]) * lw["subln"]
    o = (o * (1.0 - lam_init)).reshape(B, S, nh * hd)
    return r(o) @ r(lw["wo"]) + lw["bo"], kv


def mlp(n2d, lw, control=None, checkpoint: bool = False):
    """The gated SiLU MLP over rows n2d [T, C], a block of rows at a time."""
    r = lambda t: rounded(t, control)
    wg, wu, wd = r(lw["w_gate"]), r(lw["w_up"]), r(lw["w_down"])

    def rows(h):
        h = r(h)
        return r(jax.nn.silu(h @ wg) * (h @ wu)) @ wd
    return in_blocks(rows, n2d, TOKEN_BLOCK, checkpoint)


def layer(x, doc, lw, kind: str, depth: int, s: dict, shared=None, control=None,
          checkpoint: bool = False):
    """One layer of ``kind`` at layer index ``depth`` on x [B, S, C] -> (x', what
    it hands on: the memory m of ``ms``, (k, v) of ``mf``, else None). ``shared``:
    what ``cg`` (the memory) and ``cx`` ((k, v)) read."""
    B, S, C = x.shape
    ck = jax.checkpoint if checkpoint else (lambda f: f)   # memory only

    def mixer(x, lw, shared):
        u = layer_norm(x, lw["norm1_g"], lw["norm1_b"], s["eps"])
        if kind in _SCAN:
            return scan_mixer(u, doc, lw, s, control, checkpoint)
        if kind == "cg":
            return memory_unit(u, shared, lw, control), None
        return diff_attention(u, doc, lw, s, depth, s["W"] if kind == "sw" else None,
                              shared if kind == "cx" else None, control, checkpoint)

    y, handed = ck(mixer)(x, lw, shared)
    x = x + y
    x = x + ck(lambda x, lw: mlp(
        layer_norm(x, lw["norm2_g"], lw["norm2_b"], s["eps"]).reshape(B * S, C),
        lw, control, checkpoint))(x, lw).reshape(B, S, C)
    return x, (handed if kind in ("ms", "mf") else None)


def order(s: dict):
    """The stack's layers in order: (kind, index within its kind)."""
    n = counts(s)
    out = []
    for i in range(n["ss"]):
        out += [("ss", i), ("sw", i)]
    out += [("ms", 0), ("mf", 0)]
    for i in range(n["cg"]):
        out += [("cg", i), ("cx", i)]
    return out


def _f32(w: Weights, kind: str, i: int) -> Weights:
    """Layer ``i`` of ``kind``, float32, under the bare names."""
    prefix = kind + "."
    return {k[len(prefix):]: v[i].astype(jnp.float32)
            for k, v in w.items() if k.startswith(prefix)}


_READS = {"cg": "ms", "cx": "mf"}


def stream(w: Weights, ids, config: dict, *, control=None, checkpoint: bool = False):
    """The stream after the last layer [B, S, C] (equations 1-6)."""
    s = sizes(config)
    doc = documents(ids, s)
    x = w["embed"].astype(jnp.float32)[ids]
    handed = {}
    for kind, i in order(s):
        x, out = layer(x, doc, _f32(w, kind, i), kind, depth_of(kind, i, s), s,
                       handed.get(_READS.get(kind)), control, checkpoint)
        if out is not None:
            handed[kind] = out
    return x


def head_logits(hw: Weights, x, s: dict, control=None):
    """Equation 7's logits; hw: ``norm_f_g``, ``norm_f_b`` and ``embed``."""
    n = layer_norm(x, hw["norm_f_g"], hw["norm_f_b"], s["eps"])
    return rounded(n, control) @ rounded(hw["embed"], control).T


def forward(w: Weights, ids, config: dict, *, control=None, checkpoint: bool = False):
    """float32 logits [B, S, V]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        x = stream(w, ids, config, control=control, checkpoint=checkpoint)
        return head_logits(_head(w), x, s, control)


def _head(w: Weights) -> Weights:
    return {k: w[k].astype(jnp.float32) for k in ("norm_f_g", "norm_f_b", "embed")}


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
    ``next_token_loss`` by the chain rule a layer at a time, last to first
    (memory only: one layer's float32 weights and gradient at a time); what a
    layer handed on collects its readers' cotangents, summed in float32, before
    its own backward; the tied embedding collects the head's and the lookup's."""
    s = sizes(config)
    doc = documents(ids, s)
    signs, sq = {}, []
    # (memory only, and only where a row is longer than a block: every
    # ``jax.checkpoint`` outside a ``jit`` is a compile of its own)
    blocks = ids.shape[1] > QUERY_BLOCK

    def keep(g: Weights, kind=None, i=None):
        for name, v in g.items():
            sq.append(jnp.sum(jnp.square(v)))
            key = name if kind is None else f"{kind}.{name}"
            signs.setdefault(key, {})[i] = jnp.sign(v).astype(jnp.int8)

    with jax.default_matmul_precision("highest"):
        embed = w["embed"].astype(jnp.float32)
        x, embedded = jax.vjp(lambda e: e[ids], embed)
        inputs, handed = [], {}
        for kind, i in order(s):
            inputs.append(x)
            x, out = layer(x, doc, _f32(w, kind, i), kind, depth_of(kind, i, s), s,
                           handed.get(_READS.get(kind)), control, blocks)
            if out is not None:
                handed[kind] = out
        loss, (g_head, dx) = jax.value_and_grad(
            lambda hw, x: head_loss(hw, x, ids, s, control, blocks), argnums=(0, 1))(_head(w), x)
        d_embed = g_head.pop("embed")
        keep(g_head)
        d_handed = {k: jax.tree.map(jnp.zeros_like, v) for k, v in handed.items()}
        for (kind, i), x_in in reversed(list(zip(order(s), inputs))):
            source = _READS.get(kind)
            fn = lambda x, lw, shared: layer(x, doc, lw, kind, depth_of(kind, i, s), s,
                                             shared, control, blocks)
            _, back = jax.vjp(fn, x_in, _f32(w, kind, i), handed.get(source))
            d_out = d_handed.get(kind)
            dx, g, d_shared = back((dx, d_out))
            if source is not None:
                d_handed[source] = jax.tree.map(jnp.add, d_handed[source], d_shared)
            keep(g, kind, i)
        keep({"embed": d_embed + embedded(dx)[0]})
    n = counts(s)
    out = {name: (by[None] if None in by else
                  jnp.stack([by[i] for i in range(n[name.split(".")[0]])]))
           for name, by in signs.items()}
    return loss, jnp.sqrt(sum(sq)), out


# -- what the step requires, for the share of peak and the rooflines -----------

def matmul_params(config: dict) -> float:
    """Parameters that multiply each token on this chip: every layer's MLP and
    mixer matrices (biases, norms, the convolution and the scan's own arrays are
    counted apart or not at all) and the tied head once."""
    s = sizes(config)
    C, I, Di, N, R = s["C"], s["I"], s["Di"], s["N"], s["R"]
    q_out, kv_out = s["nh"] * s["hd"], s["kvh"] * s["hd"]
    each = {"ss": C * 2 * Di + Di * (R + 2 * N) + R * Di + Di * C,
            "sw": C * q_out + C * 2 * kv_out + q_out * C,
            "cg": 2 * C * Di, "cx": 2 * C * q_out}
    each["ms"], each["mf"] = each["ss"], each["sw"]
    n = counts(s)
    return float(sum(n[k] * (each[k] + 3 * C * I) for k in KINDS) + C * s["V"])


def scan_flops_per_row(config: dict) -> dict:
    """Operations ONE token costs ONE scan layer's recurrence, as equation 4
    writes it, over the Di x N state elements: forward ``dt A`` (1), the
    exponential (1), ``decay * h`` (1), ``(dt a) B`` (1), the sum (1), ``h C`` and
    its sum over the states (2): 7 an element, and ``dt a`` and ``D a`` 3 a
    channel; the backward, which makes the states again (5 an element) and walks
    them once more, ``dh += C dm`` (2), ``dh h decay`` (2), its products with A
    and dt and their sums (4), ``dh B`` and its sum (2), ``dh (dt a)`` and ``h dm``
    with their sums (4), ``dh decay`` (1): 20 an element, 8 a channel."""
    s = sizes(config)
    cells = s["Di"] * s["N"]
    return {"forward": 7.0 * cells + 3.0 * s["Di"], "backward": 20.0 * cells + 8.0 * s["Di"]}


def scan_bytes_per_row(config: dict, itemsize: int = 2) -> dict:
    """Bytes ONE token's scan MUST move in ONE layer whatever implements it:
    forward it reads ``a`` and ``dt`` (Di each, ``itemsize``), ``B`` and ``C`` (N
    each) and writes ``m`` (Di); backward it reads those four and ``dm`` and
    writes ``da``, ``d dt`` (Di each), ``dB`` and ``dC`` (N each, float32)."""
    s = sizes(config)
    Di, N = s["Di"], s["N"]
    return {"forward": itemsize * (3 * Di + 2 * N),
            "backward": itemsize * (5 * Di + 2 * N) + 4 * 2 * N}


def diff_pairs(doc_lens, window: Optional[int] = None) -> int:
    """The (query, key) pairs that exist under causal AND same document AND,
    with ``window``, the ``window`` latest keys, in a row whose pieces of
    documents have the lengths ``doc_lens``. Exact integers."""
    total = 0
    for n in map(int, doc_lens):
        w = n if not window else min(n, int(window))
        total += w * (w + 1) // 2 + (n - w) * w
    return total


def diff_pair_flops(config: dict) -> dict:
    """FLOPs ONE (query, key) pair of ONE query head of a half costs each kernel
    of the differential core: the forward's QK^T at the keys' width and PV at the
    pair's two value heads', 2 hd + 2 (2 hd) (384 at hd 64); the fused
    backward's five products (the scores again, dP at the values' width, dV at
    the values', dK and dQ at the keys'), 3 x 2 hd + 2 x 2 (2 hd) (896);
    ``heads``: the query heads of ONE launch (a half); ``launches``: 2 a layer."""
    s = sizes(config)
    hd = s["hd"]
    return {"forward": 2.0 * hd + 2.0 * 2 * hd, "backward": 3 * 2.0 * hd + 2 * 2.0 * 2 * hd,
            "heads": s["nh"] // 2, "launches": 2}


def train_flops_per_token(config: dict, seq: int) -> float:
    """FLOPs one trained token REQUIRES of this chip at sequence length ``seq``
    (the contract is benchmark/reference/gpt2.py's), a row taken as one
    document: 6 per matmul parameter (`matmul_params`); each scan layer's
    recurrence, forward and backward (`scan_flops_per_row`; a backward that
    makes the states again is the method's own, so it counts); each attention
    layer's pairs, a windowed layer's over ``min(seq, W)`` keys and a full or
    cross layer's over the causal half, at `diff_pair_flops`' forward and
    backward a pair and query head of both halves. Packed documents hide more,
    which is traffic's and not counted. The convolution, the norms, the gates and
    the subtraction are not counted."""
    s = sizes(config)
    n = counts(s)
    scan = scan_flops_per_row(config)
    pair = diff_pair_flops(config)
    a_pair = (pair["forward"] + pair["backward"]) * pair["heads"] * pair["launches"]
    full = diff_pairs([seq]) / float(seq)
    windowed = diff_pairs([seq], s["W"]) / float(seq)
    return (6.0 * matmul_params(config)
            + (n["ss"] + n["ms"]) * (scan["forward"] + scan["backward"])
            + a_pair * (n["sw"] * windowed + (n["mf"] + n["cx"]) * full))
