"""Decoder-only transformer family (GPT-2 / Llama / Mistral / Mixtral / OPT /
Phi / Falcon / BLOOM / GPT-NeoX / GPT-J).

The reference ships models two ways — HF models patched by kernel injection
(``module_inject/replace_module.py``) and per-arch inference impls
(``inference/v2/model_implementations``). Here one TPU-first implementation
covers the family via config: pre-norm blocks, learned or rotary positions,
LayerNorm or RMSNorm, GELU MLP or gated-SiLU MLP, MHA or GQA, optional MoE.

TPU-first structure:
- **scan over layers**: block parameters are stacked with a leading layer
  dimension and the stack is executed with ``lax.scan`` — one trace/compile of
  the block regardless of depth, XLA-friendly.
- **remat**: each block is wrapped in ``jax.checkpoint`` with a configurable
  policy (``runtime/activation_checkpointing/checkpointing.py`` builds it).
  By default the backward keeps what a matmul or a kernel produced (the
  values named with ``checkpoint_name`` below, in ``pallas_flash.py`` and in
  ``moe/layer.py``), inside the byte budget the engine reads from the
  device, and recomputes norms, rope, activations and residual adds.
- **sharding**: params carry PartitionSpecs (TP over ``model``); activations
  are constrained to ``[data, seq, -]``; Ulysses resharding happens inside
  attention (see ``sequence/layer.py``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..nn import layers as nn
from ..ops.transformer.attention import flash_attention
from ..runtime.activation_checkpointing.checkpointing import (
    KEEP_PRODUCTS, Budget, checkpointed)
from ..runtime.topology import BATCH_AXES, DATA_AXIS, MODEL_AXIS, SEQ_AXIS
from ..utils.jax_compat import with_sharding_constraint
from ..utils.scope import scoped
from ..sequence.layer import ulysses_attention

Params = Dict[str, Any]

ACT_SPEC = P(BATCH_AXES, SEQ_AXIS, None)  # [batch, seq, hidden]

# MLP activations by config name. HF's "gelu_new"/"gelu_pytorch_tanh"
# (gpt2, phi) is the tanh approximation; HF's "gelu" (falcon, galactica)
# is the exact erf form — they differ by up to ~5e-4 per neuron, which
# compounds across layers, so checkpoint ingestion must distinguish them.
ACTIVATIONS = {
    "gelu": nn.gelu,  # tanh approximation
    "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),
    "relu": jax.nn.relu,
}


def _c(x, spec):
    return with_sharding_constraint(x, spec)


@scoped("loss")
def masked_cross_entropy(logits: jax.Array, labels: jax.Array,
                         extra_mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean cross-entropy over positions where ``labels >= 0`` (−100 = HF
    ignore). One-hot contraction instead of take_along_axis: its transpose
    is a dense broadcast-multiply that GSPMD reshards freely, where the
    scatter-add transpose of a gather forces a full rematerialization when
    logits are vocab-sharded (TP lm_head). XLA fuses the one-hot into the
    reduction, so no [..., V] buffer is materialized."""
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(safe, logits.shape[-1], dtype=logp.dtype)
    nll = -jnp.sum(logp * onehot, axis=-1)
    mask = valid.astype(jnp.float32)
    if extra_mask is not None:
        mask = mask * extra_mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """What describes a sparse architecture's expert layer; nothing here
    tunes it. Which path a layer takes follows from ``capacity_factor``:
    a number is the capacity-bucketed GShard path (tokens over capacity
    are dropped, kept weights renormalised, balance loss over the first
    choice); None is the no-drop path (``moe/layer.py::
    MoE.dropless_forward``), which the three fields after it describe."""
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: Optional[float] = 1.25   # None: no capacity, no drops
    min_capacity: int = 4
    aux_loss_coef: float = 0.01          # load-balancing loss coefficient
    normalize_weights: bool = True       # HF norm_topk_prob (OLMoE: False)
    balance_loss: str = "gshard_top1"    # | 'topk_share' (OLMoE, Switch)
    z_loss_coef: float = 0.0             # router z-loss (no-drop path only)

    def __post_init__(self):
        if self.capacity_factor is not None and self.z_loss_coef:
            raise ValueError("the router z-loss is the no-drop path's "
                             "(capacity_factor=None)")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # None => MHA
    hidden_size: int = 768
    intermediate_size: Optional[int] = None  # None => 4*hidden
    activation: str = "gelu"        # 'gelu' | 'gelu_exact' | 'relu' | 'silu_gated'
    norm: str = "layernorm"          # 'layernorm' | 'rmsnorm'
    norm_eps: float = 1e-5           # HF config layer_norm_epsilon / rms_norm_eps
    position: str = "learned"        # 'learned' | 'rope' | 'alibi'
    position_offset: int = 0         # OPT pads learned positions by 2
    rope_theta: float = 10000.0
    rope_dim: Optional[int] = None   # partial rotary (phi/neox/gpt-j); None => head_dim
    rope_style: str = "half"         # 'half' (llama/neox) | 'interleaved' (gpt-j)
    # per-layer causal attention windows (mistral sliding_window; gpt-neo
    # alternating global/local): 0 = global, w > 0 = attend the last w keys.
    # A single int applies to every layer.
    attn_windows: Any = None         # Optional[int | Tuple[int, ...]]
    attn_scale: Optional[float] = None  # gpt-neo: 1.0 (unscaled); None => 1/sqrt(hd)
    embedding_norm: bool = False     # bloom: LayerNorm right after wte
    parallel_block: bool = False     # falcon/phi: x + attn(ln(x)) + mlp(ln(x))
    parallel_norms: bool = False     # falcon-40b/neox: separate ln per parallel branch
    linear_bias: Optional[bool] = None  # None => biases iff layernorm
    attn_bias: Optional[bool] = None    # gpt-j: bias-free attn, biased MLP
    attn_out_bias: Optional[bool] = None  # gpt-neo: bias-free qkv, biased out_proj
    lm_head_bias: bool = False       # phi/gpt-j lm_head carries a bias
    tie_embeddings: bool = True
    causal: bool = True              # False: bidirectional encoder (bert)
    norm_style: str = "pre"          # 'pre' | 'post' (bert-era encoders)
    type_vocab_size: int = 0         # bert segment (token-type) embeddings
    mlm_head: bool = False           # bert cls.predictions transform + bias
    # roberta: position ids are a cumsum over non-pad tokens offset by
    # padding_idx (HF create_position_ids_from_input_ids) — pads land on
    # the padding_idx row, real tokens on padding_idx+1..; requires
    # pad_token_id. position_offset still sizes the table (+2 rows).
    pad_based_positions: bool = False
    pad_token_id: Optional[int] = None
    seq_parallel: str = "ulysses"    # 'ulysses' | 'ring' (long-context SP)
    dtype: Any = jnp.float32         # compute dtype (params kept by engine policy)
    remat: bool = True
    # what the block's backward keeps: KEEP_PRODUCTS (matmul and kernel
    # outputs inside the device's byte budget) | 'nothing_saveable'/'full'
    # (recompute the whole block) | 'attention_only' | 'alternating' | any
    # jax.checkpoint_policies name
    remat_policy: str = KEEP_PRODUCTS
    # olmoe: RMSNorm with its own scale over the WHOLE projected q vector
    # [heads*head_dim] and k vector [kv_heads*head_dim], before the head
    # split and rope (HF OlmoeAttention q_norm / k_norm)
    qk_norm: bool = False
    moe: Optional[MoEConfig] = None
    moe_layer_freq: int = 1          # every k-th layer is MoE when moe is set

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    def num_parameters(self) -> int:
        h, v, L = self.hidden_size, self.vocab_size, self.num_layers
        ffn = self.ffn_size
        kv = self.kv_heads * self.head_dim
        attn = h * (h + 2 * kv) + h * h
        if self.qk_norm:
            attn += h + kv
        if self.activation == "silu_gated":
            mlp = 3 * h * ffn
        else:
            mlp = 2 * h * ffn
        if self.moe is not None:
            mlp = mlp * self.moe.num_experts + h * self.moe.num_experts
        embed = v * h + ((self.max_seq_len + self.position_offset) * h
                         if self.position == "learned" else 0)
        embed += self.type_vocab_size * h
        head = 0 if self.tie_embeddings else v * h
        if self.mlm_head:
            head += h * h + v  # prediction transform + decoder bias
        return embed + head + L * (attn + mlp)


class TransformerLM:

    #: top-level param keys :meth:`embed` reads — the overlap planner's
    #: edge-split schedule (engine._build_zeropp_micro_overlap) keeps
    #: exactly these leaves at the exposed step edges and hoists every
    #: other rest leaf across the block scans. MUST stay in sync with
    #: embed(): a leaf embed reads but this tuple omits would be
    #: classified head-side and its embed-path gradient silently dropped
    #: (the split differentiates embed only w.r.t. these leaves).
    embed_param_keys = ("wte", "wpe", "ln_emb", "wtt")

    def __init__(self, config: TransformerConfig):
        self.config = config
        c = config
        self._wte = nn.Embedding(c.vocab_size, c.hidden_size, shard=True)
        self._wpe = (nn.Embedding(c.max_seq_len + c.position_offset, c.hidden_size)
                     if c.position == "learned" else None)
        base_cls = nn.LayerNorm if c.norm == "layernorm" else nn.RMSNorm
        norm_cls = lambda features: base_cls(features, eps=c.norm_eps)
        self._norm = norm_cls
        # post-LN (bert): the last block's output LN already normalizes the
        # final hidden states — there is no separate final norm
        self._ln_f = norm_cls(c.hidden_size) if c.norm_style == "pre" else None
        # bloom normalizes embeddings before the first block; bert-era
        # encoders do the same (embeddings.LayerNorm)
        self._ln_emb = norm_cls(c.hidden_size) if c.embedding_norm else None
        # bert segment embeddings + MLM prediction head (dense→act→LN, then
        # the tied decoder with its own bias)
        self._wtt = (nn.Embedding(c.type_vocab_size, c.hidden_size)
                     if c.type_vocab_size else None)
        if c.mlm_head:
            self._mlm_dense = nn.Linear(c.hidden_size, c.hidden_size)
            self._mlm_ln = norm_cls(c.hidden_size)
        if not c.causal and c.position not in ("learned",):
            raise ValueError("bidirectional encoders use learned positions")
        if not c.causal and c.seq_parallel == "ring":
            raise ValueError("ring attention is causal-only")
        if c.pad_based_positions and c.pad_token_id is None:
            raise ValueError("pad_based_positions requires pad_token_id")
        if c.attn_windows is not None:
            if not c.causal:
                raise ValueError("attention windows are causal-only")
            if c.seq_parallel == "ring":
                raise ValueError("attention windows are not supported with "
                                 "ring sequence parallelism")
            w = c.attn_windows
            self._windows = tuple([int(w)] * c.num_layers
                                  if isinstance(w, int) else map(int, w))
            if len(self._windows) != c.num_layers:
                raise ValueError(f"attn_windows has {len(self._windows)} "
                                 f"entries for {c.num_layers} layers")
            # windows that can never bind (>= max_seq_len, e.g. mistral's
            # 4096 under a 4096 context) normalize to global, and all-global
            # patterns to None, so PP and the Pallas gate stay open for
            # effectively-windowless models
            self._windows = tuple(0 if wi >= c.max_seq_len else wi
                                  for wi in self._windows)
            if not any(self._windows):
                self._windows = None
        else:
            self._windows = None
        if c.position == "alibi":
            if c.seq_parallel == "ring":
                raise ValueError("alibi positions are not supported with "
                                 "ring sequence parallelism (K/V rotation "
                                 "loses absolute key positions)")
            from ..ops.transformer.attention import alibi_slopes
            self._alibi_slopes = alibi_slopes(c.num_heads)
        else:
            self._alibi_slopes = None
        if not c.tie_embeddings:
            self._lm_head = nn.Linear(c.hidden_size, c.vocab_size,
                                      use_bias=c.lm_head_bias, shard="column")

        # gpt2-style models use biases; falcon keeps layernorm but bias-free
        # linears (linear_bias overrides the norm-derived default)
        use_bias = (c.linear_bias if c.linear_bias is not None
                    else c.norm == "layernorm")
        # gpt-j: attention projections are bias-free while the MLP keeps
        # biases — attn_bias overrides the block-wide default for attn only
        attn_bias = c.attn_bias if c.attn_bias is not None else use_bias
        attn_out_bias = (c.attn_out_bias if c.attn_out_bias is not None
                         else attn_bias)
        kv_out = c.kv_heads * c.head_dim
        self._block_layers = {
            "ln_1": norm_cls(c.hidden_size),
            "q_proj": nn.Linear(c.hidden_size, c.hidden_size, use_bias=attn_bias, shard="column"),
            "k_proj": nn.Linear(c.hidden_size, kv_out, use_bias=attn_bias, shard="column"),
            "v_proj": nn.Linear(c.hidden_size, kv_out, use_bias=attn_bias, shard="column"),
            "o_proj": nn.Linear(c.hidden_size, c.hidden_size, use_bias=attn_out_bias, shard="row"),
        }
        if c.qk_norm:
            self._block_layers["q_norm"] = nn.RMSNorm(c.hidden_size, eps=c.norm_eps)
            self._block_layers["k_norm"] = nn.RMSNorm(kv_out, eps=c.norm_eps)
        if not c.parallel_block or c.parallel_norms:
            # parallel blocks (falcon-7b/phi) feed attention and MLP from the
            # SAME normed input — no second norm exists in the checkpoint;
            # falcon-40b's "new decoder" norms each parallel branch separately
            self._block_layers["ln_2"] = norm_cls(c.hidden_size)
        if c.moe is not None:
            from ..moe.layer import MoE
            self._moe = MoE(
                hidden_size=c.hidden_size,
                intermediate_size=c.ffn_size,
                num_experts=c.moe.num_experts,
                top_k=c.moe.top_k,
                capacity_factor=c.moe.capacity_factor,
                min_capacity=c.moe.min_capacity,
                activation=c.activation,
                normalize_weights=c.moe.normalize_weights,
                balance_loss=c.moe.balance_loss,
            )
        elif c.activation == "silu_gated":
            self._block_layers.update({
                "gate_proj": nn.Linear(c.hidden_size, c.ffn_size, use_bias=False, shard="column"),
                "up_proj": nn.Linear(c.hidden_size, c.ffn_size, use_bias=False, shard="column"),
                "down_proj": nn.Linear(c.ffn_size, c.hidden_size, use_bias=False, shard="row"),
            })
        else:
            self._block_layers.update({
                "fc_in": nn.Linear(c.hidden_size, c.ffn_size, use_bias=use_bias, shard="column"),
                "fc_out": nn.Linear(c.ffn_size, c.hidden_size, use_bias=use_bias, shard="row"),
            })

    # -- init / specs --------------------------------------------------------
    def init(self, rng: jax.Array, dtype=jnp.float32) -> Params:
        c = self.config
        rng_embed, rng_blocks, rng_head = jax.random.split(rng, 3)
        params: Params = {"wte": self._wte.init(rng_embed, dtype)}
        if self._wpe is not None:
            params["wpe"] = self._wpe.init(jax.random.fold_in(rng_embed, 1), dtype)
        if self._ln_emb is not None:
            params["ln_emb"] = self._ln_emb.init(jax.random.fold_in(rng_embed, 2), dtype)
        if self._wtt is not None:
            params["wtt"] = self._wtt.init(jax.random.fold_in(rng_embed, 3), dtype)
        if self._ln_f is not None:
            params["ln_f"] = self._ln_f.init(rng_head, dtype)
        if not c.tie_embeddings:
            params["lm_head"] = self._lm_head.init(rng_head, dtype)
        if c.mlm_head:
            r = jax.random.fold_in(rng_head, 4)
            params["mlm"] = {
                "dense": self._mlm_dense.init(r, dtype),
                "ln": self._mlm_ln.init(jax.random.fold_in(r, 1), dtype),
                "bias": jnp.zeros((c.vocab_size,), dtype),
            }

        def init_block(r):
            block, _ = nn.init_tree(self._block_layers, r, dtype)
            if c.moe is not None:
                block["moe"] = self._moe.init(jax.random.fold_in(r, 7), dtype)
            return block

        params["blocks"] = jax.vmap(init_block)(jax.random.split(rng_blocks, c.num_layers))
        return params

    def specs(self) -> Params:
        c = self.config
        specs: Params = {"wte": self._wte.specs()}
        if self._wpe is not None:
            specs["wpe"] = self._wpe.specs()
        if self._ln_emb is not None:
            specs["ln_emb"] = self._ln_emb.specs()
        if self._wtt is not None:
            specs["wtt"] = self._wtt.specs()
        if self._ln_f is not None:
            specs["ln_f"] = self._ln_f.specs()
        if not c.tie_embeddings:
            specs["lm_head"] = self._lm_head.specs()
        if c.mlm_head:
            specs["mlm"] = {"dense": self._mlm_dense.specs(),
                            "ln": self._mlm_ln.specs(),
                            "bias": P(None)}
        block_specs = {name: layer.specs() for name, layer in self._block_layers.items()}
        if c.moe is not None:
            block_specs["moe"] = self._moe.specs()
        # stacked over layers: prepend None for the layer dim
        block_specs = jax.tree.map(
            lambda s: P(None, *s), block_specs,
            is_leaf=lambda s: isinstance(s, P))
        specs["blocks"] = block_specs
        return specs

    # -- forward -------------------------------------------------------------
    def _rotate(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        """Rotary embedding, possibly PARTIAL (phi applies rope to only the
        first rope_dim of each head, passing the rest through)."""
        c = self.config
        rd = c.rope_dim or c.head_dim
        if rd >= c.head_dim:
            return nn.rotary_embedding(x, positions, c.rope_theta, c.rope_style)
        rot = nn.rotary_embedding(x[..., :rd], positions, c.rope_theta, c.rope_style)
        return jnp.concatenate([rot, x[..., rd:]], axis=-1)

    def _project(self, block: Params, name: str, h: jax.Array) -> jax.Array:
        """The block's linear layer ``name`` over ``h``, its result named
        as one the backward may keep (``remat_policy``'s default)."""
        return checkpoint_name(self._block_layers[name](block[name], h), name)

    def _attn(self, block: Params, h: jax.Array, positions: jax.Array,
              attn_mask: Optional[jax.Array] = None,
              window: Optional[jax.Array] = None) -> jax.Array:
        """Attention over the (pre-normed, or raw for post-LN) input h.
        ``attn_mask`` [B, S] (1 = real token) masks padding bidirectionally
        via the segment-ids mechanism (encoders). ``window`` (traced scalar,
        0 = global) restricts each query to the last ``window`` keys
        (mistral sliding window / gpt-neo local layers)."""
        c = self.config
        B, S, _ = h.shape
        with jax.named_scope("attn"):
            with jax.named_scope("qkv"):
                # saved as projected: QK-norm's backward needs its input
                q = self._project(block, "q_proj", h)
                k = self._project(block, "k_proj", h)
                if c.qk_norm:
                    q = self._block_layers["q_norm"](block["q_norm"], q)
                    k = self._block_layers["k_norm"](block["k_norm"], k)
                q = q.reshape(B, S, c.num_heads, c.head_dim)
                k = k.reshape(B, S, c.kv_heads, c.head_dim)
                v = self._project(block, "v_proj", h).reshape(B, S, c.kv_heads, c.head_dim)
                if c.position == "rope":
                    q = self._rotate(q, positions)
                    k = self._rotate(k, positions)
            with jax.named_scope("core"):
                out = self._attn_core(q, k, v, attn_mask, window)
            with jax.named_scope("out"):
                out = out.reshape(B, S, c.num_heads * c.head_dim)
                return self._project(block, "o_proj", out)

    def _attn_core(self, q, k, v, attn_mask, window) -> jax.Array:
        """Scores, softmax and values (XLA, flash, ring or Ulysses)."""
        c = self.config
        seg = attn_mask.astype(jnp.int32) if attn_mask is not None else None
        kw = {}
        if c.attn_scale is not None:
            kw["scale"] = c.attn_scale
        if window is not None:
            kw["window"] = window
        if c.seq_parallel == "ring":
            if seg is not None:
                raise ValueError("ring attention does not support padding "
                                 "masks (attention_mask)")
            from ..sequence.ring_attention import ring_attention
            return ring_attention(q, k, v, causal=True, scale=c.attn_scale)
        if self._alibi_slopes is not None:
            kw["alibi_slopes"] = jnp.asarray(self._alibi_slopes)
        return ulysses_attention(flash_attention, q, k, v, causal=c.causal,
                                 segment_ids=seg, **kw)

    @property
    def moe_path(self) -> Optional[str]:
        """``"dropless"``, ``"capacity"`` or None (no experts): the path
        the expert layers take, which follows from the configuration."""
        if self.config.moe is None:
            return None
        return "dropless" if self._moe.dropless else "capacity"

    def _aux_zero(self) -> jax.Array:
        """The MoE auxiliary-loss accumulator's zero: a scalar (the GShard
        balance loss), or the no-drop path's two router losses."""
        return jnp.zeros((2,) if self.moe_path == "dropless" else (),
                         dtype=jnp.float32)

    @scoped("mlp")
    def _mlp(self, block: Params, h: jax.Array
             ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
        """MLP over the PRE-NORMED input h -> (out, aux, the no-drop
        path's rows per expert [experts] or None)."""
        c = self.config
        aux, rows = self._aux_zero(), None
        if self.moe_path == "dropless":
            out, aux, rows = self._moe.dropless_forward(block["moe"], h)
        elif c.moe is not None:
            out, aux = self._moe(block["moe"], h)
        elif c.activation == "silu_gated":
            gate = nn.silu(self._project(block, "gate_proj", h))
            up = self._project(block, "up_proj", h)
            out = self._block_layers["down_proj"](block["down_proj"], gate * up)
        else:
            h2 = ACTIVATIONS[c.activation](self._project(block, "fc_in", h))
            out = self._block_layers["fc_out"](block["fc_out"], h2)
        return out, aux, rows

    @scoped("block")   # norms and residual adds are "block" and nothing finer
    def _block_fn(self, attn_mask, carry, block_and_keep):
        if len(block_and_keep) == 3:
            block, keep, window = block_and_keep
        else:  # pipeline stage path: global attention only
            block, keep = block_and_keep
            window = None
        x, positions, aux_acc = carry
        c = self.config
        # keep: per-layer stochastic-depth gate (progressive layer drop,
        # reference runtime/progressive_layer_drop.py); 1.0 = layer active
        if c.norm_style == "post":
            # bert-era encoder block: LN AFTER each residual add. The PLD
            # gate mixes OUTSIDE the norms (keep*block(x) + (1-keep)*x) so a
            # dropped layer (keep=0, gates are binary draws) is a true
            # identity — gating inside would still double-normalize x.
            h = self._block_layers["ln_1"](
                block["ln_1"], x + self._attn(block, x, positions, attn_mask))
            mlp_out, aux, rows = self._mlp(block, h)
            y = self._block_layers["ln_2"](block["ln_2"], h + mlp_out)
            x = _c(keep * y + (1 - keep) * x, ACT_SPEC)
            return (x, positions, aux_acc + keep * aux), rows
        h1 = self._block_layers["ln_1"](block["ln_1"], x)
        if c.parallel_block:
            # falcon/phi residual form: both branches read the block INPUT —
            # through one shared norm (phi/falcon-7b) or per-branch norms
            # (falcon-40b new decoder)
            attn_out = self._attn(block, h1, positions, attn_mask, window)
            hm = (self._block_layers["ln_2"](block["ln_2"], x)
                  if c.parallel_norms else h1)
            mlp_out, aux, rows = self._mlp(block, hm)
            x = _c(x + keep * (attn_out + mlp_out), ACT_SPEC)
        else:
            x = x + keep * self._attn(block, h1, positions, attn_mask, window)
            h2 = self._block_layers["ln_2"](block["ln_2"], x)
            mlp_out, aux, rows = self._mlp(block, h2)
            x = _c(x + keep * mlp_out, ACT_SPEC)
        # the scan stacks the no-drop path's rows per expert over the
        # layers ([layers, experts]); every other model's ys stay None
        return (x, positions, aux_acc + keep * aux), rows

    @scoped("embed")
    def embed(self, params: Params, input_ids: jax.Array,
              token_type_ids: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
        """Front of the network: token + position (+ segment) embeddings,
        embedding norm, cast to compute dtype. Returns (x [B,S,H],
        positions [1,S]). Split out of ``apply`` so the param-streaming
        trainer (zero/param_stream.py) can run it as its own program with
        only the embedding leaves resident."""
        c = self.config
        positions = jnp.arange(input_ids.shape[1])[None, :]
        x = self._wte(params["wte"], input_ids)
        if self._wpe is not None:
            if c.pad_based_positions:
                pad = c.pad_token_id  # __init__ rejects None
                real = (input_ids != pad).astype(jnp.int32)
                pos_ids = jnp.cumsum(real, axis=1) * real + pad
                x = x + self._wpe(params["wpe"], pos_ids)
            else:
                x = x + self._wpe(params["wpe"], positions + c.position_offset)
        if self._wtt is not None:
            tt = (token_type_ids if token_type_ids is not None
                  else jnp.zeros_like(input_ids))
            x = x + self._wtt(params["wtt"], tt)
        if self._ln_emb is not None:
            x = self._ln_emb(params["ln_emb"], x)
        return _c(x.astype(c.dtype), ACT_SPEC), positions

    @scoped("head")
    def head(self, params: Params, x: jax.Array) -> jax.Array:
        """Back of the network: final norm (pre-LN), MLM transform, LM/MLM
        head. Input is the last block's output; returns fp32 logits. The
        tied-embedding head reads ``params['wte']`` — the param-streaming
        trainer keeps the embedding leaves resident for this reason."""
        c = self.config
        if self._ln_f is not None:
            x = self._ln_f(params["ln_f"], x)
        if c.mlm_head:
            # bert cls.predictions: dense → act → LN → tied decoder + bias
            x = ACTIVATIONS[c.activation](
                self._mlm_dense(params["mlm"]["dense"], x))
            x = self._mlm_ln(params["mlm"]["ln"], x)
        if c.tie_embeddings:
            logits = self._wte.attend(params["wte"], x)
        else:
            logits = self._lm_head(params["lm_head"], x)
        if c.mlm_head:
            logits = logits + params["mlm"]["bias"].astype(logits.dtype)
        return logits.astype(jnp.float32)

    def block_apply(self, block: Params, x: jax.Array, positions: jax.Array,
                    keep=1.0, attn_mask: Optional[jax.Array] = None,
                    window: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
        """ONE transformer block over UNSTACKED per-layer params — the
        param-streaming trainer's unit of compute (reference fetches one
        module's partitions at a time, partitioned_param_coordinator.py:280).
        Returns (x', moe_aux)."""
        carry = (x, positions, self._aux_zero())
        keep = jnp.asarray(keep, self.config.dtype)
        packed = (block, keep) if window is None else (block, keep, window)
        (x2, _, aux), _ = self._block_fn(attn_mask, carry, packed)
        return x2, aux

    def scan_blocks_pipelined(self, blocks: Params, x: jax.Array,
                              positions: jax.Array, *, gather, scatter,
                              keep: Optional[jax.Array] = None,
                              attn_mask: Optional[jax.Array] = None,
                              layers_per_step: int = 1,
                              prefetch_depth: int = 1,
                              comm_scope=None, comm_edge=None,
                              scatter_err=None):
        """Layer-granular ZeRO overlap schedule over SHARDED stacked block
        params (the engine's pipelined ZeRO++/stage-3 micro step; see
        runtime/zero/overlap.py for the comm half).

        Forward: a scan whose carry holds the NEXT layer's gathered (full)
        params — iteration *l* issues the all-gather of layer *l+1*'s shard
        via ``gather`` while computing layer *l* with the already-gathered
        buffer (double-buffered prefetch; the buffer is dead after use, so
        at most two layers' full params are live). Per-layer inputs are
        saved as the only activation residuals.

        Backward (returned ``pullback(dx, daux)``): a hand-written reverse
        scan that re-gathers each layer's params (prefetched one iteration
        ahead, like ZeRO-3's backward re-fetch), recomputes the block from
        its saved input (layer-granular remat — the only memory-sane choice
        when saved residuals must not contain full params), and carries the
        just-computed full layer gradients so ``scatter`` (reduce-scatter)
        of layer *l*'s grads is issued during layer *l−1*'s backward
        compute. Gradients come back dp-sharded, fp32, dp-averaged.

        ``layers_per_step=2`` is the half-remat ('alternating') variant's
        shape: the schedule pipelines two-layer bundles — half the
        collective launches (bigger buckets) and half the saved boundary
        activations, at the same per-layer recompute.

        ``prefetch_depth=2`` (ISSUE 11; the overlap planner derives it
        when the committed map still shows exposed in-scan bytes at
        depth 1) TRIPLE-buffers the gather prefetch: the carry holds TWO
        gathered layers and iteration *l* issues layer *l+2*'s gather,
        giving each all-gather two layers of compute to hide under — at
        the cost of one more layer's full params live. Applies to the
        forward prefetch and the backward re-gather; the grad
        reduce-scatter stays one-behind (grads exist only after their
        layer's backward — there is nothing to deepen). Clamped to 1
        when fewer than 3 steps (a deeper carry would only re-gather the
        final step). Depth 1 is byte-identical to the pre-ISSUE-11
        schedule.

        ``comm_scope(k)`` (optional) is entered around each scan so the
        comm layer can account its in-body collectives as executing ``k``
        times per step (a scan body traces once but launches per
        iteration) — the engine passes the TreeComm's ``trace_executions``.
        ``comm_edge(overlapped)`` (optional) is entered around the
        pipeline-EDGE launches — the forward prologue gather and the
        epilogue grad flush, which have no compute to hide under — so
        they are recorded exposed rather than inheriting the tree's
        blanket class; the engine passes ``TreeComm.schedule_class``.

        ``scatter_err`` (optional; the overlap planner's error-feedback
        carry, runtime/overlap_planner.py) is a pytree whose leaves have
        a leading ``n_steps`` dim: per-step quantization residual state
        for ``scatter``. When provided, ``scatter(tree, err=slice)``
        must return ``(tree, new_err)``; step *s*'s slice rides the
        backward scan's xs/ys (the launch at reverse iteration *s*
        scatters step *s+1*'s grads, so xs carry ``scatter_err[1:]`` and
        the epilogue flush consumes slot 0) and ``pullback`` returns the
        updated stack as a THIRD element — the engine threads it through
        the micro-step carry so residuals telescope across accumulation
        steps (docs/COLLECTIVES.md "Error feedback").

        Returns ``(x_out, moe_aux_sum, pullback)``.
        """
        import contextlib
        scope = comm_scope or (lambda k: contextlib.nullcontext())
        edge = comm_edge or (lambda overlapped: contextlib.nullcontext())
        c = self.config
        L = c.num_layers
        lps = int(layers_per_step)
        if lps < 1 or L % lps:
            raise ValueError(f"layers_per_step={lps} must divide "
                             f"num_layers={L}")
        n_steps = L // lps
        keep = (jnp.ones((L,), c.dtype) if keep is None
                else keep.astype(c.dtype))
        windows = (jnp.asarray(self._windows, jnp.int32)
                   if self._windows is not None else None)
        bundle = lambda a: a.reshape((n_steps, lps) + a.shape[1:])
        blocksb = jax.tree.map(bundle, blocks)
        keepb = bundle(keep)
        winb = bundle(windows) if windows is not None else None
        take = lambda t, i: jax.tree.map(lambda a: a[i], t)

        def unit_call(bp, xx, kb, wb):
            aux = self._aux_zero()
            for j in range(lps):
                blk = jax.tree.map(lambda a: a[j], bp)
                w = None if wb is None else wb[j]
                xx, a = self.block_apply(blk, xx, positions, keep=kb[j],
                                         attn_mask=attn_mask, window=w)
                aux = aux + a
            return xx, aux

        depth = int(prefetch_depth)
        if depth < 1:
            raise ValueError(f"prefetch_depth={depth} must be >= 1")
        # a deeper carry needs >= 3 steps (at 2 every deep slot would
        # just re-gather the final step); the executor implements 1 and 2
        depth = 1 if n_steps <= 2 else min(depth, 2)

        if depth == 1:
            # xs slot s prefetches step s+1's shard; the last slot
            # re-gathers the final step, seeding the backward's first
            # full buffer for free
            nxt = jax.tree.map(
                lambda a: jnp.concatenate([a[1:], a[-1:]], axis=0), blocksb)
        else:
            # depth 2: xs slot s prefetches step s+2's shard (the last
            # two slots re-gather the final step — same seeding)
            nxt = jax.tree.map(
                lambda a: jnp.concatenate([a[2:], a[-1:], a[-1:]], axis=0),
                blocksb)
        xs = {"shard": nxt, "keep": keepb}
        if winb is not None:
            xs["win"] = winb
        with edge(False):  # prologue: nothing runs yet to hide it
            pf0 = gather(take(blocksb, 0))
            pf1 = gather(take(blocksb, 1)) if depth == 2 else None

        if depth == 1:
            def fwd_body(carry, xs_s):
                xx, pf, aux_acc = carry
                nf = gather(xs_s["shard"])  # independent of compute below
                y, aux = unit_call(pf, xx, xs_s["keep"], xs_s.get("win"))
                return (y, nf, aux_acc + aux), xx

            with scope(n_steps):
                (x_out, pf_last, aux_sum), acts = jax.lax.scan(
                    fwd_body, (x, pf0, self._aux_zero()), xs)
        else:
            def fwd_body(carry, xs_s):
                xx, pf_a, pf_b, aux_acc = carry
                nf = gather(xs_s["shard"])  # two steps ahead
                y, aux = unit_call(pf_a, xx, xs_s["keep"], xs_s.get("win"))
                return (y, pf_b, nf, aux_acc + aux), xx

            with scope(n_steps):
                (x_out, pf_last, _, aux_sum), acts = jax.lax.scan(
                    fwd_body, (x, pf0, pf1, self._aux_zero()), xs)

        # error-feedback carry plumbing: without scatter_err the scatter
        # call and the return arity are EXACTLY the pre-planner form
        if scatter_err is None:
            scat = lambda t, e: (scatter(t), None)
            take_err = lambda i: None
        else:
            scat = lambda t, e: scatter(t, err=e)
            take_err = lambda i: jax.tree.map(lambda a: a[i], scatter_err)

        def pullback(dx_out, daux):
            daux_ = jnp.asarray(daux, jnp.float32)
            wb_last = None if winb is None else winb[-1]
            # peel the last step: its full params came out of the forward
            # scan's final carry, so no zero-valued first scatter and no
            # branch inside the reverse scan
            _, vjp_last = jax.vjp(
                lambda p, xx: unit_call(p, xx, keepb[-1], wb_last),
                pf_last, acts[-1])
            dp, dx = vjp_last((dx_out, daux_))
            unbundle = lambda t: jax.tree.map(
                lambda a: a.reshape((L,) + a.shape[2:]), t)
            if n_steps == 1:
                with edge(False):  # epilogue flush: step's last launch
                    ds0, ne0 = scat(dp, take_err(0))
                dblocks = unbundle(jax.tree.map(lambda a: a[None], ds0))
                if scatter_err is None:
                    return dblocks, dx
                return dblocks, dx, jax.tree.map(lambda a: a[None], ne0)
            pb0 = gather(take(blocksb, n_steps - 2))
            if depth == 1:
                # reverse prefetch: slot s carries step s-1's shard (slot
                # 0 a dead self-gather — the price of one scan body shape)
                prv = jax.tree.map(
                    lambda a: jnp.concatenate([a[:1], a[:-1]],
                                              axis=0)[:n_steps - 1],
                    blocksb)
            else:
                # depth 2: slot s carries step s-2's shard (slots 0/1
                # dead clamp-gathers; depth >= 2 implies n_steps >= 3)
                prv = jax.tree.map(
                    lambda a: jnp.concatenate([a[:1], a[:1], a[:-2]],
                                              axis=0)[:n_steps - 1],
                    blocksb)
            pb1 = (gather(take(blocksb, n_steps - 3))
                   if depth == 2 else None)
            xs_b = {"shard": prv, "act": acts[:n_steps - 1],
                    "keep": keepb[:n_steps - 1]}
            if winb is not None:
                xs_b["win"] = winb[:n_steps - 1]
            if scatter_err is not None:
                # reverse iteration s scatters step s+1's grads, so its
                # xs slot carries residual stack slice [1:]; slot 0 is
                # the epilogue flush's
                xs_b["err"] = jax.tree.map(lambda a: a[1:], scatter_err)

            if depth == 1:
                def bwd_body(carry, xs_s):
                    dxx, pb, pending = carry
                    # layer l+1's grads reduce-scatter while layer l
                    # computes
                    ds_prev, ne = scat(pending, xs_s.get("err"))
                    nb = gather(xs_s["shard"])
                    _, vjp_f = jax.vjp(
                        lambda p, xx: unit_call(p, xx, xs_s["keep"],
                                                xs_s.get("win")),
                        pb, xs_s["act"])
                    dp_s, dxx_new = vjp_f((dxx, daux_))
                    return (dxx_new, nb, dp_s), (ds_prev, ne)

                with scope(n_steps - 1):
                    (dx0, _, pending0), (ds_stack, ne_stack) = jax.lax.scan(
                        bwd_body, (dx, pb0, dp), xs_b, reverse=True)
            else:
                def bwd_body(carry, xs_s):
                    dxx, pb_a, pb_b, pending = carry
                    ds_prev, ne = scat(pending, xs_s.get("err"))
                    nb = gather(xs_s["shard"])  # two steps behind
                    _, vjp_f = jax.vjp(
                        lambda p, xx: unit_call(p, xx, xs_s["keep"],
                                                xs_s.get("win")),
                        pb_a, xs_s["act"])
                    dp_s, dxx_new = vjp_f((dxx, daux_))
                    return (dxx_new, pb_b, nb, dp_s), (ds_prev, ne)

                with scope(n_steps - 1):
                    (dx0, _, _, pending0), (ds_stack, ne_stack) = \
                        jax.lax.scan(bwd_body, (dx, pb0, pb1, dp), xs_b,
                                     reverse=True)
            with edge(False):  # epilogue: flush step 0's grads, exposed
                ds0, ne0 = scat(pending0, take_err(0))
            # ds_stack[s] holds step s+1's sharded grads; step 0 is ds0
            dblocksb = jax.tree.map(
                lambda h, t: jnp.concatenate([h[None], t], axis=0),
                ds0, ds_stack)
            if scatter_err is None:
                return unbundle(dblocksb), dx0
            new_err = jax.tree.map(
                lambda h, t: jnp.concatenate([h[None], t], axis=0),
                ne0, ne_stack)
            return unbundle(dblocksb), dx0, new_err

        return x_out, aux_sum, pullback

    def apply(self, params: Params, input_ids: jax.Array,
              layer_mask: Optional[jax.Array] = None,
              token_type_ids: Optional[jax.Array] = None,
              attention_mask: Optional[jax.Array] = None,
              return_hidden: bool = False,
              return_stats: bool = False,
              remat_budget: Optional[Budget] = None) -> Tuple[jax.Array, ...]:
        """Return (logits [B,S,V] in fp32, moe_aux_loss scalar).

        ``return_stats`` appends the step's device-side statistics, a dict:
        ``moe_expert_rows`` [layers, experts] int32 on the no-drop MoE path
        (the assignments each expert received), else empty.

        ``layer_mask`` [num_layers] gates each block (PLD stochastic depth).
        ``token_type_ids`` [B,S] selects bert segment embeddings;
        ``attention_mask`` [B,S] (1 = real) masks padding in encoders.
        ``return_hidden`` short-circuits before the LM/MLM head, returning
        the final hidden states [B,S,H] (post final-norm) — the hook task
        heads (models/heads.py) build on.
        ``remat_budget``: what the device can give the blocks' saved values
        under the default ``remat_policy`` (an engine's reading; ``None``
        saves everything named) and where the decision is written.
        """
        c = self.config
        x, positions = self.embed(params, input_ids, token_type_ids)

        block_fn = functools.partial(self._block_fn, attention_mask)
        if layer_mask is None:
            keep = jnp.ones((c.num_layers,), c.dtype)
        else:
            keep = layer_mask.astype(c.dtype)
        xs = (params["blocks"], keep)
        if self._windows is not None:
            xs = xs + (jnp.asarray(self._windows, jnp.int32),)
        init = (x, positions, self._aux_zero())
        rows = None
        if c.remat and c.remat_policy == "alternating":
            # HALF-remat: scan over layer pairs, checkpointing only the
            # first of each pair — the backward recomputes every other
            # layer (half the recompute FLOPs of full remat) while the
            # scan stores residuals for only half the layers (half the
            # activation memory of no remat). The sweet spot when full
            # activations don't fit but full recompute over-pays.
            ck_fn = jax.checkpoint(block_fn)

            def pair_fn(carry, xs_pair):
                carry, _ = ck_fn(carry, jax.tree.map(lambda a: a[0], xs_pair))
                carry, _ = block_fn(carry, jax.tree.map(lambda a: a[1], xs_pair))
                return carry, None

            n_pairs = c.num_layers // 2
            xs_even = jax.tree.map(
                lambda a: a[:n_pairs * 2].reshape((n_pairs, 2) + a.shape[1:]),
                xs)
            (x, _, aux), _ = jax.lax.scan(pair_fn, init, xs_even)
            if c.num_layers % 2:  # odd depth: last layer, checkpointed
                (x, _, aux), _ = ck_fn(
                    (x, positions, aux),
                    jax.tree.map(lambda a: a[-1], xs))
        elif c.remat:
            ck_fn = checkpointed(block_fn, c.remat_policy, c.num_layers,
                                 remat_budget)
            (x, _, aux), rows = jax.lax.scan(ck_fn, init, xs)
        else:
            (x, _, aux), rows = jax.lax.scan(block_fn, init, xs)
        stats = ({} if rows is None else {"moe_expert_rows": rows},) \
            if return_stats else ()
        if return_hidden:
            if self._ln_f is not None:
                x = self._ln_f(params["ln_f"], x)
            return (x, aux) + stats
        return (self.head(params, x), aux) + stats

    # The three loss ingredients are separate methods because the ZeRO
    # overlap schedule (engine._build_zeropp_micro_overlap) composes the
    # loss around its own embed/blocks/head vjp pipeline — both schedules
    # MUST share these definitions or `overlap_comm` would silently change
    # the training objective.
    def derive_labels(self, batch: Dict[str, jax.Array]) -> jax.Array:
        """Explicit labels, or the causal next-token shift (-100 = ignore)."""
        labels = batch.get("labels")
        if labels is not None:
            return labels
        if not self.config.causal:
            raise ValueError("encoder (MLM) training requires explicit "
                             "labels — next-token shift is meaningless "
                             "bidirectionally")
        return jnp.pad(batch["input_ids"][:, 1:], ((0, 0), (0, 1)),
                       constant_values=-100)

    def head_loss(self, params: Params, x: jax.Array, labels: jax.Array,
                  extra_mask: Optional[jax.Array] = None) -> jax.Array:
        """Final norm + LM/MLM head + masked cross-entropy over the last
        block's output (the differentiated tail of the overlap schedule)."""
        return masked_cross_entropy(self.head(params, x), labels,
                                    extra_mask=extra_mask)

    def combine_aux(self, loss: jax.Array, aux: jax.Array) -> jax.Array:
        """Fold the accumulated MoE aux loss into the objective: each
        router loss under its own coefficient, averaged over the layers.
        Reads ``self.config`` alone (``PipelineModule`` borrows it)."""
        moe = self.config.moe
        if moe is None:
            return loss
        if moe.capacity_factor is None:   # the no-drop path's two losses
            aux = moe.aux_loss_coef * aux[0] + moe.z_loss_coef * aux[1]
        else:
            aux = moe.aux_loss_coef * aux
        return loss + aux / self.config.num_layers

    def loss(self, params: Params, batch: Dict[str, jax.Array],
             remat_budget: Optional[Budget] = None) -> jax.Array:
        """Cross-entropy: next-token for causal LMs (labels derived by shift
        when absent), masked-LM for encoders (labels required, -100 = ignore).
        batch: input_ids [B,S], optional labels/loss_mask/token_type_ids/
        attention_mask. ``remat_budget`` as in :meth:`apply`."""
        labels = self.derive_labels(batch)
        logits, aux = self.apply(params, batch["input_ids"],
                                 layer_mask=batch.get("layer_mask"),
                                 token_type_ids=batch.get("token_type_ids"),
                                 attention_mask=batch.get("attention_mask"),
                                 remat_budget=remat_budget)
        loss = masked_cross_entropy(logits, labels,
                                    extra_mask=batch.get("loss_mask"))
        return self.combine_aux(loss, aux)

    def loss_and_stats(self, params: Params, batch: Dict[str, jax.Array],
                       remat_budget: Optional[Budget] = None
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """``loss`` with ``apply``'s device-side statistics of the step
        (the engine keeps them on the device beside the loss)."""
        labels = self.derive_labels(batch)
        logits, aux, stats = self.apply(
            params, batch["input_ids"], layer_mask=batch.get("layer_mask"),
            token_type_ids=batch.get("token_type_ids"),
            attention_mask=batch.get("attention_mask"), return_stats=True,
            remat_budget=remat_budget)
        loss = masked_cross_entropy(logits, labels,
                                    extra_mask=batch.get("loss_mask"))
        return self.combine_aux(loss, aux), stats
