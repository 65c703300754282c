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

import collections
import contextlib
import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..nn import layers as nn
from ..runtime.activation_checkpointing.checkpointing import (
    KEEP_PRODUCTS, Budget, checkpointed, resolve_policy)
from ..runtime.topology import BATCH_AXES, DATA_AXIS, MODEL_AXIS, SEQ_AXIS
from ..utils.jax_compat import with_sharding_constraint
from ..utils.scope import scoped
from . import mixers

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


def _one_layer(stacked):
    """One layer of a stacked ``[layers, ...]`` tree, as shapes."""
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), stacked)


@jax.custom_vjp
def _taken_together(x, layer):
    """``(x, layer)`` as given; differentiated, their two cotangents pass ONE
    ``optimization_barrier``: a block that runs by itself (not in a layer scan) has
    its parameters' gradients taken before the layer in front of it starts its
    backward. Left to its scheduler, XLA puts a lone block's weight gradients off
    (nothing reads them before the optimizer) and holds their operands across the
    next run's whole backward (the Kimi Linear cell's first compile for the chip:
    5.7 GB of the layers behind live beside a scan's 5.7). Every lone block of a
    listed stack takes it, whatever its mixer or MLP: its backward is then one
    unit, as a scan's trip is."""
    return x, layer


_taken_together.defvjp(lambda x, layer: ((x, layer), None),
                       lambda _, d: jax.lax.optimization_barrier(d))


def _mlp_of(kind) -> Optional[str]:
    """A run's layer's MLP, the fifth entry of its kind where a mixed stack has
    experts (``"dense"``: a leading layer; ``"experts"``), else None."""
    return kind[4] if len(kind) > 4 else None


def _kind_label(key) -> str:
    """A kind of block's line in the remat plan's report, from `_trunk`'s
    key for it: ``mtp``, or ``[dense.][<mixer>.]<full | window<w>>[.hands_<what>]``
    and, in a mixed stack with experts, ``.dense`` | ``.experts``."""
    if key is None:
        return "mtp"
    if key[0] == "dense":
        return "dense." + _kind_label(key[1])
    window, _, *mixer = key
    return ".".join([*mixer[:1], f"window{window}" if window else "full",
                     *(f"hands_{what}" for what in mixer[1:2] if what is not None),
                     *mixer[2:]])


def _token_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Each position's negative log-likelihood of its target, by a one-hot
    contraction (:func:`masked_cross_entropy` says why not a gather)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logp.dtype)
    return -jnp.sum(logp * onehot, axis=-1)


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
    nll = _token_nll(logits, jnp.where(valid, labels, 0))
    mask = valid.astype(jnp.float32)
    if extra_mask is not None:
        mask = mask * extra_mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@scoped("loss")
def pred_heads_cross_entropy(logits: jax.Array, labels: jax.Array,
                             extra_mask: Optional[jax.Array] = None) -> jax.Array:
    """Several next-token heads on one trunk: ``logits`` [B, S, heads, V],
    ``labels`` [B, S] the FIRST head's (position i's next token, -100 =
    none). Head m's target at position i is ``labels[i + m]`` (token ``i +
    1 + m``); the loss is the mean over the heads of each head's mean
    cross-entropy over the positions that have a target."""
    heads = logits.shape[2]
    later = lambda a, m, fill: jnp.pad(a[:, m:], ((0, 0), (0, m)),
                                       constant_values=fill)
    targets = jnp.stack([later(labels, m, -100) for m in range(heads)], axis=2)
    valid = targets >= 0
    nll = _token_nll(logits, jnp.where(valid, targets, 0))          # [B, S, heads]
    mask = valid.astype(jnp.float32)
    if extra_mask is not None:
        mask = mask * jnp.stack([later(extra_mask, m, 0) for m in range(heads)],
                                axis=2).astype(jnp.float32)
    each = jnp.sum(nll * mask, axis=(0, 1)) / jnp.maximum(jnp.sum(mask, axis=(0, 1)), 1.0)
    return jnp.mean(each)


@contextlib.contextmanager
def _noise_scope():
    """The scope ``diffusion/noise``, as two components: a transform wraps a
    name-stack entry whole (``jvp(diffusion/noise)`` would be one component
    to whoever splits an ``op_name`` at its slashes)."""
    with jax.named_scope("diffusion"), jax.named_scope("noise"):
        yield


@scoped("loss")
def weighted_cross_entropy(logits: jax.Array, targets: jax.Array,
                           weights: jax.Array) -> jax.Array:
    """``sum(weights x CE(logits, targets)) / positions``: the
    block-diffusion objective's sum (a position's weight is 0 or 1 / t),
    over every position of the batch, weighted or not."""
    return jnp.sum(_token_nll(logits, targets) * weights) / targets.size


def next_token_cross_entropy(config, logits, labels, extra_mask=None) -> jax.Array:
    """The next-token objective over a head's logits: one head's masked
    cross-entropy, or with ``config.pred_heads`` the mean over the heads. (A
    function of the configuration: ``PipelineModule`` borrows
    ``TransformerLM.loss``.)"""
    if config.pred_heads > 1:
        return pred_heads_cross_entropy(logits, labels, extra_mask)
    return masked_cross_entropy(logits, labels, extra_mask=extra_mask)


#: The most elements a dense MLP's ``[rows, intermediate]`` product may have
#: before the rows are taken in slices (and ``mixers.EVA_GROUP_ELEMENTS`` the
#: most of ``[rows, heads x head]`` an EVA layer's cores may take at once
#: before the heads are taken in groups). At 32,768 rows x 11008 a product is
#: 0.72 GB in bfloat16 and a block's backward holds six of them beside a
#: dozen ``[rows, hidden]`` copies of the attention's: 9.2 GB by the chip's
#: compiler, beside 9.9 GB of training state on a 16 GB chip; in slices of
#: 4,096 rows and groups of 4 heads it holds 6.1 (PERF.md, PR 42; since PR 64
#: a group holds the cores alone, the four projections run whole). Memory
#: only: the same arithmetic, a slice's intermediates at a time.
MLP_WHOLE_ELEMENTS = 2 ** 28
MLP_SLICE_ELEMENTS = 2 ** 26


def mlp_row_slices(rows: int, width: int) -> int:
    """How many slices of its rows a dense gated MLP is computed in: 1 up to
    ``MLP_WHOLE_ELEMENTS`` elements in a ``[rows, width]`` product (every
    shape the benchmark had before a 32,768-row dense stack: the widest was
    16,384 x 10944), else the least power of two that brings a slice's
    product under ``MLP_SLICE_ELEMENTS`` and divides the rows."""
    n = 1
    if rows * width > MLP_WHOLE_ELEMENTS:
        while rows % (2 * n) == 0 and rows // n * width > MLP_SLICE_ELEMENTS:
            n *= 2
    return n


#: The share of the room a step's activations have (``Budget.room_bytes``) that
#: a head's float32 logits and their gradient, ``2 x 4 x rows x vocab`` bytes,
#: may take whole, and the share one slice of the rows may take where they do
#: not. On the chip the seven cells that ran a whole head stand at 18 to 43 %
#: of their room (Trinity's 16,384 x 25,024: 3.28 of 7.65 GB); 16,384 x 25,008
#: beside 10.98 GB of state is 3.28 GB of a room the chip read as 5.01 (PR 57),
#: 65 %, and runs in four slices of 0.82.
HEAD_WHOLE_SHARE = 0.5
HEAD_SLICE_SHARE = 0.25


def head_row_slices(rows: int, vocab: int, room_bytes: Optional[int]) -> int:
    """How many slices of its rows the final norm, the head and the
    cross-entropy are computed in: 1 where the room cannot be read (None, or
    the 0 a run across processes is given until its reading is exchanged) or
    the float32 logits and their gradient fit `HEAD_WHOLE_SHARE` of it, else
    the least power of two that divides the rows and brings a slice's under
    `HEAD_SLICE_SHARE`. Pure: the shapes and the room in, a count out."""
    n, whole = 1, 2 * 4 * rows * vocab
    if room_bytes and whole > HEAD_WHOLE_SHARE * room_bytes:
        while rows % (2 * n) == 0 and whole / n > HEAD_SLICE_SHARE * room_bytes:
            n *= 2
    return n


def head_slices(config, remat_budget: Optional[Budget], input_ids) -> int:
    """`head_row_slices` of a step over ``input_ids`` under the budget's room:
    a next-token head of a causal decoder alone (a prediction module's two
    take the same count each; without remat they keep their logits, whole),
    and 1 without a budget. (A function of the configuration:
    ``PipelineModule`` borrows ``TransformerLM.loss``.)"""
    c = config
    if (remat_budget is None or c.pred_heads > 1 or c.mlm_head or c.diffusion
            or not c.causal or (c.mtp_layers and not c.remat)):
        return 1
    return head_row_slices(input_ids.size, c.vocab_size, remat_budget.room_bytes)


#: The products over the vocabulary a differentiated step makes a head, by the
#: head's form: ``whole`` keeps its float32 logits for the backward (logits,
#: the rows' gradient, the matrix's), ``fused`` takes a slice's gradient where
#: its logits are (`TransformerLM.fused_head_loss`: the same three), ``rerun``
#: makes a slice's logits again in the backward (float16 alone).
HEAD_PASSES = {"whole": 3, "fused": 3, "rerun": 4}


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
    # the no-drop path's DeepSeek-V3 form (``moe/layer.py::MoE`` has each
    # field's meaning): sigmoid affinities chosen under a correction bias
    # that ``bias_update`` moves by load after each step (``noaux_tc``; no
    # gradient, no Adam, no decay), the chosen affinities times
    # ``routed_scale``, a shared expert of ``shared_width`` beside the
    # routed ones, the sequence-wise balance loss under
    # ``seq_balance_coef`` (summed over the layers; ``aux_loss_coef`` and
    # ``z_loss_coef`` are the softmax router's), and the range of experts
    # this chip holds of a layer that several chips share (None: all)
    router: str = "softmax"              # | 'sigmoid_bias'
    routed_scale: float = 1.0
    shared_width: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    seq_balance_coef: float = 0.0
    bias_update: float = 0.0
    # what the no-drop router reads: the FFN's own pre-normed input, or the
    # block's un-normed input, routed before the token mixer runs
    router_input: str = "ffn_input"      # | 'block_input'

    def __post_init__(self):
        if self.capacity_factor is not None and self.z_loss_coef:
            raise ValueError("the router z-loss is the no-drop path's "
                             "(capacity_factor=None)")
        if self.router != "sigmoid_bias" and (self.seq_balance_coef
                                              or self.bias_update):
            raise ValueError("the sequence-wise balance loss and the bias "
                             "update are the 'sigmoid_bias' router's")


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN's rotary frequencies (Peng et al. 2023, as DeepSeek-V3 and HF
    ``rope_scaling: {"type": "yarn"}`` use them): frequency ``i`` of a
    rotary width ``d`` is divided by ``factor`` where its wavelength is
    long (``i >= high``), left alone where it is short (``i <= low``) and
    blended linearly between, ``low`` / ``high`` the indices whose
    wavelengths fit ``beta_fast`` / ``beta_slow`` turns into
    ``original_max_position`` positions. Attention scores are scaled by
    ``softmax_scale ** 2`` more (``0.1 mscale_all_dim ln(factor) + 1``),
    and cos and sin by the ratio of the two mscales."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def band(self, dim: int, theta: float) -> Tuple[int, int]:
        """(low, high): the frequency indices between which the blend runs."""
        at = lambda turns: (dim * math.log(self.original_max_position
                                           / (turns * 2 * math.pi))
                            / (2 * math.log(theta)))
        return (max(math.floor(at(self.beta_fast)), 0),
                min(math.ceil(at(self.beta_slow)), dim - 1))

    def frequencies(self, dim: int, theta: float) -> jax.Array:
        """The ``dim // 2`` scaled inverse frequencies, float32."""
        i = jnp.arange(dim // 2, dtype=jnp.float32)
        freqs = theta ** (-2.0 * i / dim)
        low, high = self.band(dim, theta)
        ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        return freqs * (1.0 - ramp) + freqs / self.factor * ramp

    @staticmethod
    def _mscale(factor: float, m: float) -> float:
        return 1.0 if factor <= 1.0 or not m else 0.1 * m * math.log(factor) + 1.0

    @property
    def softmax_scale(self) -> float:
        """What the attention scores' scale is multiplied by, squared."""
        return self._mscale(self.factor, self.mscale_all_dim)

    @property
    def cos_sin_scale(self) -> float:
        return (self._mscale(self.factor, self.mscale)
                / self._mscale(self.factor, self.mscale_all_dim))


@dataclasses.dataclass(frozen=True)
class IndexerConfig:
    """A learned selection of keys inside ``mha`` attention (DeepSeek Sparse
    Attention's lightning indexer; ``ops/transformer/attention.py`` has the
    equations): ``heads`` small query heads and ONE key head of ``head_dim``
    score every visible key of a query, the ``topk`` largest are the keys its
    main heads attend, and the indexer learns from their own distribution
    (a KL term beside the language-model loss, which passes no gradient to it)."""
    heads: int
    head_dim: int
    topk: int


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # None => MHA
    hidden_size: int = 768
    # the width of one head where heads x head is not the hidden size
    # (afmoe: 32 heads of 128 on a stream of 2048); None => hidden / heads
    head_size: Optional[int] = None
    intermediate_size: Optional[int] = None  # None => 4*hidden
    # 'gelu' | 'gelu_exact' | 'relu' | 'silu_gated' | 'relu_gated' (experts only)
    activation: str = "gelu"
    norm: str = "layernorm"          # 'layernorm' | 'rmsnorm'
    norm_eps: float = 1e-5           # HF config layer_norm_epsilon / rms_norm_eps
    position: str = "learned"        # 'learned' | 'rope' | 'alibi'
    position_offset: int = 0         # OPT pads learned positions by 2
    rope_theta: float = 10000.0
    rope_dim: Optional[int] = None   # partial rotary (phi/neox/gpt-j); None => head_dim
    rope_style: str = "half"         # 'half' (llama/neox) | 'interleaved' (gpt-j)
    # rope by sections (Qwen2-VL's ``mrope_section``): how many of a head's
    # frequency pairs each of THREE position streams (temporal, height, width)
    # turns, in that order; a batch's ``position_ids`` [3, B, L] gives the
    # streams (without them all three are a token's index: plain rope)
    rope_sections: Optional[Tuple[int, ...]] = None
    # per-layer causal attention windows (mistral sliding_window; gpt-neo
    # alternating global/local; afmoe's layer_types): 0 = global, w > 0 =
    # attend the last w keys. A single int applies to every layer. The
    # windows are STATIC in ``TransformerLM.apply``: the layer scan's unit
    # is one period of the layers' kinds (``TransformerLM.scan_plan``), so a
    # windowed layer's attention is built knowing its window (the flash
    # kernel's grids are cut to it) and runs under the scope
    # ``attn/core_window``. Only the ZeRO-3 pipelined scan still hands the
    # window over traced.
    attn_windows: Any = None         # Optional[int | Tuple[int, ...]]
    # which layers a rotary ``position`` turns: 'all', or 'windowed' (afmoe:
    # the layers with a window alone; a global layer has no positional term)
    rope_layers: str = "all"
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
    # 'pre' | 'post' (bert-era encoders) | 'sandwich' (afmoe: a sub-block's
    # input is normed as in 'pre' AND its output before it is added,
    # x + post_ln(f(ln(x))), under the scope ``norm_post``)
    norm_style: str = "pre"
    # the embedding's output times this (afmoe's mup_enabled: hidden ** 0.5)
    embedding_scale: Optional[float] = None
    # packed documents: a token id that ENDS a document. Attention then stays
    # inside a document (segment ids = the separators before a position);
    # None: a row is one document
    document_separator: Optional[int] = None
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
    # (recompute the whole block) | any jax.checkpoint_policies name
    remat_policy: str = KEEP_PRODUCTS
    # olmoe: RMSNorm with its own scale over the WHOLE projected q vector
    # [heads*head_dim] and k vector [kv_heads*head_dim], before the head
    # split and rope (HF OlmoeAttention q_norm / k_norm)
    qk_norm: bool = False
    # with qk_norm: RMSNorm over each HEAD's vector of q and of k instead,
    # one gain of head_dim shared by the heads (query heads and the fewer
    # key heads alike), before rope
    qk_norm_per_head: bool = False
    moe: Optional[MoEConfig] = None
    moe_layer_freq: int = 1          # every k-th layer is MoE when moe is set
    # 'latent' (DeepSeek-V2/V3 multi-head latent attention, no query
    # compression): keys and values come from ONE compressed vector a token
    # (``kv_latent_rank`` wide, normed) through an up-projection, a head's
    # query and key are ``qk_nope_dim`` such values and ``qk_rope_dim``
    # rotated ones (the key's rotated part is one vector shared by the
    # heads), its value ``v_head_dim`` wide. 'mha': the projections above.
    # 'eva' (EVA, arXiv:2302.04542, as EvaByte runs it): a query sees the
    # exact keys of its own window of ``eva_window`` positions and ONE learned
    # summary a chunk of ``eva_chunk`` for every window before it, under one
    # softmax (``ops/transformer/attention.py::eva_visible``); a layer holds
    # two vectors a head for the summaries (``eva_phi``, ``eva_mu``). Windows
    # and chunks are counted from the row's start; no document ids enter.
    attention: str = "mha"
    # ``mha`` over a learned selection of each query's keys (`IndexerConfig`)
    indexer: Optional[IndexerConfig] = None
    eva_window: int = 0
    eva_chunk: int = 0
    kv_latent_rank: int = 0
    # latent attention's compressed QUERY (DeepSeek-V2's q_lora_rank): q =
    # q_b_proj(RMSNorm(q_a_proj(h))), the rank in between; 0: ``q_proj`` alone
    q_latent_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional[YarnScaling] = None
    # a sigmoid gate on the attention output, element-wise, from the
    # sub-block's input (Qiu et al. 2025): o_proj(attn * sigmoid(x W_g));
    # either kind of ``attention``
    attn_gate: bool = False
    # FarSkip-Collective's residual (Dukler et al. 2025): sub-block i reads
    # the stream as it stood BEFORE sub-block i-1 added to it,
    # r_i = r_(i-1) + f_i(norm(r_(i-2))), r_(-1) = r_0
    farskip: bool = False
    # the first layers' MLP is dense, ``dense_intermediate_size`` wide, where
    # the others have experts (DeepSeek's first_k_dense_replace); they run
    # before the scan with parameters of their own (``dense_blocks``)
    first_dense_layers: int = 0
    dense_intermediate_size: Optional[int] = None
    # multi-token prediction (DeepSeek-V3 report, section 2.2): one module
    # after the last block predicts the token after next from the final
    # stream and the next token's embedding, through a block of its own and
    # the shared embedding and head; its loss counts ``mtp_loss_coef`` times
    mtp_layers: int = 0
    mtp_loss_coef: float = 0.3
    # the training objective: 'next_token' (by shift; an encoder's masked-LM
    # over given labels) | 'block_diffusion' (BD3-LM, arXiv:2503.09573, the
    # vectorised form): every block of ``block_length`` positions (a power
    # of two, counted from the row's start) draws t ~ U[1e-3, 1] and each of
    # its tokens becomes ``mask_token_id`` with probability t; the network
    # runs on the clean AND the noised copy of a row (2 L rows of
    # activations for L tokens, one position for both copies of a token)
    # under ONE mask (``ops/transformer/attention.py::blockdiff_visible``);
    # the head reads the noised copy alone, unshifted, and a masked position's
    # cross-entropy counts 1 / t, over rows x L. The noise is a pure function
    # of the batch (``TransformerLM.noise_key``: ``batch["noise_key"]`` or
    # ``noise_seed`` folded with the ids). ``mask_token_id`` may lie one past
    # the vocabulary: the embedding then has that row more than the head.
    # next-token heads on the one trunk: the head's ``pred_heads x vocab``
    # outputs at position i are head m's logits for token i + 1 + m, and the
    # loss is the mean over the heads of each head's mean cross-entropy over
    # the positions that have such a token (EvaByte's ``num_pred_heads``; no
    # module of its own, unlike ``mtp_layers``)
    pred_heads: int = 1
    # RMSNorm's gain is 1 + scale (EvaByte's ``norm_add_unit_offset``)
    norm_unit_offset: bool = False
    # a branch is added to the stream in float32 and the sum rounded to the
    # compute dtype (EvaByte's ``fp32_skip_add``)
    residual_fp32: bool = False
    objective: str = "next_token"
    block_length: int = 4
    mask_token_id: Optional[int] = None
    noise_seed: int = 0
    # manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
    # Hyper-Connections, arXiv:2409.19606): the residual is n =
    # ``residual_streams`` streams of the hidden size, carried side by side as
    # vec(X) ``[B, S, n x H]``. Every sub-layer (attention, MLP) has
    # coefficients of its own, made a position at a time from the RMS-normed
    # vec(X) (`TransformerLM._hc_coefficients`): it reads ``sum_i H_pre[i]
    # X[i]``, and writes ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``,
    # ``H_res`` made doubly stochastic by ``hc_sinkhorn_iters`` rounds of
    # Sinkhorn from ``exp(clamp(., hc_res_clamp))``, every sum guarded by
    # ``hc_eps``. The stack starts from n copies of the embedding and ends in
    # the streams' sum. 1: the one stream of every other model.
    residual_streams: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    # state-space layers (Mamba-1's selective scan, arXiv:2312.00752): with
    # ``ssm_state`` (states a channel; 0: none) every layer l with ``l %
    # ssm_period == 0`` has, in attention's place, ``[a, z] = u W_in`` (``ssm_expand
    # x hidden`` channels each), a causal depthwise convolution of ``ssm_conv``
    # taps and a SiLU on ``a``, ``[r, B, C] = a W_x`` (``ssm_dt_rank``, None:
    # ceil(hidden / 16), and twice the states), ``dt = softplus(r W_dt + b)``,
    # the scan ``h_t = exp(dt_t A) h_{t-1} + dt_t a_t (x) B_t``, ``m_t = h_t C_t +
    # D a_t`` (``ops/transformer/pallas_scan.py``) and ``(m * silu(z)) W_out``. In
    # a packed row (``document_separator``) the state and the taps start anew
    # at a document's first token.
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: Optional[int] = None
    ssm_period: int = 2
    # Mamba-2's scan layers (arXiv:2405.21060; state-space duality): with
    # ``ssm_heads`` (0: Mamba-1's, above) a scan layer has ``ssm_heads`` heads of
    # ``ssm_head_dim`` channels over ``ssm_state`` states, ONE scalar decay a head
    # and B and C shared by the heads of each of ``ssm_groups`` groups: ``[z, xBC,
    # dt_raw] = u W_in`` (``Di + (Di + 2 G N) + heads``), ``xBC = silu(conv(xBC) +
    # b)`` over all ``Di + 2 G N`` channels, ``[a, B, C] = xBC``, ``dt =
    # softplus(dt_raw + dt_bias)``, ``A = -exp(A_log)`` a head, the recurrence
    # above with ``exp(dt_t A)`` a head's scalar (``ops/transformer/pallas_ssd.py``,
    # chunked), and ``RMSNorm_Di(m * silu(z)) W_out``: the gate goes in BEFORE the
    # norm, whose statistic is over a group's channels and whose gain is learned.
    # ``ssm_chunk`` names the published kernels' chunk (a record's line; the
    # program's kernel picks its own, the mathematics does not depend on it).
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_chunk: int = 256
    # each layer's token mixer by name where no rule gives it (Granite 4.0-H's
    # ``layer_types``): a tuple of ``num_layers`` names of ``mixers.KINDS``
    # (``"ssd"``, ``"mha"``); the stack is then a mixed one
    layer_mixers: Optional[Tuple[str, ...]] = None
    # Kimi Delta Attention layers (KDA; Kimi Linear, arXiv:2510.26692), named
    # ``"kda"`` by ``layer_mixers``: ``kda_heads`` heads (0: none) with keys and
    # values of ``kda_head_dim``. ``q, k, v = silu(conv(u W_{q,k,v}))`` (a causal
    # depthwise convolution of ``kda_conv`` taps, no bias, inside a document), q
    # and k L2-normalised a head and q times ``kda_head_dim ** -0.5``; a decay a
    # CHANNEL ``g_t = -exp(A_log) softplus((u W_fa) W_fb + dt_bias)`` (``A_log`` a
    # head's scalar, ``dt_bias`` a channel's; the low rank a head's width,
    # ``kda_head_dim``) and a write strength a head ``beta_t = sigmoid(u W_b)``, both
    # float32; the state a head ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t))
    # S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``
    # (``ops/transformer/pallas_kda.py``, chunked; S is 0 before a document's first
    # token); and ``(RMSNorm_head(o) * sigmoid((u W_ga) W_gb)) W_o``, the norm over
    # a head's values with one learned gain of a head's width. The chunk is the
    # kernels' own choice: the mathematics does not depend on it.
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv: int = 4
    # a branch's output times this before it is added to the stream, and the
    # logits divided by this (Granite's ``residual_multiplier``,
    # ``logits_scaling``); 1.0: absent, nothing traced
    residual_scale: float = 1.0
    logits_divisor: float = 1.0
    # differential attention (arXiv:2410.05258): the heads are paired by
    # parity, each half a softmax of its own over the pair's two value heads
    # side by side, ``o = RMSNorm(o1 - lambda o2) (1 - lambda_init)``, ``lambda =
    # exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`` from four learned vectors a
    # layer, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` at layer l
    differential_attention: bool = False
    # a cross-decoder (YOCO, arXiv:2405.05254; SambaY, arXiv:2507.06607): layer
    # ``shared_from`` is a scan layer whose scan output m every later scan-slot
    # layer reads in a scan's place (a gated memory unit, ``(silu(u W_1) * m)
    # W_2``), and layer ``shared_from + 1`` an attention layer whose keys and
    # values every later attention layer attends with queries of its own
    shared_from: Optional[int] = None

    @property
    def ssm_inner(self) -> int:
        if self.ssm_heads:
            return self.ssm_heads * self.ssm_head_dim
        return self.ssm_expand * self.hidden_size

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def ssm_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.hidden_size // 16)

    @property
    def mixed(self) -> bool:
        """Whether the layers' mixers are not all plain attention of one
        parameter layout: the layers then run as `TransformerLM.run_plan` lays
        them out and their parameters lie under ``params["runs"]``, a stack a
        run and place in its unit, in ``params["blocks"]``' place."""
        return bool(self.ssm_state or self.differential_attention
                    or self.shared_from is not None or self.layer_mixers is not None)

    def mixer_of(self, layer: int) -> Tuple[str, Optional[str]]:
        """Layer ``layer``'s token mixer (a name of ``mixers.KINDS``) and what
        it hands on: the ONE place a layer's mixer is picked from the
        configuration. A plain stack's layers all have ``attention``'s
        (``"mha"`` under an ``indexer``: ``"selected"``) and hand nothing on; a
        mixed stack's either what ``layer_mixers`` names (``"ssd"`` | ``"kda"`` |
        ``"mha"`` | ``"latent"``, nothing handed on; `TransformerLM` holds the list to
        the depth and to those names) or, without a list, what a decoder-hybrid-decoder stack's ``l %
        ssm_period`` rule gives: ``("ssm" | "attn" | "gmu" | "cross", None |
        "memory" | "kv")``."""
        if not self.mixed:
            return ("selected" if self.indexer is not None else self.attention), None
        if self.layer_mixers is not None:       # a list, where no rule gives the kinds
            return self.layer_mixers[layer], None
        scan = bool(self.ssm_state) and layer % self.ssm_period == 0
        at = self.shared_from
        if at is not None and layer >= at + 2:
            return ("gmu" if scan else "cross"), None
        hands = None if at is None or layer < at else ("memory" if scan else "kv")
        return ("ssm" if scan else "attn"), hands

    @property
    def diffusion(self) -> bool:
        return self.objective == "block_diffusion"

    @property
    def embedding_rows(self) -> int:
        """Rows of the token embedding: the vocabulary, and the mask
        token's row where it lies past it."""
        if self.diffusion:
            return max(self.vocab_size, self.mask_token_id + 1)
        return self.vocab_size

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        if self.attention == "latent":
            return self.qk_nope_dim + self.qk_rope_dim
        return self.head_size or self.hidden_size // self.num_heads

    @property
    def scan_layers(self) -> int:
        """The layers the scan runs: all but the leading dense ones."""
        return self.num_layers - self.first_dense_layers

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    def num_parameters(self) -> int:
        """The embeddings, the head, every layer's MLP (in a mixed stack the
        norms too) and each layer's mixer's own count (``Mixer.parameters``)."""
        h, v, L = self.hidden_size, self.vocab_size, self.num_layers
        ffn = self.ffn_size
        if self.activation in ("silu_gated", "relu_gated"):
            mlp = 3 * h * ffn
        else:
            mlp = 2 * h * ffn
        if self.moe is not None:
            mlp = mlp * self.moe.num_experts + h * self.moe.num_experts
        embed = self.embedding_rows * h + (
            (self.max_seq_len + self.position_offset) * h
            if self.position == "learned" else 0)
        embed += self.type_vocab_size * h
        head = 0 if self.tie_embeddings else self.pred_heads * v * h
        if self.mlm_head:
            head += h * h + v  # prediction transform + decoder bias
        mlps = L * mlp
        if self.mixed and self.moe is not None:
            # a listed stack's own: the leading dense MLPs, then a chip's share of
            # every expert layer (the experts held, the whole router and its bias,
            # the shared expert), leaf by leaf
            lo, hi = self.moe.experts_held or (0, self.moe.num_experts)
            experts = ((hi - lo) * 3 * h * ffn + h * self.moe.num_experts
                       + self.moe.num_experts * (self.moe.router == "sigmoid_bias")
                       + 3 * h * self.moe.shared_width)
            mlps = (self.first_dense_layers * 3 * h * (self.dense_intermediate_size or 0)
                    + self.scan_layers * experts)
        of = mixers.build(self)
        total = embed + head + mlps + sum(
            of[self.mixer_of(l)[0]].parameters() for l in range(L))
        if self.mixed:
            # LayerNorm's and the projections' biases counted (the published
            # 3.8 B of a 32-layer SambaY stack is this sum)
            total += (2 * L + 1) * (2 if self.norm == "layernorm" else 1) * h
        return total


#: What a configuration can ask beyond the plain causal pre-norm decoder (one
#: stream through blocks of one kind, ``mha`` over whole rows, dense MLPs, one
#: next-token head), under its own spelling: whether a model uses it, and
#: what running it takes. A consumer that computes less than
#: ``TransformerLM.loss`` (`block_apply`, ``PipelineModule``,
#: ``RaggedInferenceModel``) lists what it runs and `TransformerLM.require`
#: refuses the rest: what is added here is refused until a consumer lists it.
MECHANISMS: Dict[str, Tuple[Callable[["TransformerLM"], bool], str]] = {
    "causal=False": (lambda m: not m.config.causal,
                     "a bidirectional encoder, not a decoder: it attends both ways under a mask"),
    "norm_style='post'": (lambda m: m.config.norm_style == "post",
                          "a block norms after each residual add, and there is no final norm"),
    "norm_style='sandwich'": (lambda m: m.config.norm_style == "sandwich",
                              "a branch's output has a norm of its own before it is added"),
    "mlm_head": (lambda m: m.config.mlm_head,
                 "the masked-LM head transforms and norms the stream before the tied decoder"),
    "type_vocab_size": (lambda m: bool(m.config.type_vocab_size),
                        "segment embeddings are added from the batch's token_type_ids"),
    "pad_based_positions": (lambda m: m.config.pad_based_positions,
                            "positions count the tokens that are not padding"),
    "attn_windows": (lambda m: m._windows is not None,
                     "each layer attends under its own window"),
    "rope_layers='windowed'": (
        lambda m: m.config.position == "rope" and not all(rope for _, rope in m._kinds),
        "some layers turn queries and keys, others do not: a block must know its layer's kind"),
    "document_separator": (lambda m: m.config.document_separator is not None,
                           "attention stays inside a packed document, found from the whole row"),
    "embedding_scale": (lambda m: m.config.embedding_scale is not None,
                        "the embedding's output is multiplied before the first block"),
    "residual_fp32": (lambda m: m.config.residual_fp32,
                      "a branch is added to the stream in float32"),
    "qk_norm": (lambda m: m.config.qk_norm, "queries and keys are normed before rope"),
    "attn_gate": (lambda m: m.config.attn_gate,
                  "the attention output is gated from the sub-block's input"),
    "attention='latent'": (lambda m: m.config.attention == "latent",
                           "keys and values come from one compressed vector a token"),
    "attention='eva'": (lambda m: m.config.attention == "eva",
                        "a query sees its window's keys and a learned summary a chunk before it"),
    "indexer": (lambda m: m.config.indexer is not None,
                "a learned indexer picks each query's keys and adds a loss of its own"),
    "rope_sections": (lambda m: m.config.rope_sections is not None,
                      "a head's frequency pairs are turned by three position streams"),
    "pred_heads": (lambda m: m.config.pred_heads > 1,
                   "the head's outputs are several next-token heads under a loss of their own"),
    "q_latent_rank": (lambda m: bool(m.config.q_latent_rank),
                      "latent attention's queries come through a compressed, normed vector"),
    "residual_streams": (lambda m: m.config.residual_streams > 1,
                         "a block carries n residual streams mixed by a doubly-stochastic "
                         "matrix a sub-layer (hyper-connections)"),
    "ssm_state": (lambda m: bool(m.config.ssm_state),
                  "some layers carry a selective scan's state along the row in attention's place"),
    "ssm_heads": (lambda m: bool(m.config.ssm_heads),
                  "the scan layers are Mamba-2's: heads with one decay each over shared B and "
                  "C, a chunked core, a gated norm"),
    "kda_heads": (lambda m: bool(m.config.kda_heads),
                  "some layers are Kimi Delta Attention's: a state a head under a decay a "
                  "channel and a delta-rule write, carried along the row"),
    "layer_mixers": (lambda m: m.config.layer_mixers is not None,
                     "each layer's token mixer is named by a list: the stack runs as runs "
                     "of kinds, its parameters a stack a run"),
    "residual_scale": (lambda m: m.config.residual_scale != 1.0,
                       "a branch's output is multiplied before it is added to the stream"),
    "logits_divisor": (lambda m: m.config.logits_divisor != 1.0,
                       "the head's logits are divided by a constant, in the loss and its "
                       "gradient"),
    "differential_attention": (lambda m: m.config.differential_attention,
                               "paired heads' two softmaxes are subtracted under a learned scalar"),
    "shared_from": (lambda m: m.config.shared_from is not None,
                    "later layers read one layer's keys and values and one layer's scan "
                    "output: a layer cannot be applied alone"),
    "farskip": (lambda m: m.config.farskip,
                "a block carries two streams: the residual now and a sub-block earlier"),
    "first_dense_layers": (lambda m: bool(m.config.first_dense_layers),
                           "the leading layers are dense blocks of their own, run before the scan"),
    "mtp_layers": (lambda m: bool(m.config.mtp_layers),
                   "a prediction module after the last block has a block and a loss of its own"),
    "objective='block_diffusion'": (lambda m: m.config.diffusion,
                                    "a clean and a noised copy of every row run under one mask"),
    "moe": (lambda m: m.config.moe is not None,
            "the MLP is a layer of experts with an auxiliary loss"),
    "moe.capacity_factor=None": (lambda m: m.moe_path == "dropless",
                                 "the no-drop path carries two router losses and rows per expert"),
    "moe.bias_update": (lambda m: m.config.moe is not None and bool(m.config.moe.bias_update),
                        "the router's bias moves by the step's load, which loss_and_stats returns"),
    "moe.router_input='block_input'": (
        lambda m: m._routes_ahead,
        "the router reads the block's un-normed input: the routing is made before the "
        "token mixer runs and lives across it"),
}


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
        if c.objective not in ("next_token", "block_diffusion"):
            raise ValueError(f"objective {c.objective!r} is not 'next_token' "
                             "or 'block_diffusion'")
        if c.diffusion and c.mask_token_id is None:
            raise ValueError("objective='block_diffusion' needs mask_token_id")
        self._wte = nn.Embedding(c.embedding_rows, c.hidden_size, shard=True)
        self._wpe = (nn.Embedding(c.max_seq_len + c.position_offset, c.hidden_size)
                     if c.position == "learned" else None)
        if c.norm_unit_offset and c.norm != "rmsnorm":
            raise ValueError("norm_unit_offset is RMSNorm's")
        base_cls = (nn.LayerNorm if c.norm == "layernorm" else functools.partial(
            nn.RMSNorm, unit_offset=True) if c.norm_unit_offset else nn.RMSNorm)
        norm_cls = lambda features: base_cls(features, eps=c.norm_eps)
        self._norm = norm_cls
        # post-LN (bert): the last block's output LN already normalizes the
        # final hidden states — there is no separate final norm
        self._ln_f = norm_cls(c.hidden_size) if c.norm_style != "post" else None
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
        if c.rope_layers not in ("all", "windowed"):
            raise ValueError(f"rope_layers {c.rope_layers!r} is not 'all' or 'windowed'")
        # each layer's KIND, static: (window, 0 = global; whether a rotary
        # position turns its queries and keys)
        self._kinds = tuple(
            (w, c.position == "rope" and (c.rope_layers == "all" or w > 0))
            for w in (self._windows or (0,) * c.num_layers))
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
            self._lm_head = nn.Linear(c.hidden_size, c.pred_heads * c.vocab_size,
                                      use_bias=c.lm_head_bias, shard="column")

        # gpt2-style models use biases; falcon keeps layernorm but bias-free
        # linears (linear_bias overrides the norm-derived default)
        use_bias = (c.linear_bias if c.linear_bias is not None
                    else c.norm == "layernorm")
        lin = lambda i, o, bias, shard: nn.Linear(i, o, use_bias=bias, shard=shard)
        # each layer's token mixer and what it hands on, picked ONCE
        # (``TransformerConfig.mixer_of``), and one `mixers.Mixer` a kind by
        # its name; a plain stack has one kind, `_mixer`
        self._check_layer_mixers()
        self._mixer_kinds = tuple(c.mixer_of(l) for l in range(c.num_layers))
        self._mixers = mixers.build(c, self)
        self._mixer = None if c.mixed else next(iter(self._mixers.values()))
        # whether a mixer returns a loss of its own (the carry then holds the
        # pair (the MoE accumulator, that loss so far)), and the mixer whose plan
        # stands for the stack's attention launches: the first that attends
        self._has_mixer_loss = any(m.has_loss for m in self._mixers.values())
        self._attending = next((m for m in self._mixers.values() if m.attends), None)
        for mixer in self._mixers.values():
            mixer.check()
        # what every block has, with a plain stack's one mixer's layers; a
        # mixed stack's mixers' layers, a stack a kind
        self._block_layers = {"ln_1": norm_cls(c.hidden_size),
                              **({} if c.mixed else self._mixer.layers())}
        self._mixer_layers: Dict[str, Dict[str, Any]] = (
            {name: mixer.layers() for name, mixer in self._mixers.items()}
            if c.mixed else {})
        if c.residual_streams > 1:
            # a sub-layer's coefficients (`_hc_coefficients`)
            for name in ("hc_attn", "hc_mlp"):
                self._block_layers[name] = nn.HyperConnection(
                    c.residual_streams, c.hidden_size)
        if not c.parallel_block or c.parallel_norms:
            # parallel blocks (falcon-7b/phi) feed attention and MLP from the
            # SAME normed input — no second norm exists in the checkpoint;
            # falcon-40b's "new decoder" norms each parallel branch separately
            self._block_layers["ln_2"] = norm_cls(c.hidden_size)
        if c.norm_style == "sandwich":
            # the norms of the two branches' OUTPUTS
            self._block_layers["post_ln_1"] = norm_cls(c.hidden_size)
            self._block_layers["post_ln_2"] = norm_cls(c.hidden_size)
        gated = lambda width: {
            "gate_proj": lin(c.hidden_size, width, False, "column"),
            "up_proj": lin(c.hidden_size, width, False, "column"),
            "down_proj": lin(width, c.hidden_size, False, "row"),
        }
        # what a leading dense layer has where the others have experts
        self._dense_mlp_layers = {}
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
                router=c.moe.router, routed_scale=c.moe.routed_scale,
                shared_width=c.moe.shared_width,
                experts_held=c.moe.experts_held,
                router_input=c.moe.router_input,
            )
            if c.first_dense_layers:
                self._dense_mlp_layers = gated(c.dense_intermediate_size)
        elif c.activation == "silu_gated":
            self._block_layers.update(gated(c.ffn_size))
        else:
            self._block_layers.update({
                "fc_in": nn.Linear(c.hidden_size, c.ffn_size, use_bias=use_bias, shard="column"),
                "fc_out": nn.Linear(c.ffn_size, c.hidden_size, use_bias=use_bias, shard="row"),
            })
        if c.mtp_layers:
            self._mtp_layers = {
                "norm_h": norm_cls(c.hidden_size), "norm_e": norm_cls(c.hidden_size),
                "merge": lin(2 * c.hidden_size, c.hidden_size, False, None),
                "ln_f": norm_cls(c.hidden_size),
            }
        self._check_kinds()

    @property
    def _mixed_rope(self) -> bool:
        """Whether some layers turn their queries and keys and others do not."""
        return len({rope for _, rope in self._kinds}) > 1

    def _check_kinds(self) -> None:
        """What the second kind of layer, the two streams and the
        prediction module are written for, and nothing wider."""
        c = self.config
        mha_heads = all(mixer.mha_heads for mixer in self._mixers.values())
        if c.remat and c.remat_policy != KEEP_PRODUCTS:
            resolve_policy(c.remat_policy)      # (an unknown name is refused here)
        if c.rope_sections is not None and (
                c.position != "rope" or c.rope_style != "half" or c.rope_dim
                or not mha_heads or len(c.rope_sections) != 3
                or sum(c.rope_sections) != c.head_dim // 2):
            raise ValueError(
                f"rope_sections {c.rope_sections}: three sections of a plain "
                f"'half' rope that add up to head_dim / 2 ({c.head_dim // 2})")
        if c.pred_heads < 1 or (c.pred_heads > 1 and (
                c.tie_embeddings or not c.causal or c.diffusion or c.mtp_layers
                or c.mlm_head)):
            raise ValueError(
                "pred_heads: next-token heads of a causal decoder with its own "
                "head, no prediction module, no block diffusion")
        if c.rope_scaling is not None and c.position != "rope":
            raise ValueError("rope_scaling needs position='rope'")
        if c.qk_norm_per_head and not c.qk_norm:
            raise ValueError("qk_norm_per_head says how qk_norm is taken")
        if c.first_dense_layers and (
                c.moe is None or c.activation != "silu_gated"
                or not c.dense_intermediate_size
                or not 0 < c.first_dense_layers < c.num_layers):
            raise ValueError(
                "first_dense_layers: leading gated-SiLU layers of "
                "dense_intermediate_size in a model whose other layers "
                "have experts")
        if c.mtp_layers not in (0, 1):
            raise NotImplementedError("one multi-token-prediction module")
        if c.mtp_layers and (not c.causal or c.norm_style != "pre"):
            raise ValueError("multi-token prediction is a causal pre-norm decoder's")
        if (c.farskip or c.first_dense_layers or c.mtp_layers) and (
                c.norm_style == "post" or c.parallel_block):
            raise ValueError(
                "farskip, first_dense_layers and mtp_layers are written for "
                "sequential pre-norm blocks")
        if (c.farskip or c.mtp_layers) and (
                c.norm_style != "pre" or self._windows is not None):
            raise ValueError("farskip and mtp_layers are written for pre-norm "
                             "blocks without windows")
        if c.residual_streams < 1 or (c.residual_streams > 1 and (
                c.norm_style != "pre" or c.parallel_block or c.farskip
                or c.residual_fp32 or self._has_mixer_loss or c.diffusion
                or not c.causal or c.hc_sinkhorn_iters < 1)):
            raise ValueError(
                "residual_streams: hyper-connected streams are written for a causal "
                "decoder's sequential pre-norm blocks: no FarSkip, parallel block, "
                "float32 residual, indexer or block diffusion")
        if c.residual_fp32 and (c.norm_style == "post" or c.farskip
                                or c.parallel_block):
            raise ValueError("residual_fp32 is written for sequential pre-norm "
                             "or sandwich blocks")
        if c.activation == "relu_gated" and c.moe is None:
            raise ValueError("activation='relu_gated' is an expert layer's "
                             "(moe/layer.py); the dense MLPs have 'silu_gated'")
        if self._routes_ahead and (c.norm_style == "post" or c.parallel_block or c.farskip
                                   or c.residual_streams > 1):
            raise NotImplementedError(
                "moe.router_input='block_input' (the routing made before the token "
                "mixer) is written for sequential pre-norm or sandwich blocks of one "
                "stream: no post-norm, parallel block, FarSkip or hyper-connected streams")
        if c.residual_scale != 1.0 and (c.norm_style == "post" or c.parallel_block
                                        or c.farskip or c.residual_streams > 1):
            raise NotImplementedError(
                "residual_scale (a branch's output times a constant before it is added) "
                "is written for sequential pre-norm or sandwich blocks of one stream: no "
                "post-norm, parallel block, FarSkip or hyper-connected streams")
        if c.norm_style not in ("pre", "post", "sandwich"):
            raise ValueError(f"norm_style {c.norm_style!r}")
        if c.norm_style == "sandwich" and c.parallel_block:
            raise ValueError("sandwich norms are a sequential block's")
        if c.diffusion:
            b = c.block_length
            if b < 1 or b & (b - 1):
                raise ValueError(f"block_length {b} is no power of two")
            if (not c.causal or not mha_heads or c.position != "rope"
                    or self._windows is not None or self._mixed_rope
                    or c.seq_parallel == "ring" or c.tie_embeddings
                    or c.norm_style == "post" or c.farskip or c.mtp_layers
                    or c.mlm_head or c.type_vocab_size):
                raise ValueError(
                    "objective='block_diffusion' is written for a rotary "
                    "decoder with its own head: no latent attention, window, "
                    "ALiBi or learned position, ring attention, tied "
                    "embedding, post-norm, FarSkip or prediction module")
        if c.document_separator is not None and (
                not c.causal or c.seq_parallel == "ring"):
            raise ValueError("document_separator: packed documents are a causal "
                             "decoder's, and not ring attention's")

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

        def init_block(r, dense=False):
            layers = self._block_layers
            if dense:
                layers = {**layers, **self._dense_mlp_layers}
            block, _ = nn.init_tree(layers, r, dtype)
            if c.moe is not None and not dense:
                block["moe"] = self._moe.init(jax.random.fold_in(r, 7), dtype)
            return block

        if c.mixed:
            # a stack a run and place in its unit (`run_plan`): what a layer
            # scan reads and what its gradient comes back as, no slice between
            params["runs"] = {
                str(i): {str(j): jax.vmap(
                    lambda r, kind=kind: self._init_run_block(kind, r, dtype))(
                        jax.random.split(jax.random.fold_in(rng_blocks, 64 * i + j), repeats))
                    for j, kind in enumerate(unit)}
                for i, (unit, repeats) in enumerate(self.run_plan)}
        else:
            params["blocks"] = jax.vmap(init_block)(
                jax.random.split(rng_blocks, c.scan_layers))
        if self._leading_dense:
            params["dense_blocks"] = jax.vmap(functools.partial(init_block, dense=True))(
                jax.random.split(jax.random.fold_in(rng_blocks, 1), c.first_dense_layers))
        if c.mtp_layers:
            r = jax.random.fold_in(rng_head, 5)
            params["mtp"], _ = nn.init_tree(self._mtp_layers, r, dtype)
            params["mtp"]["blocks"] = jax.vmap(init_block)(
                jax.random.split(jax.random.fold_in(r, 1), c.mtp_layers))
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
        dense_specs = {**block_specs, **{name: layer.specs() for name, layer
                                         in self._dense_mlp_layers.items()}}
        if c.moe is not None:
            block_specs["moe"] = self._moe.specs()
        # stacked over layers: prepend None for the layer dim
        stacked = lambda tree: jax.tree.map(
            lambda s: P(None, *s), tree, is_leaf=lambda s: isinstance(s, P))
        if c.mixed:
            specs["runs"] = {
                str(i): {str(j): stacked({
                    **{name: layer.specs() for name, layer in self._layers_of(kind).items()},
                    **({"moe": self._moe.specs()} if _mlp_of(kind) == "experts" else {})})
                         for j, kind in enumerate(unit)}
                for i, (unit, _) in enumerate(self.run_plan)}
        else:
            specs["blocks"] = stacked(block_specs)
        if self._leading_dense:
            specs["dense_blocks"] = stacked(dense_specs)
        if c.mtp_layers:
            specs["mtp"] = {name: layer.specs() for name, layer in self._mtp_layers.items()}
            specs["mtp"]["blocks"] = stacked(block_specs)
        return specs

    def _layers_of(self, kind) -> Dict[str, Any]:
        """The ``nn`` layers of one block of a mixed stack: what every block has,
        its mixer's (``kind``: `run_plan`'s, the mixer third) and, for a leading
        dense layer of a stack with experts, the dense MLP's (an expert layer's
        ``moe`` is no ``nn`` layer: `_init_run_block` adds it)."""
        dense = self._dense_mlp_layers if _mlp_of(kind) == "dense" else {}
        return {**self._block_layers, **self._mixer_layers[kind[2]], **dense}

    def _init_run_block(self, kind, r, dtype) -> Params:
        """One block of a mixed stack's run, as `init_block` makes a plain one's."""
        block, _ = nn.init_tree(self._layers_of(kind), r, dtype)
        if _mlp_of(kind) == "experts":
            block["moe"] = self._moe.init(jax.random.fold_in(r, 7), dtype)
        return block

    @property
    def _leading_dense(self) -> int:
        """The dense layers that run before the stack from a tree of their own
        (``params["dense_blocks"]``): a plain stack's ``first_dense_layers``; a
        mixed stack's lie in its runs."""
        return 0 if self.config.mixed else self.config.first_dense_layers

    # -- forward -------------------------------------------------------------
    def _rotate(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        """Rotary embedding, possibly PARTIAL (phi applies rope to only the
        first rope_dim of each head, passing the rest through)."""
        c = self.config
        if positions.ndim == 3:
            return self._rotate_sections(x, positions)
        rd = c.rope_dim or c.head_dim
        if rd >= c.head_dim:
            return nn.rotary_embedding(x, positions, c.rope_theta, c.rope_style)
        rot = nn.rotary_embedding(x[..., :rd], positions, c.rope_theta, c.rope_style)
        return jnp.concatenate([rot, x[..., rd:]], axis=-1)

    def _rotate_sections(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        """Rope by sections (``rope_sections``): frequency pair i, ``theta **
        (-i / half)``, is turned by the position stream of its section.
        positions [3, B, S]; x [B, S, heads, head]; the pair (i, i + half)."""
        c = self.config
        half = c.head_dim // 2
        stream = np.repeat(np.arange(3), c.rope_sections)           # [half]
        freqs = c.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        at = jnp.moveaxis(positions.astype(jnp.float32)[stream], 0, -1)  # [B, S, half]
        angles = (at * freqs)[:, :, None, :]
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)

    def _rotate_tail(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        """Latent attention's rotary embedding: the LAST ``qk_rope_dim`` of
        each head turned (``rope_style`` pairs, ``rope_scaling``'s
        frequencies), the first ``qk_nope_dim`` passed through."""
        c = self.config
        scaling = c.rope_scaling
        freqs = (None if scaling is None
                 else scaling.frequencies(c.qk_rope_dim, c.rope_theta))
        rot = nn.rotary_embedding(
            x[..., c.qk_nope_dim:], positions, c.rope_theta, c.rope_style,
            freqs=freqs, scale=1.0 if scaling is None else scaling.cos_sin_scale)
        return jnp.concatenate([x[..., :c.qk_nope_dim], rot], axis=-1)

    def _project(self, block: Params, name: str, h: jax.Array) -> jax.Array:
        """The block's linear layer ``name`` over ``h``, its result named
        as one the backward may keep (``remat_policy``'s default)."""
        return checkpoint_name(self._layer(name)(block[name], h), name)

    def _layer(self, name: str):
        """A layer every block has, or a leading dense block's, by name (a
        mixer's own: ``Mixer.layers``)."""
        if name in self._block_layers:
            return self._block_layers[name]
        return self._dense_mlp_layers[name]

    # -- a mixed stack's layout (``TransformerConfig.mixed``) ------------------
    @functools.cached_property
    def run_plan(self) -> Tuple[Tuple[Tuple[Any, ...], int], ...]:
        """How a mixed stack's layers run, from their static kinds ``(window,
        rope, mixer, hands)``: consecutive RUNS ``(unit, repeats)``, each the
        longest stretch from where the last ended that is some unit of kinds
        repeated at least twice (the shortest such unit), which ``lax.scan``
        runs; layers that repeat nothing join into one run of one repeat, run a
        block at a time. A decoder-hybrid-decoder stack of 32 layers: ``(scan,
        window) x 8``, ``(scan*, full) x 1``, ``(memory unit, cross) x 7``."""
        kinds = tuple(k + m for k, m in zip(self._kinds, self._mixer_kinds))
        if self.config.moe is not None:
            # a fifth entry where the stack has experts: the layer's MLP
            dense = self.config.first_dense_layers
            kinds = tuple(kind + ("dense" if l < dense else "experts",)
                          for l, kind in enumerate(kinds))
        runs, i, n = [], 0, len(kinds)
        while i < n:
            p, r = 1, 1
            for unit in range(1, (n - i) // 2 + 1):
                times = 1
                while (kinds[i + times * unit:i + (times + 1) * unit]
                       == kinds[i:i + unit]):
                    times += 1
                if times >= 2 and times * unit > p * r:
                    p, r = unit, times
            if r == 1 and runs and runs[-1][1] == 1:
                runs[-1] = (runs[-1][0] + kinds[i:i + 1], 1)
            else:
                runs.append((kinds[i:i + p], r))
            i += p * r
        return tuple(runs)

    def _scan_runs(self, init, runs: Params, keep: jax.Array, block_of, shapes_only=False):
        """A mixed stack's layers as `run_plan` lays them out over ``runs``
        (``params["runs"]``) -> (the last carry, the no-drop path's rows per expert
        ``[expert layers, experts]`` or None). What the boundary layers hand on
        is a value the later blocks take as an argument (a scan's body closes
        over it: kept once, its cotangent summed over the readers in its own,
        float32, dtype). ``shapes_only``: reckon every kind of block for the
        remat budget (`_KeptBlock.reckon`) and run nothing."""
        c = self.config
        lam_init = mixers.lambda_init(c.num_layers)
        shared: Dict[str, Any] = {}
        carry, at, rows = init, 0, []
        if shapes_only:
            shared = self._handed_shapes(*init[0].shape[:2])
        # what a kind's layer reads of an earlier layer, as one more argument
        args = lambda kind: tuple(shared[what] for what in (self._mixers[kind[2]].reads,)
                                  if what is not None)
        for i, (unit, repeats) in enumerate(self.run_plan):
            p = len(unit)
            stacks = [runs[str(i)][str(j)] for j in range(p)]
            gates = [(keep[at + j:at + p * repeats:p], lam_init[at + j:at + p * repeats:p])
                     for j in range(p)]
            if shapes_only:
                for j, kind in enumerate(unit):
                    block_of(kind).reckon(
                        init, _one_layer((stacks[j],) + gates[j]) + args(kind))
            elif repeats == 1:
                for j, kind in enumerate(unit):
                    layer = jax.tree.map(lambda a: a[0], (stacks[j],) + gates[j])
                    x, layer = _taken_together(carry[0], layer)
                    carry = (x,) + tuple(carry[1:])
                    carry, (r, handed) = block_of(kind)(carry, layer + args(kind))
                    if r is not None:
                        rows.append(r[None])
                    if kind[3] is not None:
                        shared[kind[3]] = handed
            else:
                if any(kind[3] is not None for kind in unit):
                    raise NotImplementedError("a layer that hands its tensors on repeats")
                fns = [block_of(kind) for kind in unit]
                extra = [args(kind) for kind in unit]

                def unit_fn(carry, xs_unit):
                    out = []
                    for fn, layer, more in zip(fns, xs_unit, extra):
                        carry, (r, _) = fn(carry, layer + more)
                        out += [] if r is None else [r]
                    return carry, jnp.stack(out) if out else None

                carry, r = jax.lax.scan(
                    unit_fn, carry, [(stacks[j],) + gates[j] for j in range(p)])
                if r is not None:       # [repeats, the unit's expert layers, experts]
                    rows.append(r.reshape((-1,) + r.shape[2:]))
            at += p * repeats
        return carry, (jnp.concatenate(rows, axis=0) if rows else None)

    def _handed_shapes(self, B: int, S: int) -> Dict[str, Any]:
        """What the boundary layers hand on over ``B`` rows of ``S`` tokens, by
        its name, as shapes (``Mixer.handed_shape``): nothing in a plain stack."""
        return {hands: self._mixers[name].handed_shape(B, S)
                for name, hands in dict.fromkeys(self._mixer_kinds) if hands is not None}

    @property
    def moe_path(self) -> Optional[str]:
        """``"dropless"``, ``"capacity"`` or None (no experts): the path
        the expert layers take, which follows from the configuration."""
        if self.config.moe is None:
            return None
        return "dropless" if self._moe.dropless else "capacity"

    def _moe_aux_zero(self) -> jax.Array:
        """The MoE auxiliary-loss accumulator's zero: a scalar (the GShard
        balance loss), or the no-drop path's two router losses."""
        return jnp.zeros((2,) if self.moe_path == "dropless" else (),
                         dtype=jnp.float32)

    def _aux_zero(self):
        """What the layers' carry starts from beside the stream: the MoE
        accumulator, with a mixer that has a loss of its own the pair (that,
        the loss so far: an indexer's L_I), with hyper-connected streams the
        pair (that, H_res's error so far)."""
        aux = self._moe_aux_zero()
        if self.config.residual_streams > 1 or self._has_mixer_loss:
            # (with streams: the largest distance so far of a row or column sum
            # of a sub-layer's H_res from 1: `_hc_sublayer`)
            return aux, jnp.zeros((), jnp.float32)
        return aux

    @property
    def _routes_ahead(self) -> bool:
        """Whether an expert layer's router reads the block's un-normed input,
        before the token mixer runs (``MoEConfig.router_input``)."""
        return self.config.moe is not None and self.config.moe.router_input == "block_input"

    def _check_layer_mixers(self) -> None:
        """A configuration's list of layer kinds, held where the model is built:
        a name a layer, of the two kinds a list may carry, a scan layer among them
        (`mixers._Mixed.check` then holds the stack), and none of what the ``l %
        ssm_period`` rule's stacks have beside it."""
        c, kinds = self.config, self.config.layer_mixers
        if kinds is None:
            if c.ssm_heads:
                raise ValueError("ssm_heads: Mamba-2's scan layers are named by "
                                 "layer_mixers ('ssd'), not by the ssm_period rule")
            return
        if (len(kinds) != c.num_layers or set(kinds) - {"ssd", "kda", "mha", "latent"}
                or not {"ssd", "kda"} & set(kinds)):
            raise ValueError(
                f"layer_mixers {kinds!r}: one name a layer ({c.num_layers}), each 'ssd' "
                "or 'mha', 'kda' or 'latent', with a scan layer ('ssd', 'kda') among "
                "them (a stack of attention alone is a plain one: leave layer_mixers None)")
        if c.differential_attention or c.shared_from is not None:
            raise ValueError("layer_mixers: differential_attention and shared_from are "
                             "the ssm_period rule's stacks'")
        if ("latent" in kinds) != (c.attention == "latent") or {"mha", "latent"} <= set(kinds):
            raise ValueError(
                f"layer_mixers {kinds!r} with attention={c.attention!r}: a list's attention "
                "layers are all 'mha' or all 'latent' (attention='latent' gives the heads' "
                "sizes), not both")
        if ("kda" in kinds) != bool(c.kda_heads) or ("ssd" in kinds) != bool(c.ssm_heads):
            raise ValueError(
                f"layer_mixers {kinds!r}: 'kda' layers need kda_heads ({c.kda_heads}) and "
                f"'ssd' layers ssm_heads ({c.ssm_heads}), and neither is set without its layers")
        if c.moe is not None and (c.moe_layer_freq != 1 or c.moe.capacity_factor is not None):
            raise NotImplementedError(
                "layer_mixers with experts: every layer after the leading dense ones is "
                "an expert layer of the no-drop path (moe_layer_freq=1, capacity_factor=None)")

    def _route_ahead(self, block: Params, x: jax.Array):
        """An expert block's routing from its own un-normed input ``x``, made
        before the token mixer is called (`_routes_ahead`; None for every other
        block), under ``mlp`` where a trace counts the expert layer (the layer
        names its own ``moe/route/ahead`` inside)."""
        if not self._routes_ahead or "moe" not in block:
            return None
        with jax.named_scope("mlp"):
            return self._moe.route(block["moe"], x)

    @scoped("mlp")
    def _mlp(self, block: Params, h: jax.Array, routing=None
             ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
        """MLP over ``h``, the sub-block's PRE-NORMED input (what the dense
        layers and the experts multiply) -> (out, aux, the no-drop path's rows
        per expert [experts] or None). ``routing``: the expert layer's routing
        where it was made from ANOTHER tensor, the block's un-normed input
        before the mixer (`_route_ahead`); None: the router reads ``h``."""
        c = self.config
        aux, rows = self._moe_aux_zero(), None
        if "moe" in block and self.moe_path == "dropless":
            out, aux, rows = self._moe.dropless_forward(block["moe"], h, routing)
        elif "moe" in block:
            out, aux = self._moe(block["moe"], h)
        elif c.activation == "silu_gated":
            out = self._gated_mlp(block, h)
        else:
            h2 = ACTIVATIONS[c.activation](self._project(block, "fc_in", h))
            out = self._block_layers["fc_out"](block["fc_out"], h2)
        return out, aux, rows

    def _gated_mlp(self, block: Params, h: jax.Array) -> jax.Array:
        """The dense gated-SiLU MLP; over slices of its rows where one
        ``[rows, width]`` product is too large (``mlp_row_slices``: the same
        arithmetic a slice at a time, a slice's products made again in its
        own backward, so that they are never all held)."""
        def rows(h, project):
            gate = nn.silu(project("gate_proj", h))
            up = project("up_proj", h)
            return self._layer("down_proj")(block["down_proj"], gate * up)

        B, S, H = h.shape
        slices = mlp_row_slices(B * S, self._layer("gate_proj").out_features)
        if slices == 1:
            return rows(h, functools.partial(self._project, block))
        # (nothing in a slice is named: the block's policy would keep a named
        # value of every slice, stacked)
        unnamed = lambda name, h: self._layer(name)(block[name], h)
        out = jax.lax.map(jax.checkpoint(functools.partial(rows, project=unnamed)),
                          h.reshape(slices, B * S // slices, H))
        return out.reshape(B, S, H)

    # -- hyper-connected residual streams (``residual_streams`` > 1) ----------
    def _hc_coefficients(self, hc: Params, X: jax.Array):
        """One sub-layer's mixing coefficients from the streams ``X`` ``[B, S,
        n x H]`` (vec(X): the n streams side by side), a position at a time, in
        float32 -> ``(H_pre [n, B, S], H_post [n, B, S], H_res [n, n, B, S])``
        (the positions LAST: they fill the lanes, n and n x n lead):
        ``m = (vec(X) rsqrt(mean(vec(X)^2) + eps)) Phi``, as ``rsqrt(..) x
        (vec(X) Phi)`` in one pass over the streams as they lie
        (``ops/transformer/pallas_hc.py``: the kernel or its XLA form by
        `_hc_route`); ``H_pre = sigmoid(a_pre m_pre + b_pre)``; ``H_post = 2
        sigmoid(a_post m_post + b_post)``; ``H_res`` = ``hc_sinkhorn_iters``
        rounds (every row over its sum + eps, then every column) from
        ``exp(clamp(a_res m_res + b_res))``. Scope ``hc/coeff``."""
        from ..ops.transformer import pallas_hc
        with jax.named_scope("hc"), jax.named_scope("coeff"):
            m = pallas_hc.coeff_product(X, hc["phi"], self.config.hc_eps,
                                        self._hc_route(X.shape[1], X.dtype)[0])
        return self._hc_mixes(hc, m)

    def _hc_mixes(self, hc: Params, m: jax.Array):
        """`_hc_coefficients`' second half: ``m [n (n + 2), B, S]`` float32 ->
        ``(H_pre, H_post, H_res)``, the sigmoids and the Sinkhorn rounds."""
        c, n = self.config, self.config.residual_streams
        f32 = jnp.float32
        with jax.named_scope("hc"), jax.named_scope("coeff"):
            alpha, bias = hc["alpha"].astype(f32), hc["bias"].astype(f32)
            at = lambda lo, hi, a: a * m[lo:hi] + bias[lo:hi, None, None]
            pre = jax.nn.sigmoid(at(0, n, alpha[0]))
            post = 2.0 * jax.nn.sigmoid(at(n, 2 * n, alpha[1]))
            res = jnp.exp(jnp.clip(at(2 * n, 2 * n + n * n, alpha[2]), *c.hc_res_clamp))
            res = res.reshape((n, n) + res.shape[1:])
            # (unrolled, not a ``lax.scan``: a loop keeps every round's matrix
            # for its backward as one stacked buffer, 0.19 GB more of the step's
            # temporaries at 8,192 rows, which the new cell did not have: PERF.md, PR 55)
            for _ in range(c.hc_sinkhorn_iters):
                res = res / (jnp.sum(res, axis=1, keepdims=True) + c.hc_eps)
                res = res / (jnp.sum(res, axis=0, keepdims=True) + c.hc_eps)
            return pre, post, res

    def _hc_route(self, seq: int, dtype) -> Tuple[str, Optional[int]]:
        """``(route, tile_rows)`` of a sub-layer's coefficients over rows of
        ``seq`` positions of streams of ``dtype`` here:
        ``pallas_hc.choose_route`` by the backend, the type, the shape and the
        live mesh's devices, and the kernel's row tile (None on the XLA route)."""
        from ..ops.transformer import pallas_hc
        c = self.config
        K = c.residual_streams * c.hidden_size
        route = pallas_hc.choose_route(seq, K, dtype, jax.default_backend(),
                                       mixers.devices())
        return route, pallas_hc.choose_tiles(seq, K).tm if route == "kernel" else None

    def _hc_streams(self, X: jax.Array):
        """The n streams of vec(X) ``[B, S, n x H]``, each ``[B, S, H]`` float32."""
        H = self.config.hidden_size
        return [X[..., i * H:(i + 1) * H].astype(jnp.float32)
                for i in range(self.config.residual_streams)]

    def _hc_sublayer(self, hc: Params, X: jax.Array, branch) -> Tuple[jax.Array, Any, jax.Array]:
        """ONE sub-layer under hyper-connections, the helper every sub-layer
        goes through: the coefficients (`_hc_coefficients`), the weighted read
        ``u = sum_i H_pre[i] X[i]`` (scope ``hc/pre``), ``y, *rest =
        branch(u)`` (the norm and the attention or the MLP), the mix and the
        write-back ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` (scope
        ``hc/post``), all mixing in float32 from and to the streams' dtype.
        -> ``(X', rest, err)``, ``err`` the largest distance of a row or a column
        sum of ``H_res`` from 1 (no gradient). Nothing here is named: a
        block's backward makes the coefficients and both mixings again."""
        n = self.config.residual_streams
        pre, post, res = self._hc_coefficients(hc, X)
        err = jax.lax.stop_gradient(jnp.maximum(
            jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0)),
            jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0))))
        with jax.named_scope("hc"), jax.named_scope("pre"):
            u = sum(pre[i][..., None] * x for i, x in enumerate(self._hc_streams(X)))
            u = u.astype(X.dtype)
        y, *rest = branch(u)
        with jax.named_scope("hc"), jax.named_scope("post"):
            streams, y32 = self._hc_streams(X), y.astype(jnp.float32)
            X = jnp.concatenate([
                (sum(res[i, j][..., None] * streams[j] for j in range(n))
                 + post[i][..., None] * y32).astype(X.dtype)
                for i in range(n)], axis=-1)
        return X, rest, err

    @scoped("block")   # norms and residual adds are "block" and nothing finer
    def _block_fn(self, attn_mask, carry, layer, kind=None):
        """One block -> (carry, (the no-drop path's rows per expert or None,
        what the layer hands on or None)). ``kind``: the layer's static
        ``(window, rope)`` of ``_kinds`` (None: global attention, ``position``
        says whether rope), in a mixed stack `run_plan`'s ``(window, rope,
        mixer, hands)``. ``layer``: ``(block, keep)`` and what else the layer's
        mixer is given (``Mixer.take``): in a mixed stack ``lambda_init`` and
        what it reads of an earlier layer; the ZeRO-3 pipelined scan, one
        program for every layer, still hands a TRACED window over this way."""
        block, keep, *given = layer
        x, positions, aux_acc = carry
        c = self.config
        mixer = self._mixer or self._mixers[kind[2]]    # (a mixed stack's kinds name theirs)
        given = mixer.take(tuple(given))
        # keep: per-layer stochastic-depth gate (progressive layer drop,
        # reference runtime/progressive_layer_drop.py); 1.0 = layer active
        if c.norm_style == "post":
            # bert-era encoder block: LN AFTER each residual add. The PLD
            # gate mixes OUTSIDE the norms (keep*block(x) + (1-keep)*x) so a
            # dropped layer (keep=0, gates are binary draws) is a true
            # identity — gating inside would still double-normalize x.
            # (neither the layer's kind nor what it is given enters: as it was)
            attn_out, handed, _ = mixer(block, x, positions, attn_mask)
            h = self._block_layers["ln_1"](block["ln_1"], x + attn_out)
            mlp_out, aux, rows = self._mlp(block, h)
            y = self._block_layers["ln_2"](block["ln_2"], h + mlp_out)
            x = _c(keep * y + (1 - keep) * x, ACT_SPEC)
            return (x, positions, aux_acc + keep * aux), (rows, handed)
        if c.residual_streams > 1:
            # x = vec(X), the n streams: each sub-layer reads a mix of them
            # and writes to all of them (`_hc_sublayer`)
            def attn(u):
                out, handed, _ = mixer(
                    block, self._block_layers["ln_1"](block["ln_1"], u),
                    positions, attn_mask, kind, given)
                return keep * out, handed

            x, (handed,), err_attn = self._hc_sublayer(block["hc_attn"], x, attn)
            def mlp(u):
                out, aux, rows = self._mlp(
                    block, self._block_layers["ln_2"](block["ln_2"], u))
                return keep * out, aux, rows

            x, (aux, rows), err_mlp = self._hc_sublayer(block["hc_mlp"], x, mlp)
            moe_acc, err_acc = aux_acc
            return (_c(x, ACT_SPEC), positions, (
                moe_acc + keep * aux, jnp.maximum(err_acc, jnp.maximum(err_attn, err_mlp)))), (
                    rows, handed)
        if c.farskip:
            # x = (r_(i-1), r_(i-2)): attention reads the stream as it stood
            # before the sub-block in front of it, and so does the MLP
            near, far = x
            attn_out, handed, _ = mixer(
                block, self._block_layers["ln_1"](block["ln_1"], far), positions, None)
            after_attn = near + keep * attn_out
            mlp_out, aux, rows = self._mlp(
                block, self._block_layers["ln_2"](block["ln_2"], near))
            x = (_c(after_attn + keep * mlp_out, ACT_SPEC), after_attn)
            return (x, positions, aux_acc + keep * aux), (rows, handed)
        # (a router that reads the block's input decides before the mixer runs)
        routing = self._route_ahead(block, x)
        h1 = self._block_layers["ln_1"](block["ln_1"], x)
        # (a mixer's own loss, here on: an indexer's KL, None elsewhere)
        attn_out, handed, own = mixer(block, h1, positions, attn_mask, kind, given)
        if c.parallel_block:
            # falcon/phi residual form: both branches read the block INPUT —
            # through one shared norm (phi/falcon-7b) or per-branch norms
            # (falcon-40b new decoder)
            hm = (self._block_layers["ln_2"](block["ln_2"], x)
                  if c.parallel_norms else h1)
            mlp_out, aux, rows = self._mlp(block, hm)
            x = _c(x + keep * (attn_out + mlp_out), ACT_SPEC)
        else:
            # 'sandwich': a branch's output is normed before it is added
            post = ((lambda name, y: y) if c.norm_style != "sandwich"
                    else functools.partial(self._norm_post, block))
            add = self._add_fp32 if c.residual_fp32 else (lambda x, y: x + y)
            if c.residual_scale != 1.0:
                # a branch's output times a constant: the float32 product rounded,
                # as the published multiply (0.22 is no bfloat16 number)
                plain = post
                post = lambda name, y: (plain(name, y).astype(jnp.float32)
                                        * c.residual_scale).astype(y.dtype)
            x = add(x, keep * post("post_ln_1", attn_out))
            h2 = self._block_layers["ln_2"](block["ln_2"], x)
            mlp_out, aux, rows = self._mlp(block, h2, routing)
            x = _c(add(x, keep * post("post_ln_2", mlp_out)), ACT_SPEC)
            if own is not None:
                moe_acc, own_acc = aux_acc
                return (x, positions, (moe_acc + keep * aux, own_acc + keep * own)), (
                    rows, handed)
        # the scan stacks the no-drop path's rows per expert over the
        # layers ([layers, experts]); every other model's ys stay None
        return (x, positions, aux_acc + keep * aux), (rows, handed)

    @staticmethod
    def _add_fp32(x: jax.Array, y: jax.Array) -> jax.Array:
        """``x + y`` summed in float32, the sum in x's dtype
        (``residual_fp32``)."""
        return (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype)

    @scoped("norm_post")
    def _norm_post(self, block: Params, name: str, y: jax.Array) -> jax.Array:
        return self._block_layers[name](block[name], y)

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
        if c.embedding_scale is not None:
            x = x * jnp.asarray(c.embedding_scale, x.dtype)
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
    def head(self, params: Params, x: jax.Array,
             ln_f: Optional[Params] = None) -> jax.Array:
        """Back of the network: final norm (pre-LN), MLM transform, LM/MLM
        head. Input is the last block's output; returns fp32 logits. The
        tied-embedding head reads ``params['wte']`` — the param-streaming
        trainer keeps the embedding leaves resident for this reason.
        ``ln_f``: another final norm's parameters (the prediction module
        has its own in front of the shared head)."""
        c = self.config
        if self._ln_f is not None:
            x = self._ln_f(params["ln_f"] if ln_f is None else ln_f, x)
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
        if c.pred_heads > 1:     # [B, S, heads, V]: head m reads token i + 1 + m
            logits = logits.reshape(logits.shape[:-1] + (c.pred_heads, c.vocab_size))
        logits = logits.astype(jnp.float32)
        # (every form of the head's loss computes its logits here)
        return logits if c.logits_divisor == 1.0 else logits / c.logits_divisor

    @functools.cached_property
    def mechanisms(self) -> Tuple[str, ...]:
        """The names of `MECHANISMS` this model's configuration uses."""
        return tuple(name for name, (uses, _) in MECHANISMS.items() if uses(self))

    def require(self, consumer: str, runs) -> None:
        """Refuse, by name and reason, every mechanism this model uses that
        ``consumer`` (its name, for the message) does not list in ``runs``."""
        unknown = set(runs) - set(MECHANISMS)
        if unknown:
            raise KeyError(f"{consumer} lists {sorted(unknown)}, which are no MECHANISMS")
        unmet = [name for name in self.mechanisms if name not in runs]
        if unmet:
            raise NotImplementedError(
                f"{consumer} does not run " + "; ".join(
                    f"{name} ({MECHANISMS[name][1]})" for name in unmet)
                + ": TransformerLM.loss (initialize -> train_batch) runs the whole model")

    #: what one block of `_block_fn` without its layer's kind carries, and
    #: what `embed` and `head` (which its callers run around it) do
    _BLOCK_APPLY_RUNS = frozenset({
        "causal=False", "norm_style='post'", "norm_style='sandwich'", "mlm_head",
        "type_vocab_size", "pad_based_positions", "attn_windows",
        "embedding_scale", "residual_fp32", "qk_norm", "attn_gate",
        "attention='latent'", "moe", "moe.capacity_factor=None"})

    def block_apply(self, block: Params, x: jax.Array, positions: jax.Array,
                    keep=1.0, attn_mask: Optional[jax.Array] = None,
                    window: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
        """ONE transformer block over UNSTACKED per-layer params — the
        param-streaming trainer's unit of compute (reference fetches one
        module's partitions at a time, partitioned_param_coordinator.py:280).
        Returns (x', moe_aux)."""
        self.require("one block at a time (parameter streaming, the ZeRO-3 "
                     "pipelined scan)", self._BLOCK_APPLY_RUNS)
        carry = (x, positions, self._aux_zero())
        keep = jnp.asarray(keep, self.config.dtype)
        layer = (block, keep) if window is None else (block, keep, window)
        (x2, _, aux), _ = self._block_fn(attn_mask, carry, layer)
        return x2, aux

    def scan_blocks_pipelined(self, blocks: Params, x: jax.Array,
                              positions: jax.Array, *, gather, scatter,
                              keep: Optional[jax.Array] = None,
                              attn_mask: Optional[jax.Array] = None,
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

        One layer a step. A step's leaves keep a leading dimension of one
        (``[1, ...]``): the comm tree's buckets and error-feedback state are
        laid out for it (``engine._build_zeropp_micro_overlap``).

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
        n_steps = L = c.num_layers
        keep = (jnp.ones((L,), c.dtype) if keep is None
                else keep.astype(c.dtype))
        windows = (jnp.asarray(self._windows, jnp.int32)
                   if self._windows is not None else None)
        bundle = lambda a: a.reshape((n_steps, 1) + a.shape[1:])
        blocksb = jax.tree.map(bundle, blocks)
        keepb = bundle(keep)
        winb = bundle(windows) if windows is not None else None
        take = lambda t, i: jax.tree.map(lambda a: a[i], t)

        def unit_call(bp, xx, kb, wb):
            xx, aux = self.block_apply(
                jax.tree.map(lambda a: a[0], bp), xx, positions, keep=kb[0],
                attn_mask=attn_mask, window=None if wb is None else wb[0])
            return xx, self._aux_zero() + aux

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
              remat_budget: Optional[Budget] = None,
              position_ids: Optional[jax.Array] = None) -> Tuple[jax.Array, ...]:
        """Return (logits [B,S,V] in fp32, ``[B,S,heads,V]`` with
        ``pred_heads``; moe_aux_loss scalar).

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
        ``position_ids`` [3, B, S]: the three position streams of
        ``rope_sections`` (None: a token's index for all three). With an
        indexer ``moe_aux_loss`` is the pair (that, L_I): `combine_aux` adds both.
        """
        if not return_hidden:
            self._charge_head(remat_budget, input_ids)
        # under the block-diffusion objective: the FIRST DENOISING PASS of
        # every block, the noised copy all mask tokens (position i's logits
        # read the clean tokens of strictly earlier blocks alone)
        noised = (jnp.full_like(input_ids, self.config.mask_token_id)
                  if self.config.diffusion else None)
        x, aux, stats, _ = self._trunk(
            params, input_ids, layer_mask, token_type_ids, attention_mask,
            remat_budget, with_mtp=False, noised_ids=noised,
            position_ids=position_ids)
        stats = (stats,) if return_stats else ()
        if return_hidden:
            if self._ln_f is not None:
                x = self._ln_f(params["ln_f"], x)
            return (x, aux) + stats
        return (self.head(params, x), aux) + stats

    def head_form(self, slices: int) -> str:
        """Which of `HEAD_PASSES`' forms a differentiated step's head takes:
        ``whole`` where its float32 logits are kept for the backward (one
        slice and no prediction module under remat), else ``fused``, or
        ``rerun`` under float16 (`fused_head_loss` says why)."""
        c = self.config
        if slices == 1 and not (c.mtp_layers and c.remat):
            return "whole"
        return "rerun" if jnp.dtype(c.dtype) == jnp.float16 else "fused"

    def _charge_head(self, remat_budget: Optional[Budget], input_ids) -> None:
        """Tell the budget what a differentiated step holds outside its
        blocks: a head's float32 logits and their gradient, whole or one
        slice's at a time (two heads run one after the other, so one
        counts), and under the ``fused`` form what each head's forward hands
        its backward beside them: the stream's gradient in the stream's
        dtype and the head's parameters' in float32."""
        if remat_budget is not None:
            c = self.config
            slices = head_slices(self.config, remat_budget, input_ids)
            form = self.head_form(slices)
            remat_budget.outside_bytes = (2 * 4 * input_ids.size * c.vocab_size
                                          * c.pred_heads) // slices
            if form == "fused":
                handed = (input_ids.size * c.hidden_size * jnp.dtype(c.dtype).itemsize
                          + 4 * c.hidden_size * (c.vocab_size + 1))
                remat_budget.outside_bytes += handed * (2 if c.mtp_layers else 1)
            remat_budget.totals.update(head_form=form, head_passes=HEAD_PASSES[form])
            if slices > 1:
                remat_budget.totals["head_row_slices"] = slices

    def _trunk(self, params, input_ids, layer_mask, token_type_ids,
               attention_mask, remat_budget, with_mtp: bool,
               noised_ids: Optional[jax.Array] = None,
               position_ids: Optional[jax.Array] = None):
        """Embedding and every block: (the last block's output stream,
        the accumulated MoE aux, the step's statistics, the prediction
        module's output stream or None). ``with_mtp``: run the module (a
        training loss asks for it; logits alone do not need it).
        ``noised_ids`` (the block-diffusion objective): the noised copy of
        ``input_ids``; the blocks then run on ``[B, 2 L]`` rows, the clean
        copy's and then the noised copy's, and the stream returned is the
        NOISED copy's ``[B, L, H]`` (the clean copy's rows after the last
        layer feed nothing; they are computed all the same)."""
        c = self.config
        if c.diffusion:
            if attention_mask is not None or token_type_ids is not None:
                raise ValueError("objective='block_diffusion' takes full rows: "
                                 "no attention_mask, no token_type_ids")
            L = input_ids.shape[1]
            if L % c.block_length:
                raise ValueError(f"a row of {L} tokens is no multiple of "
                                 f"block_length {c.block_length}")
            x, positions = self.embed(
                params, jnp.concatenate([input_ids, noised_ids], axis=1))
            positions = positions % L     # one position for both copies
        else:
            x, positions = self.embed(params, input_ids, token_type_ids)
        if position_ids is not None:
            if c.rope_sections is None or position_ids.shape != (3,) + input_ids.shape:
                raise ValueError("position_ids [3, B, S] are rope_sections' three "
                                 f"streams; got {position_ids.shape}")
            positions = position_ids

        documents = None
        if c.document_separator is not None:
            if attention_mask is not None:
                raise ValueError("packed documents (document_separator) take "
                                 "no attention_mask: a row is full")
            attention_mask = documents = self._documents(input_ids)
        block_fn = functools.partial(self._block_fn, attention_mask)
        if layer_mask is None:
            keep = jnp.ones((c.num_layers,), c.dtype)
        else:
            keep = layer_mask.astype(c.dtype)
        dense = self._leading_dense
        after = keep[dense:]         # the gates of the layers `_stack` runs
        # FarSkip carries two streams: (r_(i-1), r_(i-2)), r_(-1) = r_0;
        # hyper-connections n, which start as n copies of the embedding
        init = ((x, x) if c.farskip else self._hc_start(x), positions, self._aux_zero())
        layers_of = self._layers_of_kind(with_mtp)
        blocks: Dict[Any, Callable] = {}

        def block_of(kind=None, leading=False):
            """``block_fn`` for a layer of ``kind`` under the remat policy
            (``leading``: one of the dense layers in front, whose
            parameters are another tree): one kind of the step's blocks, in
            as many layers as the step runs of it. A kind is built once
            (and under the default policy traced once a shape)."""
            key = ("dense", kind) if leading else kind
            if key not in blocks:
                fn = functools.partial(block_fn, kind=kind)
                blocks[key] = checkpointed(
                    fn, c.remat_policy, layers_of[key], remat_budget,
                    _kind_label(key)) if c.remat else fn
            return blocks[key]

        if remat_budget is not None:
            # what outlives its layer and is never made again: the keys, the
            # values and the scan output a boundary layer hands on, and their
            # cotangents (float32), live through every block between
            remat_budget.handed_bytes = sum(
                a.size * (a.dtype.itemsize + 4) for a in jax.tree.leaves(
                    self._handed_shapes(*input_ids.shape)))
        if c.remat and c.remat_policy == KEEP_PRODUCTS:
            # every kind of block is reckoned before the first is applied:
            # the step has one decision, over all of them
            for i in range(dense):
                block_of(self._kinds[i], leading=True).reckon(
                    init, _one_layer((params["dense_blocks"], keep[:1])))
            self._stack(init, params, after, block_of, shapes_only=True)
            if with_mtp and c.mtp_layers:
                block_of().reckon(init, _one_layer((params["mtp"]["blocks"], keep[:1])))

        for i in range(dense):       # the leading dense layers, one by one
            layer = jax.tree.map(lambda a: a[i], params["dense_blocks"])
            init, _ = block_of(self._kinds[i], leading=True)(init, (layer, keep[i]))
        (x, _, aux), rows = self._stack(init, params, after, block_of)
        if c.farskip:
            x = x[0]
        x = self._hc_collapse(x)
        if c.diffusion:
            x = x[:, input_ids.shape[1]:]
        mtp_x = None
        if with_mtp and c.mtp_layers:
            with jax.named_scope("mtp"):
                # position i: the final stream and the NEXT token's
                # embedding. The row's last position has no next token and
                # is given the row's first (its loss is masked and the
                # causal mask keeps it from every other position)
                nxt = self._wte(params["wte"], jnp.roll(input_ids, -1, axis=1))
                mp = params["mtp"]
                with jax.named_scope("merge"):
                    merged = self._mtp_layers["merge"](mp["merge"], jnp.concatenate(
                        [self._mtp_layers["norm_h"](mp["norm_h"], x),
                         self._mtp_layers["norm_e"](mp["norm_e"], nxt.astype(c.dtype))],
                        axis=-1))
                merged = _c(merged, ACT_SPEC)
                start = ((merged, merged) if c.farskip else self._hc_start(merged),
                         positions, aux)
                (mtp_x, _, aux), (mtp_rows, _) = block_of()(
                    start, (jax.tree.map(lambda a: a[0], mp["blocks"]),
                            jnp.ones((), c.dtype)))
                if c.farskip:
                    mtp_x = mtp_x[0]
                mtp_x = self._hc_collapse(mtp_x)
            if rows is not None:
                rows = jnp.concatenate([rows, mtp_rows[None]], axis=0)
        stats = self._stats_of(rows)
        if c.residual_streams > 1:
            # (``engine.attn_last_step()["hc_res_row_err"]``: a projection that
            # stopped projecting shows here)
            stats = {**stats, "attn_hc_res_row_err": aux[1]}
        if c.document_separator is not None:
            stats = {**stats, **self._attn_tile_stats(documents)}
        for mixer in self._mixers.values():
            stats = {**stats, **mixer.step_stats(documents, input_ids.shape)}
        return x, aux, stats, mtp_x

    def _layers_of_kind(self, with_mtp: bool) -> Dict[Any, int]:
        """How many of a step's blocks are of each kind, under `_trunk`'s
        keys: a leading dense layer's ``("dense", kind)``, a stack layer's
        kind (a mixed stack's as `run_plan` spells it), the prediction
        module's None."""
        c = self.config
        layers = collections.Counter(
            ("dense", kind) for kind in self._kinds[:self._leading_dense])
        if c.mixed:
            runs = self.run_plan
        else:
            unit, repeats, tail = self.scan_plan
            runs = ((unit, repeats), (tail, 1))
        for unit, repeats in runs:
            layers.update({kind: repeats * unit.count(kind) for kind in unit})
        if with_mtp and c.mtp_layers:
            layers[None] = c.mtp_layers
        return dict(layers)

    def _hc_start(self, x: jax.Array) -> jax.Array:
        """The stack's first carry from one stream ``[B, S, H]``: with
        hyper-connections n copies side by side, vec(X) ``[B, S, n x H]``."""
        n = self.config.residual_streams
        return x if n == 1 else _c(jnp.tile(x, (1, 1, n)), ACT_SPEC)

    def _hc_collapse(self, x: jax.Array) -> jax.Array:
        """The stack's last carry as one stream: the n streams' sum (float32,
        rounded once)."""
        if self.config.residual_streams == 1:
            return x
        with jax.named_scope("hc"), jax.named_scope("post"):
            return _c(sum(self._hc_streams(x)).astype(x.dtype), ACT_SPEC)

    def _documents(self, input_ids: jax.Array) -> jax.Array:
        """Each position's document in a packed row, [B, S] int32: the
        separators BEFORE it (a separator ends its own document)."""
        ends = (input_ids == self.config.document_separator).astype(jnp.int32)
        return jnp.cumsum(ends, axis=1) - ends

    @functools.cached_property
    def attn_tile_kinds(self) -> Tuple[Tuple[str, int], ...]:
        """The kinds of attention launch that a step's tile count
        (``attn_tiles`` of its statistics) has a line for, ``(name, window,
        0 = none)``: none without packed documents; a mixer's one kind where
        it has one (``Mixer.tile_kind``: ``blockdiff`` under the
        block-diffusion objective, ``dsa`` under a learned selection); else
        ``window`` for the layers' static window (``window_<w>`` where they
        have several) and ``full``."""
        c = self.config
        if c.document_separator is None:
            return ()
        fixed = tuple(mixer.tile_kind for mixer in self._mixers.values()
                      if mixer.tile_kind is not None)
        if fixed:
            return fixed
        windows = sorted({w for w, _ in self._kinds}, reverse=True)
        several = sum(map(bool, windows)) > 1
        return tuple(("full" if not w else f"window_{w}" if several else "window", w)
                     for w in windows)

    def _attn_tile_stats(self, documents: jax.Array) -> Dict[str, jax.Array]:
        """``attn_tiles``, int32 ``[kinds, 2 (forward, backward), 2 (the
        tiles the position test alone runs, the tiles run)]``: how far the
        row's documents cut one flash launch's tiles a head, for each of
        `attn_tile_kinds`, by the kernels' own table and predicate
        (``pallas_flash.tiles_run``) at the launch the kernel route's plan
        lists for the shape. Nothing where the kernel cannot run it."""
        from ..ops.transformer import pallas_flash as pf
        c = self.config
        lines = []
        for _, window in self.attn_tile_kinds:
            # (``"pallas"``: the plan wherever the kernel CAN run)
            plan = self._attending.plan(*documents.shape, window, mode="pallas")
            if plan.route != "kernel":
                return {}
            (at,) = plan.launches
            if c.diffusion:
                q_ids = jnp.concatenate([documents, documents], axis=1)
                mask = dict(blockdiff=c.block_length)
            else:
                q_ids, mask = documents, dict(causal=c.causal, window=at.window)
            lines.append(jnp.stack([
                jnp.stack(pf.tiles_run(q_ids, documents, tile, **mask))
                for tile in (at.tiles.fwd, at.tiles.bwd)]))
        return {"attn_tiles": jnp.stack(lines)}

    def attention_records(self, batch: Optional[int] = None, seq: Optional[int] = None
                          ) -> Tuple[Dict[str, Any], Optional[Dict[str, Any]]]:
        """``(attn, diffusion)``: what an engine keeps as ``attn_totals`` and
        ``diffusion_totals`` (docs/OBSERVABILITY.md has every key; None for
        another objective). Without a shape what the configuration says; with
        the rows and tokens a step is traced for, also each kind's route, how
        its backward makes dq and where its launches take the operands' heads
        (``layout``), off the launches' own plans. Each kind of mixer declares
        and fills its own entry (``Mixer.record``); the lines by ``window`` /
        ``full`` are read here, off the attending mixer's plan under its tag."""
        c = self.config
        # (a mixed stack: its attention layers alone)
        attending = [w for (w, _), (name, _) in zip(self._kinds, self._mixer_kinds)
                     if self._mixers[name].attends]
        windows = sorted({w for w in attending if w})
        layers = {"window": sum(1 for w in attending if w),
                  "full": sum(1 for w in attending if not w)}
        attn = {"layers_window": layers["window"], "layers_full": layers["full"],
                "window": windows[0] if len(windows) == 1 else (windows or None),
                "kv_heads": c.kv_heads,
                # the query heads that share a key head
                "group": c.num_heads // c.kv_heads,
                "documents": c.document_separator is not None,
                "route": {"window": None, "full": None},
                "dq": {"window": None, "full": None},
                "layout": {"window": None, "full": None}}
        for mixer in self._mixers.values():
            attn.update(mixer.record(batch, seq))
        diffusion = attn.pop("diffusion", None)
        if c.residual_streams > 1:
            # (the streams' record rides here: an engine copies this dict whole)
            attn["hc"] = {"streams": c.residual_streams,
                          "sinkhorn_iters": c.hc_sinkhorn_iters,
                          "sublayers": 2 * (c.num_layers + c.mtp_layers),
                          "route": None, "tile_rows": None}
            if seq is not None:
                route, tile_rows = self._hc_route(seq * self.rows_per_token, c.dtype)
                attn["hc"].update(route=route, tile_rows=tile_rows)
        mixer = self._attending
        if seq is not None and mixer is not None and mixer.tag is not None:
            tag = mixer.tag
            plans = {w: mixer.plan(batch, seq, w) for w in [0] + windows}
            # (sliding layers of several widths: the mode they share, else both)
            under = sorted({plans[w].dq(tag) or "" for w in windows})
            attn["route"] = {kind: plans[0].route if n else None
                             for kind, n in layers.items()}
            attn["dq"] = {
                "window": ("+".join(under) or None) if layers["window"] else None,
                "full": plans[0].dq(tag) if layers["full"] else None}
            attn["layout"] = {
                "window": plans[windows[0]].layout(tag) if layers["window"] else None,
                "full": plans[0].layout(tag) if layers["full"] else None}
        return attn, diffusion

    def traced_rows_records(self, stats: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
        """What a step's statistics add to ``attn_totals``, by its key there
        (an engine asks once, of the first step's: the rows the step was traced
        for): each mixer's own (``Mixer.traced_records``)."""
        found: Dict[str, Dict[str, Any]] = {}
        for mixer in self._mixers.values():
            found.update(mixer.traced_records(stats))
        return found

    def expert_records(self, batch: Optional[int] = None, seq: Optional[int] = None,
                       *, expert_layers: int = 0, dtype=None, devices: int = 1,
                       kept: Tuple[str, ...] = ()) -> Dict[str, Any]:
        """What an engine's ``moe_totals`` says of the expert layers (nothing
        without): ``experts_published`` and ``experts_held`` and, with the
        rows and tokens a step is traced for (the no-drop path), the route
        and the products a step launches by kind over ``expert_layers``
        layers, from static shapes. A differentiated layer launches each
        product forward, as a row and as a weight gradient, and forward again
        where the block is rematerialised and the policy kept no such name
        (``kept``: the names saved; another policy than ``KEEP_PRODUCTS``
        keeps none). ``dtype``: the operands'; ``devices``: the mesh's."""
        c = self.config
        if c.moe is None:
            return {}
        moe = self._moe
        record = {"experts_published": moe.num_experts,
                  "experts_held": moe.held[1] - moe.held[0],
                  "router_input": moe.router_input, "activation": moe.activation}
        if seq is None:
            return record
        from ..ops.transformer import pallas_gmm, pallas_segment_sum
        backend = jax.default_backend()
        tokens = batch * seq * self.rows_per_token     # a noised copy's rows too
        counts = {route: dict.fromkeys(pallas_gmm.KINDS, 0) for route in ("kernel", "xla")}
        for name, m, k, n, g in moe.grouped_products(tokens):
            route = counts[pallas_gmm.choose_route(m, k, n, g, dtype, backend, devices)]
            route["forward"] += expert_layers * (1 + (c.remat and name not in kept))
            route["row_gradient"] += expert_layers
            route["weight_gradient"] += expert_layers
        used = [r for r in counts if any(counts[r].values())]
        record.update(
            grouped_matmul_route=used[0] if len(used) == 1 else "mixed",
            products_kernel=counts["kernel"], products_xla=counts["xla"])
        back = moe.rows_back(tokens)
        if back is not None:
            # the combine forward (again where the backward reruns the block)
            # and the dispatch's backward gather the buffer's rows once each
            record.update(
                combine_route=pallas_segment_sum.choose_route(
                    *back, dtype, backend, devices),
                combine_rows_moved=expert_layers * (2 + bool(c.remat)) * back[0])
        return record

    @property
    def returns_step_stats(self) -> bool:
        """Whether ``loss_and_stats`` returns statistics a fused step carries
        out beside the loss: the no-drop path's rows per expert, the masked
        share of block diffusion, packed documents' count of tiles."""
        return (self.moe_path == "dropless" or self.config.diffusion
                or self.config.document_separator is not None
                or self.config.residual_streams > 1
                or any(mixer.has_step_stats for mixer in self._mixers.values()))

    @functools.cached_property
    def scan_plan(self) -> Tuple[Tuple[Any, ...], int, Tuple[Any, ...]]:
        """How the layers after the leading dense ones run, from their static
        kinds ``(window, rope)``: ``(unit, repeats, tail)``. ``unit`` is the
        kinds' shortest period, which ``lax.scan`` runs ``repeats`` times
        (each layer of the unit traced once, as its own kind); ``tail`` is
        what is left of a last, partial period, run one block at a time
        after the scan. One kind throughout: a unit of one. afmoe's 30
        expert layers (s F s s s F ...): the unit (s, F, s, s) seven times
        and a tail of (s, F)."""
        kinds = self._kinds[self.config.first_dense_layers:]
        n = len(kinds)
        period = next(p for p in range(1, n + 1)
                      if all(kinds[i] == kinds[i - p] for i in range(p, n)))
        return kinds[:period], n // period, kinds[n - n % period:]

    def _stack(self, init, params: Params, keep: jax.Array, block_of,
               shapes_only: bool = False):
        """The layers after the leading dense ones by the stack's layout,
        the ONE way through them: ``params["runs"]`` as `run_plan` lays a
        mixed stack out (`_scan_runs`), else ``params["blocks"]`` as
        `scan_plan` does (`_scan_by_kind`) -> (carry, the no-drop path's rows
        per expert or None). ``keep``: those layers' gates. ``shapes_only``: reckon every kind of block for
        the remat budget (`_KeptBlock.reckon`) and run nothing."""
        if self.config.mixed:
            return self._scan_runs(init, params["runs"], keep, block_of, shapes_only)
        xs = (params["blocks"], keep)
        if not shapes_only:
            return self._scan_by_kind(init, xs, block_of)
        unit, _, tail = self.scan_plan
        for kind in unit + tail:
            block_of(kind).reckon(init, _one_layer(xs))

    def _scan_by_kind(self, init, xs, block_of):
        """The layer scan over ``xs`` (stacked blocks and their keep gates)
        as ``scan_plan`` lays it out, ``block_of(kind)`` a layer's function
        -> (carry, the no-drop path's rows per expert ``[layers, experts]``
        or None)."""
        unit, repeats, tail = self.scan_plan
        fns = [block_of(kind) for kind in unit]
        p, n = len(unit), len(unit) * repeats
        if p == 1:
            carry, (rows, _) = jax.lax.scan(fns[0], init, xs)
        else:
            def unit_fn(carry, xs_unit):
                out = []
                for i, fn in enumerate(fns):
                    carry, (r, _) = fn(carry, jax.tree.map(lambda a: a[i], xs_unit))
                    out.append(r)
                return carry, None if out[0] is None else jnp.stack(out)

            carry, rows = jax.lax.scan(unit_fn, init, jax.tree.map(
                lambda a: a[:n].reshape((repeats, p) + a.shape[1:]), xs))
            if rows is not None:
                rows = rows.reshape((n,) + rows.shape[2:])
        for i, kind in enumerate(tail):
            carry, (r, _) = block_of(kind)(carry, jax.tree.map(lambda a: a[n + i], xs))
            if rows is not None:
                rows = jnp.concatenate([rows, r[None]], axis=0)
        return carry, rows

    def _stats_of(self, rows: Optional[jax.Array]) -> Dict[str, jax.Array]:
        """The step's device-side statistics from the no-drop path's
        assignments per expert ``[expert layers, experts]`` (the prediction
        module's layer last): ``moe_expert_rows``, the columns of the
        experts held here, and with a bias that load moves
        ``moe_router_load``, every column."""
        if rows is None:
            return {}
        lo, hi = self._moe.held
        stats = {"moe_expert_rows": rows[:, lo:hi]}
        if self.config.moe.bias_update:
            stats["moe_router_load"] = rows
        return stats

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
                  extra_mask: Optional[jax.Array] = None, slices: int = 1) -> jax.Array:
        """Final norm + LM/MLM head + masked cross-entropy over the last
        block's output (the differentiated tail of the overlap schedule).
        ``slices`` > 1 (`head_row_slices`): `fused_head_loss`, the same sum
        over slices of the rows, so that the float32 logits of all the rows
        are never held."""
        if slices == 1:
            return next_token_cross_entropy(self.config, self.head(params, x), labels,
                                            extra_mask)
        return self.fused_head_loss(params, x, labels, extra_mask, slices)

    def fused_head_loss(self, params: Params, x: jax.Array, labels: jax.Array,
                        extra_mask: Optional[jax.Array], slices: int,
                        ln_f: Optional[Params] = None, scale: float = 1.0) -> jax.Array:
        """``scale`` x the masked cross-entropy of `head` over ``x`` [B, S, H]
        (what `head_loss` returns over whole logits), for a head whose float32
        logits are not kept for the backward. The rows are walked in
        ``slices`` slices; the head's matrix and the final norm (``ln_f``:
        another one's parameters) enter the slices in float32 (cast down
        inside a slice), so their gradients are summed over the slices in
        float32.

        Differentiated, a slice's gradient is taken WHERE ITS LOGITS ARE: the
        head is the last thing a forward computes and the first a backward
        differentiates, so the forward rule takes `jax.vjp` of each slice's
        share of the loss at cotangent 1, writes the slice's ``dx`` into its
        rows and adds the parameters' gradients to the scan's carry. Those
        are the residuals, and the backward rule multiplies them by the
        scalar that reaches it (1.0 on a fused step: ``scale`` carries a
        coefficient the caller knows, so that nothing is rounded twice).
        Three products over the vocabulary a slice (logits, ``dx``, the
        matrix's gradient) and one softmax, where a `jax.checkpoint` of the
        slice makes four and two; one slice's logits are held at a time. Not
        differentiated, it is the sum over the slices and no gradient.

        float16 keeps autodiff's order (a `jax.checkpoint` of the slice, its
        logits made again in the backward): a loss scale has to reach the
        logits' gradient before it is rounded, and a gradient taken at
        cotangent 1 in float16 would underflow."""
        B, S, H = x.shape
        f32 = jnp.float32
        head = {k: params[k] for k in
                ("ln_f", "wte" if self.config.tie_embeddings else "lm_head")}
        if ln_f is not None:
            head["ln_f"] = ln_f
        dtypes = jax.tree.map(lambda a: a.dtype, head)
        wide = jax.tree.map(lambda a: a.astype(f32), head)
        valid = labels >= 0
        mask = valid.astype(f32) * (1.0 if extra_mask is None else extra_mask.astype(f32))
        rows = lambda *operands: tuple(      # the scan's operands: [slices, rows a slice, ...]
            a.reshape((slices, B * S // slices) + a.shape[2:]) for a in operands)
        mean = lambda total, weights_sum: scale * (total / jnp.maximum(weights_sum, 1.0))

        def slice_sum(wide, xb, targets, weights):
            narrow = jax.tree.map(lambda a, dtype: a.astype(dtype), wide, dtypes)
            logits = self.head(narrow, xb[None])[0]
            with jax.named_scope("loss"):
                return jnp.sum(_token_nll(logits, targets) * weights)

        def summed(slice_sum):
            def value(wide, x, targets, weights):
                step = lambda total, xs: (total + slice_sum(wide, *xs), None)
                with jax.named_scope("head"):
                    total, _ = jax.lax.scan(step, jnp.zeros((), f32),
                                            rows(x, targets, weights))
                return mean(total, jnp.sum(weights))
            return value

        operands = wide, x, jnp.where(valid, labels, 0), mask
        if x.dtype == jnp.float16:
            return summed(jax.checkpoint(slice_sum))(*operands)

        def forward(wide, x, targets, weights):
            weights_sum = jnp.sum(weights)

            def step(carry, xs):
                xb, *rest = xs

                def share(wide, xb):
                    total = slice_sum(wide, xb, *rest)
                    return mean(total, weights_sum), total
                _, pull, total = jax.vjp(share, wide, xb, has_aux=True)
                dwide, dxb = pull(jnp.ones((), f32))
                return (carry[0] + total, jax.tree.map(jnp.add, carry[1], dwide)), dxb

            with jax.named_scope("head"):
                (total, dwide), dx = jax.lax.scan(
                    step, (jnp.zeros((), f32), jax.tree.map(jnp.zeros_like, wide)),
                    rows(x, targets, weights))
            return mean(total, weights_sum), (dwide, dx.reshape(x.shape))

        def backward(grads, g):
            with jax.named_scope("head"):
                dwide, dx = jax.tree.map(lambda a: g.astype(a.dtype) * a, grads)
            return dwide, dx, None, None

        fused = jax.custom_vjp(summed(slice_sum))
        fused.defvjp(forward, backward)
        return fused(*operands)

    def combine_aux(self, loss: jax.Array, aux: jax.Array) -> jax.Array:
        """Fold the accumulated MoE aux loss into the objective: each
        router loss under its own coefficient, averaged over the layers
        (the sigmoid router's sequence-wise balance loss is summed over
        them, as its report has it). Reads ``self.config`` alone
        (``PipelineModule`` borrows it)."""
        moe = self.config.moe
        if self.config.residual_streams > 1:
            aux = aux[0]       # (the pair's other half is a statistic, no loss)
        if isinstance(aux, tuple):
            # the pair (the MoE accumulator, a mixer's own loss: an indexer's
            # L_I), which counts once (coefficient 1)
            aux, own = aux
            loss = loss + own
        if moe is None:
            return loss
        if moe.router == "sigmoid_bias":
            return loss + moe.seq_balance_coef * aux[0]
        if moe.capacity_factor is None:   # the no-drop path's two losses
            aux = moe.aux_loss_coef * aux[0] + moe.z_loss_coef * aux[1]
        else:
            aux = moe.aux_loss_coef * aux
        return loss + aux / self.config.num_layers

    def loss(self, params: Params, batch: Dict[str, jax.Array],
             remat_budget: Optional[Budget] = None) -> jax.Array:
        """The training objective, one of three: next-token cross-entropy
        for causal LMs (labels derived by shift when absent), masked-LM for
        encoders (labels required, -100 = ignore), and with
        ``objective='block_diffusion'`` the weighted denoising loss over a
        clean and a noised copy of every row (:meth:`_diffusion_loss`; the
        batch's ``input_ids`` and optional ``noise_key`` alone).
        batch: input_ids [B,S], optional labels/loss_mask/token_type_ids/
        attention_mask. ``remat_budget`` as in :meth:`apply`. Calls
        ``self.apply``, ``self.derive_labels`` and ``self.combine_aux``
        alone where there is no prediction module, no noise and no rope by
        sections (``PipelineModule`` borrows it)."""
        c = self.config
        if (c.mtp_layers or c.diffusion or c.rope_sections is not None
                or head_slices(self.config, remat_budget, batch["input_ids"]) > 1):
            # (three position streams are a batch's, which `apply` as
            # ``PipelineModule`` has it does not take; a head over slices of
            # the rows is `head_loss`'s)
            return self.loss_and_stats(params, batch, remat_budget)[0]
        labels = self.derive_labels(batch)
        logits, aux = self.apply(params, batch["input_ids"],
                                 layer_mask=batch.get("layer_mask"),
                                 token_type_ids=batch.get("token_type_ids"),
                                 attention_mask=batch.get("attention_mask"),
                                 remat_budget=remat_budget)
        loss = next_token_cross_entropy(self.config, logits, labels,
                                        batch.get("loss_mask"))
        return self.combine_aux(loss, aux)

    def loss_and_stats(self, params: Params, batch: Dict[str, jax.Array],
                       remat_budget: Optional[Budget] = None
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """``loss`` with the device-side statistics of the step (the engine
        keeps them on the device beside the loss). With a prediction module
        the objective is the next-token loss plus ``mtp_loss_coef`` times
        the module's loss on the token after next."""
        c = self.config
        if c.diffusion:
            return self._diffusion_loss(params, batch, remat_budget)
        labels = self.derive_labels(batch)
        mask = batch.get("loss_mask")
        self._charge_head(remat_budget, batch["input_ids"])
        x, aux, stats, mtp_x = self._trunk(
            params, batch["input_ids"], batch.get("layer_mask"),
            batch.get("token_type_ids"), batch.get("attention_mask"),
            remat_budget, with_mtp=True, position_ids=batch.get("position_ids"))
        if mtp_x is None:
            loss = self.head_loss(params, x, labels, extra_mask=mask,
                                  slices=head_slices(self.config, remat_budget, batch["input_ids"]))
            for mixer in self._mixers.values():
                stats = {**stats, **mixer.loss_stats(loss, aux)}
            return self.combine_aux(loss, aux), stats
        later = lambda a, fill: jnp.pad(a[:, 1:], ((0, 0), (0, 1)),
                                        constant_values=fill)

        module = (mtp_x, later(labels, -100), None if mask is None else later(mask, 0))
        slices = head_slices(self.config, remat_budget, batch["input_ids"])
        if self.head_form(slices) != "whole":
            # two float32 logit tables: neither is kept for the backward
            loss = self.fused_head_loss(params, x, labels, mask, slices)
            with jax.named_scope("mtp"):
                loss = loss + self.fused_head_loss(
                    params, *module, slices, ln_f=params["mtp"]["ln_f"],
                    scale=c.mtp_loss_coef)
            return self.combine_aux(loss, aux), stats

        def head_loss(x, labels, mask, ln_f=None):
            return masked_cross_entropy(self.head(params, x, ln_f=ln_f),
                                        labels, extra_mask=mask)
        loss = head_loss(x, labels, mask)
        with jax.named_scope("mtp"):
            loss = loss + c.mtp_loss_coef * head_loss(*module, params["mtp"]["ln_f"])
        return self.combine_aux(loss, aux), stats

    # -- the block-diffusion objective ----------------------------------------
    @property
    def rows_per_token(self) -> int:
        """Rows of activations a data token costs every layer: 2 under the
        block-diffusion objective (a clean and a noised copy), else 1."""
        return 2 if self.config.diffusion else 1

    def noise_key(self, batch: Dict[str, jax.Array]) -> jax.Array:
        """The key a step's noise is drawn from, a pure function of the
        batch: ``batch["noise_key"]`` where the caller gives one, else
        ``noise_seed`` folded with ``f(input_ids)``, ``f`` the sum over the
        flattened ids of ``id x (2 x index + 1)`` in uint32 arithmetic,
        shifted right one bit (a fresh batch gives fresh noise, the same
        batch the same)."""
        key = batch.get("noise_key")
        if key is not None:
            return key
        ids = batch["input_ids"].reshape(-1).astype(jnp.uint32)
        odd = 2 * jnp.arange(ids.size, dtype=jnp.uint32) + 1
        return jax.random.fold_in(jax.random.PRNGKey(self.config.noise_seed),
                                  jnp.sum(ids * odd, dtype=jnp.uint32) >> 1)

    def noise(self, batch: Dict[str, jax.Array]
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """(the noised copy of ``input_ids``, each position's loss weight
        [B, L] float32: 1 / t where it was masked and 0 elsewhere, which
        positions were masked). A block's t ~ U[1e-3, 1] and each token's
        Bernoulli(t) are two uniform draws from the two splits of
        :meth:`noise_key` (linear schedule, alpha_t = 1 - t)."""
        c = self.config
        ids = batch["input_ids"]
        B, L = ids.shape
        with _noise_scope():
            key_t, key_mask = jax.random.split(self.noise_key(batch))
            t = jax.random.uniform(key_t, (B, L // c.block_length), jnp.float32,
                                   minval=1e-3, maxval=1.0)
            t = jnp.repeat(t, c.block_length, axis=1)
            masked = jax.random.uniform(key_mask, (B, L), jnp.float32) < t
            noised = jnp.where(masked, jnp.asarray(c.mask_token_id, ids.dtype), ids)
            return noised, jnp.where(masked, 1.0 / t, 0.0), masked

    def _diffusion_loss(self, params: Params, batch: Dict[str, jax.Array],
                        remat_budget: Optional[Budget] = None
                        ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """The block-diffusion objective (``TransformerConfig.objective``):
        ``(1 / (rows x L)) x sum over masked i of (1 / t_blk(i)) x
        CE(logits_i, x_0[i])``, the logits the head's over the noised copy,
        unshifted. The statistics gain the step's masked share and the mean
        weight of a masked position."""
        if batch.get("labels") is not None or batch.get("loss_mask") is not None:
            raise ValueError("objective='block_diffusion' draws its own targets: "
                             "the batch takes no labels and no loss_mask")
        ids = batch["input_ids"]
        noised, weights, masked = self.noise(batch)
        self._charge_head(remat_budget, ids)
        x, aux, stats, _ = self._trunk(
            params, ids, batch.get("layer_mask"), batch.get("token_type_ids"),
            batch.get("attention_mask"), remat_budget, with_mtp=False,
            noised_ids=noised)
        logits = self.head(params, x)
        with _noise_scope():
            count = jnp.sum(masked, dtype=jnp.float32)
            stats = {**stats, "diffusion_masked_share": count / ids.size,
                     "diffusion_mean_weight": jnp.sum(weights) / jnp.maximum(count, 1.0)}
        return self.combine_aux(weighted_cross_entropy(logits, ids, weights), aux), stats

    def hold_router_bias(self, old: Params, new: Params,
                         load: Optional[jax.Array]) -> Params:
        """``new`` (a tree shaped as the parameters, after an optimizer's
        step from ``old``) with every router's correction bias taken from
        ``old`` instead and moved by ``load`` alone (``sharded_moe.
        bias_step`` under ``bias_update``; ``load`` None: left where it
        was): the leaf no gradient, moment or decay touches. ``load``:
        ``moe_router_load`` of the step's statistics."""
        from ..moe.sharded_moe import bias_step
        rate, n = self.config.moe.bias_update, self.config.scan_layers
        moved = lambda bias, counts: (bias if load is None else bias_step(
            bias.astype(jnp.float32), counts, rate))

        def held(new_tree, old_tree, counts):
            bias = moved(old_tree["moe"]["bias"], counts)
            return {**new_tree, "moe": {**new_tree["moe"], "bias": bias.astype(
                new_tree["moe"]["bias"].dtype)}}

        if self.config.mixed:
            # a run's stack (run i, place j of a unit of p) holds layers at + j, at +
            # j + p, ...; every layer after the leading dense ones is an expert layer
            runs, at, dense = {}, 0, self.config.first_dense_layers
            for i, (unit, repeats) in enumerate(self.run_plan):
                p, run = len(unit), dict(new["runs"][str(i)])
                for j, kind in enumerate(unit):
                    if "moe" in run[str(j)]:
                        lo = at + j - dense
                        run[str(j)] = held(run[str(j)], old["runs"][str(i)][str(j)],
                                           None if load is None else load[lo:lo + p * repeats:p])
                runs[str(i)], at = run, at + p * repeats
            return {**new, "runs": runs}
        out = {**new, "blocks": held(new["blocks"], old["blocks"],
                                     None if load is None else load[:n])}
        if "mtp" in new:
            out["mtp"] = {**new["mtp"], "blocks": held(
                new["mtp"]["blocks"], old["mtp"]["blocks"],
                None if load is None else load[n:])}
        return out

    @property
    def has_router_bias(self) -> bool:
        moe = self.config.moe
        return moe is not None and moe.router == "sigmoid_bias"
