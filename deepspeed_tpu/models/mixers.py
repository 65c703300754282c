"""A layer's token mixer as a value: the one place an architecture's attention
(or what stands in its place) lives.

``TransformerConfig.mixer_of(layer)`` picks each layer's kind and
``TransformerLM`` holds one `Mixer` a kind (`build`): ``mha``, ``selected``
(``mha`` under ``config.indexer``), ``latent`` or ``eva`` for a plain stack;
``ssm``, ``ssd``, ``kda``, ``attn``, ``gmu`` and ``cross`` (and ``mha`` or ``latent``
by a list of layer kinds) in a mixed one. A block's residual
and norm style is NOT a mixer's: ``TransformerLM._block_fn`` has it. A new
architecture's mixer is a class here, its name in `KINDS`, a branch of
``mixer_of``, and a name in ``checkpointing.SAVE_ORDER`` for each value its
backward may keep.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..nn import layers as nn
from ..ops.transformer.attention import flash_attention
from ..sequence.layer import ulysses_attention
from ..utils.scope import scoped

Params = Dict[str, Any]

#: The most elements of ``[rows, heads x head]`` an EVA layer's cores (rope,
#: summaries, the two launches, their float32 partials and the merge) may take
#: at once before the heads are taken in groups (``transformer.
#: MLP_WHOLE_ELEMENTS`` has the reckoning). Memory only: the same arithmetic.
EVA_GROUP_ELEMENTS = 2 ** 24


#: The most elements of ``[rows of a row, batch x heads x head]`` a KDA layer's
#: convolutions, gates and gated norm (float32 passes of that size, a dozen live at
#: once in a backward) may take whole before they are taken a slice of the row at a
#: time. Memory only: the same arithmetic.
KDA_WHOLE_ELEMENTS = 2 ** 25
KDA_SLICE_ELEMENTS = 2 ** 24


def kda_row_slices(rows: int, width: int) -> int:
    """How many slices of a row a KDA layer's work around its core is computed in:
    1 up to `KDA_WHOLE_ELEMENTS` elements of ``[rows, width]``, else the least power
    of two that brings a slice under `KDA_SLICE_ELEMENTS` and divides the rows."""
    n = 1
    if rows * width > KDA_WHOLE_ELEMENTS:
        while rows % (2 * n) == 0 and rows // n * width > KDA_SLICE_ELEMENTS:
            n *= 2
    return n


def eva_head_groups(rows: int, heads: int, head_dim: int) -> int:
    """How many groups of its heads an EVA layer's cores are computed in: the
    least divisor of ``heads`` that brings a group's ``[rows, heads x
    head_dim]`` under ``EVA_GROUP_ELEMENTS``."""
    return next(g for g in range(1, heads + 1) if heads % g == 0
                and (g == heads or rows * (heads // g) * head_dim <= EVA_GROUP_ELEMENTS))


def lambda_init(layers: int) -> jax.Array:
    """Differential attention's constant at each layer, ``0.8 - 0.6 exp(-0.3
    l)``, float32 (a mixed stack hands every layer its own)."""
    return jnp.asarray([0.8 - 0.6 * math.exp(-0.3 * l) for l in range(layers)],
                       jnp.float32)


def _topology():
    from ..runtime import topology as topo_mod
    return topo_mod.get_topology() if topo_mod.is_initialized() else None


def devices() -> int:
    """The live mesh's devices (a Pallas launch is not partitioned)."""
    return 1 if _topology() is None else _topology().world_size


def _whole_rows(what: str) -> None:
    """Refuse sequence parallelism for a mixer that reads whole rows."""
    topo = _topology()
    if topo is not None and topo.sequence_parallel_size > 1:
        raise NotImplementedError(
            f"{what}, which sequence parallelism "
            f"(sequence={topo.sequence_parallel_size}) divides")


def _linear(i: int, o: int, bias: bool, shard: Optional[str]) -> nn.Linear:
    return nn.Linear(i, o, use_bias=bias, shard=shard)


class Mixer:
    """One kind of token mixer, bound to a configuration and, to run, check,
    plan or record, to the ``TransformerLM`` that holds it (``host``: its
    ``_rotate*``, ``_alibi_slopes``, ``_windows``, ``_kinds``).

    ``mixer(block, h, positions, documents, kind, given) -> (out, handed,
    aux)`` over the (pre-normed, or raw for post-LN) input ``h``:
    ``documents`` [B, S] each position's packed document (an encoder's padding
    mask: 1 = real); ``kind`` the layer's static ``(window, rope)``, in a
    mixed stack ``(window, rope, mixer, hands)``; ``given`` what the layer is
    handed beside its parameters (`take`); ``handed`` what a boundary layer of
    a mixed stack hands on and ``aux`` a loss of its own, None elsewhere."""

    name = ""
    attends = False         # whether `plan` gives a launch (the record counts its layers)
    mha_heads = False       # ``mha``'s plain projections: rope by sections, block diffusion
    reads: Optional[str] = None     # what of an earlier layer it reads: "memory" | "kv"
    has_loss = False        # ``aux``: the carry then holds (the MoE accumulator, that so far)
    has_step_stats = False
    #: the ONE kind of launch ``TransformerLM.attn_tile_kinds`` has a line for
    tile_kind: Optional[Tuple[str, int]] = None
    #: the launch tag its layers' ``route`` / ``dq`` / ``layout`` by ``window`` /
    #: ``full`` are read under off `plan`; None: `record` carries its routes
    tag: Optional[str] = None

    def __init__(self, config, host=None):
        self.c, self.host = config, host
        self._l: Optional[Dict[str, Any]] = None

    def layers(self) -> Dict[str, Any]:
        """Its ``nn`` layers by name: a block's leaves beside the norms and
        the MLP's (`_layers`, built once)."""
        if self._l is None:
            self._l = self._layers()
        return self._l

    def check(self) -> None:
        """Refuse what the mixer is not written for."""
        if self.c.q_latent_rank and self.name != "latent":
            raise ValueError("q_latent_rank is latent attention's compressed query")

    def take(self, given: tuple) -> tuple:
        """``given`` as ``__call__`` reads it: a reader of an earlier layer's
        tensors (`reads`) casts its copy once, before the block's first norm."""
        if self.reads is None:
            return given
        lam_init, shared = given
        with jax.named_scope("attn"), jax.named_scope("shared"):
            return lam_init, jax.tree.map(lambda t: t.astype(self.c.dtype), shared)

    def plan(self, batch: int, seq: int, window: int = 0, mode: Optional[str] = None):
        """The ``attention.Plan`` of one layer's call over ``batch`` whole rows
        of ``seq`` tokens here (``window``: the layer's static one; ``mode``:
        `attn_mode`'s, or given); None for a mixer that launches no attention."""
        return None

    def record(self, batch: Optional[int] = None, seq: Optional[int] = None) -> Dict[str, Any]:
        """Its entry of ``engine.attn_totals`` by its key there
        (docs/OBSERVABILITY.md has every key): what the configuration says
        and, with the rows and tokens a step is traced for, what the launches'
        own plans say (None before)."""
        return {}

    def step_stats(self, documents: Optional[jax.Array], shape) -> Dict[str, jax.Array]:
        """Its lines of a step's device-side statistics over ``shape`` rows."""
        return {}

    def loss_stats(self, loss: jax.Array, aux) -> Dict[str, jax.Array]:
        """Its lines beside the language-model ``loss`` and the carry's ``aux``."""
        return {}

    def traced_records(self, stats: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
        """What the first step's statistics add to its `record`, by its key."""
        return {}

    def _plan(self, batch, seq):
        return None if seq is None else self.plan(batch, seq)

    def _project(self, block: Params, name: str, h: jax.Array) -> jax.Array:
        """Its linear layer ``name`` over ``h``, the result named as one the
        backward may keep (``remat_policy``'s default)."""
        return checkpoint_name(self.layers()[name](block[name], h), name)

    # -- a mixed stack's hand-over -------------------------------------------------
    def count(self, name: str) -> int:
        """How many layers of the stack have the mixer ``name``."""
        return sum(1 for l in range(self.c.num_layers) if self.c.mixer_of(l)[0] == name)

    def shared_dtype(self, what: str):
        """The dtype a layer's keys and values (``"kv"``) or scan output
        (``"memory"``) are handed on in: float32 where several layers read them,
        so that their cotangents are summed in float32 (a reader casts its copy
        down); the stream's own with one reader."""
        readers = self.count("cross" if what == "kv" else "gmu")
        return jnp.float32 if readers > 1 else self.c.dtype

    def _hand(self, handed, hands: Optional[str]):
        """What the layer hands on as ``hands``, in `shared_dtype`."""
        if hands is None:
            return None
        with jax.named_scope("attn"), jax.named_scope("shared"):
            return jax.tree.map(lambda t: t.astype(self.shared_dtype(hands)), handed)


class Attention(Mixer):
    """What the attending kinds share: the core's call, the gate, the plan."""

    attends, tag = True, "flash"

    def _biases(self) -> Tuple[bool, bool]:
        """(the q, k, v projections' bias, the output projection's): biases
        with LayerNorm unless ``linear_bias`` says (falcon), ``attn_bias`` for
        the attention alone (gpt-j), ``attn_out_bias`` for ``o_proj`` (gpt-neo)."""
        c = self.c
        use_bias = c.linear_bias if c.linear_bias is not None else c.norm == "layernorm"
        attn_bias = c.attn_bias if c.attn_bias is not None else use_bias
        return attn_bias, (c.attn_out_bias if c.attn_out_bias is not None else attn_bias)

    def _gate_layer(self, attn_out: int) -> Dict[str, Any]:
        return ({"attn_gate": _linear(self.c.hidden_size, attn_out, False, "column")}
                if self.c.attn_gate else {})

    def _gated(self, block: Params, h: jax.Array, out: jax.Array) -> jax.Array:
        """``out`` under the attention gate: times the sigmoid (float32) of
        the sub-block's input through ``attn_gate``, element-wise."""
        with jax.named_scope("gate"):
            gate = checkpoint_name(self.layers()["attn_gate"](
                block["attn_gate"], h), "attn_gate")
            return out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)

    def _attn_core(self, q, k, v, attn_mask, window, scale=None, tag=None) -> jax.Array:
        """Scores, softmax and values (XLA, flash, ring or Ulysses). ``tag``: a
        two-width launch's name (``pallas_flash.TAGS``)."""
        c = self.c
        seg = attn_mask.astype(jnp.int32) if attn_mask is not None else None
        kw = {} if tag is None else {"tag": tag}
        scale = c.attn_scale if scale is None else scale
        if scale is not None:
            kw["scale"] = scale
        if window is not None:
            kw["window"] = window
        if c.seq_parallel == "ring":
            if seg is not None:
                raise ValueError("ring attention does not support padding "
                                 "masks (attention_mask)")
            from ..sequence.ring_attention import ring_attention
            return ring_attention(q, k, v, causal=True, scale=scale)
        if self.host._alibi_slopes is not None:
            kw["alibi_slopes"] = jnp.asarray(self.host._alibi_slopes)
        return ulysses_attention(flash_attention, q, k, v, causal=c.causal,
                                 segment_ids=seg, **kw)

    def _launch(self, window: int) -> Tuple[int, int, Dict[str, Any]]:
        """(query heads, key heads, the mask's keywords) of one launch's plan."""
        c = self.c
        return c.num_heads, c.kv_heads, dict(causal=c.causal, window=window or None)

    def plan(self, batch: int, seq: int, window: int = 0, mode: Optional[str] = None):
        from ..ops.transformer import attention
        c = self.c
        heads, kv_heads, mask = self._launch(window)
        return attention.plan(
            (batch, seq * self.host.rows_per_token, heads, c.head_dim),
            (batch, seq, kv_heads, c.head_dim), jax.default_backend(),
            attention.attn_mode() if mode is None else mode,
            jnp.dtype(c.dtype).itemsize, **mask)


class Mha(Attention):
    """Multi-head attention over whole rows. ``documents`` masks padding
    bidirectionally via the segment-ids mechanism (encoders); with packed
    documents it holds each position's document. The layer's window restricts
    each query to the last ``window`` keys (mistral sliding window / gpt-neo
    local layers / afmoe's sliding layers): a Python int where the layer's
    kind is static (0 or None = global; the core then runs under the scope
    ``core_window``), or, ``given`` by the ZeRO-3 pipelined scan, a traced
    scalar (0 = global). The kind's ``rope``: whether a rotary position turns
    this layer's queries and keys (None: ``position`` says)."""

    name, mha_heads = "mha", True

    def __init__(self, config, host=None):
        super().__init__(config, host)
        if config.diffusion:        # one launch kind, its routes in the record
            self.tile_kind, self.tag = ("blockdiff", 0), None

    def take(self, given: tuple) -> tuple:
        # (a mixed stack hands every layer differential attention's constant,
        # which is not this mixer's; a plain stack's traced window passes)
        return () if self.c.mixed else given

    def _layers(self) -> Dict[str, Any]:
        c = self.c
        attn_bias, attn_out_bias = self._biases()
        q_out, kv_out = c.num_heads * c.head_dim, c.kv_heads * c.head_dim
        layers = {"q_proj": _linear(c.hidden_size, q_out, attn_bias, "column"),
                  "k_proj": _linear(c.hidden_size, kv_out, attn_bias, "column"),
                  "v_proj": _linear(c.hidden_size, kv_out, attn_bias, "column"),
                  "o_proj": _linear(q_out, c.hidden_size, attn_out_bias, "row")}
        if c.qk_norm:
            per_head = c.qk_norm_per_head
            layers["q_norm"] = nn.RMSNorm(c.head_dim if per_head else q_out, eps=c.norm_eps)
            layers["k_norm"] = nn.RMSNorm(c.head_dim if per_head else kv_out, eps=c.norm_eps)
        return {**layers, **self._gate_layer(q_out)}

    def parameters(self) -> int:
        """Every leaf of `layers` but the projections' biases (until PR 59
        neither the gate nor a head's own norm was reckoned)."""
        c, h = self.c, self.c.hidden_size
        q_out, kv = c.num_heads * c.head_dim, c.kv_heads * c.head_dim
        norms = 2 * c.head_dim if c.qk_norm_per_head else q_out + kv
        return (2 * h * q_out + 2 * h * kv + norms * bool(c.qk_norm)
                + h * q_out * bool(c.attn_gate))

    def _launch(self, window: int):
        c = self.c
        if c.diffusion:
            return c.num_heads, c.kv_heads, dict(blockdiff=c.block_length)
        return super()._launch(window)

    def record(self, batch=None, seq=None) -> Dict[str, Any]:
        c, plan = self.c, self._plan(batch, seq)
        if not c.diffusion:
            return {}
        return {"diffusion": {
            "block_length": c.block_length, "rows_per_token": self.host.rows_per_token,
            "route": plan and plan.route, "dq": plan and plan.dq("blockdiff"),
            "layout": plan and plan.layout("blockdiff")}}

    def __call__(self, block, h, positions, documents, kind=None, given=()):
        c = self.c
        B, S, _ = h.shape
        window, rope = (kind or (None, None))[:2]
        if given:
            (window,) = given
        if rope is None:
            rope = c.position == "rope"
        if isinstance(window, int) and window <= 0:
            window = None
        with jax.named_scope("attn"):
            q, k, v = self._qkv(block, h, positions, rope)
            if c.diffusion:
                # both copies of the row under one mask; documents holds the
                # clean copy's [B, L]
                with jax.named_scope("core_blockdiff"):
                    out = self._blockdiff_core(q, k, v, documents)
            else:
                with jax.named_scope("core_window" if isinstance(window, int) else "core"):
                    out = self._attn_core(q, k, v, documents, window)
            out = out.reshape(B, S, c.num_heads * c.head_dim)
            if c.attn_gate:
                out = self._gated(block, h, out)
            with jax.named_scope("out"):
                return self._project(block, "o_proj", out), None, None

    @scoped("qkv")
    def _qkv(self, block: Params, h: jax.Array, positions: jax.Array, rope: bool):
        """``mha``'s projected, normed and turned q [B, S, heads, head] and k,
        v [B, S, kv heads, head]."""
        c, layers = self.c, self.layers()
        B, S, _ = h.shape
        # saved as projected: QK-norm's backward needs its input
        q = self._project(block, "q_proj", h)
        k = self._project(block, "k_proj", h)
        per_head = c.qk_norm and c.qk_norm_per_head
        if c.qk_norm and not per_head:
            q = layers["q_norm"](block["q_norm"], q)
            k = layers["k_norm"](block["k_norm"], k)
        q = q.reshape(B, S, c.num_heads, c.head_dim)
        k = k.reshape(B, S, c.kv_heads, c.head_dim)
        if per_head:
            q = layers["q_norm"](block["q_norm"], q)
            k = layers["k_norm"](block["k_norm"], k)
        v = self._project(block, "v_proj", h).reshape(B, S, c.kv_heads, c.head_dim)
        if rope:
            q = self.host._rotate(q, positions)
            k = self.host._rotate(k, positions)
        return q, k, v

    def _blockdiff_core(self, q, k, v, documents) -> jax.Array:
        """Scores, softmax and values of the clean and the noised copy under
        the block-diffusion mask (``attention.blockdiff_attention``)."""
        from ..ops.transformer.attention import blockdiff_attention
        _whole_rows("objective='block_diffusion': one mask over a clean and a "
                    "noised copy of the whole row")
        return blockdiff_attention(q, k, v, self.c.block_length, documents,
                                   scale=self.c.attn_scale)


class Selected(Mha):
    """``mha`` over a learned selection of each query's keys
    (``config.indexer``; ``ops/transformer/attention.py`` has the equations)
    -> (the branch's output, None, this layer's share of L_I: the rows' KL
    summed, over rows x L). The indexer reads ``stop_gradient(h)`` and the
    selection has no gradient, so the language-model loss reaches no indexer
    leaf; the KL's target is the main attention's own distribution under
    stop_gradient, so L_I reaches nothing else. Scopes: ``attn/indexer``
    (projections and scores), ``attn/select``, ``attn/core_dsa``,
    ``attn/indexer_kl``."""

    name, has_loss, has_step_stats = "selected", True, True
    tile_kind, tag = ("dsa", 0), None

    def _layers(self) -> Dict[str, Any]:
        c, ix = self.c, self.c.indexer
        return {**super()._layers(),
                "indexer_q": _linear(c.hidden_size, ix.heads * ix.head_dim, False, None),
                "indexer_k": _linear(c.hidden_size, ix.head_dim, False, None),
                "indexer_k_norm": nn.LayerNorm(ix.head_dim, eps=1e-6),
                "indexer_w": _linear(c.hidden_size, ix.heads, False, None)}

    def check(self) -> None:
        super().check()
        c, host = self.c, self.host
        if (c.attention != "mha" or not c.causal or c.position != "rope"
                or host._windows is not None or host._mixed_rope or c.diffusion
                or c.seq_parallel == "ring" or c.norm_style == "post"
                or c.parallel_block or c.farskip or c.attn_gate
                or min(c.indexer.heads, c.indexer.head_dim, c.indexer.topk) < 1
                or c.indexer.head_dim % 2):
            raise ValueError(
                "indexer: a selection inside 'mha' attention of a causal rotary "
                "pre-norm or sandwich decoder: no window, block diffusion, ring "
                "attention, parallel block, FarSkip or attention gate")

    def parameters(self) -> int:
        h, ix = self.c.hidden_size, self.c.indexer
        return super().parameters() + (
            h * (ix.heads * ix.head_dim + ix.head_dim + ix.heads) + 2 * ix.head_dim)

    def _launch(self, window: int):
        return self.c.num_heads, self.c.kv_heads, dict(selected=self.c.indexer.topk)

    def record(self, batch=None, seq=None) -> Dict[str, Any]:
        from ..ops.transformer.attention import (attn_mode, kl_launch, packed_rows,
                                                 select_launch)
        ix, plan = self.c.indexer, self._plan(batch, seq)
        return {"dsa": {
            "topk": ix.topk, "indexer_heads": ix.heads, "indexer_head_dim": ix.head_dim,
            "route": plan and plan.route,
            "select": plan and select_launch(seq, jax.default_backend(), attn_mode())[0],
            "select_tiles": None, "select_rows": None,
            "dq": plan and plan.dq("dsa"), "layout": plan and plan.layout("dsa"),
            "kl": plan and kl_launch(plan, seq)[0], "kl_tiles": None, "operand": "bits",
            "operand_bytes": plan and batch * packed_rows(seq) * seq}}

    def step_stats(self, documents, shape) -> Dict[str, jax.Array]:
        # selected over visible pairs, from the documents alone: a query
        # with v visible keys picks min(v, topk) of them in every layer
        from ..ops.transformer import attention, pallas_flash, pallas_indexer_kl
        topk = self.c.indexer.topk
        docs = documents if documents is not None else jnp.zeros(shape, jnp.int32)
        visible = attention.visible_counts(docs).astype(jnp.float32)
        stats = {"attn_selected_share": jnp.sum(
            jnp.minimum(visible, topk)) / jnp.sum(visible)}
        # the tiles the KL's kernel and the selection's run a layer, of
        # their grids', and the queries the selection finds a threshold for
        tiles = {"kl": attention.kl_launch(self.plan(*docs.shape), docs.shape[1])[1],
                 "select": attention.select_launch(
                     docs.shape[1], jax.default_backend(), attention.attn_mode())[1]}
        for name, tile in tiles.items():
            if tile is not None:
                stats[f"dsa_{name}_tiles"] = jnp.stack([
                    pallas_flash.tiles_run(docs, docs, tile)[1],
                    jnp.int32(pallas_indexer_kl.tiles_of(*docs.shape, tile))])
        if tiles["select"] is not None:
            stats["dsa_select_rows"] = jnp.stack([
                jnp.sum(visible > topk, dtype=jnp.int32), jnp.int32(visible.size)])
        return stats

    def loss_stats(self, loss, aux) -> Dict[str, jax.Array]:
        return {"attn_lm_loss": loss, "attn_indexer_kl": aux[1]}    # L_LM and L_I

    def traced_records(self, stats) -> Dict[str, Dict[str, Any]]:
        """``kl_tiles`` and ``select_tiles``, ``[run, of]`` tiles of one
        layer's ``indexer_kl_fwd`` and ``dsa_select`` launch
        (``pallas_flash.tiles_run`` at each launch's tile), and
        ``select_rows``, ``[thresholded, of]``: the queries with more than
        ``topk`` visible keys; each where its launch is the kernel."""
        found = {name: [int(n) for n in np.asarray(stats["dsa_" + name])]
                 for name in ("kl_tiles", "select_tiles", "select_rows")
                 if "dsa_" + name in stats}
        return {"dsa": found} if found else {}

    def __call__(self, block, h, positions, documents, kind=None, given=()):
        from ..ops.transformer import attention
        c, topk = self.c, self.c.indexer.topk
        B, S, _ = h.shape
        _whole_rows("indexer: a query's selection is over the whole row")
        if documents is None:
            documents = jnp.zeros((B, S), jnp.int32)
        scale = c.attn_scale or c.head_dim ** -0.5
        with jax.named_scope("attn"):
            q, k, v = self._qkv(block, h, positions, True)
            q_idx, k_idx, w, picked = self.selection(block, h, positions, documents)
            with jax.named_scope("core_dsa"):
                out, lse = attention.selected_attention(
                    q, k, v, picked, documents, topk, scale)
            with jax.named_scope("indexer_kl"):
                kl = attention.indexer_kl(
                    q_idx, k_idx, w, *jax.lax.stop_gradient((q, k, lse)), picked,
                    documents, float(scale)) / (B * S)
            with jax.named_scope("out"):
                return self._project(block, "o_proj", out.reshape(
                    B, S, c.num_heads * c.head_dim)), None, kl

    def selection(self, block: Params, h: jax.Array, positions: jax.Array,
                  documents: jax.Array):
        """A layer's indexer over its normed input ``h`` -> (q_idx [B, S, J,
        d], k_idx [B, S, d], w [B, S, J] float32 and scaled, the selection as
        the operand its readers unpack: bits, int8 [B, S / 8, S]
        (``attention.pack_selection``), kept under the name ``dsa_mask``). No
        gradient reaches ``h``."""
        from ..ops.transformer import attention
        c, ix, layers = self.c, self.c.indexer, self.layers()
        B, S, _ = h.shape
        x = jax.lax.stop_gradient(h)
        with jax.named_scope("indexer"):
            at = positions[0] if positions.ndim == 3 else positions
            turned = lambda a: nn.rotary_embedding(a, at, c.rope_theta, "half")
            q_idx = turned(self._project(block, "indexer_q", x).reshape(
                B, S, ix.heads, ix.head_dim))
            k_idx = layers["indexer_k_norm"](
                block["indexer_k_norm"], self._project(block, "indexer_k", x))
            k_idx = turned(k_idx[:, :, None, :])[:, :, 0, :]
            w = layers["indexer_w"](block["indexer_w"], x).astype(
                jnp.float32) * (ix.heads ** -0.5 * ix.head_dim ** -0.5)
        # (its two scopes are opened a block of queries at a time, inside)
        picked = checkpoint_name(attention.dsa_select(
            q_idx, k_idx, w, documents, ix.topk), "dsa_mask")
        return q_idx, k_idx, w, picked


class Latent(Attention):
    """Multi-head latent attention (DeepSeek-V2/V3; the configuration's
    ``attention='latent'`` has the sizes): training form, the keys and values
    decompressed for every token, so the attention core sees plain heads and
    takes the route any model's takes (value heads narrower than the key
    heads: ``flash_attention``'s two-width launch, tagged ``mla``, under the
    scope ``attn/core_mla``)."""

    name = "latent"

    def __init__(self, config, host=None):
        super().__init__(config, host)
        self._widths = config.v_head_dim != config.head_dim
        self.tag = "mla" if self._widths else "flash"

    def _layers(self) -> Dict[str, Any]:
        c = self.c
        q_out, attn_out = c.num_heads * c.head_dim, c.num_heads * c.v_head_dim
        q_layers = {"q_proj": _linear(c.hidden_size, q_out, False, "column")}
        if c.q_latent_rank:
            q_layers = {
                "q_a_proj": _linear(c.hidden_size, c.q_latent_rank, False, None),
                "q_a_norm": nn.RMSNorm(c.q_latent_rank, eps=c.norm_eps),
                "q_b_proj": _linear(c.q_latent_rank, q_out, False, "column")}
        layers = {
            **q_layers,
            # the compressed keys and values with the shared rotary key
            "kv_a_proj": _linear(c.hidden_size, c.kv_latent_rank + c.qk_rope_dim, False, None),
            "kv_a_norm": nn.RMSNorm(c.kv_latent_rank, eps=c.norm_eps),
            "kv_b_proj": _linear(c.kv_latent_rank,
                                 c.num_heads * (c.qk_nope_dim + c.v_head_dim), False, "column"),
            "o_proj": _linear(attn_out, c.hidden_size, False, "row")}
        if c.qk_norm:      # (a head at a time: `check` refuses the other)
            layers["q_norm"] = nn.RMSNorm(c.head_dim, eps=c.norm_eps)
            layers["k_norm"] = nn.RMSNorm(c.head_dim, eps=c.norm_eps)
        return {**layers, **self._gate_layer(attn_out)}

    def check(self) -> None:
        super().check()
        c = self.c
        if min(c.kv_latent_rank, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim) <= 0:
            raise ValueError("latent attention needs kv_latent_rank, "
                             "qk_nope_dim, qk_rope_dim and v_head_dim")
        if (c.position not in ("rope", "none") or c.num_kv_heads not in (None, c.num_heads)
                or c.attn_windows is not None or c.norm_style != "pre"
                or c.parallel_block or not c.causal
                or (c.qk_norm and not c.qk_norm_per_head)):
            raise ValueError(
                "latent attention is written for a causal pre-norm decoder, rotary "
                "or with no positions at all (position='none': the shared "
                "qk_rope_dim stay in the products, unturned), with as many key "
                "heads as query heads, no windows, and QK-norm (if any) per head")
        if self._widths and c.seq_parallel == "ring":
            raise NotImplementedError(
                f"value heads of {c.v_head_dim} beside query heads of "
                f"{c.head_dim}: ring attention takes one head size")

    def parameters(self) -> int:
        """Every leaf of `layers` (until PR 59 ``num_parameters`` counted a
        latent layer as ``mha``'s four square projections)."""
        c, h, nh = self.c, self.c.hidden_size, self.c.num_heads
        q_out, r = nh * c.head_dim, c.q_latent_rank
        return ((h * r + r + r * q_out if r else h * q_out)
                + h * (c.kv_latent_rank + c.qk_rope_dim) + c.kv_latent_rank
                + c.kv_latent_rank * nh * (c.qk_nope_dim + c.v_head_dim)
                + nh * c.v_head_dim * h + 2 * c.head_dim * bool(c.qk_norm)
                + h * nh * c.v_head_dim * bool(c.attn_gate))

    def _launch(self, window: int):
        heads, kv_heads, mask = super()._launch(window)
        if self._widths:
            mask["v_dim"] = self.c.v_head_dim
        return heads, kv_heads, mask

    def record(self, batch=None, seq=None) -> Dict[str, Any]:
        c, plan = self.c, self._plan(batch, seq)
        if not self._widths:
            return {}
        return {"mla": {"qk_dim": c.head_dim, "v_dim": c.v_head_dim,
                        "q_rank": c.q_latent_rank, "kv_rank": c.kv_latent_rank,
                        "route": plan and plan.route, "dq": plan and plan.dq("mla"),
                        "layout": plan and plan.layout("mla")}}

    def __call__(self, block, h, positions, documents, kind=None, given=()):
        c, layers = self.c, self.layers()
        B, S, _ = h.shape
        nh, nope, vd = c.num_heads, c.qk_nope_dim, c.v_head_dim
        with jax.named_scope("attn"):
            if c.q_latent_rank:
                with jax.named_scope("latent"):
                    # the compressed query: down, a norm, up to every head
                    q_a = checkpoint_name(layers["q_a_proj"](
                        block["q_a_proj"], h), "q_latent")
                    q = self._project(block, "q_b_proj", layers["q_a_norm"](
                        block["q_a_norm"], q_a)).reshape(B, S, nh, c.head_dim)
            else:
                with jax.named_scope("qkv"):
                    q = self._project(block, "q_proj", h).reshape(B, S, nh, c.head_dim)
            with jax.named_scope("latent"):
                kv_a = checkpoint_name(
                    layers["kv_a_proj"](block["kv_a_proj"], h), "kv_latent")
                latent = layers["kv_a_norm"](
                    block["kv_a_norm"], kv_a[..., :c.kv_latent_rank])
                kv = checkpoint_name(
                    layers["kv_b_proj"](block["kv_b_proj"], latent),
                    "kv_up").reshape(B, S, nh, nope + vd)
            with jax.named_scope("qkv"):
                k_rope = jnp.broadcast_to(kv_a[:, :, None, c.kv_latent_rank:],
                                          (B, S, nh, c.qk_rope_dim))
                k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
                v = kv[..., nope:]
                if c.qk_norm:
                    q = layers["q_norm"](block["q_norm"], q)
                    k = layers["k_norm"](block["k_norm"], k)
                if c.position == "rope":
                    q = self.host._rotate_tail(q, positions)
                    k = self.host._rotate_tail(k, positions)
            # (values narrower than the keys: the two-width launches, their own scope)
            with jax.named_scope("core" if vd == c.head_dim else "core_mla"):
                scale = c.attn_scale or c.head_dim ** -0.5
                if c.rope_scaling is not None:
                    scale *= c.rope_scaling.softmax_scale ** 2
                out = self._attn_core(q, k, v, documents, None, scale=scale)
            out = out.reshape(B, S, nh * vd)
            if c.attn_gate:
                out = self._gated(block, h, out)
            with jax.named_scope("out"):
                return self._project(block, "o_proj", out), None, None


class Eva(Attention):
    """EVA attention (arXiv:2302.04542, as EvaByte runs it; the
    configuration's ``attention='eva'``): the projections and rope as any
    layer's, one summary key and value a chunk under ``attn/eva_summaries``
    (kept for the backward under the names ``eva_kbar`` / ``eva_vbar``), the
    core under ``attn/core_eva`` (``attention.eva_attention``). A row too long
    for its heads' cores at once (`eva_head_groups`) takes rope, summaries and
    core a group of heads at a time (a head's attention reads no other head);
    the four projections run once a layer over all heads on either path."""

    name, tag = "eva", None

    def _layers(self) -> Dict[str, Any]:
        c = self.c
        attn_bias, attn_out_bias = self._biases()
        width = c.num_heads * c.head_dim
        return {"q_proj": _linear(c.hidden_size, width, attn_bias, "column"),
                "k_proj": _linear(c.hidden_size, width, attn_bias, "column"),
                "v_proj": _linear(c.hidden_size, width, attn_bias, "column"),
                "o_proj": _linear(width, c.hidden_size, attn_out_bias, "row"),
                "eva_phi": nn.HeadVectors(c.num_heads, c.head_dim),
                "eva_mu": nn.HeadVectors(c.num_heads, c.head_dim)}

    def check(self) -> None:
        super().check()
        c = self.c
        if (c.eva_chunk < 1 or c.eva_window < c.eva_chunk
                or c.eva_window % c.eva_chunk):
            raise ValueError(f"EVA attention needs a window ({c.eva_window}) "
                             f"of whole chunks ({c.eva_chunk})")
        if (not c.causal or c.num_kv_heads not in (None, c.num_heads)
                or self.host._windows is not None or c.position == "alibi"
                or c.seq_parallel == "ring" or c.diffusion
                or c.document_separator is not None or c.attn_gate
                or c.qk_norm or c.norm_style == "post"
                or (c.linear_bias if c.attn_bias is None else c.attn_bias)
                is not False):
            raise ValueError(
                "EVA attention is written for a causal decoder with as "
                "many key heads as query heads: no sliding window, ALiBi, "
                "ring attention, block diffusion, packed documents (its "
                "windows are the row's), attention gate, QK-norm, post-norm "
                "or bias on its projections (linear_bias=False)")

    def parameters(self) -> int:
        width = self.c.num_heads * self.c.head_dim
        return 4 * self.c.hidden_size * width + 2 * width

    def _launch(self, window: int):
        c = self.c
        return c.num_heads, c.kv_heads, dict(eva=(c.eva_window, c.eva_chunk))

    def record(self, batch=None, seq=None) -> Dict[str, Any]:
        c, plan = self.c, self._plan(batch, seq)
        return {"eva": {
            "window": c.eva_window, "chunk": c.eva_chunk,
            "summaries_a_row": plan and seq // c.eva_chunk, "pred_heads": c.pred_heads,
            "head_groups": plan and eva_head_groups(batch * seq, c.num_heads, c.head_dim),
            "projected": "whole",
            "route": plan and plan.route, "dq_local": plan and plan.dq("eva_local"),
            "dq_far": plan and plan.dq("eva_far"),
            "layout": plan and plan.layout("eva_local")}}

    def __call__(self, block, h, positions, documents=None, kind=None, given=()):
        c = self.c
        B, S, _ = h.shape
        _whole_rows("attention='eva': windows and chunks are counted over the whole row")
        groups = eva_head_groups(B * S, c.num_heads, c.head_dim)
        phi, mu = block["eva_phi"]["value"], block["eva_mu"]["value"]
        with jax.named_scope("attn"):
            with jax.named_scope("qkv"):
                q, k, v = (self._project(block, name, h)
                           for name in ("q_proj", "k_proj", "v_proj"))
            if groups > 1:
                # (its result is the branch's output: named once a layer as
                # below. Kept, it is the MLP's input in the block's recompute,
                # which then drops the group scan whole: a group's forward
                # runs twice a step, not three times)
                return checkpoint_name(self._by_group(groups)(
                    q, k, v, phi, mu, block["o_proj"]["kernel"].astype(h.dtype),
                    positions), "o_proj"), None, None
            out = self._eva_heads(q, k, v, phi, mu, positions, named=True)
            with jax.named_scope("out"):
                return self._project(block, "o_proj", out), None, None

    def _by_group(self, groups: int):
        """``(q, k, v [B, S, heads x head], phi, mu, wo, positions) -> [B, S,
        hidden]``: rope, summaries and cores a group of heads at a time, the
        output projection once over all of them. A group is for the cores'
        memory: what a trip holds is its columns of q, k, v and its ``[B, S,
        group x head]`` of output, and the scan carries nothing. The backward
        is written out because ``wo``'s gradient reads the cores' output: left
        to the product's own rule, the block's recompute would run every
        group's forward again to have it. Here a trip of the backward makes
        its group's forward again (as ``jax.checkpoint`` around a group would),
        takes ``wo``'s rows of the gradient from it, and pulls the output's
        cotangent back to its columns of q, k, v; nothing in a group is named
        (the block's policy would keep a named value of every group, stacked)."""
        c = self.c

        def columns(a):
            return jnp.moveaxis(a.reshape(*a.shape[:2], groups, -1), 2, 0)

        def whole(a):
            return jnp.moveaxis(a, 0, 2).reshape(*a.shape[1:3], -1)

        def operands(q, k, v, phi, mu):
            return (columns(q), columns(k), columns(v),
                    *(a.reshape(groups, -1, c.head_dim) for a in (phi, mu)))

        @jax.custom_vjp
        def branch(q, k, v, phi, mu, wo, positions):
            _, out = jax.lax.scan(
                lambda _, of_group: (None, self._eva_heads(*of_group, positions)),
                None, operands(q, k, v, phi, mu))
            with jax.named_scope("out"):
                return whole(out) @ wo

        def backward(given, d):
            q, k, v, phi, mu, wo, positions = given

            def one_group(_, of_group):
                *of_heads, wo_g = of_group
                out, pull = jax.vjp(
                    lambda *a: self._eva_heads(*a, positions), *of_heads)
                with jax.named_scope("out"):
                    d_wo, d_out = jnp.einsum("bsc,bsh->ch", out, d), d @ wo_g.T
                return None, (*pull(d_out), d_wo)

            _, (dq, dk, dv, dphi, dmu, d_wo) = jax.lax.scan(
                one_group, None,
                (*operands(q, k, v, phi, mu), wo.reshape(groups, -1, wo.shape[-1])))
            return (whole(dq), whole(dk), whole(dv), dphi.reshape(phi.shape),
                    dmu.reshape(mu.shape), d_wo.reshape(wo.shape), None)

        branch.defvjp(lambda *given: (branch(*given), given), backward)
        return branch

    def _eva_heads(self, q, k, v, phi, mu, positions,
                   named: bool = False) -> jax.Array:
        """Rope, summaries and the core over projected q, k, v ``[B, S,
        heads x head]`` of some of the layer's heads (``phi``, ``mu`` theirs)
        -> ``[B, S, heads x head]``. ``named``: the summaries are values the
        backward may keep (``eva_kbar`` / ``eva_vbar``)."""
        from ..ops.transformer.attention import eva_attention, eva_summaries
        c = self.c
        B, S, wide = q.shape
        heads = lambda a: a.reshape(B, S, wide // c.head_dim, c.head_dim)
        q, k, v = heads(q), heads(k), heads(v)
        if c.position == "rope":
            with jax.named_scope("qkv"):
                q, k = self.host._rotate(q, positions), self.host._rotate(k, positions)
        with jax.named_scope("eva_summaries"):
            kbar, vbar = eva_summaries(k, v, phi, mu, c.eva_chunk)
            if named:
                kbar = checkpoint_name(kbar, "eva_kbar")
                vbar = checkpoint_name(vbar, "eva_vbar")
        with jax.named_scope("core_eva"):
            out = eva_attention(q, k, v, kbar, vbar, c.eva_window, c.eva_chunk,
                                scale=c.attn_scale)
        return out.reshape(B, S, wide)


# -- a mixed stack's kinds (``TransformerConfig.mixed``): scan layers, memory
# -- units, differential attention, a cross-decoder --------------------------------

def first_of_document(documents: Optional[jax.Array], shape) -> jax.Array:
    """``[B, S]`` bool: a row's first position, and a packed document's first
    token (where a scan layer's state and its convolution's taps start anew)."""
    B, S = shape
    start = jnp.broadcast_to(jnp.arange(S)[None, :] == 0, (B, S))
    if documents is None:
        return start
    return start | (documents != jnp.pad(documents[:, :-1], ((0, 0), (1, 0))))


def short_conv(w: jax.Array, bias: Optional[jax.Array], a: jax.Array,
               documents: Optional[jax.Array]) -> jax.Array:
    """A scan layer's causal depthwise convolution over ``a`` ``[B, S, Di]``,
    float32: ``sum_s w[taps - 1 - s] a[t - s] (+ bias)`` over the taps s whose
    token ``t - s`` lies in the row and in t's document (``w`` ``[taps, Di]``)."""
    B, S, _ = a.shape
    taps = w.shape[0]
    w, a32 = w.astype(jnp.float32), a.astype(jnp.float32)
    at = jnp.arange(S)[None, :]
    out = a32 * w[taps - 1]
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    for s in range(1, min(taps, S)):
        seen = at >= s
        if documents is not None:
            seen = seen & (documents == jnp.pad(documents[:, :S - s], ((0, 0), (s, 0))))
        back = jnp.pad(a32[:, :S - s], ((0, 0), (s, 0), (0, 0)))
        out = out + jnp.where(seen[..., None], back, 0.0) * w[taps - 1 - s]
    return out


class _Mixed(Mixer):
    """What a mixed stack's mixers refuse together."""

    def check(self) -> None:
        super().check()
        c = self.c
        listed = c.layer_mixers is not None
        stack = ("a stack named by layer_mixers" if listed else
                 "a stack by the ssm_period rule (ssm_state, differential_attention, "
                 "shared_from)")
        # what THIS stack cannot have, the first that holds: one cause a message
        causes = {
            "causal=False": (not c.causal, "its layers are a causal decoder's"),
            f"norm_style={c.norm_style!r}": (
                c.norm_style != "pre", "its blocks are sequential pre-norm blocks"),
            "parallel_block": (c.parallel_block, "its blocks are sequential pre-norm blocks"),
            "farskip": (c.farskip, "one residual stream is carried through the runs"),
            "residual_streams": (c.residual_streams > 1,
                                 "one residual stream is carried through the runs"),
            "indexer": (c.indexer is not None, "no layer kind of it selects its keys"),
            "objective='block_diffusion'": (
                c.diffusion, "a scan layer's state runs along one copy of the row"),
            "mtp_layers": (bool(c.mtp_layers), "a prediction module's block is a plain stack's"),
            "qk_norm": (c.qk_norm, "its attention layers take q and k as projected"),
            "attn_gate": (c.attn_gate, "its attention layers have no gate"),
            "seq_parallel='ring'": (c.seq_parallel == "ring",
                                    "a scan layer reads the whole row"),
            "position='alibi'": (c.position == "alibi", "its layers take rope or no position"),
            f"activation={c.activation!r}": (c.activation != "silu_gated",
                                             "its MLPs and experts are gated SiLU"),
            # (a list may carry experts behind leading dense layers, and latent heads)
            "moe": (c.moe is not None and not listed,
                    "experts run in a stack named by layer_mixers alone"),
            f"attention={c.attention!r}": (
                c.attention != "mha" and not (listed and c.attention == "latent"),
                "its attention layers are 'mha' (a list may name 'latent' ones)"),
            "moe.router_input='block_input'": (
                c.moe is not None and c.moe.router_input == "block_input",
                "a run's experts are routed from the normed stream"),
        }
        for name, (held, why) in causes.items():
            if held:
                raise ValueError(f"{name} is not computed in {stack}: {why}")
        at = c.shared_from
        if at is not None and not (
                0 <= at < c.num_layers - 2 and c.ssm_state
                and c.mixer_of(at) == ("ssm", "memory")
                and c.mixer_of(at + 1) == ("attn", "kv")
                and not self.host._kinds[at + 1][0]):
            raise ValueError(
                f"shared_from {at}: a scan layer (l % ssm_period == 0) followed by a "
                "full attention layer, with layers after both")


class Ssm(_Mixed):
    """A selective-scan layer (Mamba-1, arXiv:2312.00752;
    ``TransformerConfig.ssm_state`` has the equations) -> (the branch's output,
    the scan's output m ``[B, S, Di]`` before the gate where the layer hands it
    on: the memory a cross-decoder's units read, None). Scopes ``ssm/in`` (the
    in projection, the convolution), ``ssm/scan`` (``x_proj``, ``dt_proj``, the
    scan) and ``ssm/out`` (the gate and the out projection)."""

    name, has_step_stats = "ssm", True

    def _layers(self) -> Dict[str, Any]:
        c, di = self.c, self.c.ssm_inner
        return {"in_proj": _linear(c.hidden_size, 2 * di, False, "column"),
                "ssm": nn.ScanParams(di, c.ssm_state, c.ssm_conv),
                "x_proj": _linear(di, c.ssm_rank + 2 * c.ssm_state, False, None),
                "dt_proj": _linear(c.ssm_rank, di, False, "column"),
                "out_proj": _linear(di, c.hidden_size, False, "row")}

    def check(self) -> None:
        super().check()
        if min(self.c.ssm_conv, self.c.ssm_expand, self.c.ssm_period) < 1:
            raise ValueError("ssm_state needs ssm_conv, ssm_expand and ssm_period >= 1")

    def parameters(self) -> int:
        c, h = self.c, self.c.hidden_size
        di, n, r = c.ssm_inner, c.ssm_state, c.ssm_rank
        return (h * 2 * di + (c.ssm_conv + 1) * di + di * (r + 2 * n)
                + r * di + di + di * n + di + di * h)

    def handed_shape(self, B: int, S: int):
        """What a boundary layer hands on, as shapes: the scan's output."""
        return jax.ShapeDtypeStruct((B, S, self.c.ssm_inner), self.shared_dtype("memory"))

    def record(self, batch=None, seq=None) -> Dict[str, Any]:
        # (the scan layers' record rides in ``attn_totals``, which an engine
        # copies whole)
        from ..ops.transformer import pallas_scan
        c = self.c
        route = seq and pallas_scan.choose_route(
            batch * seq, c.ssm_inner, c.ssm_state, jax.default_backend(), devices())
        kernel = route == "kernel"
        return {"ssm": {
            "kind": "selective", "heads": None, "head_dim": None, "groups": None,
            "layers": self.count("ssm"), "memory_units": self.count("gmu"),
            "d_inner": c.ssm_inner, "d_state": c.ssm_state, "conv": c.ssm_conv,
            "dt_rank": c.ssm_rank, "route": route,
            "chunk": route and (pallas_scan.CHUNK if kernel else pallas_scan.XLA_CHUNK),
            "tile": pallas_scan.choose_tile(c.ssm_inner, pallas_scan.CHUNK, c.ssm_state)
            if kernel else None}}

    def step_stats(self, documents, shape) -> Dict[str, jax.Array]:
        # (``engine.attn_last_step()["ssm_resets"]``: the times a scan layer's
        # state started anew in the step's rows, a row's start or a document's)
        return {"attn_ssm_resets": jnp.sum(
            first_of_document(documents, shape), dtype=jnp.int32)}

    def __call__(self, block, h, positions, documents, kind, given=()):
        from ..ops.transformer import pallas_scan
        c, layers = self.c, self.layers()
        B, S, _ = h.shape
        Di, N, R = c.ssm_inner, c.ssm_state, c.ssm_rank
        ssm = block["ssm"]
        project = lambda name, x, keep: checkpoint_name(
            layers[name](block[name], x), keep)
        with jax.named_scope("ssm"):
            with jax.named_scope("in"):
                az = project("in_proj", h, "ssm_in")
                a = nn.silu(short_conv(ssm["conv"], ssm["conv_bias"], az[..., :Di], documents)).astype(h.dtype)
            with jax.named_scope("scan"):
                rbc = project("x_proj", a, "ssm_x")
                dt_raw = project("dt_proj", rbc[..., :R], "ssm_dt")
                flat = lambda t: t.reshape((B * S,) + t.shape[2:])
                m = pallas_scan.selective_scan(
                    flat(a), flat(dt_raw), -jnp.exp(ssm["A_log"].astype(jnp.float32)),
                    flat(rbc[..., R:R + N]), flat(rbc[..., R + N:]), ssm["D"],
                    ssm["dt_bias"], flat(first_of_document(documents, (B, S))),
                    devices=devices()).reshape(B, S, Di)
            with jax.named_scope("out"):
                y = layers["out_proj"](block["out_proj"], m * nn.silu(az[..., Di:]))
        return y, self._hand(m, kind[3]), None


class Ssd(Ssm):
    """A Mamba-2 layer (arXiv:2405.21060; ``TransformerConfig.ssm_heads`` has the
    equations) -> (the branch's output, None, None): heads of ``ssm_head_dim``
    channels with one scalar decay each, B and C shared by a group's heads, the
    convolution over a, B and C together, the gate BEFORE a norm over a group's
    channels. Scopes ``ssm/in`` (the in projection and the convolution),
    ``ssm/ssd`` (dt, the decays and the chunked core, ``pallas_ssd.ssd``) and
    ``ssm/out`` (the gated norm and the out projection)."""

    name = "ssd"

    def _layers(self) -> Dict[str, Any]:
        c, di = self.c, self.c.ssm_inner
        shared = 2 * c.ssm_groups * c.ssm_state
        return {"in_proj": _linear(c.hidden_size, 2 * di + shared + c.ssm_heads, False,
                                   "column"),
                "ssm": nn.ScanParams(di + shared, c.ssm_state, c.ssm_conv, heads=c.ssm_heads),
                "ssd_norm": nn.RMSNorm(di, eps=c.norm_eps),
                "out_proj": _linear(di, c.hidden_size, False, "row")}

    def check(self) -> None:
        super().check()
        c = self.c
        if c.ssm_heads < 1 or c.ssm_groups < 1 or c.ssm_heads % c.ssm_groups or not c.ssm_state:
            raise ValueError(
                f"ssm_heads {c.ssm_heads}: Mamba-2's scan layers need ssm_state and "
                f"ssm_groups ({c.ssm_groups}) that divide the heads")
        _whole_rows("a state-space layer carries its state along the whole row")

    def parameters(self) -> int:
        c, h, di = self.c, self.c.hidden_size, self.c.ssm_inner
        conv = di + 2 * c.ssm_groups * c.ssm_state
        return (h * (di + conv + c.ssm_heads) + (c.ssm_conv + 1) * conv
                + 3 * c.ssm_heads + di + di * h)

    def record(self, batch=None, seq=None) -> Dict[str, Any]:
        from ..ops.transformer import pallas_ssd
        c = self.c
        route = seq and pallas_ssd.choose_route(
            batch * seq, c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups,
            jax.default_backend(), devices())
        kernel = route == "kernel"
        return {"ssm": {
            "kind": "ssd", "heads": c.ssm_heads, "head_dim": c.ssm_head_dim,
            "groups": c.ssm_groups, "layers": self.count("ssd"), "memory_units": 0,
            "d_inner": c.ssm_inner, "d_state": c.ssm_state, "conv": c.ssm_conv,
            "dt_rank": None, "route": route,
            "chunk": route and (pallas_ssd.CHUNK if kernel
                                else pallas_ssd.xla_chunk(c.ssm_chunk)),
            "tile": pallas_ssd.choose_tile(c.ssm_heads, c.ssm_head_dim, c.ssm_groups)
            if kernel else None}}

    def __call__(self, block, h, positions, documents, kind, given=()):
        from ..ops.transformer import pallas_ssd
        c, layers = self.c, self.layers()
        B, S, _ = h.shape
        Di, N, G, H = c.ssm_inner, c.ssm_state, c.ssm_groups, c.ssm_heads
        ssm, f32 = block["ssm"], jnp.float32
        flat = lambda t: t.reshape((B * S,) + t.shape[2:])
        with jax.named_scope("ssm"):
            with jax.named_scope("in"):
                # ONE matrix, three products over its columns: the gate is read
                # after the core, and a whole ``[rows, 2 Di + 2 G N + H]`` result
                # held for it made XLA run the projection three times in a
                # rematerialised backward (the chip's first trace, PR 65)
                kernel = block["in_proj"]["kernel"]
                cut = lambda lo, hi: h @ kernel[:, lo:hi].astype(h.dtype)
                z = checkpoint_name(cut(0, Di), "ssm_z")
                xbc = checkpoint_name(cut(Di, 2 * Di + 2 * G * N), "ssm_in")
                dt_raw = cut(2 * Di + 2 * G * N, None)
                xbc = nn.silu(short_conv(ssm["conv"], ssm["conv_bias"], xbc, documents)).astype(h.dtype)
            with jax.named_scope("ssd"):
                dt = jax.nn.softplus(dt_raw.astype(f32) + ssm["dt_bias"].astype(f32))
                m = pallas_ssd.ssd(
                    flat(xbc[..., :Di]), flat(dt), -jnp.exp(ssm["A_log"].astype(f32)),
                    flat(xbc[..., Di:Di + G * N]), flat(xbc[..., Di + G * N:]), ssm["D"],
                    flat(first_of_document(documents, (B, S))), G,
                    devices=devices(), published_chunk=c.ssm_chunk).reshape(B, S, Di)
            with jax.named_scope("out"):
                # the gate goes in before the norm, a group's channels a statistic
                gated = (m.astype(f32) * nn.silu(z.astype(f32))).reshape(B, S, G, Di // G)
                normed = gated * jax.lax.rsqrt(
                    jnp.mean(gated * gated, axis=-1, keepdims=True) + c.norm_eps)
                normed = normed.reshape(B, S, Di) * block["ssd_norm"]["scale"].astype(f32)
                y = layers["out_proj"](block["out_proj"], normed.astype(h.dtype))
        return y, None, None


class Kda(_Mixed):
    """A Kimi Delta Attention layer (Kimi Linear, arXiv:2510.26692;
    ``TransformerConfig.kda_heads`` has the equations) -> (the branch's output,
    None, None): ``kda_heads`` heads whose state is ``[kda_head_dim, kda_head_dim]``
    under a decay a CHANNEL and a delta-rule write. Scopes, all under ``attn``:
    ``kda_in`` (the three projections, their convolutions and SiLU; the
    normalisation of q and k a head is the core's), ``kda_gate`` (both low-rank gates, the softplus,
    ``beta``), ``core_kda`` (the chunked recurrence, ``pallas_kda.kda``) and
    ``kda_out`` (the gated norm a head and the out projection)."""

    name = "kda"

    def _layers(self) -> Dict[str, Any]:
        c, wide, rank = self.c, self.c.kda_inner, self.c.kda_head_dim
        low = lambda out: _linear(rank, out, False, "column")
        return {**{name: _linear(c.hidden_size, wide, False, "column")
                   for name in ("q_proj", "k_proj", "v_proj")},
                "kda": nn.DeltaParams(c.kda_heads, wide, c.kda_conv),
                "kda_fa": _linear(c.hidden_size, rank, False, None), "kda_fb": low(wide),
                "kda_ga": _linear(c.hidden_size, rank, False, None), "kda_gb": low(wide),
                "kda_beta": _linear(c.hidden_size, c.kda_heads, False, None),
                "kda_norm": nn.RMSNorm(c.kda_head_dim, eps=c.norm_eps),
                "o_proj": _linear(wide, c.hidden_size, False, "row")}

    def check(self) -> None:
        super().check()
        c = self.c
        if min(c.kda_heads, c.kda_head_dim, c.kda_conv) < 1:
            raise ValueError(
                f"a 'kda' layer needs kda_heads ({c.kda_heads}), kda_head_dim "
                f"({c.kda_head_dim}) and kda_conv ({c.kda_conv}) >= 1")
        _whole_rows("a delta-rule layer carries its state along the whole row")

    def parameters(self) -> int:
        c, h, wide, rank = self.c, self.c.hidden_size, self.c.kda_inner, self.c.kda_head_dim
        return (4 * h * wide + 2 * (h * rank + rank * wide) + h * c.kda_heads
                + 3 * c.kda_conv * wide + c.kda_heads + wide + c.kda_head_dim)

    def record(self, batch=None, seq=None) -> Dict[str, Any]:
        from ..ops.transformer import pallas_kda
        c = self.c
        route = seq and pallas_kda.choose_route(
            batch * seq, c.kda_heads, c.kda_head_dim, c.kda_head_dim,
            jax.default_backend(), devices())
        kernel = route == "kernel"
        return {"kda": {
            "heads": c.kda_heads, "key_dim": c.kda_head_dim, "value_dim": c.kda_head_dim,
            "conv": c.kda_conv, "gate_rank": c.kda_head_dim,
            "layers": [l for l in range(c.num_layers) if c.mixer_of(l)[0] == "kda"],
            "route": route,
            "chunk": route and (pallas_kda.CHUNK if kernel else pallas_kda.xla_chunk(seq)[0]),
            # (a grid step: one head over a span of rows)
            "tile": [1, pallas_kda.SPAN] if kernel else None}}

    def _front(self, block: Params, h: jax.Array, documents: Optional[jax.Array],
               named: bool):
        """What the core reads, from the normed input ``h`` ``[B, S, C]`` -> (q, k, v
        ``[B, S, H x D]`` in the stream's dtype, g the same float32, beta ``[B, S, H]``
        float32, the output gate ``[B, S, H x D]``). ``named``: the products are
        values the backward may keep."""
        c, layers, small, f32 = self.c, self.layers(), block["kda"], jnp.float32
        B, S, _ = h.shape
        keep = checkpoint_name if named else (lambda a, name: a)
        low = lambda a, b: layers[b](block[b], layers[a](block[a], h))
        with jax.named_scope("kda_in"):
            # (q and k are normalised a head, and q scaled, where the core reads them)
            q, k, v = (nn.silu(short_conv(
                small["conv_" + name[0]], None,
                keep(layers[name](block[name], h), name), documents)).astype(h.dtype)
                for name in ("q_proj", "k_proj", "v_proj"))
        with jax.named_scope("kda_gate"):
            # a decay a channel (not positive), a write strength a head, float32
            decay = keep(low("kda_fa", "kda_fb"), "kda_decay").astype(f32)
            g = (-jnp.exp(small["A_log"].astype(f32))[:, None] * jax.nn.softplus(
                decay + small["dt_bias"].astype(f32)).reshape(B, S, c.kda_heads, -1)
                ).reshape(B, S, -1)
            beta = jax.nn.sigmoid(layers["kda_beta"](block["kda_beta"], h).astype(f32))
            gate = keep(low("kda_ga", "kda_gb"), "kda_gate")
        return q, k, v, g, beta, gate

    def _back(self, block: Params, o: jax.Array, gate: jax.Array, named: bool) -> jax.Array:
        """RMSNorm over a head's values (one learned gain of a head's width), times
        the sigmoid of the output gate, through the out projection."""
        c, f32 = self.c, jnp.float32
        B, S, _ = o.shape
        with jax.named_scope("kda_out"):
            o32 = o.astype(f32).reshape(B, S, c.kda_heads, c.kda_head_dim)
            normed = o32 * jax.lax.rsqrt(
                jnp.mean(o32 * o32, axis=-1, keepdims=True) + c.norm_eps)
            normed = normed * block["kda_norm"]["scale"].astype(f32)
            gated = (normed * jax.nn.sigmoid(gate.astype(f32).reshape(o32.shape))).reshape(
                B, S, -1).astype(o.dtype)
            if named:
                return self._project(block, "o_proj", gated)
            return self.layers()["o_proj"](block["o_proj"], gated)

    def _by_slices(self, block: Params, h: jax.Array, documents, slices: int):
        """`_front` and `_back` for a long row, a slice of the row at a time (memory
        only; a slice reads the taps' rows before it, inside its document): each slice
        is made again in its own backward and nothing in it is named (the block's
        policy would keep a named value of every slice, stacked) -> (`_front`'s six
        over the whole row, ``(o, gate) -> the branch's output``)."""
        B, S, _ = h.shape
        n, halo = S // slices, self.c.kda_conv - 1
        docs = jnp.zeros((B, S), jnp.int32) if documents is None else documents
        wide_h = jnp.pad(h, ((0, 0), (halo, 0), (0, 0)))
        wide_d = jnp.pad(docs, ((0, 0), (halo, 0)), constant_values=-1)
        window = lambda a, i: jax.lax.dynamic_slice_in_dim(a, i * n, n + halo, axis=1)
        whole = lambda a: jnp.moveaxis(a, 0, 1).reshape((B, S) + a.shape[3:])
        by_slice = lambda a: jnp.moveaxis(a.reshape((B, slices, n) + a.shape[2:]), 1, 0)

        # (a scope opened inside a ``lax.map`` body stands behind ``while/body`` in an
        # operation's name: ``attn`` is opened again inside, so that a reader finds
        # ``attn/kda_in`` side by side)
        def front(i):
            with jax.named_scope("attn"):
                made = self._front(block, window(wide_h, i), window(wide_d, i), named=False)
            return tuple(a[:, halo:] for a in made)

        def back(xs):
            with jax.named_scope("attn"):
                return self._back(block, *xs, named=False)

        def finish(o, gate):
            y = jax.lax.map(jax.checkpoint(back), (by_slice(o), by_slice(gate)))
            # (the branch's output outlives the slices: named once a layer)
            return checkpoint_name(whole(y), "o_proj")
        made = jax.lax.map(jax.checkpoint(front), jnp.arange(slices))
        return tuple(map(whole, made)), finish

    def __call__(self, block, h, positions, documents, kind, given=()):
        from ..ops.transformer import pallas_kda
        c = self.c
        B, S, _ = h.shape
        flat = lambda t: t.reshape((B * S,) + t.shape[2:])
        slices = kda_row_slices(S, B * c.kda_inner)
        with jax.named_scope("attn"):
            if slices == 1:
                made = self._front(block, h, documents, named=True)
                finish = lambda o, gate: self._back(block, o, gate, named=True)
            else:
                made, finish = self._by_slices(block, h, documents, slices)
            q, k, v, g, beta, gate = made
            with jax.named_scope("core_kda"):
                o = pallas_kda.kda(
                    flat(q), flat(k), flat(v), flat(g), flat(beta),
                    flat(first_of_document(documents, (B, S))), devices=devices(),
                    row=S).reshape(B, S, -1)
            return finish(o, gate), None, None


class MemoryUnit(_Mixed):
    """A gated memory unit (arXiv:2507.06607, section 2): ``(silu(h W_1) * m)
    W_2``, ``m`` an earlier layer's scan output at the same token. Scope
    ``gmu``."""

    name, reads = "gmu", "memory"

    def _layers(self) -> Dict[str, Any]:
        c, di = self.c, self.c.ssm_inner
        return {"gmu_in": _linear(c.hidden_size, di, False, "column"),
                "gmu_out": _linear(di, c.hidden_size, False, "row")}

    def parameters(self) -> int:
        return 2 * self.c.hidden_size * self.c.ssm_inner

    def __call__(self, block, h, positions, documents, kind, given=()):
        (_, memory), layers = given, self.layers()
        with jax.named_scope("gmu"):
            gate = nn.silu(checkpoint_name(
                layers["gmu_in"](block["gmu_in"], h), "gmu_in"))
            return layers["gmu_out"](block["gmu_out"], gate * memory), None, None


class MixedAttention(_Mixed, Attention):
    """A mixed stack's attention: a fused key and value projection, the layer's
    ``(window, rope)`` kind, and with ``differential_attention`` the heads
    paired by parity -> (the branch's output, (k, v) as attended ``[B, S, kv
    heads, head]`` where the layer hands them on, None). ``given``:
    differential attention's constant at this layer and, for a cross layer, an
    earlier layer's keys and values (it projects queries alone)."""

    name, mha_heads = "attn", True
    _made = ("kv_proj",)    # the projections of its own keys and values

    def __init__(self, config, host=None):
        super().__init__(config, host)
        self.tag = "diff" if config.differential_attention else "flash"

    def _layers(self) -> Dict[str, Any]:
        c = self.c
        attn_bias, attn_out_bias = self._biases()
        q_out, kv_out = c.num_heads * c.head_dim, c.kv_heads * c.head_dim
        diff = ({"diff_lambda": nn.HeadVectors(4, c.head_dim, init_scale=0.1),
                 "diff_norm": nn.RMSNorm(2 * c.head_dim, eps=c.norm_eps)}
                if c.differential_attention else {})
        return {"q_proj": _linear(c.hidden_size, q_out, attn_bias, "column"),
                **{name: _linear(c.hidden_size, 2 * kv_out, attn_bias, "column")
                   for name in self._made},
                "o_proj": _linear(q_out, c.hidden_size, attn_out_bias, "row"), **diff}

    def check(self) -> None:
        super().check()
        c = self.c
        if c.differential_attention and (c.num_heads % 2 or c.kv_heads % 2
                                         or (c.num_heads // 2) % (c.kv_heads // 2)):
            raise ValueError("differential_attention pairs the heads by parity: an "
                             "even number of query heads over an even number of key heads")

    def parameters(self) -> int:
        """(LayerNorm's models' projections' biases counted.)"""
        c, h, bias = self.c, self.c.hidden_size, self.c.norm == "layernorm"
        q_out, kv = c.num_heads * c.head_dim, len(self._made) * c.kv_heads * c.head_dim
        return (h * q_out + 2 * h * kv + q_out * h + bias * (q_out + 2 * kv + h)
                + (6 * c.head_dim if c.differential_attention else 0))

    def handed_shape(self, B: int, S: int):
        """What a boundary layer hands on, as shapes: its keys and values."""
        c = self.c
        kv = jax.ShapeDtypeStruct((B, S, c.kv_heads, c.head_dim), self.shared_dtype("kv"))
        return kv, kv

    def _launch(self, window: int):
        heads, kv_heads, mask = super()._launch(window)
        if self.c.differential_attention:
            # ONE half's launch: half the heads, the pair's two value heads wide
            heads, kv_heads = heads // 2, kv_heads // 2
            mask.update(v_dim=2 * self.c.head_dim, tag="diff")
        return heads, kv_heads, mask

    def record(self, batch=None, seq=None) -> Dict[str, Any]:
        c = self.c
        if not c.differential_attention:
            return {}
        return {"diff": {"qk_dim": c.head_dim, "v_dim": 2 * c.head_dim,
                         "launches_a_layer": 2, "shared_readers": self.count("cross")}}

    def __call__(self, block, h, positions, documents, kind, given=()):
        c = self.c
        window, rope, _, hands = kind
        lam_init, *kv = given
        B, S, _ = h.shape
        nh, kvh, hd = c.num_heads, c.kv_heads, c.head_dim
        if isinstance(window, int) and window <= 0:
            window = None
        with jax.named_scope("attn"):
            with jax.named_scope("qkv"):
                q = self._project(block, "q_proj", h).reshape(B, S, nh, hd)
                if rope:
                    q = self.host._rotate(q, positions)
                if not kv:
                    made = self._project(block, "kv_proj", h)
                    k = made[..., :kvh * hd].reshape(B, S, kvh, hd)
                    v = made[..., kvh * hd:].reshape(B, S, kvh, hd)
                    if rope:
                        k = self.host._rotate(k, positions)
                else:
                    k, v = kv[0]
            if c.differential_attention:
                with jax.named_scope("core_diff"):
                    out = self._diff_core(block, q, k, v, documents, window, lam_init)
            else:
                with jax.named_scope("core_window" if window else "core"):
                    out = self._attn_core(q, k, v, documents, window)
            with jax.named_scope("out"):
                out = self._project(block, "o_proj", out.reshape(B, S, nh * hd))
        return out, self._hand((k, v), hands), None

    def _diff_core(self, block: Params, q, k, v, documents, window, lam_init) -> jax.Array:
        """Differential attention's core (``TransformerConfig.
        differential_attention``): the heads paired by parity; each half ONE
        softmax of its queries over its keys, multiplied into the pair's two
        value heads side by side (twice the keys' width: the two-width launch
        tagged ``"diff"``, two launches a layer); the halves subtracted under
        lambda and normed a pair in float32."""
        f32 = jnp.float32
        values = jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], axis=-1)
        first, second = (
            self._attn_core(q[:, :, i::2], k[:, :, i::2], values, documents, window,
                            tag="diff").astype(f32) for i in (0, 1))
        lam = block["diff_lambda"]["value"].astype(f32)
        lam = (jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3]))
               + lam_init)
        out = self.layers()["diff_norm"](block["diff_norm"], first - lam * second)
        return (out * (1.0 - lam_init)).astype(q.dtype)


class Cross(MixedAttention):
    """A cross-decoder's attention layer (YOCO, arXiv:2405.05254): queries of
    its own over an earlier layer's keys and values."""

    name, reads, _made = "cross", "kv", ()


#: a mixer's class by ``TransformerConfig.mixer_of``'s name for it
KINDS = {cls.name: cls for cls in (Mha, Selected, Latent, Eva, Ssm, Ssd, Kda, MixedAttention,
                                   MemoryUnit, Cross)}


def build(config, host=None) -> Dict[str, Mixer]:
    """One mixer a kind the configuration's layers have
    (``TransformerConfig.mixer_of``), by its name, in the order the layers
    first have them."""
    names = dict.fromkeys(config.mixer_of(l)[0] for l in range(config.num_layers))
    unknown = [name for name in names if name not in KINDS]
    if unknown:
        raise ValueError(f"attention {unknown[0]!r} is not 'mha', 'latent' or 'eva'")
    return {name: KINDS[name](config, host) for name in names}
