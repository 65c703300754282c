"""Ragged inference model over a blocked KV cache.

Counterpart of the reference per-arch inference models
(``inference/v2/model_implementations/llama_v2/model.py:217`` — forward =
``_forward_embed`` → per-layer attention/MLP over ragged batch →
``_forward_unembed``). One implementation covers the whole decoder family by
reusing :class:`~deepspeed_tpu.models.transformer.TransformerLM`'s config and
parameter layout (GPT-2 / Llama / Mistral / Mixtral / OPT / Phi / Falcon
presets).

Two static-shape programs replace the reference's ragged CUDA path
(Dynamic SplitFuse is preserved at the scheduler level, see
``scheduler.py``):

- ``prefill_chunk``: T tokens of ONE sequence (bucketed T), writes their KV
  into the sequence's pages, causal attention over gathered history+chunk,
  returns the last valid token's logits.
- ``decode``: B sequences × 1 token (bucketed B), writes KV, paged
  attention via the Pallas TPU kernel, returns logits for all B.

The KV cache flows through functionally ([L, kvH, P, ps, D], carried through
the layer loop with dynamic_update_slice; donated at the jit boundary so XLA
updates it in place).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ...models.transformer import ACTIVATIONS, TransformerLM
from ...nn import layers as nn
from ...utils.scope import scoped
from .kernels.paged_attention import (chunk_prefill_attention, paged_decode_attention,
                                      ragged_chunk_attention)

Params = Dict[str, Any]


class RaggedInferenceModel:

    #: what `_layer_loop` (a plain pre-norm block of its own over paged keys
    #: and values, one token a sequence a step) and `_embed` compute before
    #: ``TransformerLM.head``; the experts run without drops (``_moe_serve``)
    RUNS = frozenset({"attn_windows", "moe"})

    def __init__(self, model: TransformerLM, block_size: int, max_blocks_per_seq: int,
                 use_pallas: bool = None, ragged_block_q: int = 8,
                 replicate_kv_writes: bool = False):
        self.model = model
        self.config = model.config
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.use_pallas = use_pallas
        # MQA under tp>1 (kv_heads % tp != 0): the KV projection's head dim
        # cannot shard, and GSPMD's partitioning of the rope'd K scatter
        # over the mesh's DATA axis mis-sums replicated updates (each data
        # rank contributes the full update set — written K comes out
        # scaled by the data-axis size). Pinning the pre-scatter operand
        # replicated keeps the partitioner on the single-scatter path.
        # Engine-set; never used on the shard_map (data-sharded pool)
        # dispatch, which requires tp == 1.
        self.replicate_kv_writes = replicate_kv_writes
        # atom tile of the unified wave program (wave_forward)
        self.ragged_block_q = ragged_block_q
        c = self.config
        model.require("the ragged serving engine (RaggedInferenceModel)", self.RUNS)
        # per-layer sliding windows (mistral / gpt-neo): a [L] vector read
        # inside the layer loop; forces the XLA paged path (the stock Pallas
        # kernel takes no window mask)
        if model._windows is not None:
            self._windows_arr = jnp.asarray(model._windows, jnp.int32)
            self.use_pallas = False
        else:
            self._windows_arr = None
        # gpt-neo's unscaled attention: thread the config's scale override
        # into every paged program (None → the kernels' 1/sqrt(D) default)
        self._scale = c.attn_scale
        # bloom: per-head ALiBi bias threaded into every paged-attention
        # program (forces the XLA path; the stock Pallas kernel has no bias)
        self._alibi = (jnp.asarray(model._alibi_slopes)
                       if model._alibi_slopes is not None else None)
        # MoE serving routes DROPLESS: capacity_factor = num_experts makes
        # capacity == token count, so no token is ever dropped — the
        # training path's capacity cropping is a throughput/regularization
        # trade that would make generation depend on how requests are
        # batched (and diverge from HF/reference inference semantics; the
        # reference's inference top_k_gating is dropless,
        # ragged_ops.cpp:20-47)
        if c.moe is not None:
            import dataclasses as _dc
            self._moe_serve = _dc.replace(
                model._moe, capacity_factor=float(c.moe.num_experts),
                min_capacity=1)
        else:
            self._moe_serve = None

    # -- shared pieces ------------------------------------------------------
    @scoped("embed")
    def _embed(self, params: Params, tokens: jax.Array, positions: jax.Array) -> jax.Array:
        """tokens [N] -> [N, hidden] (reference ``_forward_embed``, ragged_embed)."""
        m = self.model
        x = m._wte(params["wte"], tokens)
        if m._wpe is not None:
            pos = jnp.clip(positions, 0, self.config.max_seq_len - 1)
            x = x + m._wpe(params["wpe"], pos + self.config.position_offset)
        if m._ln_emb is not None:
            x = m._ln_emb(params["ln_emb"], x)
        return x.astype(self.config.dtype)

    def _qkv(self, block: Params, h: jax.Array, positions: jax.Array):
        """PRE-NORMED h [N, hidden] -> q [N, H, D], k/v [N, kvH, D] with rope
        (possibly partial, phi) applied."""
        c, m = self.config, self.model
        N = h.shape[0]
        q = m._block_layers["q_proj"](block["q_proj"], h).reshape(N, c.num_heads, c.head_dim)
        k = m._block_layers["k_proj"](block["k_proj"], h).reshape(N, c.kv_heads, c.head_dim)
        v = m._block_layers["v_proj"](block["v_proj"], h).reshape(N, c.kv_heads, c.head_dim)
        if c.position == "rope":
            q = m._rotate(q, positions)
            k = m._rotate(k, positions)
        return q, k, v

    @scoped("mlp")
    def _mlp(self, block: Params, h: jax.Array) -> jax.Array:
        """MLP over the PRE-NORMED input h."""
        c, m = self.config, self.model
        if c.moe is not None:
            out, _ = self._moe_serve(block["moe"], h[None, :, :])
            return out[0]
        if c.activation == "silu_gated":
            gate = nn.silu(m._block_layers["gate_proj"](block["gate_proj"], h))
            up = m._block_layers["up_proj"](block["up_proj"], h)
            return m._block_layers["down_proj"](block["down_proj"], gate * up)
        h2 = ACTIVATIONS[c.activation](m._block_layers["fc_in"](block["fc_in"], h))
        return m._block_layers["fc_out"](block["fc_out"], h2)

    def _write_kv(self, pages: jax.Array, new: jax.Array, flat_idx: jax.Array) -> jax.Array:
        """pages [kvH, P, ps, D]; new [N, kvH, D]; flat_idx [N] into P*ps.

        The reference's ``linear_kv_copy``/``kv_rotary_embeddings`` kernel
        (ragged_ops.cpp:20-47) — here a scatter XLA turns into an in-place
        dynamic update on the donated cache.
        """
        if self.replicate_kv_writes:
            from jax.sharding import PartitionSpec
            new = jax.lax.with_sharding_constraint(new, PartitionSpec())
        kvH, P, ps, D = pages.shape
        flat = pages.reshape(kvH, P * ps, D)
        flat = flat.at[:, flat_idx, :].set(new.astype(pages.dtype).transpose(1, 0, 2))
        return flat.reshape(kvH, P, ps, D)

    def _layer_loop(self, params: Params, k_pages, v_pages, x, attn_fn, write_idx,
                    positions):
        """Run all layers with the stacked cache carried functionally."""
        L = self.config.num_layers
        blocks = params["blocks"]

        c, m = self.config, self.model

        def body(l, carry):
            x, k_pages, v_pages = carry
            block = jax.tree.map(lambda a: a[l], blocks)
            h1 = m._block_layers["ln_1"](block["ln_1"], x)
            with jax.named_scope("attn/qkv"):
                q, k, v = self._qkv(block, h1, positions)
            with jax.named_scope("kv_write"):
                k_l = self._write_kv(k_pages[l], k, write_idx)
                v_l = self._write_kv(v_pages[l], v, write_idx)
                k_pages = k_pages.at[l].set(k_l)
                v_pages = v_pages.at[l].set(v_l)
            win = (self._windows_arr[l] if self._windows_arr is not None
                   else None)
            # narrow KV store (fp8 cache): the attention kernels upcast
            # AFTER their per-sequence block gathers (paged_attention.py
            # _gather_pages), so the full pool is never widened
            with jax.named_scope("attn/core"):
                attn_out = attn_fn(q, k_l, v_l, win)
            with jax.named_scope("attn/out"):
                o = m._block_layers["o_proj"](
                    block["o_proj"], attn_out.reshape(x.shape[0], -1))
            if c.parallel_block:
                # falcon/phi: MLP reads the block INPUT through a shared
                # (phi/falcon-7b) or per-branch (falcon-40b) norm
                hm = (m._block_layers["ln_2"](block["ln_2"], x)
                      if c.parallel_norms else h1)
                x = x + o + self._mlp(block, hm)
            else:
                x = x + o
                h2 = m._block_layers["ln_2"](block["ln_2"], x)
                x = x + self._mlp(block, h2)
            return (x, k_pages, v_pages)

        x, k_pages, v_pages = jax.lax.fori_loop(0, L, body, (x, k_pages, v_pages))
        return x, k_pages, v_pages

    # -- programs -----------------------------------------------------------
    def wave_forward(self, params: Params, k_pages, v_pages,
                     tokens, positions, write_idx,
                     cu_q_lens, kv_lens, page_tables, last_rows):
        """THE unified ragged-wave program (ISSUE 6 tentpole): ONE atom
        class instead of ``ragged_forward``'s two. The host atom builder
        (``ragged/wave.py``) flattens any wave composition — decode
        tokens, prefill chunks, any mix — into a flat token stream
        ``tokens [N]`` plus per-atom descriptors, and every layer's
        attention is a single :func:`ragged_paged_attention` launch.
        Projections / MLP / norms run fused over the compact [N] stream
        (padded rows are dead weight, not per-class padding products).

        ``write_idx [N]`` are host-computed flat slots into the (LOCAL)
        pool — under a data-sharded pool this program runs per-rank
        inside ``shard_map`` and every gather/write stays rank-local.
        Returns (logits [R, V] — one row per scheduled sequence-chunk,
        selected by ``last_rows`` — k_pages, v_pages).
        """
        from .kernels.ragged_paged_attention import ragged_paged_attention

        x = self._embed(params, tokens, positions)          # [N, hid]
        max_flat = k_pages.shape[2] * self.block_size
        write_idx = jnp.clip(write_idx, 0, max_flat - 1)

        def attn(q, k_l, v_l, window):
            # use_pallas=None: the ragged kernel's own dispatch policy
            # (DSTPU_RAGGED_ATTN env; ALiBi/window/fp8 force XLA inside)
            return ragged_paged_attention(
                q, k_l, v_l, kv_lens, page_tables, cu_q_lens,
                scale=self._scale, block_q=self.ragged_block_q,
                use_pallas=None, alibi_slopes=self._alibi,
                window=window)

        x, k_pages, v_pages = self._layer_loop(
            params, k_pages, v_pages, x, attn, write_idx, positions)
        sel = x[jnp.clip(last_rows, 0, x.shape[0] - 1)]
        logits = self.model.head(params, sel)
        return logits, k_pages, v_pages

    def ragged_forward(self, params: Params, k_pages, v_pages,
                       d_tokens, d_positions, d_context_lens, d_block_tables,
                       p_tokens, p_positions, p_valid, p_history, p_block_tables):
        """THE SplitFuse program: one dispatch over a ragged batch mixing two
        atom classes (the reference's ``build_atoms``/``flash_attn_by_atoms``,
        ragged_ops.cpp:20-47):

        - decode atoms  — [Bd] single tokens, paged Pallas attention, NOT
          padded to the prefill chunk length;
        - prefill atoms — [Sp, T] chunk grid, batched chunk attention.

        Projections / MLP / norms run fused over the concatenated token
        stream [Bd + Sp*T] — the fixed-size forward composition that is the
        point of Dynamic SplitFuse. Either class may be empty (static).
        Returns (logits [Bd + Sp, V] — decode rows first, then each prefill
        chunk's last valid token — k_pages, v_pages).
        """
        ps = self.block_size
        Bd = d_tokens.shape[0]
        Sp, T = p_tokens.shape
        max_flat = k_pages.shape[2] * ps
        max_pos = self.max_blocks_per_seq * ps - 1

        tokens = jnp.concatenate([d_tokens, p_tokens.reshape(-1)])
        positions = jnp.concatenate([d_positions, p_positions.reshape(-1)])
        x = self._embed(params, tokens, positions)          # [N, hid]

        # KV write targets. decode: one slot per row; prefill: grid slots,
        # padded tokens land in the reserved null block 0.
        d_pos = jnp.clip(d_positions, 0, max_pos)
        d_pages = jnp.take_along_axis(
            d_block_tables, jnp.clip(d_pos[:, None] // ps, 0,
                                     d_block_tables.shape[1] - 1), axis=1)[:, 0]
        d_write = d_pages * ps + d_pos % ps
        p_pos = jnp.clip(p_positions, 0, max_pos)
        p_pages = jnp.take_along_axis(
            p_block_tables, jnp.clip(p_pos // ps, 0,
                                     p_block_tables.shape[1] - 1), axis=1)
        p_ok = jnp.arange(T)[None, :] < p_valid[:, None]
        p_write = jnp.where(p_ok, p_pages * ps + p_pos % ps, 0)
        write_idx = jnp.clip(
            jnp.concatenate([d_write, p_write.reshape(-1)]), 0, max_flat - 1)

        def attn(q, k_l, v_l, window):
            outs = []
            if Bd:
                outs.append(paged_decode_attention(
                    q[:Bd], k_l, v_l, d_context_lens, d_block_tables,
                    scale=self._scale, use_pallas=self.use_pallas,
                    alibi_slopes=self._alibi, window=window))
            if Sp:
                op = ragged_chunk_attention(
                    q[Bd:].reshape(Sp, T, *q.shape[1:]), k_l, v_l,
                    p_history, p_block_tables, scale=self._scale,
                    alibi_slopes=self._alibi, window=window)
                outs.append(op.reshape(Sp * T, *op.shape[2:]))
            return outs[0] if len(outs) == 1 else jnp.concatenate(outs)

        x, k_pages, v_pages = self._layer_loop(
            params, k_pages, v_pages, x, attn, write_idx, positions)

        rows = [x[:Bd]]
        if Sp:
            last = jnp.clip(p_valid - 1, 0, T - 1)
            rows.append(x[Bd:].reshape(Sp, T, -1)[jnp.arange(Sp), last])
        logits = self.model.head(params, jnp.concatenate(rows) if Sp else rows[0])
        return logits, k_pages, v_pages

    def prefill_chunk(self, params: Params, k_pages, v_pages, tokens, positions,
                      block_table, history_len, n_valid):
        """One sequence, T_pad chunk tokens. Returns (last_logits [V],
        k_pages, v_pages)."""
        ps = self.block_size
        T = tokens.shape[0]
        max_flat = k_pages.shape[2] * ps  # P * ps

        x = self._embed(params, tokens, positions)

        pos_c = jnp.clip(positions, 0, self.max_blocks_per_seq * ps - 1)
        pages_of = jnp.take(block_table, pos_c // ps, mode="clip")
        write_idx = jnp.where(jnp.arange(T) < n_valid,
                              pages_of * ps + pos_c % ps, 0)
        write_idx = jnp.clip(write_idx, 0, max_flat - 1)

        ctx_idx = (block_table[:, None] * ps + jnp.arange(ps)[None, :]).reshape(-1)

        def attn(q, k_l, v_l, window):
            kf = k_l.reshape(k_l.shape[0], -1, k_l.shape[-1])
            k_ctx = kf[:, ctx_idx, :].astype(q.dtype)  # fp8 store: widen
            vf = v_l.reshape(v_l.shape[0], -1, v_l.shape[-1])
            v_ctx = vf[:, ctx_idx, :].astype(q.dtype)  # the gather only
            return chunk_prefill_attention(q, k_ctx, v_ctx, history_len,
                                           scale=self._scale,
                                           alibi_slopes=self._alibi,
                                           window=window)

        x, k_pages, v_pages = self._layer_loop(
            params, k_pages, v_pages, x, attn, write_idx, positions)
        last = jnp.clip(n_valid - 1, 0, T - 1)
        logits = self.model.head(params, x[last][None, :])[0]
        return logits, k_pages, v_pages

    def decode_burst(self, params: Params, k_pages, v_pages, tokens, positions,
                     block_tables, rng, temperatures, num_steps: int):
        """K decode steps for B sequences in ONE compiled program — sampling
        happens ON DEVICE between steps (greedy when temperature <= 0, else
        categorical), so a serving loop pays one dispatch+fetch round trip
        per K tokens instead of per token.

        Returns (tokens_out [B, K], k_pages, v_pages). ``positions[b]`` is
        the position of the INPUT token (= seen_tokens); blocks for all K
        steps must be pre-allocated in ``block_tables``.
        """
        ps = self.block_size
        B = tokens.shape[0]
        max_flat = k_pages.shape[2] * ps
        max_pos = self.max_blocks_per_seq * ps - 1

        def one(carry, _):
            tokens, positions, k_pages, v_pages, rng = carry
            x = self._embed(params, tokens, positions)
            pos_c = jnp.clip(positions, 0, max_pos)
            # clamp the gather index to the bucketed table width, like
            # ragged_forward/prefill_chunk — never rely on XLA's implicit
            # out-of-bounds clamp
            page_slot = jnp.clip(pos_c // ps, 0, block_tables.shape[1] - 1)
            pages_of = jnp.take_along_axis(block_tables, page_slot[:, None],
                                           axis=1)[:, 0]
            write_idx = jnp.clip(pages_of * ps + pos_c % ps, 0, max_flat - 1)

            def attn(q, k_l, v_l, window):
                return paged_decode_attention(q, k_l, v_l, pos_c + 1,
                                              block_tables, scale=self._scale,
                                              use_pallas=self.use_pallas,
                                              alibi_slopes=self._alibi,
                                              window=window)

            x, k_pages, v_pages = self._layer_loop(
                params, k_pages, v_pages, x, attn, write_idx, positions)
            logits = self.model.head(params, x)              # [B, V]
            with jax.named_scope("sample"):
                rng, sub = jax.random.split(rng)
                greedy = jnp.argmax(logits, axis=-1)
                temp = jnp.maximum(temperatures, 1e-6)[:, None]
                sampled = jax.random.categorical(sub, logits / temp, axis=-1)
                nxt = jnp.where(temperatures <= 0.0, greedy, sampled).astype(jnp.int32)
            return (nxt, positions + 1, k_pages, v_pages, rng), nxt

        carry = (tokens, positions, k_pages, v_pages, rng)
        (_, _, k_pages, v_pages, _), toks = jax.lax.scan(
            one, carry, None, length=num_steps)
        return toks.T, k_pages, v_pages                    # [B, K]

    def decode(self, params: Params, k_pages, v_pages, tokens, positions,
               context_lens, block_tables):
        """B sequences × 1 token. Returns (logits [B, V], k_pages, v_pages)."""
        ps = self.block_size
        B = tokens.shape[0]
        max_flat = k_pages.shape[2] * ps

        x = self._embed(params, tokens, positions)

        pos_c = jnp.clip(positions, 0, self.max_blocks_per_seq * ps - 1)
        pages_of = jnp.take_along_axis(block_tables, (pos_c // ps)[:, None],
                                       axis=1)[:, 0]
        write_idx = jnp.clip(pages_of * ps + pos_c % ps, 0, max_flat - 1)

        def attn(q, k_l, v_l, window):
            return paged_decode_attention(q, k_l, v_l, context_lens, block_tables,
                                          scale=self._scale,
                                          use_pallas=self.use_pallas,
                                          alibi_slopes=self._alibi,
                                          window=window)

        x, k_pages, v_pages = self._layer_loop(
            params, k_pages, v_pages, x, attn, write_idx, positions)
        logits = self.model.head(params, x)
        return logits, k_pages, v_pages
