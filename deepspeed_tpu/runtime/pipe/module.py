"""Pipeline parallelism.

Counterpart of the reference ``runtime/pipe/`` subsystem: ``PipelineModule``
(module.py:86) partitions layers across stages; ``PipelineEngine``
(engine.py:55) interprets an instruction schedule (schedule.py:189) and moves
activations between stage processes with P2P sends (p2p.py:50).

TPU-first redesign — **SPMD collective-permute pipelining**: there are no
per-stage processes. Stage parameters carry a leading ``[num_stages, ...]``
dimension sharded over the ``pipe`` mesh axis; one jitted program runs on
every device. Each pipeline *tick* applies every stage to its current
microbatch in parallel (a ``vmap`` over the stage dim) and then shifts
activations one stage forward with ``jnp.roll`` over the stage-sharded dim —
which XLA's SPMD partitioner lowers to exactly the neighbor
``collective_permute`` over ICI that the reference's ``p2p.send/recv``
performs with NCCL. The GPipe fill/drain schedule (M microbatches, P stages,
M+P-1 ticks) is a ``lax.scan``; ``jax.grad`` through it yields the backward
pipeline automatically, with XLA's scheduler overlapping the permutes with
compute — subsuming the reference's hand-written 1F1B instruction
interpreter (``_exec_schedule``, pipe/engine.py:1357).

``PipelineModule`` exposes the same ``init/specs/loss`` protocol as
``TransformerLM``, so ``DeepSpeedEngine`` (and ZeRO sharding on the
non-pipe dims) works unchanged — the counterpart of DeepSpeed selecting
``PipelineEngine`` for ``PipelineModule`` models (deepspeed/__init__.py:156).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...models.transformer import ACT_SPEC, TransformerConfig, TransformerLM, _c
from ..activation_checkpointing.checkpointing import checkpointed
from ..topology import PIPE_AXIS


class PipelineModule:
    """Transformer LM with its blocks partitioned over pipeline stages.

    ``num_stages`` must divide ``config.num_layers``; partitioning is uniform
    (the reference's ``partition_method='uniform'``; its parameter-balanced
    mode is meaningless here because every stage holds the same block shapes).
    """

    #: what the stage scan (``TransformerLM._block_fn`` without a mask or a
    #: layer's kind, one stream, a scalar auxiliary loss) carries between
    #: ``TransformerLM.embed`` and ``.head``, under ``.loss``
    RUNS = frozenset({
        "norm_style='sandwich'", "embedding_scale", "residual_fp32", "qk_norm",
        "attn_gate", "attention='latent'", "pred_heads", "moe"})

    def __init__(self, config: TransformerConfig, num_stages: int,
                 num_microbatches: int = None):
        assert config.num_layers % num_stages == 0, (
            f"num_layers {config.num_layers} not divisible by num_stages {num_stages}")
        self.config = config
        self.num_stages = num_stages
        self.layers_per_stage = config.num_layers // num_stages
        self.num_microbatches = num_microbatches or num_stages
        self._lm = TransformerLM(config)
        self._lm.require("PipelineModule", self.RUNS)

    # -- params: reshape blocks [L, ...] -> [P, L/P, ...] --------------------
    def init(self, rng: jax.Array, dtype=jnp.float32) -> Dict[str, Any]:
        params = self._lm.init(rng, dtype)
        params["blocks"] = jax.tree.map(
            lambda x: x.reshape((self.num_stages, self.layers_per_stage) + x.shape[1:]),
            params["blocks"])
        return params

    def specs(self) -> Dict[str, Any]:
        specs = self._lm.specs()
        specs["blocks"] = jax.tree.map(
            lambda s: P(PIPE_AXIS, *s), specs["blocks"],
            is_leaf=lambda s: isinstance(s, P))
        return specs

    # -- pipelined forward ---------------------------------------------------
    def _stage_fn(self, stage_blocks, x, positions, passes: int = 1,
                  remat_budget=None):
        """Run this stage's layer slice (a scan like the dense model)."""
        def block_fn(carry, block):
            # attn_mask=None: PP drives causal decoder stages (encoders with
            # padding masks aren't pipelined)
            return self._lm._block_fn(
                None, carry, (block, jnp.asarray(1.0, self.config.dtype)))
        init = (x, positions, jnp.zeros((), jnp.float32))
        if not self.config.remat:
            (x, _, aux), _ = jax.lax.scan(block_fn, init, stage_blocks)
            return x, aux
        # the dense model's block policy from the same configuration. What
        # a stage saves it saves once for every pass of the schedule through it
        layers = jax.tree.leaves(stage_blocks)[0].shape[0]
        ck_fn = checkpointed(block_fn, self.config.remat_policy,
                             layers * passes, remat_budget)
        (x, _, aux), _ = jax.lax.scan(ck_fn, init, stage_blocks)
        return x, aux

    def apply(self, params: Dict[str, Any], input_ids: jax.Array,
              layer_mask=None, token_type_ids=None,
              attention_mask=None, remat_budget=None
              ) -> Tuple[jax.Array, jax.Array]:
        assert layer_mask is None, \
            "progressive layer drop is not supported under pipeline parallelism"
        assert token_type_ids is None and attention_mask is None, \
            "encoder inputs are not supported under pipeline parallelism"
        c = self.config
        M, S = self.num_microbatches, input_ids.shape[1]
        B = input_ids.shape[0]
        assert B % M == 0, f"batch {B} not divisible by num_microbatches {M}"
        mb = B // M
        self._lm._charge_head(remat_budget, input_ids)
        x, positions = self._lm.embed(params, input_ids)

        # microbatch major: [M, mb, S, D]
        x_mb = x.reshape(M, mb, S, c.hidden_size)

        Pst = self.num_stages
        # the scan executes the INSTRUCTION SCHEDULE (schedule.py): tick
        # count, stage-0 feed and last-stage emit all derive from it — the
        # schedule is the single source of truth, the scan its interpreter
        from .schedule import forward_tick_plan
        ticks, feed_plan, emit_plan = forward_tick_plan(M, Pst)
        feed_plan = jnp.asarray(feed_plan)   # [ticks] mb to load, -1=bubble
        emit_plan = jnp.asarray(emit_plan)   # [ticks] mb emitted, -1=bubble
        buf = jnp.zeros((Pst, mb, S, c.hidden_size), c.dtype)
        out_mb = jnp.zeros((M, mb, S, c.hidden_size), c.dtype)
        aux_total = jnp.zeros((), jnp.float32)

        stage_ids = jnp.arange(Pst)
        stage_fn = functools.partial(self._stage_fn, passes=ticks,
                                     remat_budget=remat_budget)

        def tick(carry, t):
            buf, out_mb, aux_total = carry
            # shift activations one stage forward: roll over the pipe-sharded
            # stage dim == collective_permute on ICI
            shifted = jnp.roll(buf, shift=1, axis=0)
            # LoadMicroBatch: stage 0 ingests the scheduled microbatch
            # (zeros during drain bubbles)
            feed_idx = feed_plan[t]
            feed = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.maximum(feed_idx, 0), axis=0, keepdims=False)
            feed = jnp.where(feed_idx >= 0, feed, jnp.zeros_like(feed))
            inp = shifted.at[0].set(feed)
            # every stage computes in parallel (stage dim sharded over pipe)
            out, aux = jax.vmap(stage_fn, in_axes=(0, 0, None))(
                params["blocks"], inp, positions)
            # last stage emits the scheduled microbatch during drain
            emit_idx = emit_plan[t]
            out_mb = jax.lax.cond(
                emit_idx >= 0,
                lambda o: jax.lax.dynamic_update_index_in_dim(o, out[Pst - 1], jnp.maximum(emit_idx, 0), axis=0),
                lambda o: o, out_mb)
            # only count aux for real (non-bubble) stage work
            live = jnp.logical_and(stage_ids <= t, stage_ids > t - M)
            aux_total = aux_total + jnp.sum(aux * live)
            return (out, out_mb, aux_total), None

        (buf, out_mb, aux_total), _ = jax.lax.scan(
            tick, (buf, out_mb, aux_total), jnp.arange(ticks))

        x = _c(out_mb.reshape(B, S, c.hidden_size), ACT_SPEC)
        return self._lm.head(params, x), aux_total

    # The shared loss ingredients (transformer.py): ``TransformerLM.loss``
    # calls ``self.derive_labels``/``self.combine_aux``, and both read only
    # ``self.config`` — borrowing them keeps the pipelined loss math
    # identical to the dense model's by construction.
    derive_labels = TransformerLM.derive_labels
    combine_aux = TransformerLM.combine_aux

    def loss(self, params: Dict[str, Any], batch: Dict[str, jax.Array],
             remat_budget=None) -> jax.Array:
        return TransformerLM.loss(self, params, batch, remat_budget)  # same loss math
