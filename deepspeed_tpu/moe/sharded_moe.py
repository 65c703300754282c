"""Gating + expert dispatch math.

Counterpart of the reference ``deepspeed/moe/sharded_moe.py``: ``TopKGate``
(:348), ``top1gating`` (:184), ``_capacity`` (:162), ``_AllToAll`` (:95),
``MOELayer`` (:425). The reference dispatches tokens with einsum-built
one-hot masks and a ``torch.distributed`` all-to-all across the expert
group; here the same capacity-bucketed dispatch is built with static shapes
(XLA requirement) and the expert exchange is expressed through sharding:
the dispatch tensor [experts, capacity, d] carries a sharding constraint
that splits the expert dim over the ``expert`` mesh axis, so the SPMD
partitioner emits the all-to-all over ICI.

Load-balancing aux loss follows the reference (GShard l_aux = E * Σ me·ce,
sharded_moe.py:266-272).

Beside the GShard gate stands the router of the dropless sparse models
(``softmax_topk_router``: OLMoE, arXiv:2409.02060): no capacity, no drop,
the top-k probabilities used as they are or renormalised, and the paper's
two router losses. ``moe/layer.py`` takes it when a layer's
``capacity_factor`` is None. ``sigmoid_bias_router`` stands beside it: the
DeepSeek-V3 report's router (sigmoid affinities, a correction bias that load
and not gradient moves, the sequence-wise balance loss).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


def capacity(num_tokens: int, num_experts: int, capacity_factor: float,
             min_capacity: int) -> int:
    """Reference ``_capacity`` (sharded_moe.py:162) — tokens per expert."""
    cap = int(num_tokens * capacity_factor * 1.0 / num_experts)
    return max(cap, min_capacity)


def top_k_gating_indices(logits: jax.Array, top_k: int, capacity_: int):
    """Top-k gate with capacity, in INDEX form.

    logits: [tokens, experts]. Returns
      expert_idx [tokens, k] int32 — chosen expert per (token, choice)
      pos        [tokens, k] int32 — slot inside the expert's capacity bucket
      keep       [tokens, k] bool  — False when the bucket overflowed
      weight     [tokens, k] f32   — normalized combine weight (0 if dropped)
      aux_loss   scalar (GShard load-balancing loss, scaled by E)
      me         [experts] mean gate probability (for monitoring)

    The index form is what the dispatch actually needs: building dense
    one-hot [tokens, experts, capacity] masks and contracting them (the
    reference's einsum dispatch, sharded_moe.py:425) costs
    O(tokens*experts*capacity*hidden) FLOPs — quadratic in tokens; the
    gather/scatter dispatch built from indices is O(tokens*k*hidden).

    ROUTE-PARITY CONTRACT (ISSUE 11): the fused Pallas route kernel
    (``ops/transformer/pallas_moe.py::_route_kernel``) replicates this
    function's fp32 operation sequence EXACTLY — same softmax, same
    lowest-index tie rule (``lax.top_k`` == masked re-argmax), same
    position ranks (the cumsum as a triangular product), capacity clamps
    and weight normalization —
    so kernel- and XLA-path routing decisions are bit-identical. Any
    change here must be mirrored there;
    ``tests/unit/ops/test_pallas_moe.py::TestRoute`` pins the pair.
    """
    tokens, num_experts = logits.shape
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    # top-k expert choice per token
    _, expert_idx = jax.lax.top_k(gates, top_k)  # [tokens, k]

    # aux loss from the top-1 assignment like the reference (top1gating :238)
    mask1 = jax.nn.one_hot(expert_idx[:, 0], num_experts, dtype=jnp.float32)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    aux_loss = jnp.sum(me * ce) * num_experts

    # process the k choices sequentially so capacity counting is consistent
    counts = jnp.zeros((num_experts,), dtype=jnp.int32)
    gate_sum = jnp.zeros((tokens,), dtype=jnp.float32)
    idxs, poss, keeps, gatews = [], [], [], []
    for k in range(top_k):
        idx_k = expert_idx[:, k]  # [tokens]
        mask_k = jax.nn.one_hot(idx_k, num_experts, dtype=jnp.int32)
        # rank of each token within the tokens routed to the same expert
        pos_in_expert = jnp.cumsum(mask_k, axis=0) - mask_k  # [tokens, experts]
        pos_k = jnp.sum(pos_in_expert * mask_k, axis=1) + counts[idx_k]
        keep = pos_k < capacity_
        gate_k = jnp.take_along_axis(gates, idx_k[:, None], axis=1)[:, 0] * keep
        idxs.append(idx_k)
        poss.append(jnp.minimum(pos_k, capacity_ - 1))
        keeps.append(keep)
        gatews.append(gate_k)
        counts = counts + jnp.sum(mask_k * keep[:, None], axis=0)
        gate_sum = gate_sum + gate_k

    # normalize combine weights over kept choices (reference top2gating :341)
    denom = jnp.maximum(gate_sum, 1e-9)
    weight = jnp.stack(gatews, axis=1) / denom[:, None]
    return (jnp.stack(idxs, axis=1).astype(jnp.int32),
            jnp.stack(poss, axis=1).astype(jnp.int32),
            jnp.stack(keeps, axis=1),
            weight, aux_loss, me)


BALANCE_LOSSES = ("gshard_top1", "topk_share")


# The no-drop routers' bookkeeping is written over the one-hot of the picks,
# ``[tokens, k, experts]`` bool, which is never stored: each reader is one
# fused compare-select-reduce. Nothing here is a scatter, a scatter-add or a
# gather of scalars, which the TPU runs one element at a time (8.7, 4.6 and
# 7.9 ns each on the v5e: docs/KERNELS.md, "The no-drop expert path").

def _picks(expert_idx: jax.Array, num_experts: int) -> jax.Array:
    """[tokens, k] int -> [tokens, k, experts] bool: the one-hot of each pick."""
    return expert_idx[:, :, None] == jnp.arange(num_experts, dtype=expert_idx.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _picked(scores: jax.Array, expert_idx: jax.Array, num_experts: int) -> jax.Array:
    """``scores[t, expert_idx[t, j]]``, [tokens, k] float32: the scores
    selected under the picks' one-hot, and the largest over the experts (one
    entry is selected, so it is that entry to the bit; and no sum, which the
    compiler would merge with a sum over the k that follows and add the k
    in another order). Backward: a pick's cotangent lands on one expert."""
    return jnp.max(jnp.where(_picks(expert_idx, num_experts), scores[:, None, :],
                             -jnp.inf), axis=-1)


def _picked_fwd(scores, expert_idx, num_experts):
    return _picked(scores, expert_idx, num_experts), expert_idx


def _picked_bwd(num_experts, expert_idx, g):
    return jnp.sum(jnp.where(_picks(expert_idx, num_experts), g[:, :, None], 0.0),
                   axis=1), None


_picked.defvjp(_picked_fwd, _picked_bwd)


def softmax_topk_router(logits: jax.Array, top_k: int, *, normalize: bool,
                        balance_loss: str = "topk_share"):
    """The dropless router: a float32 softmax over ALL experts' logits,
    ``lax.top_k`` (the lowest index wins a tie), and the k probabilities
    as routing weights: as they are (``normalize`` False: OLMoE's
    ``norm_topk_prob`` false) or divided by their sum (Mixtral). No
    capacity: every one of the ``tokens x top_k`` assignments reaches its
    expert.

    logits: [tokens, experts]. Returns
      expert_idx [tokens, k] int32
      weight     [tokens, k] f32
      losses     [2] f32: (load-balancing loss, router z-loss)
      rows       [experts] int32: assignments each expert received

    Load balancing, by ``balance_loss``: ``"topk_share"`` is
    ``E x sum_e f_e x P_e`` with ``f_e`` the share of the ``tokens x k``
    assignments that went to expert ``e`` and ``P_e`` its mean router
    probability (OLMoE paper, section 3; Switch's loss over k choices);
    ``"gshard_top1"`` is the capacity gate's (``f_e`` from the first
    choice alone). The z-loss is ``mean(logsumexp(logits)^2)``.
    """
    if balance_loss not in BALANCE_LOSSES:
        raise ValueError(f"balance_loss {balance_loss!r} is not one of "
                         f"{BALANCE_LOSSES}")
    tokens, num_experts = logits.shape
    logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    # ``top_k`` for the picks alone: its values' gradient is a scatter-add
    _, expert_idx = jax.lax.top_k(jax.lax.stop_gradient(gates), top_k)
    weight = _picked(gates, expert_idx, num_experts)
    if normalize:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    rows = jnp.sum(_picks(expert_idx, num_experts), axis=(0, 1), dtype=jnp.int32)
    if balance_loss == "topk_share":
        share = rows.astype(jnp.float32) / (tokens * top_k)
    else:
        share = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], num_experts,
                                        dtype=jnp.float32), axis=0)
    balance = num_experts * jnp.sum(share * jnp.mean(gates, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return (expert_idx.astype(jnp.int32), weight, jnp.stack([balance, z]), rows)


def sigmoid_bias_router(logits: jax.Array, bias: jax.Array, top_k: int, *,
                        normalize: bool, routed_scale: float, rows_per_seq: int):
    """The router of DeepSeek-V3's report (section 2.1.2, ``noaux_tc``):
    ``s = sigmoid(logits)`` in float32 over ALL experts; the ``top_k``
    largest of ``s + bias`` are chosen (one group; the lowest index wins a
    tie); the routing weights are the chosen ``s`` WITHOUT the bias,
    divided by their sum (+1e-20) when ``normalize``, times
    ``routed_scale``. ``bias`` enters under ``stop_gradient``: load moves
    it (:func:`bias_step`), no gradient does. No capacity, no drop.

    logits: [tokens, experts], ``tokens`` = sequences x ``rows_per_seq``,
    sequence-major. Returns ``(expert_idx [tokens, k] int32, weight
    [tokens, k] f32, losses [2] f32, rows [experts] int32)``: ``losses[0]``
    is the sequence-wise balance loss ``sum_e f_e P_e`` averaged over the
    sequences, ``f_e = E / (k T) x #{t of the sequence that chose e}`` and
    ``P_e`` the sequence's mean of ``s_e / sum_j s_j`` (the report's
    formula 17-20, without its coefficient); ``losses[1]`` is 0: this
    router has no z-loss."""
    tokens, num_experts = logits.shape
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    biased = s + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, expert_idx = jax.lax.top_k(biased, top_k)
    weight = _picked(s, expert_idx, num_experts)
    if normalize:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * routed_scale
    seqs = tokens // rows_per_seq
    chose = jnp.sum(_picks(expert_idx, num_experts)
                    .reshape(seqs, rows_per_seq * top_k, num_experts),
                    axis=1, dtype=jnp.int32)
    f = chose.astype(jnp.float32) * (num_experts / (top_k * rows_per_seq))
    p = jnp.mean((s / jnp.sum(s, axis=-1, keepdims=True))
                 .reshape(seqs, rows_per_seq, num_experts), axis=1)
    balance = jnp.mean(jnp.sum(f * p, axis=-1))
    return (expert_idx.astype(jnp.int32), weight,
            jnp.stack([balance, jnp.zeros((), jnp.float32)]),
            jnp.sum(chose, axis=0))


def bias_step(bias: jax.Array, load: jax.Array, rate: float) -> jax.Array:
    """``noaux_tc``'s update of the correction bias after a step: up by
    ``rate`` for an expert that drew fewer assignments than the mean, down
    for one that drew more. ``load``: the assignments each expert drew in
    the step, the last axis over the experts."""
    load = load.astype(jnp.float32)
    mean = jnp.mean(load, axis=-1, keepdims=True)
    return bias + rate * jnp.sign(mean - load).astype(bias.dtype)


def top_k_gating(logits: jax.Array, top_k: int, capacity_: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Top-k gate with capacity, in DENSE one-hot form (API parity with the
    reference's top1gating/top2gating tensors).

    logits: [tokens, experts]. Returns
      combine   [tokens, experts, capacity]  — weights for gathering results
      dispatch  [tokens, experts, capacity]  — boolean one-hot routing
      aux_loss  scalar (GShard load-balancing loss, scaled by E)
      me        [experts] mean gate probability (for monitoring)
    """
    tokens, num_experts = logits.shape
    expert_idx, pos, keep, weight, aux_loss, me = \
        top_k_gating_indices(logits, top_k, capacity_)
    combine = jnp.zeros((tokens, num_experts, capacity_), dtype=jnp.float32)
    dispatch = jnp.zeros((tokens, num_experts, capacity_), dtype=bool)
    token_ids = jnp.arange(tokens)
    for k in range(expert_idx.shape[1]):
        combine = combine.at[token_ids, expert_idx[:, k], pos[:, k]].add(
            jnp.where(keep[:, k], weight[:, k], 0.0))
        dispatch = dispatch.at[token_ids, expert_idx[:, k], pos[:, k]].max(
            keep[:, k])
    return combine, dispatch, aux_loss, me
