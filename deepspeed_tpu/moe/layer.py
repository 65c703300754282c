"""MoE layer with expert parallelism.

Counterpart of the reference ``deepspeed/moe/layer.py`` (``MoE`` :16) +
``experts.py`` (``Experts`` :10). Experts are a stacked parameter tensor
[num_experts, ...] sharded over the ``expert`` mesh axis; dispatched tokens
get a sharding constraint on the expert dimension so XLA emits the
all-to-all over ICI that the reference performs with ``_AllToAll``
(sharded_moe.py:95). Dispatch/combine are index-based gather/scatter
(O(tokens*k*hidden), the layout work the reference's cutlass
moe_gather/moe_scatter kernels do) rather than dense one-hot einsums
(O(tokens*experts*capacity*hidden) — quadratic in tokens); the expert FFN
itself runs as a batched einsum over the (expert-sharded) expert dim,
which IS the grouped-GEMM on the MXU (reference cutlass moe_gemm,
inference/v2/kernels/cutlass_ops).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops.transformer import pallas_gmm, pallas_segment_sum
from ..runtime import topology as topo_mod
from ..runtime.topology import BATCH_AXES, DATA_AXIS, EXPERT_AXIS
from ..utils.jax_compat import with_sharding_constraint
from .sharded_moe import (capacity as _capacity, sigmoid_bias_router,
                          softmax_topk_router, top_k_gating_indices)

Params = Dict[str, Any]

#: ``MoE.activation`` -> the function on the gate product of a gated expert
#: MLP (``act(x W_gate) * (x W_up)``, stacks ``wi_gate`` / ``wi_up`` / ``wo``);
#: ``'gelu'``, the one form that is not gated, has one ``wi`` stack
GATE_ACTIVATIONS = {"silu_gated": jax.nn.silu, "relu_gated": jax.nn.relu}
ACTIVATIONS = tuple(GATE_ACTIVATIONS) + ("gelu",)


class Routing(NamedTuple):
    """What the no-drop router decides of ``T`` tokens (``MoE.route``): the
    ``k`` experts each chose ``[T, k]`` int32, their float32 weights ``[T,
    k]``, the two router losses ``[2]`` and the assignments each expert drew
    ``[experts]`` int32. Made from the tensor the experts multiply or, where
    the router reads the block's input, before the token mixer runs."""
    eidx: jax.Array
    weight: jax.Array
    losses: jax.Array
    rows: jax.Array


def _c(x, spec):
    return with_sharding_constraint(x, spec)


def moe_reference_forward(params: Params, tokens: jax.Array, *,
                          top_k: int, capacity: int, activation: str,
                          mask_pad: bool) -> Tuple[jax.Array, jax.Array]:
    """The dead-EP XLA expert path as ONE pure statement: gating ->
    capacity-slot gather -> grouped-einsum FFN -> weighted combine.
    ``tokens`` [T, H] -> (out [T, H], aux). This is the numerics
    reference the fused Pallas kernel pair (ISSUE 11,
    ``ops/transformer/pallas_moe.py``) is held to — its interpret-mode
    parity suite compares against this function, and the kernel path's
    ``custom_vjp`` backward IS this function's VJP (one statement of the
    gradient math shared with the ``DSTPU_MOE_KERNEL=xla`` hatch)."""
    n_tok, h = tokens.shape
    e = params["gate"].shape[-1]
    logits = tokens @ params["gate"].astype(tokens.dtype)
    eidx, pos, keep, weight, aux, _ = top_k_gating_indices(
        logits, top_k, capacity)
    cap = capacity
    slot = jnp.where(keep, eidx * cap + pos, e * cap).reshape(-1)
    src = jnp.zeros((e * cap + 1,), jnp.int32).at[slot].set(
        jnp.repeat(jnp.arange(n_tok, dtype=jnp.int32), top_k) + 1,
        mode="drop")[:e * cap]
    gathered = tokens[jnp.maximum(src - 1, 0)]
    if mask_pad:
        gathered = jnp.where((src > 0)[:, None], gathered,
                             jnp.zeros((), tokens.dtype))
    expert_in = gathered.reshape(e, cap, h)
    if activation in GATE_ACTIVATIONS:
        gate = GATE_ACTIVATIONS[activation](jnp.einsum(
            "ech,ehf->ecf", expert_in, params["wi_gate"].astype(tokens.dtype)))
        up = jnp.einsum("ech,ehf->ecf", expert_in,
                        params["wi_up"].astype(tokens.dtype))
        mid = gate * up
    else:
        mid = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", expert_in,
                                     params["wi"].astype(tokens.dtype)))
    expert_out = jnp.einsum("ecf,efh->ech", mid,
                            params["wo"].astype(tokens.dtype))
    flat_out = expert_out.reshape(e * cap, h)
    picked = flat_out[jnp.where(keep, eidx * cap + pos, 0)]
    w = (weight * keep).astype(tokens.dtype)
    return jnp.sum(picked * w[:, :, None], axis=1), aux


# -- the no-drop path's row movement ------------------------------------------
# ``order`` sorts the ``tokens x top_k`` assignments by expert (stable, so
# inside an expert the rows stand in token order) and ``inv`` is its inverse
# permutation. Both directions of both movements are row GATHERS: the
# transpose of a gather is a scatter-add, which the TPU serialises, so each
# movement carries its own backward, the gather by the other permutation.

def _sorted_by(key):
    """-> (order, inv), int32 each: the stable sort of the assignments by
    ``key`` and its inverse permutation, a sort each. The place is the
    second key of the first (what a stable sort is, without the third
    operand the compiler gives one); ``order`` is a permutation, so where
    each assignment stands in it is ``order`` sorted with the place as its
    payload. (A scatter of the places would run one element at a time, at
    five times the sort's cost: see ``_token_order``.)"""
    at = jnp.arange(key.shape[0], dtype=jnp.int32)
    order = jax.lax.sort((key, at), num_keys=2, is_stable=False)[1]
    return order, jax.lax.sort((order, at), num_keys=1, is_stable=False)[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows(tokens, order, inv, top_k):
    """[T, h] -> [T x k, h]: row ``i`` is the token of sorted assignment
    ``i``. Backward: each token's k rows, found by ``inv``, summed."""
    return tokens.at[order // top_k].get(mode="promise_in_bounds")


def _dispatch_rows_fwd(tokens, order, inv, top_k):
    return _dispatch_rows(tokens, order, inv, top_k), inv


def _dispatch_rows_bwd(top_k, inv, g):
    picked = g.at[inv].get(mode="promise_in_bounds", unique_indices=True)
    d = jnp.sum(picked.reshape(-1, top_k, g.shape[-1]).astype(jnp.float32), axis=1)
    return d.astype(g.dtype), None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _unsort_rows(rows, order, inv):
    """[T x k, h] in expert order -> the same rows in assignment order
    (token-major). Backward: sorted again, by ``order``."""
    return rows.at[inv].get(mode="promise_in_bounds", unique_indices=True)


def _unsort_rows_fwd(rows, order, inv):
    return _unsort_rows(rows, order, inv), order


def _unsort_rows_bwd(order, g):
    return (g.at[order].get(mode="promise_in_bounds", unique_indices=True),
            None, None)


_unsort_rows.defvjp(_unsort_rows_fwd, _unsort_rows_bwd)


# A chip that holds some of the experts sorts the assignments of ITS experts
# first (``order``: held assignments by expert, then the absent ones) and
# moves the first ``cap`` sorted rows alone; ``held`` counts the real ones.
# Back to the tokens, both movements go over the buffer's ``cap`` rows and
# never over ``tokens x top_k``: ``by_token`` lists the buffer's assignments in
# token order and ``perm`` the buffer row of each (``_token_order``), so ONE
# gather brings the rows into token order and a sorted segment sum
# (``pallas_segment_sum``) adds each token's.

def _token_order(order, held):
    """-> (perm, by_token), ``cap`` int32 each: the buffer's first ``held``
    rows in token order. ``order`` is by expert and inside an expert by
    token, so its first ``held`` assignments SORTED are the held assignments
    token-major: one sort of ``cap`` keys with the row as its payload (a
    sort is the cheapest index operation the TPU has: a scatter or a gather
    of as many scalars costs ten times as much). ``by_token[i]`` is the
    assignment and ``perm[i]`` its row of the buffer; from ``held`` on
    ``perm`` lists the unfilled rows and ``by_token`` reads 0."""
    at = jnp.arange(order.shape[0], dtype=jnp.int32)
    real = at < held
    by_token, perm = jax.lax.sort(
        (jnp.where(real, order, jnp.iinfo(jnp.int32).max), at), num_keys=1)
    return perm, jnp.where(real, by_token, 0)


def _sum_held_rows(rows, scale, perm, by_token, held, n_tok, top_k):
    """[cap, h] rows of the buffer -> [T, h] float32: each token's rows
    among the first ``held``, times ``scale`` ([cap] float32 in token order;
    None: as they are), summed."""
    in_token_order = rows.at[perm].get(mode="promise_in_bounds", unique_indices=True)
    return pallas_segment_sum.segment_sum(in_token_order, by_token // top_k, scale,
                                          held, n_tok, _mesh_devices())


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _dispatch_held_rows(tokens, order, perm, by_token, held, shape):
    """[T, h] -> [cap, h] (``order`` has ``cap`` entries; ``shape`` = (T,
    top_k)): row ``i`` is the token of sorted assignment ``i``. Backward:
    each token's held rows summed (``_sum_held_rows``)."""
    return tokens.at[order // shape[1]].get(mode="promise_in_bounds")


def _dispatch_held_rows_fwd(tokens, order, perm, by_token, held, shape):
    return (_dispatch_held_rows(tokens, order, perm, by_token, held, shape),
            (perm, by_token, held))


def _dispatch_held_rows_bwd(shape, res, g):
    d = _sum_held_rows(g, None, *res, *shape)
    return d.astype(g.dtype), None, None, None, None


_dispatch_held_rows.defvjp(_dispatch_held_rows_fwd, _dispatch_held_rows_bwd)


@jax.custom_vjp
def _combine_held_rows(rows, weight, order, inv, perm, by_token, held):
    """[cap, h] rows in expert order, [T, k] routing weights -> [T, h]
    float32: each token's held rows weighted (in float32) and summed
    (``_sum_held_rows``; no ``[T, k, h]`` tensor: most slots are some other
    chip's). The buffer's rows past ``held`` are other tokens' (and a
    grouped matmul leaves rows past its last group as it found them, on the
    TPU possibly no number): selected away, not multiplied by 0.
    Backward, in the ``cap`` rows' own order: the rows' gradient is the
    token's times the weight, the weight's the row's product with it."""
    return _combine_held_rows_fwd(rows, weight, order, inv, perm, by_token, held)[0]


def _combine_held_rows_fwd(rows, weight, order, inv, perm, by_token, held):
    n_tok, k = weight.shape
    scale = weight.reshape(-1).at[by_token].get(mode="promise_in_bounds")
    out = _sum_held_rows(rows, scale, perm, by_token, held, n_tok, k)
    return out, (rows, weight, order, inv, held)


def _combine_held_rows_bwd(res, g):
    rows, weight, order, inv, held = res
    k = weight.shape[1]
    real = (jnp.arange(rows.shape[0]) < held)[:, None]
    g_rows = g.at[order // k].get(mode="promise_in_bounds")          # [cap, h]
    w_rows = weight.reshape(-1).at[order].get(mode="promise_in_bounds")
    d_rows = jnp.where(real, g_rows * w_rows[:, None], 0.0).astype(rows.dtype)
    dot = jnp.sum(jnp.where(real, g_rows * rows.astype(jnp.float32), 0.0), axis=-1)
    d_w = jnp.where(inv < held,
                    dot.at[jnp.minimum(inv, rows.shape[0] - 1)]
                    .get(mode="promise_in_bounds"), 0.0).reshape(weight.shape)
    return d_rows, d_w, None, None, None, None, None


_combine_held_rows.defvjp(_combine_held_rows_fwd, _combine_held_rows_bwd)


def _once(run, fn, like):
    """``fn()`` if ``run`` else zeros shaped ``like``, as a loop of at most
    one trip (no branch instruction: a profile counts a loop's body once,
    where it counts a conditional and its branch both)."""
    zeros = jax.tree.map(jnp.zeros_like, like)
    return jax.lax.while_loop(lambda c: c[0], lambda c: (jnp.zeros((), bool), fn()),
                              (run, zeros))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _overflow_rows(layer, run, tokens, by_expert, stacks):
    """What the held experts add for the assignments beyond the sorted
    buffer, ``layer._every_token`` under ``by_expert`` (0 but for those
    assignments), run only in a step that has any (``run``): [T, h]
    float32. Both directions are loops of at most one trip, so a step
    without overflow pays nothing for the guarantee that no row is dropped."""
    return _once(run, lambda: layer._every_token(tokens, by_expert, stacks),
                 jax.ShapeDtypeStruct(tokens.shape, jnp.float32))


def _overflow_rows_fwd(layer, run, tokens, by_expert, stacks):
    return (_overflow_rows(layer, run, tokens, by_expert, stacks),
            (run, tokens, by_expert, stacks))


def _overflow_rows_bwd(layer, res, g):
    run, *args = res
    grads = _once(run, lambda: jax.vjp(layer._every_token, *args)[1](g), tuple(args))
    return (None,) + tuple(grads)


_overflow_rows.defvjp(_overflow_rows_fwd, _overflow_rows_bwd)


def _mesh_devices() -> int:
    """The devices of the live mesh (1 where no engine has set one up): what
    ``pallas_gmm.choose_route`` keeps its kernel out of a partitioned
    program by."""
    return topo_mod.get_topology().world_size if topo_mod.is_initialized() else 1


def held_capacity(assignments: int, held: int, experts: int) -> int:
    """Rows of the buffer a chip that holds ``held`` of ``experts`` experts
    sorts its rows into, of the ``assignments`` = tokens x top_k a step
    routes: three times the even share, in whole 512s, at most all of them
    (then the buffer can never be short). Pure."""
    even = -(-assignments * held // experts)
    return min(assignments, -(-3 * even // 512) * 512)


@dataclasses.dataclass(frozen=True)
class MoE:
    hidden_size: int
    intermediate_size: int
    num_experts: int = 8
    top_k: int = 2
    #: None = no capacity and no drops: the sorted, grouped-matmul path
    #: (``dropless_forward``); a number = the capacity-bucketed GShard path
    capacity_factor: Optional[float] = 1.25
    min_capacity: int = 4
    #: the expert MLP's form (``ACTIVATIONS``): 'silu_gated' | 'relu_gated'
    #: (``act(x W_gate) * (x W_up)``) | 'gelu' (one ``wi`` stack, not gated)
    activation: str = "silu_gated"
    init_scale: float = 0.02
    #: the no-drop router's description (the capacity gate renormalises
    #: over the kept choices and balances over the first choice, always)
    normalize_weights: bool = True
    balance_loss: str = "gshard_top1"  # | 'topk_share' (sharded_moe.BALANCE_LOSSES)
    #: the no-drop path's router: 'softmax' (``softmax_topk_router``) |
    #: 'sigmoid_bias' (``sigmoid_bias_router``: a ``bias`` leaf that load
    #: moves and no gradient, weights times ``routed_scale``)
    router: str = "softmax"
    routed_scale: float = 1.0
    #: width of the shared expert every token passes, unweighted (0: none)
    shared_width: int = 0
    #: the experts THIS chip holds, ``(first, past the last)`` of
    #: ``num_experts``: the router stays whole, the weight stacks hold these
    #: alone and the layer returns their part of the result (what the other
    #: chips' experts would add is left out, and nothing stands in for them
    #: or their exchange). None: all of them.
    experts_held: Optional[Tuple[int, int]] = None
    #: what the no-drop router reads: 'ffn_input' (the tensor the experts
    #: multiply: the block's stream after the mixer, normed) | 'block_input'
    #: (the block's un-normed input, BEFORE the token mixer runs: the caller
    #: makes the `Routing` with `route` and hands it to `dropless_forward`)
    router_input: str = "ffn_input"
    #: fused Pallas kernel dispatch (ISSUE 11): None = the
    #: ``DSTPU_MOE_KERNEL`` env gate (auto: Pallas on single-chip TPU,
    #: XLA elsewhere); 'xla'/'pallas' pin per-layer (lint entries,
    #: parity tests). The kernel serves the dead-EP composition only —
    #: a live expert/pipeline mesh keeps the GSPMD exchange path.
    kernel: Any = None

    def __post_init__(self):
        if self.activation not in ACTIVATIONS:
            raise ValueError(f"activation {self.activation!r} is none of {ACTIVATIONS}")
        if self.router_input not in ("ffn_input", "block_input"):
            raise ValueError(f"router_input {self.router_input!r} is not 'ffn_input' "
                             "or 'block_input'")
        if not self.dropless and self.router_input != "ffn_input":
            raise ValueError(
                "router_input='block_input' (a router that reads the block's "
                "input before the token mixer) is the no-drop path's "
                "(capacity_factor=None): the capacity path routes inside the layer")
        if not self.dropless and not (self.normalize_weights
                                      and self.balance_loss == "gshard_top1"):
            raise ValueError(
                "the capacity path renormalises the kept weights and takes "
                "its balance loss from the first choice; normalize_weights="
                "False and other balance losses need capacity_factor=None "
                "(the no-drop path)")
        if not self.dropless and (self.router != "softmax" or self.shared_width
                                  or self.experts_held is not None):
            raise ValueError(
                "the sigmoid router, a shared expert and a share of the "
                "experts (under either router: experts_held is the softmax "
                "router's too) are the no-drop path's (capacity_factor=None)")
        if self.router not in ("softmax", "sigmoid_bias"):
            raise ValueError(f"router {self.router!r} is not 'softmax' or "
                             "'sigmoid_bias'")
        lo, hi = self.held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of {self.num_experts} experts")

    @property
    def dropless(self) -> bool:
        return self.capacity_factor is None

    @property
    def gated(self) -> bool:
        """Whether the expert MLP is gated (stacks ``wi_gate`` / ``wi_up``)."""
        return self.activation in GATE_ACTIVATIONS

    @property
    def held(self) -> Tuple[int, int]:
        """The range of experts whose weights this layer holds."""
        return self.experts_held or (0, self.num_experts)

    def init(self, rng, dtype=jnp.float32) -> Params:
        h, f = self.hidden_size, self.intermediate_size
        e = self.held[1] - self.held[0]
        ks = jax.random.split(rng, 4)
        scale = self.init_scale

        def w(r, shape):
            return (jax.random.normal(r, shape, jnp.float32) * scale).astype(dtype)

        params = {"gate": w(ks[0], (h, self.num_experts))}
        if self.gated:
            params["wi_gate"] = w(ks[1], (e, h, f))
            params["wi_up"] = w(ks[2], (e, h, f))
        else:
            params["wi"] = w(ks[1], (e, h, f))
        params["wo"] = w(ks[3], (e, f, h))
        if self.router == "sigmoid_bias":
            params["bias"] = jnp.zeros((self.num_experts,), dtype)
        if self.shared_width:
            sk = jax.random.split(jax.random.fold_in(rng, 1), 3)
            params["shared"] = {"gate_proj": w(sk[0], (h, self.shared_width)),
                                "up_proj": w(sk[1], (h, self.shared_width)),
                                "down_proj": w(sk[2], (self.shared_width, h))}
        return params

    def specs(self) -> Params:
        expert_w = P(EXPERT_AXIS, None, None)
        out = {"gate": P(None, None), "wo": expert_w}
        if self.gated:
            out["wi_gate"] = expert_w
            out["wi_up"] = expert_w
        else:
            out["wi"] = expert_w
        if self.router == "sigmoid_bias":
            out["bias"] = P(None)
        if self.shared_width:
            out["shared"] = {k: P(None, None)
                             for k in ("gate_proj", "up_proj", "down_proj")}
        return out

    def _expert_ffn(self, params: Params, expert_in: jax.Array,
                    dtype) -> jax.Array:
        """The expert FFN as batched einsums over the (expert-sharded)
        expert dim — the grouped-GEMM on the MXU. Operates on any
        capacity extent, so the overlap planner's chunked dispatch can
        run it per capacity chunk (bitwise: each slot's row contracts
        the same operands either way)."""
        if self.gated:
            gate = GATE_ACTIVATIONS[self.activation](jnp.einsum(
                "ech,ehf->ecf", expert_in, params["wi_gate"].astype(dtype)))
            up = jnp.einsum("ech,ehf->ecf", expert_in,
                            params["wi_up"].astype(dtype))
            mid = gate * up
        else:
            mid = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", expert_in,
                                         params["wi"].astype(dtype)))
        return jnp.einsum("ecf,efh->ech", mid, params["wo"].astype(dtype))

    def route(self, params: Params, route_from: jax.Array) -> Routing:
        """The no-drop router over ``route_from`` [batch, seq, hidden]: what
        a block whose router reads its input (``router_input='block_input'``)
        calls BEFORE the token mixer, under ``moe/route/ahead``
        (``dropless_forward`` routes the tensor the experts multiply itself,
        under ``moe/route``). The sort by expert, which needs nothing of the
        mixer either, stays with the rows it moves."""
        b, s, h = route_from.shape
        with jax.named_scope("moe/route"), jax.named_scope("ahead"):
            return self._router(params, route_from.reshape(b * s, h), s)

    def _router(self, params: Params, tokens: jax.Array, rows_per_seq: int) -> Routing:
        """``tokens`` [T, hidden], sequence-major -> float32 logits, the
        ``top_k`` experts a token, their weights, the router losses and the
        assignments each expert drew."""
        # float32 logits at full precision: a bf16 logit moves tokens
        # between experts whose probabilities are close
        logits = jnp.dot(tokens.astype(jnp.float32),
                         params["gate"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        logits = checkpoint_name(logits, "moe_logits")
        if self.router == "sigmoid_bias":
            return Routing(*sigmoid_bias_router(
                logits, params["bias"], self.top_k, normalize=self.normalize_weights,
                routed_scale=self.routed_scale, rows_per_seq=rows_per_seq))
        return Routing(*softmax_topk_router(
            logits, self.top_k, normalize=self.normalize_weights,
            balance_loss=self.balance_loss))

    def dropless_forward(self, params: Params, x: jax.Array,
                         routing: Optional[Routing] = None
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """The no-drop path. x: [batch, seq, hidden] -> (out, router losses
        [2] = (load balancing, z-loss), rows [experts] int32: assignments
        each expert received). ``routing``: what `route` made of another
        tensor than ``x`` (None: routed here, from ``x``).

        The ``tokens x top_k`` assignments are sorted by expert, their rows
        gathered once, the expert FFN run as grouped matmuls over
        ``rows[experts]`` (``pallas_gmm.grouped_matmul``: the in-repo kernel
        or ``jax.lax.ragged_dot`` by its ``choose_route``; the backward
        products are a grouped matmul and one that contracts over the
        ragged rows),
        and each token's k rows weighted and summed in float32. No
        ``[experts, tokens, ..]`` tensor exists forward or backward, an
        expert with no rows costs nothing and one with most of them is
        exact. docs/KERNELS.md has the chip readings that chose this."""
        topo = topo_mod.get_topology() if topo_mod.is_initialized() else None
        if topo is not None and (topo.expert_parallel_size > 1
                                 or topo.pipe_parallel_size > 1):
            raise NotImplementedError(
                "the no-drop MoE path (capacity_factor=None) runs with every "
                "expert on each chip; expert and pipeline parallelism for it "
                f"are not implemented (expert={topo.expert_parallel_size}, "
                f"pipe={topo.pipe_parallel_size}). Use a capacity_factor, or "
                "a mesh without those axes")
        if (routing is None) != (self.router_input == "ffn_input"):
            raise ValueError(
                f"router_input={self.router_input!r}: the routing is made "
                + ("by the caller, before the token mixer (MoE.route)"
                   if routing is None else "here, from the experts' own input"))
        b, s, h = x.shape
        n_tok, dt = b * s, x.dtype
        tokens = x.reshape(n_tok, h)
        if routing is None:
            with jax.named_scope("moe/route"):
                routing = self._router(params, tokens, s)
        eidx, weight, losses, rows = routing
        if self.held == (0, self.num_experts):
            out = self._all_rows(params, tokens, eidx, weight, rows)
        else:
            out = self._held_rows(params, tokens, eidx, weight, rows)
        if self.shared_width:
            with jax.named_scope("moe/shared"):
                sp = params["shared"]
                first = lambda name: checkpoint_name(
                    tokens @ sp[name].astype(dt), name)
                mid = jax.nn.silu(first("gate_proj")) * first("up_proj")
                out = out + (mid @ sp["down_proj"].astype(dt)).astype(jnp.float32)
        return out.astype(dt).reshape(b, s, h), losses, rows

    def _ffn(self, params: Params, rows_in: jax.Array, product) -> jax.Array:
        """The expert MLP over rows, ``product(a, name)`` its matmuls; the
        first products are named for the block's remat policy (what the
        backward may keep: the activation's gradient needs them)."""
        first = lambda name: checkpoint_name(product(rows_in, name), name)
        if self.gated:
            mid = GATE_ACTIVATIONS[self.activation](first("wi_gate")) * first("wi_up")
        else:
            mid = jax.nn.gelu(first("wi"))
        return product(mid, "wo")

    def grouped_products(self, n_tok: int) -> Tuple[Tuple[str, int, int, int, int], ...]:
        """``(name, m, k, n, g)`` of each grouped matmul one forward of the
        no-drop path launches over ``n_tok`` tokens, ``rows [m, k] x stack
        [g, k, n]``, in the order ``_ffn`` calls them: static shapes, for the
        engine's counters."""
        g = self.held[1] - self.held[0]
        m = n_tok * self.top_k
        if g != self.num_experts:
            m = held_capacity(m, g, self.num_experts)
        h, f = self.hidden_size, self.intermediate_size
        first = ("wi_gate", "wi_up") if self.gated else ("wi",)
        return tuple((name, m, h, f, g) for name in first) + (("wo", m, f, h, g),)

    def rows_back(self, n_tok: int) -> Optional[Tuple[int, int, int]]:
        """``(rows, tokens, h)`` of the sum that brings a share's buffer back
        to the tokens (``_sum_held_rows``: the combine forward, and again
        where the backward reruns it, and the dispatch's backward, ``rows``
        gathered a pass), None with every expert held (``_all_rows`` moves
        all ``tokens x top_k`` rows, each one real): static shapes, for the
        engine's counters."""
        g = self.held[1] - self.held[0]
        if g == self.num_experts:
            return None
        return held_capacity(n_tok * self.top_k, g, self.num_experts), n_tok, self.hidden_size

    def _all_rows(self, params, tokens, eidx, weight, rows) -> jax.Array:
        """Every expert is here: all ``tokens x top_k`` assignments sorted
        by expert, gathered, multiplied and combined. -> [T, h] float32."""
        n_tok, h = tokens.shape
        k, dt = self.top_k, tokens.dtype
        with jax.named_scope("moe/route"):
            order, inv = _sorted_by(eidx.reshape(-1))
        with jax.named_scope("moe/dispatch"):
            expert_in = _dispatch_rows(tokens, order, inv, k)
        with jax.named_scope("moe/experts"):
            expert_out = self._ffn(params, expert_in, lambda a, name: pallas_gmm.grouped_matmul(
                a, params[name].astype(dt), rows, _mesh_devices()))
        with jax.named_scope("moe/combine"):
            # the combine's gradient by the routing weights needs the
            # experts' rows as it reads them, in assignment order: named
            # after the gather, so a backward that keeps them gathers once
            picked = checkpoint_name(_unsort_rows(expert_out, order, inv),
                                     "wo").reshape(n_tok, k, h)
            return jnp.sum(picked.astype(jnp.float32) * weight[:, :, None], axis=1)

    def _held_rows(self, params, tokens, eidx, weight, rows) -> jax.Array:
        """This chip's experts' part of the result, [T, h] float32: the
        assignments of the held experts sorted first, THOSE rows gathered
        into a buffer of ``held_capacity`` rows (a static shape: three
        times the even share, multiplied whole), grouped matmuls over the held weight
        stacks, combined under the full-width routing weights. Nothing is
        dropped: the assignments a step has beyond the buffer (the held
        experts can draw up to all ``tokens x top_k``) go through
        ``_overflow_rows``, which costs nothing in a step that has none."""
        lo, hi = self.held
        n_tok, h = tokens.shape
        k, dt, nh = self.top_k, tokens.dtype, hi - lo
        cap = held_capacity(n_tok * k, nh, self.num_experts)
        with jax.named_scope("moe/route"):
            local = eidx.reshape(-1) - lo
            key = jnp.where((local >= 0) & (local < nh), local, nh)
            order, inv = _sorted_by(key)
            order = order[:cap]
            ends = jnp.cumsum(rows[lo:hi])
            held = ends[-1]
            # the groups as far as the buffer reaches; the last one takes the
            # buffer's unfilled rows with it, so that the grouped matmuls
            # multiply ``cap`` rows whatever the router did: a step's time
            # does not follow the held experts' luck (those rows are some
            # token's, their results are selected away and their gradients 0)
            ends = jnp.minimum(ends, cap)
            filled = ends[-1]
            in_buffer = jnp.diff(jnp.append(ends[:-1], cap), prepend=0)
            perm, by_token = _token_order(order, filled)
        with jax.named_scope("moe/dispatch"):
            expert_in = _dispatch_held_rows(tokens, order, perm, by_token, filled, (n_tok, k))
        with jax.named_scope("moe/experts"):
            expert_out = checkpoint_name(
                self._ffn(params, expert_in, lambda a, name: pallas_gmm.grouped_matmul(
                    a, params[name].astype(dt), in_buffer, _mesh_devices())), "wo")
        with jax.named_scope("moe/combine"):
            out = _combine_held_rows(expert_out, weight, order, inv, perm, by_token, filled)
        if cap == n_tok * k:
            return out
        with jax.named_scope("moe/experts"):
            # [held, T]: each token's weight for each held expert, over the
            # assignments the buffer had no room for
            beyond = (inv >= cap).reshape(eidx.shape) & (eidx >= lo) & (eidx < hi)
            onehot = (eidx[:, :, None] - lo == jnp.arange(nh)).astype(jnp.float32)
            by_expert = jnp.einsum("tk,tke->et", jnp.where(beyond, weight, 0.0), onehot)
            stacks = {n: params[n] for n in ("wi_gate", "wi_up", "wi", "wo")
                      if n in params}
            return out + _overflow_rows(self, held > cap, tokens, by_expert, stacks)

    def _every_token(self, tokens, by_expert, stacks) -> jax.Array:
        """sum_e by_expert[e] x E_e(tokens), every held expert over every
        token, one expert at a time. -> [T, h] float32."""
        dt = tokens.dtype

        @jax.checkpoint
        def one(acc, xs):
            w, weights = xs
            y = self._ffn(weights, tokens, lambda a, name: a @ weights[name].astype(dt))
            return acc + y.astype(jnp.float32) * w[:, None], None

        return jax.lax.scan(one, jnp.zeros(tokens.shape, jnp.float32),
                            (by_expert, stacks))[0]

    def __call__(self, params: Params, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """x: [batch, seq, hidden] → (out, aux_loss); the no-drop path's
        aux is its two router losses, [2]."""
        if self.dropless:
            return self.dropless_forward(params, x)[:2]
        b, s, h = x.shape
        tokens = x.reshape(b * s, h)
        n_tok = b * s
        cap = _capacity(n_tok, self.num_experts, self.capacity_factor, self.min_capacity)

        # Fused Pallas kernel path (ISSUE 11, ops/transformer/pallas_moe
        # .py): route select + capacity scatter, the slot gather + wire
        # cast, and the grouped FFN + combine-scatter run as hand
        # kernels instead of the XLA op chain. DSTPU_MOE_KERNEL follows
        # the PR 10 discipline (auto = Pallas on single-chip TPU, XLA
        # elsewhere; 'xla' = bitwise hatch — this method's XLA path is
        # untouched; 'pallas' = force, interpret off-TPU). The kernel
        # serves the dead-EP/no-pipe composition: with a live expert
        # axis the exchange is GSPMD-mediated and stays XLA (the
        # multi-chip note in docs/KERNELS.md).
        from ..ops.transformer import pallas_moe
        from ..runtime import overlap_planner as op_mod
        if pallas_moe.moe_kernel_resolution(
                top_k=self.top_k, activation=self.activation,
                dtype=x.dtype, tokens=n_tok,
                num_experts=self.num_experts, hidden=h,
                kernel=self.kernel) == "pallas":
            # wired under the planner's chunked-dispatch scan: the plan's
            # scan-carry placement chunks the capacity dim so chunk c+1's
            # gather+cast launch issues from the carry under chunk c's
            # FFN+combine kernel (depth 1 — the kernel executor's clamp).
            # The carry rides the FUSED combine epilogue only: shapes
            # over the fused-combine VMEM budget run the split FFN +
            # token-major combine launches straight-line, so derive no
            # chunk count there (a derived nc the kernel cannot execute
            # would silently overstate the schedule).
            plan = op_mod.plan_for("moe-dispatch")
            nbytes = self.num_experts * cap * h * x.dtype.itemsize
            nc = (op_mod.moe_chunks_for_bytes(nbytes)
                  if (plan.placement == op_mod.PLACEMENT_SCAN_CARRY
                      and pallas_moe.moe_fused_combine_fits(n_tok, h))
                  else 1)
            fwd = pallas_moe.make_moe_forward(
                top_k=self.top_k, capacity=cap,
                activation=self.activation, mask_pad=False, n_chunks=nc)
            out2d, aux = fwd(params, tokens)
            return out2d.reshape(b, s, h), aux

        logits = tokens @ params["gate"].astype(x.dtype)
        eidx, pos, keep, weight, aux, _ = top_k_gating_indices(
            logits, self.top_k, cap)
        e = self.num_experts

        # Dispatch by GATHER, not by one-hot einsum: the reference's
        # "tec,th->ech" dispatch matmul costs O(tokens*experts*cap*hidden)
        # — quadratic in tokens (experts*cap ~ top_k*cf*tokens). Building
        # the inverse slot→token map is an O(tokens*k) integer scatter and
        # the row gather moves O(experts*cap*hidden) bytes with zero FLOPs
        # (the grouped-GEMM data layout the reference needs cutlass
        # moe_gather/moe_scatter kernels for, ragged_ops.cpp:20-47).
        slot = jnp.where(keep, eidx * cap + pos, e * cap).reshape(-1)
        src = jnp.zeros((e * cap + 1,), jnp.int32).at[slot].set(
            jnp.repeat(jnp.arange(n_tok, dtype=jnp.int32), self.top_k) + 1,
            mode="drop")[:e * cap]
        # under PIPELINE composition the dispatch/combine gathers sit inside
        # the stage vmap, where the partitioner cannot move their operands
        # from the stage-propagated sharding to the expert layout without an
        # "involuntary full rematerialization" fallback (a silent perf
        # cliff); pin the gather boundaries explicitly there. In the pure-EP
        # regime the propagated shardings are already right — and the pinned
        # replication would CHANGE the exchange pattern — so this is
        # trace-time conditional on a real pipe axis.
        pipelined = (topo_mod.is_initialized()
                     and topo_mod.get_topology().pipe_parallel_size > 1)
        if pipelined:
            tokens = _c(tokens, P(BATCH_AXES, None))
        # Unfilled capacity slots gather token 0's row UNMASKED: the
        # combine below never reads them (their combine weight is 0 and no
        # token's slot index points at them), so their contribution to
        # every output — and therefore their backward cotangent — is
        # exactly zero *as long as the pad rows' activations stay finite*.
        # Masking them with a where() would add a full [e*cap, h] select
        # plus its backward per layer for bytes that are already dead.
        # fp16 keeps the mask: a pad row routed through an expert it was
        # never assigned to can overflow fp16's range, and 0 * inf = NaN
        # would poison the expert-weight gradients (bf16/fp32 share
        # fp32's exponent range, so a pad row overflows only where a real
        # row would too). DSTPU_MOE_MASK_PAD=1 forces the masked form
        # (trace-time; for A/B).
        # Dispatch/combine transport plan (ISSUE 8, docs/COLLECTIVES.md):
        # the expert exchange is GSPMD-mediated (the constraints below make
        # the partitioner emit the all-to-all), so the wire narrows by
        # CASTING the dispatched activations — bf16 by default, exact
        # no-op when the model already computes in a <=2-byte dtype. Only
        # a live expert axis pays an exchange; without one the cast would
        # cost accuracy for zero wire bytes.
        from .. import comm as dist
        from ..runtime import overlap_planner as op_mod
        live_ep = (topo_mod.is_initialized()
                   and topo_mod.get_topology().expert_parallel_size > 1)
        wire_dtype = None
        if live_ep and x.dtype.itemsize > 2:
            tp = dist.resolve_transport(
                "activation", "all_to_all", e * cap * h * x.dtype.itemsize,
                (EXPERT_AXIS,))
            if tp.width == "bf16":
                wire_dtype = jnp.bfloat16

        def _exchange(t, spec):
            if wire_dtype is None:
                return _c(t, spec)
            return _c(t.astype(wire_dtype), spec).astype(x.dtype)

        mask_pad = (x.dtype == jnp.float16
                    or os.environ.get("DSTPU_MOE_MASK_PAD") == "1")

        # Overlap plan (ISSUE 9, runtime/overlap_planner.py): the planner's
        # scan-carry placement chunks the dispatch over the CAPACITY dim —
        # chunk c+1's token gather + expert exchange are issued from the
        # scan carry while chunk c's expert FFN computes, so the dispatch
        # wire hides under expert compute instead of fully preceding it.
        # Exact: each slot's gather row and FFN contraction are identical;
        # only launch placement changes. Since ISSUE 11 the COMBINE-side
        # exchange also rides the scan body: each chunk's expert rows
        # re-gather to tokens under a chunk mask right after that chunk's
        # FFN (every token's k slots span chunks, so the mask selects the
        # choices whose capacity slot lives in this chunk), which puts
        # nc-1 of the nc combine launches inside the body's circular
        # slack window — Layer D classifies them overlapped — leaving
        # only the LAST chunk's combine as the budget-justified epilogue
        # edge. Chunking is clamped to a divisor of the capacity and
        # skipped entirely under pipeline composition (the stage vmap
        # pins its own constraints) or a dead expert axis.
        plan = op_mod.plan_for("moe-dispatch")
        # the plan decides PLACEMENT; the chunk count scales with THIS
        # layer's actual exchange bytes (the committed n_chunks records
        # the audit entry's decision, not a production layer's). top_k>2
        # pins nc=1: the masked per-chunk combine below reassociates a
        # token's k weighted terms into chunk order, exact only while at
        # most two terms exist — beyond that the unchunked program is the
        # exactness contract.
        nc = (op_mod.moe_chunks_for_bytes(e * cap * h * x.dtype.itemsize)
              if (plan.placement == op_mod.PLACEMENT_SCAN_CARRY
                  and live_ep and not pipelined and self.top_k <= 2)
              else 1)
        while nc > 1 and cap % nc:
            nc -= 1

        if nc > 1:
            capc = cap // nc
            src_chunks = src.reshape(e, nc, capc).transpose(1, 0, 2)
            # token-side chunk membership: choice (t, k)'s capacity slot
            # lives in chunk pos // capc at local position pos % capc
            chunk_of = pos // capc
            pos_in = pos - chunk_of * capc

            def fetch(sc):
                flat = sc.reshape(-1)
                g = tokens[jnp.maximum(flat - 1, 0)]
                if mask_pad:
                    g = jnp.where((flat > 0)[:, None], g,
                                  jnp.zeros((), x.dtype))
                return _exchange(g.reshape(e, capc, h),
                                 P(EXPERT_AXIS, BATCH_AXES, None))

            def combine_chunk(y_c, c_idx):
                # masked per-chunk re-gather (ISSUE 11): the return
                # exchange materializes at this row gather, so placing it
                # here — inside the scan body / before the epilogue's
                # final adds — is what moves the combine wire off the
                # step edge. Algebraically exact vs the whole-capacity
                # epilogue gather for top-k <= 2 (each kept choice
                # contributes from exactly one chunk, masked-out choices
                # multiply by an exact 0, two-term addition commutes) —
                # and bitwise in the pinned tests/unit/moe composition;
                # across a LIVE expert exchange the partitioner may
                # reassociate the shard reduction around the weighted
                # sum, so engine-level parity with the unchunked program
                # is float-tolerance there (same class as the backward,
                # which PR 9 already pinned at tolerance).
                if wire_dtype is not None:
                    y_c = y_c.astype(wire_dtype)
                y_c = _c(y_c, P(EXPERT_AXIS, BATCH_AXES, None))
                flat_c = y_c.reshape(e * capc, h)
                in_chunk = keep & (chunk_of == c_idx)
                rows = flat_c[jnp.where(in_chunk, eidx * capc + pos_in, 0)]
                w_c = (weight * in_chunk).astype(x.dtype)
                return jnp.sum(rows.astype(x.dtype) * w_c[:, :, None],
                               axis=1)

            chunk_elems = e * capc * h
            wire = chunk_elems * (2 if wire_dtype is not None
                                  else x.dtype.itemsize)
            logical = chunk_elems * x.dtype.itemsize
            # prologue fetch is the pipeline edge (nothing to hide it);
            # the in-scan prefetches overlap the previous chunk's FFN
            dist.record_collective("all_to_all", logical, (EXPERT_AXIS,),
                                   overlapped=False, wire_bytes=wire)
            dist.record_collective("all_to_all", logical, (EXPERT_AXIS,),
                                   overlapped=True, count=nc - 1,
                                   wire_bytes=wire)
            # combine side: nc-1 masked re-gathers ride the scan body
            # (hidden in the circular slack window); the last chunk's
            # combine is the epilogue edge
            dist.record_collective("all_to_all", logical, (EXPERT_AXIS,),
                                   overlapped=True, count=nc - 1,
                                   wire_bytes=wire)
            dist.record_collective("all_to_all", logical, (EXPERT_AXIS,),
                                   overlapped=False, wire_bytes=wire)
            cur = fetch(src_chunks[0])

            def body(carry, xs_c):
                payload, acc = carry
                nxt = fetch(xs_c["src"])  # independent of the FFN below
                y_c = self._expert_ffn(params, payload, x.dtype)
                acc = acc + combine_chunk(y_c, xs_c["idx"])
                return (nxt, acc), None

            (last, acc), _ = jax.lax.scan(
                body, (cur, jnp.zeros((n_tok, h), x.dtype)),
                {"src": src_chunks[1:],
                 "idx": jnp.arange(nc - 1, dtype=jnp.int32)})
            y_last = self._expert_ffn(params, last, x.dtype)
            out = acc + combine_chunk(y_last, jnp.int32(nc - 1))
            return out.reshape(b, s, h), aux
        else:
            gathered = tokens[jnp.maximum(src - 1, 0)]
            if mask_pad:
                gathered = jnp.where((src > 0)[:, None], gathered,
                                     jnp.zeros((), x.dtype))
            if pipelined:
                gathered = _c(gathered, P(None, None))
            expert_in = gathered.reshape(e, cap, h)
            # all-to-all over ICI: expert dim sharded across the expert axis
            expert_in = _exchange(expert_in, P(EXPERT_AXIS, BATCH_AXES, None))
            # expert FFN as batched einsum over the (sharded) expert dim
            expert_out = self._expert_ffn(params, expert_in, x.dtype)

        # inverse all-to-all + combine back to tokens: per-token gather of
        # its k slots, weighted sum — O(tokens*k*hidden). The return
        # exchange materializes at the row gather below (the partitioner
        # reshards the expert-sharded rows to the token layout there), so
        # the wire cast must PERSIST through the gather — cast back only
        # on the picked rows.
        if wire_dtype is not None:
            expert_out = expert_out.astype(wire_dtype)
        expert_out = _c(expert_out, P(EXPERT_AXIS, BATCH_AXES, None))
        flat_out = expert_out.reshape(e * cap, h)
        picked = flat_out[jnp.where(keep, eidx * cap + pos, 0)]  # [t, k, h]
        picked = picked.astype(x.dtype)
        w = (weight * keep).astype(x.dtype)
        out = jnp.sum(picked * w[:, :, None], axis=1)
        return out.reshape(b, s, h), aux
