"""Activation checkpointing.

Counterpart of the reference ``runtime/activation_checkpointing/
checkpointing.py`` (``CheckpointFunction`` :484, ``checkpoint`` :989,
``partition_activations`` :373, ``CudaRNGStatesTracker`` :122).

On TPU the core capability is ``jax.checkpoint`` (rematerialization): XLA
recomputes saved activations in backward instead of storing them, which is
the same FLOPs-for-memory trade the reference implements with autograd
shims. The extra modes map as:

- ``partition_activations`` (slice saved activations across MP ranks):
  a remat *policy* that saves only layer boundaries plus a sharding
  constraint over the ``model`` axis on what is saved — ``checkpoint`` here
  accepts a spec to apply to saved residuals.
- ``cpu_checkpointing``: ``jax.checkpoint`` policies with offload
  (``save_and_offload_only_these_names``) — exposed via ``offload=True``.
- RNG state tracking: unnecessary; JAX PRNG keys are explicit values that
  replay identically in recompute.

The config-driven entry (``configure``/``checkpoint``) keeps the reference's
module-level API so ported training code works.

**What ``remat=True`` keeps** (``KEEP_PRODUCTS``, the models' default
``remat_policy``). A block's backward keeps the values that cost a matmul or
a kernel to make and recomputes what is elementwise or a reduction over a
row. The producers name them with ``jax.ad_checkpoint.checkpoint_name``
(``models/transformer.py``, ``moe/layer.py``, ``pallas_flash.py``; they import
nothing from here), :func:`checkpointed` reads the names and their bytes off
the block's own differentiated jaxpr, :func:`choose_saved` takes the prefix
of :data:`SAVE_ORDER` that fits a byte budget, and :func:`saved_budget` makes
that budget of the room the engine reads from the device when the step is
first traced (:class:`Budget`). Without a reading everything named is saved.

**What the budget is charged first** (PR 35): every layer's input, and the
step's own working set in bytes, reckoned from the step's shapes while it is
traced: the largest block's forward and backward as :func:`live_bytes` walks
their jaxpr (every kind of block a step runs is reckoned before the first is
decided: they run one after another, so the largest counts), and what lives
outside the blocks, which the model fills in (a head's float32 logits and
their gradient). The sum is charged at :data:`WORKING_SHARE`, one number
fitted on the chip; what is left, over :data:`STACK_COST`, is the budget.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import Literal, jaxpr_as_fun

from ...telemetry import setup_spans
from ..topology import MODEL_AXIS

_CONFIG = {
    "partition_activations": False,
    "contiguous_memory_optimization": False,
    "cpu_checkpointing": False,
    "num_checkpoints": None,
    "synchronize": False,
    "profile": False,
    "policy": "full",
}

#: the models' default ``remat_policy``: keep what a matmul or a kernel
#: produced (the values named in :data:`SAVE_ORDER`), inside the budget
KEEP_PRODUCTS = "matmul_and_kernel_outputs"

POLICIES = {
    # save nothing; recompute everything (classic gradient checkpointing)
    "full": None,
    "nothing_saveable": None,
    # save matmul outputs (skip recomputing the big GEMMs)
    "dots_saveable": "dots_saveable",
    "checkpoint_dots": "dots_saveable",
    # save matmuls that have no batch dims (weight-stationary)
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims_saveable",
    "checkpoint_dots_with_no_batch_dims": "dots_with_no_batch_dims_saveable",
}


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None,
              policy: Optional[str] = None) -> None:
    """Reference ``checkpointing.configure`` — stores module-level flags."""
    if deepspeed_config is not None:
        ac = getattr(deepspeed_config, "activation_checkpointing_config", None)
        if ac is not None:
            _CONFIG.update(
                partition_activations=ac.partition_activations,
                contiguous_memory_optimization=ac.contiguous_memory_optimization,
                cpu_checkpointing=ac.cpu_checkpointing,
                num_checkpoints=ac.number_checkpoints,
                synchronize=ac.synchronize_checkpoint_boundary,
                profile=ac.profile,
                policy=ac.policy,
            )
    for key, value in (("partition_activations", partition_activations),
                       ("contiguous_memory_optimization", contiguous_checkpointing),
                       ("num_checkpoints", num_checkpoints),
                       ("cpu_checkpointing", checkpoint_in_cpu),
                       ("synchronize", synchronize),
                       ("profile", profile),
                       ("policy", policy)):
        if value is not None:
            _CONFIG[key] = value


def is_configured() -> bool:
    return True


def resolve_policy(name: Optional[str]):
    """An explicit policy name -> what ``jax.checkpoint(policy=)`` takes:
    a row of ``POLICIES`` or any ``jax.checkpoint_policies`` name; another
    name is refused with the names there are."""
    if not name:
        name = _CONFIG["policy"]
    mapped = POLICIES.get(name, name)
    if mapped is None:
        return None
    if not hasattr(jax.checkpoint_policies, mapped):
        raise ValueError(
            f"remat policy {name!r} is none of {[KEEP_PRODUCTS, *POLICIES]} "
            "and no name of jax.checkpoint_policies")
    return getattr(jax.checkpoint_policies, mapped)


# ---------------------------------------------------------------------------
# what remat=True keeps: named values inside a byte budget
# ---------------------------------------------------------------------------

#: The names a block's backward may keep, in the order they are taken, in
#: groups that are kept or recomputed together (the flash kernel runs again
#: unless both its results are kept; XLA merges matmuls that share an input,
#: so q and k kept without v spared nothing on the chip). The order is what a
#: kept byte spared the backward as MEASURED on the v5e in both benchmark
#: cells (PERF.md, PR 30; ms of a step for each GB kept): the kernel's output
#: and row statistics (22), the no-drop experts' grouped products and the
#: router's float32 logits (19), the dense MLP's first product and the
#: attention output projection (4.0), the q, k, v projections (3.8: their
#: layout copies in front of the kernel are recomputed either way). A value
#: under any other name is never kept: an XLA attention route's ``[B,H,S,S]``
#: scores (``attn_big``) are no candidate.
#: At 16,384 tokens a step (PERF.md, PR 35, the two long-sequence cells, the
#: fill probe's synced steps): the kernel's pair with the router's logits
#: spared 45 and 68 ms a GB (0.87 and 0.51 GB kept: a second ``flash_fwd`` a
#: layer), the experts' first two products 15-24 and 12 (their launches of
#: 1.25 ms), the experts' rows after the combine's gather 0 (1.06 GB in the
#: latent-attention cell, 715.3 against 714.9 ms): the order holds, and its
#: third group is worth its bytes only where they are few.
#: Latent attention's and the gate's products (PR 32) stand where what a
#: kept byte spares puts them, reckoned and still not measured (no cell's
#: budget reaches them): ``attn_gate``
#: and ``kv_latent`` are products over the hidden size like ``o_proj`` and
#: ``q_proj`` (2 x hidden FLOPs recomputed for each 2 bytes kept) and join
#: their groups' neighbourhood; ``kv_up`` contracts over the latent rank, a
#: quarter of that a byte, and comes last. A shared expert's first products
#: are a dense MLP's and take its names (``gate_proj``, ``up_proj``).
#: EVA's summaries (``eva_kbar``, ``eva_vbar``; one a chunk of 16: a
#: sixteenth of k and v's bytes) come second, reckoned and not measured: kept,
#: they spare the backward the in-chunk softmax and two weighted sums over
#: the whole of k and v, a pass bound by memory. They are named where a
#: layer's heads run at once; where they run in groups (a 32,768-row step)
#: nothing inside a group is named, since a value kept from inside a group's
#: own ``jax.checkpoint`` would be stacked over the groups, whole. What
#: OUTLIVES the groups is named: the branch's output, the float32 sum of the
#: groups' output projections cast to the stream's dtype, is the value the
#: ungrouped path calls ``o_proj`` and takes that name, once a layer (PR 43).
#: It is such a row's only candidate. Kept (1.07 GB over four layers of
#: ``bf16[1, 32768, 4096]``), it is the MLP's input in the block's recompute,
#: which then drops the group scan whole: a group's forward runs twice a
#: step, not three times. On the chip (PERF.md, PR 43) the kept gigabyte
#: raised the step's true peak by 1.07 GB (14.09 -> 15.16 of 16.91: a kept
#: byte at 1.0) and spared 216 ms of a 1,975 ms step, 201 ms a GB: the
#: dearest reading of this order, since one name stands for a whole branch.
#: EVA's two launches name their residuals after their tags
#: (``attn_o_eva_local``, ...), which this order does not list: they are
#: made again.
#: A learned selection's values (PR 48) lead the order, reckoned from what a
#: kept byte spares and not measured one by one (the cell's budget keeps all
#: of them): ``indexer_kl_*``, the three gradients the KL's forward takes in its
#: one pass over every head's scores (45 MB a layer at 16,384 rows; made again
#: they cost that whole pass, 2.8 TFLOP a layer in XLA); ``dsa_mask``, the
#: selection as its readers unpack it (bits, int8 ``[B, S / 8, S]``, 33.6 MB a
#: layer at the Keye cell's row, since PR 50; made again it costs the indexer's scores over every visible pair and the
#: threshold); the selected launch's pair ``attn_lse_dsa`` / ``attn_o_dsa`` as
#: the plain launch's pair. The indexer's two projections (``indexer_q``,
#: ``indexer_k``) are products over the hidden size and stand with ``q_proj``.
#: Latent attention's two-width launch (PR 55) names its pair after its tag
#: (``attn_lse_mla`` / ``attn_o_mla``) and stands right after the plain pair,
#: for the plain pair's reason; the compressed query's two products
#: (``q_latent`` over the hidden size, ``q_b_proj`` over the rank, which stands in
#: ``q_proj``'s place) join the projections' group. Hyper-connections name
#: nothing: the coefficients, the Sinkhorn rounds and both mixings are made
#: again in the backward (arXiv:2512.24880's own choice), and the carry the
#: budget is charged for every layer is the block's real one, the n streams
#: (``_bytes(carry)``: 28,672 B a token at n = 4 x 3584 in bfloat16).
#: A scan layer's values (PR 57) stand where what a kept byte spares puts them,
#: reckoned and not measured one by one: ``ssm_m`` and ``ssm_state``, the scan
#: kernel's result and its chunks' entry states (10,240 + 2,560 B a token at 5120
#: channels of 16 states in chunks of 128; made again they cost the whole
#: forward scan, vector-unit work the matrix unit cannot hide), lead as the flash
#: pairs do, and differential attention's launches name their pair after their
#: tag (``attn_lse_diff`` / ``attn_o_diff``: two launches a layer share the
#: names, so the budget reckons one launch's bytes for both, PR 48's rule, and
#: the pair is kept or made again together); ``ssm_in`` and ``gmu_in`` are
#: products over the hidden size like the MLP's first and join ``o_proj``'s
#: neighbourhood; ``kv_proj`` (a mixed stack's fused key and value projection)
#: stands in front of ``k_proj``'s group, and ``ssm_dt`` (contracted over dt's
#: rank, 160) and ``ssm_x`` (192 wide), cheap to make again, beside it.
SAVE_ORDER = (("indexer_kl_dq", "indexer_kl_dk", "indexer_kl_dw"), ("dsa_mask",), ("attn_lse_dsa", "attn_o_dsa"),
              ("attn_lse", "attn_o"), ("attn_lse_mla", "attn_o_mla"),
              ("attn_lse_diff", "attn_o_diff"), ("ssm_m", "ssm_state"),
              ("eva_kbar", "eva_vbar"),
              ("moe_logits",), ("wi_gate", "wi_up"),
              ("wi",), ("wo",), ("fc_in",), ("gate_proj", "up_proj"),
              ("o_proj",), ("attn_gate",), ("ssm_in", "gmu_in"),
              ("kv_proj",), ("ssm_dt", "ssm_x"),
              ("q_proj", "k_proj", "v_proj", "kv_latent", "q_latent", "q_b_proj",
               "indexer_q", "indexer_k"),
              ("kv_up",))

#: What a saved byte costs the step's peak, measured on the chip by filling
#: the device until a step fails (PERF.md, PR 30): 1.0 to 1.2 once something
#: is kept (the layer scan keeps a ``[layers, ...]`` stack and copies a
#: layer's slice out of it before the backward reads it).
STACK_COST = 1.2

#: What the step's reckoned working set (:attr:`Budget.working_bytes`) is
#: charged at: the walk counts every link of an elementwise chain that XLA
#: fuses, and the head and a block are not live together. Fitted on the chip
#: to the fill probe's peaks of four cells (PERF.md, PR 35).
WORKING_SHARE = 0.4


def choose_saved(candidates: Mapping[str, int],
                 budget_bytes: Optional[int]) -> Tuple[str, ...]:
    """The names to save. ``candidates``: name -> bytes the value takes over
    all layers. Whole groups are taken in :data:`SAVE_ORDER` while the
    running total fits ``budget_bytes``: a prefix, so a cheaper group never
    displaces a dearer one. ``None`` is no budget and saves every candidate
    the order lists; 0 saves none. Pure: bytes and one number in, names
    out."""
    saved, total = [], 0
    for group in SAVE_ORDER:
        names = [n for n in group if n in candidates]
        total += sum(candidates[n] for n in names)
        if budget_bytes is not None and total > budget_bytes:
            break
        saved += names
    return tuple(saved)


def saved_budget(room_bytes: Optional[int], layers: int, carry_bytes: int,
                 working_bytes: int) -> Optional[int]:
    """The bytes the saved values may take, of ``room_bytes``: what the
    device can give the step's activations (:class:`Budget`). First comes
    what the step needs besides: every layer's input (``carry_bytes``, the
    scan's own residual) and its working set (``working_bytes`` as
    :attr:`Budget.working_bytes` reckons it, at :data:`WORKING_SHARE`);
    what is left is divided by :data:`STACK_COST`. ``None`` (no reading)
    stays ``None``: no budget. Pure."""
    if room_bytes is None:
        return None
    left = room_bytes - layers * carry_bytes - WORKING_SHARE * working_bytes
    return max(0, int(left / STACK_COST))


@dataclasses.dataclass
class Budget:
    """What an engine hands a model's differentiated call (``remat_budget=``):
    ``room_bytes``, what the device can give the step's activations, counted
    over the whole batch (free memory less the gradients, times the ways the
    mesh splits an activation), or ``None`` where it cannot be read. The
    step's working set is reckoned while it is traced, in two parts:
    ``outside_bytes``, what lives outside the blocks, which the model knows
    and fills in (a head's float32 logits and their gradient), and
    ``block_bytes``, the largest block's forward and backward as
    :func:`live_bytes` walks them, which every :func:`checkpointed` block
    raises when it is reckoned (blocks run one after another, so the largest
    counts). Each block under ``KEEP_PRODUCTS`` writes what it decided into
    ``totals`` while it is traced."""
    room_bytes: Optional[int]
    totals: Dict[str, Any] = dataclasses.field(default_factory=dict)
    outside_bytes: int = 0
    block_bytes: int = 0

    @property
    def working_bytes(self) -> int:
        return self.outside_bytes + self.block_bytes


def _aval_bytes(var) -> int:
    aval = var.aval
    return aval.size * aval.dtype.itemsize if hasattr(aval, "dtype") else 0


def _bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def named_bytes(jaxpr) -> Dict[str, int]:
    """name -> bytes of every ``checkpoint_name``d value in ``jaxpr`` and
    the jaxprs inside it (not a Pallas kernel's body)."""
    found: Dict[str, int] = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            found[eqn.params["name"]] = _aval_bytes(eqn.outvars[0])
        elif eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found.update(named_bytes(sub))
    return found


#: results XLA never writes out by themselves (the consumer reads the
#: smaller operand)
_NEVER_WRITTEN = frozenset({"broadcast_in_dim", "iota"})


def live_bytes(jaxpr) -> int:
    """The most bytes live at once while ``jaxpr`` runs in program order,
    beside its inputs: an equation's results from where they are made to
    their last use, the jaxpr's own results to its end; inside an equation
    that holds jaxprs (a ``pjit``, a loop's body, a branch) what the worst of
    them holds; a ``pallas_call``'s results as declared, its body not looked
    into. A ``checkpoint_name`` is its operand under another name. An upper
    bound of what the compiled program holds, since XLA fuses elementwise
    chains of which every link counts here. Pure: a jaxpr in, bytes out."""
    is_var = lambda v: not isinstance(v, Literal)
    alias: Dict[Any, Any] = {}
    last: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name == "name" and is_var(eqn.invars[0]):
            alias[eqn.outvars[0]] = alias.get(eqn.invars[0], eqn.invars[0])
        for v in filter(is_var, eqn.invars):
            last[alias.get(v, v)] = i
    for v in filter(is_var, jaxpr.outvars):
        last[alias.get(v, v)] = len(jaxpr.eqns)
    held: Dict[Any, int] = {}
    live = peak = 0
    for i, eqn in enumerate(jaxpr.eqns):
        prim = eqn.primitive.name
        if prim == "name":
            continue
        made = 0 if prim in _NEVER_WRITTEN else sum(map(_aval_bytes, eqn.outvars))
        inside = 0 if prim == "pallas_call" else max(
            (live_bytes(getattr(sub, "jaxpr", sub))
             for sub in jax.core.jaxprs_in_params(eqn.params)), default=0)
        peak = max(peak, live + max(made, inside))
        if prim not in _NEVER_WRITTEN:
            for v in eqn.outvars:
                if v in last:            # a result nothing reads is gone at once
                    held[v] = _aval_bytes(v)
                    live += held[v]
        for v in {alias.get(v, v) for v in filter(is_var, eqn.invars)}:
            if last[v] == i:
                live -= held.pop(v, 0)   # an input of the jaxpr was never held
    return peak


def _backward_of(block: Callable) -> Callable:
    """``block``'s forward and backward as one function of its arguments
    (the cotangents are ones: a traced shape, no value)."""
    def both(*args):
        out, pull = jax.vjp(block, *args)
        return pull(jax.tree.map(
            lambda o: jnp.ones(o.shape, o.dtype)
            if jnp.issubdtype(o.dtype, jnp.inexact)
            else np.zeros(o.shape, jax.dtypes.float0), out))
    return both


class _KeptBlock:
    """``block_fn(carry, layer)`` under ``KEEP_PRODUCTS``, one of ``layers``
    blocks held to ``budget``. The block is traced once for each shape it is
    given: to its own jaxpr, and that jaxpr's forward and backward to one
    more, off which the names, their bytes and the block's live bytes are
    read (a custom VJP's residuals are named in its forward rule, which only
    differentiation traces). :meth:`reckon` does that much and may be called
    ahead for every kind of block of a step, so that each is held to the
    largest; calling the block decides and applies the policy."""

    def __init__(self, block_fn: Callable, layers: int, budget: Optional[Budget]):
        self.block_fn, self.layers, self.budget = block_fn, layers, budget
        self._traced: Dict[Any, Any] = {}

    def reckon(self, carry, layer):
        args = jax.tree.leaves((carry, layer))
        key = (jax.tree.structure((carry, layer)),
               tuple((x.shape, str(x.dtype)) for x in args))
        if key not in self._traced:
            closed, out = jax.make_jaxpr(self.block_fn, return_shape=True)(carry, layer)
            # the block's own trace, above, the step needs anyway; what the
            # plan adds to it is a span (``setup_totals["remat_plan_s"]``;
            # the choice itself, in __call__, is a few dict operations)
            with setup_spans.remat_plan():
                both = jax.make_jaxpr(_backward_of(jaxpr_as_fun(closed)))(*args).jaxpr
                self._traced[key] = closed, out, named_bytes(both)
                if self.budget is not None:
                    self.budget.block_bytes = max(
                        self.budget.block_bytes,
                        _bytes((args, out)) + live_bytes(both))
        return self._traced[key]

    def __call__(self, carry, layer):
        closed, out, named = self.reckon(carry, layer)
        budget = self.budget
        candidates = {n: self.layers * named[n] for group in SAVE_ORDER
                      for n in group if n in named}
        budget_bytes = None if budget is None else saved_budget(
            budget.room_bytes, self.layers, _bytes(carry), budget.working_bytes)
        saved = choose_saved(candidates, budget_bytes)
        if budget is not None:
            budget.totals.update(
                policy=KEEP_PRODUCTS, saved=saved,
                saved_bytes=sum(candidates[n] for n in saved),
                candidate_bytes=sum(candidates.values()),
                room_bytes=budget.room_bytes, budget_bytes=budget_bytes,
                working_bytes=budget.working_bytes,
                block_bytes=budget.block_bytes, outside_bytes=budget.outside_bytes)
        block = jax.checkpoint(jaxpr_as_fun(closed), policy=jax.checkpoint_policies
                               .save_only_these_names(*saved))
        return jax.tree.unflatten(jax.tree.structure(out),
                                  block(*jax.tree.leaves((carry, layer))))


def checkpointed(block_fn: Callable, policy: Optional[str], layers: int,
                 budget: Optional[Budget] = None) -> Callable:
    """``jax.checkpoint(block_fn)`` under the named policy, for a
    ``lax.scan`` of ``block_fn(carry, layer)`` over ``layers`` blocks: the
    one builder of the models' block policy (``TransformerLM.apply`` and
    the pipeline stage both call it). Every explicit name is
    :func:`resolve_policy`'s. ``KEEP_PRODUCTS`` saves the named values that
    :func:`choose_saved` admits into :func:`saved_budget`'s bytes
    (:class:`_KeptBlock`)."""
    if policy != KEEP_PRODUCTS:
        return jax.checkpoint(block_fn, policy=resolve_policy(policy or "full"))
    return _KeptBlock(block_fn, layers, budget)


def checkpoint(function: Callable, *args, policy: Optional[str] = None, **kwargs) -> Any:
    """Reference ``checkpointing.checkpoint`` (:989): run ``function`` under
    rematerialization. Unlike the reference this composes with jit/scan and
    never needs RNG bookkeeping."""
    wrapped = jax.checkpoint(function, policy=resolve_policy(policy))
    return wrapped(*args, **kwargs)


def checkpoint_wrapper(function: Callable, policy: Optional[str] = None) -> Callable:
    """Decorator form used by models."""
    return jax.checkpoint(function, policy=resolve_policy(policy))


class CheckpointFunction:
    """API-parity shim for code importing the autograd class (reference
    :484); ``apply`` simply delegates to :func:`checkpoint`."""

    @staticmethod
    def apply(run_function, *args):
        return checkpoint(run_function, *args)


def model_parallel_reconfigure_tp_seed(seed: int):
    """Reference ``model_parallel_cuda_manual_seed`` (:199) — returns a
    per-TP-rank folded key instead of mutating global RNG state."""
    base = jax.random.PRNGKey(seed)
    try:
        idx = jax.lax.axis_index(MODEL_AXIS)
        return jax.random.fold_in(base, idx)
    except Exception:
        return base


def get_rng_state_tracker():
    """RNG trackers are unnecessary under explicit PRNG keys; kept for import
    parity with Megatron-style code."""
    return None
