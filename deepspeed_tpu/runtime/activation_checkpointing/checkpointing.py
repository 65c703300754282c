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
each kind of block's own differentiated jaxpr (:func:`named_bytes`), the step
takes ONE prefix of :data:`SAVE_ORDER` over every kind's bytes times that
kind's layers (:func:`step_candidates`, :func:`choose_saved`,
:meth:`Budget.saved`), and :func:`saved_budget` makes the budget of the room
the engine reads from the device when the step is first traced
(:class:`Budget`). Without a reading everything named is saved.

**What the budget is charged first** (PR 35; the arithmetic since PR 60):
the input of every block of the step, whatever its kind, and the step's
working set in bytes, whole, reckoned from the step's shapes while it is
traced: the largest block's forward and backward as :func:`live_bytes` walks
their jaxpr the way XLA fuses it (every kind of block a step runs is reckoned
before the first is applied: they run one after another, so the largest
counts), or what lives outside the blocks less the gradients, where that is
more (a head's float32 logits and their gradient, which the model fills in:
the head runs before a gradient exists); beside either, what a boundary layer
hands on to later layers, which outlives the blocks with the gradients live.
What is left is the budget, of which a kept byte takes :data:`STACK_COST`
(:func:`step_costs`). No margin stands beside them, and none can: the walk
reads UNDER the true working set in three of the nine benchmark cells (by
0.44 GB at the worst) and ``STACK_COST`` over the kept bytes' true cost in
eight, and it is their SUM that is never under the runtime's own figure
(``memory_totals["step_extra_bytes"]``), by 36 MB at the nearest; a margin of
more than 48 MB moves the nearest cell's choice inside a twentieth of its room
(PERF.md, PR 60, has the nine rows with the two terms apart).
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

#: The names a step's blocks may keep, in the order they are taken, in
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
#: names; both launches' values are kept, so the budget counts the name twice,
#: since PR 60, and the pair is kept or made again together); ``ssm_in`` and ``gmu_in`` are
#: products over the hidden size like the MLP's first and join ``o_proj``'s
#: neighbourhood; ``kv_proj`` (a mixed stack's fused key and value projection)
#: stands in front of ``k_proj``'s group, and ``ssm_dt`` (contracted over dt's
#: rank, 160) and ``ssm_x`` (192 wide), cheap to make again, beside it. A
#: Mamba-2 layer (PR 65) names its core's result and its chunks' entry states
#: ``ssd_m`` / ``ssd_state`` (8,192 B a token each at 64 x 64 x 128 in chunks of
#: 256), right behind the selective scan's pair and for its reason, and the in
#: projection's two wide products ``ssm_in`` (the convolution's input) and
#: ``ssm_z`` (the gate), which join ``ssm_in``'s group.
SAVE_ORDER = (("indexer_kl_dq", "indexer_kl_dk", "indexer_kl_dw"), ("dsa_mask",), ("attn_lse_dsa", "attn_o_dsa"),
              ("attn_lse", "attn_o"), ("attn_lse_mla", "attn_o_mla"),
              ("attn_lse_diff", "attn_o_diff"), ("ssm_m", "ssm_state"),
              ("ssd_m", "ssd_state"), ("kda_o", "kda_state"),
              ("eva_kbar", "eva_vbar"),
              ("moe_logits",), ("wi_gate", "wi_up"),
              ("wi",), ("wo",), ("fc_in",), ("gate_proj", "up_proj"),
              ("o_proj",), ("attn_gate",), ("ssm_in", "ssm_z", "gmu_in"),
              ("kda_decay", "kda_gate"),
              ("kv_proj",), ("ssm_dt", "ssm_x"),
              ("q_proj", "k_proj", "v_proj", "kv_latent", "q_latent", "q_b_proj",
               "indexer_q", "indexer_k"),
              ("kv_up",))

#: What a saved byte costs the step's peak, measured on the chip by filling
#: the device until a step fails (PERF.md, PR 30): 1.0 to 1.2 once something
#: is kept (the scan keeps a ``[layers, ...]`` stack and copies a layer's slice
#: out of it before the backward reads it; kept step less nothing-kept step
#: over the kept bytes: 1.23 over the dense cell's 36 trips, 1.00 over eight
#: and four, 0.64-0.90 in three expert cells; 0.78 and 0.35 in two cells whose
#: layers run by themselves: PERF.md, PR 60). Every kept byte is charged it,
#: stacked or not: what it over-charges is the one cover the walk's optimism
#: has (the module docstring says by how much).
STACK_COST = 1.2

def choose_saved(costs: Mapping[str, int],
                 budget_bytes: Optional[int]) -> Tuple[str, ...]:
    """The names to save. ``costs``: name -> the bytes keeping the value
    costs the step's peak over all its layers that make it
    (:func:`step_costs`). Whole groups are taken in :data:`SAVE_ORDER` while
    the running total fits ``budget_bytes``: a prefix, so a cheaper group
    never displaces a dearer one. ``None`` is no budget and saves every
    candidate the order lists; 0 saves none. Pure: bytes and one number in,
    names out."""
    saved, total = [], 0
    for group in SAVE_ORDER:
        names = [n for n in group if n in costs]
        total += sum(costs[n] for n in names)
        if budget_bytes is not None and total > budget_bytes:
            break
        saved += names
    return tuple(saved)


def saved_budget(room_bytes: Optional[int], blocks: int, carry_bytes: int,
                 working_bytes: int) -> Optional[int]:
    """The bytes the saved values may cost, of ``room_bytes``: what the
    device can give the step's activations (:class:`Budget`), less what the
    step needs besides: the input of every one of its ``blocks``, whatever
    their kinds (``carry_bytes``, the scan's own residual), its working set
    (``working_bytes`` as :attr:`Budget.working_bytes` reckons it, whole).
    ``None`` (no reading) stays ``None``: no budget. Pure."""
    if room_bytes is None:
        return None
    return max(0, room_bytes - blocks * carry_bytes - working_bytes)


@dataclasses.dataclass
class Kind:
    """One kind of block as a step runs it: in ``layers`` of the step's
    blocks; each takes ``carry_bytes`` of input, holds ``block_bytes`` while
    its forward and backward run (arguments and the carry it returns with
    :func:`live_bytes` of the pair) and names ``named`` (name -> bytes in ONE
    layer, a name two launches share counted twice)."""
    layers: int
    carry_bytes: int
    block_bytes: int
    named: Dict[str, int]


def step_candidates(kinds: Mapping[str, Kind]) -> Dict[str, int]:
    """name -> bytes over the whole step: each kind's bytes times that
    kind's layers, summed over the kinds that name it. Pure."""
    total: Dict[str, int] = {}
    for kind in kinds.values():
        for name, size in kind.named.items():
            total[name] = total.get(name, 0) + kind.layers * size
    return total


def step_costs(kinds: Mapping[str, Kind]) -> Dict[str, int]:
    """name -> what keeping it costs the step's peak: :data:`STACK_COST` for
    each of its bytes in every layer that makes it. Pure."""
    return {name: int(np.ceil(round(STACK_COST * size, 3)))
            for name, size in step_candidates(kinds).items()}


@dataclasses.dataclass
class Budget:
    """What an engine hands a model's differentiated call (``remat_budget=``):
    ``room_bytes``, what the device can give the step's activations, counted
    over the whole batch (free memory less the gradients, times the ways the
    mesh splits an activation), or ``None`` where it cannot be read. The
    step's working set is reckoned while it is traced, in three parts:
    ``outside_bytes``, what lives outside the blocks, and ``handed_bytes``,
    what a boundary layer hands on to later layers with its cotangent, both
    of which the model knows and fills in, and ``block_bytes``, the largest
    block's forward and backward as :func:`live_bytes` walks them, which every
    :func:`checkpointed` block raises when it is reckoned (blocks run one
    after another, so the largest counts). ``grads_bytes``: the gradients'
    bytes, which the engine took out of the room. ``kinds``: every kind of
    block reckoned so far, by its label. The step has ONE decision
    (:meth:`saved`), made of all of them when the first block is applied and
    written into ``totals``."""
    room_bytes: Optional[int]
    totals: Dict[str, Any] = dataclasses.field(default_factory=dict)
    outside_bytes: int = 0
    block_bytes: int = 0
    grads_bytes: int = 0
    handed_bytes: int = 0
    kinds: Dict[str, Kind] = dataclasses.field(default_factory=dict)
    _decided: Optional[Tuple[int, Tuple[str, ...]]] = None

    @property
    def working_bytes(self) -> int:
        """What the step holds at its worst beside the gradients, every
        block's input and the kept values: the largest block's bytes while
        the blocks run, or, where that is more, what lives outside the blocks
        less the gradients: the head runs before the first block's backward
        has made one, in the room that was set aside for them (on the chip
        the 36-layer dense step's peak is the head's, with no gradient live:
        PERF.md, PR 60). What is handed from layer to layer stands beside
        either: it is made in the forward and read until its maker's
        backward, through every block between."""
        return max(self.outside_bytes - self.grads_bytes, self.block_bytes) + self.handed_bytes

    def saved(self) -> Tuple[str, ...]:
        """The names the step's blocks keep: one prefix of
        :data:`SAVE_ORDER` over :func:`step_costs` of every kind reckoned,
        inside :func:`saved_budget`'s bytes. Decided once (again only if a
        kind has been reckoned since) and written into ``totals``: the
        step's ``saved`` / ``saved_bytes`` / ``candidate_bytes`` (bytes over
        the layers that make a name: :func:`step_candidates`), what the saved
        cost (``saved_cost_bytes``, of ``budget_bytes``) and, by kind,
        ``saved_by_kind`` (label -> layers, the block's bytes, the names of
        the prefix that kind has, their bytes over its layers)."""
        if self._decided is None or self._decided[0] != len(self.kinds):
            listed = {n for group in SAVE_ORDER for n in group}
            candidates = {n: size for n, size in step_candidates(self.kinds).items()
                          if n in listed}
            costs = step_costs(self.kinds)
            blocks = sum(kind.layers for kind in self.kinds.values())
            carry = max((kind.carry_bytes for kind in self.kinds.values()), default=0)
            budget_bytes = saved_budget(self.room_bytes, blocks, carry, self.working_bytes)
            saved = choose_saved(costs, budget_bytes)
            by_kind = {}
            for label, kind in self.kinds.items():
                names = tuple(n for n in saved if n in kind.named)
                by_kind[label] = {"layers": kind.layers,
                                  "block_bytes": kind.block_bytes, "saved": names,
                                  "saved_bytes": kind.layers * sum(kind.named[n] for n in names)}
            self.totals.update(
                policy=KEEP_PRODUCTS, saved=saved,
                saved_bytes=sum(candidates[n] for n in saved),
                saved_cost_bytes=sum(costs[n] for n in saved),
                candidate_bytes=sum(candidates.values()),
                room_bytes=self.room_bytes, budget_bytes=budget_bytes,
                working_bytes=self.working_bytes, block_bytes=self.block_bytes,
                outside_bytes=self.outside_bytes, grads_bytes=self.grads_bytes,
                handed_bytes=self.handed_bytes, carries_bytes=blocks * carry,
                saved_by_kind=by_kind)
            self._decided = len(self.kinds), saved
        return self._decided[1]


def _aval_bytes(var) -> int:
    aval = var.aval
    return aval.size * aval.dtype.itemsize if hasattr(aval, "dtype") else 0


def _bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def named_bytes(jaxpr) -> Dict[str, int]:
    """name -> the bytes a block's backward holds if it keeps the
    ``checkpoint_name``d values of ``jaxpr`` and of the jaxprs inside it (not
    a Pallas kernel's body). A name that occurs twice (two launches of one
    layer that share their tag) counts twice: both values are kept. A value
    named inside a ``scan`` is kept for every trip, stacked; a ``cond`` keeps
    its dearest branch's; inside a ``while`` nothing can be kept (no
    backward reads a loop of unknown length), so a name there counts for
    nothing."""
    found: Dict[str, int] = {}

    def add(names: Mapping[str, int], times: int = 1) -> None:
        for name, size in names.items():
            found[name] = found.get(name, 0) + times * size

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "name":
            add({eqn.params["name"]: _aval_bytes(eqn.outvars[0])})
        elif prim == "cond":
            branches = [named_bytes(b.jaxpr) for b in eqn.params["branches"]]
            add({n: max(b.get(n, 0) for b in branches) for b in branches for n in b})
        elif prim not in ("pallas_call", "while"):
            for sub in jax.core.jaxprs_in_params(eqn.params):
                add(named_bytes(getattr(sub, "jaxpr", sub)),
                    eqn.params["length"] if prim == "scan" else 1)
    return found


#: The primitives whose result XLA writes nowhere by itself: a link of an
#: elementwise / broadcast / convert / reshape / static-slice chain rides in the
#: fusion of whatever reads it, so what the chain READS stays live until that
#: reader runs and the link takes no bytes. Every other primitive's result is
#: put in memory where it is made (a matmul's, a convolution's, a kernel's or
#: another custom call's, a reduction's, a scan's, a sort's, a gather's, a
#: scatter's, a ``dynamic_update_slice``'s, a transpose's copy, a loop's
#: carry), and so is a fused link that is named or leaves its jaxpr. A
#: primitive this set has not heard of counts as written: the safe side.
_FUSED = frozenset({
    "abs", "add", "add_any", "and", "atan2", "bitcast_convert_type", "broadcast_in_dim",
    "ceil", "clamp", "convert_element_type", "cos", "div", "eq", "erf",
    "erf_inv", "exp", "exp2", "expm1", "floor", "ge", "gt", "integer_pow",
    "iota", "is_finite", "le", "log", "log1p", "logistic", "lt", "max", "min",
    "mul", "ne", "neg", "nextafter", "not", "or", "pad", "pow", "reduce_precision",
    "rem", "reshape", "round", "rsqrt", "select_n", "shift_left",
    "shift_right_arithmetic", "shift_right_logical", "sign", "sin", "slice",
    "split", "sqrt", "square", "squeeze", "sub", "tanh", "xor"})

#: equations XLA inlines where they stand (their body is part of the caller's
#: program, so a chain fuses across their edge)
_INLINED = frozenset({"jit", "pjit", "closed_call", "core_call", "custom_jvp_call",
                      "custom_vjp_call"})


def _equations(jaxpr, rename=None):
    """``jaxpr``'s equations in program order as ``(equation, inputs,
    results)``, the bodies of :data:`_INLINED` equations in their place and
    under their caller's variables (a literal input is dropped). A result
    such a body passes through, or makes of nothing, comes as an equation of
    None: a link from what it passes to the caller's variable."""
    rename = rename or {}
    is_var = lambda v: not isinstance(v, Literal)
    for eqn in jaxpr.eqns:
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name not in _INLINED or len(subs) != 1:
            yield (eqn, [rename.get(v, v) for v in eqn.invars if is_var(v)],
                   [rename.get(v, v) for v in eqn.outvars])
            continue
        body = getattr(subs[0], "jaxpr", subs[0])
        inner = {b: rename.get(v, v) for b, v in zip(body.invars, eqn.invars) if is_var(v)}
        given = set(body.invars) | set(body.constvars)
        made = {}             # what the body makes, under the caller's variable
        for b, v in zip(body.outvars, eqn.outvars):
            if is_var(b) and b not in given:
                made.setdefault(b, rename.get(v, v))
        names = {**inner, **made}
        yield from _equations(body, names)
        for b, v in zip(body.outvars, eqn.outvars):
            v = rename.get(v, v)
            if not is_var(b) or made.get(b) is not v:
                yield None, ([names.get(b, b)] if is_var(b) else []), [v]


def live_bytes(jaxpr) -> int:
    """The most bytes live at once while ``jaxpr`` runs in program order,
    beside its inputs, counted as the compiled program holds them: a result
    counts from where XLA must put it in memory to the last equation that
    reads it, directly or through a chain of :data:`_FUSED` links (a link
    itself takes nothing: it is made inside its reader's fusion); a jaxpr's
    own results, and a value under a ``checkpoint_name``, are written
    whatever made them. Inside an equation that holds jaxprs (a loop's body,
    a branch, an inner ``jax.checkpoint``) what the worst of them holds, a
    loop's carry and results with it; a ``pjit`` or a custom derivative's
    call is read in its caller's place; a ``pallas_call``'s results as
    declared, its body not looked into. A reckoning from the jaxpr alone
    (no compile): XLA may still write a link two readers share, and reorders
    what program order keeps apart. Pure: a jaxpr in, bytes out."""
    eqns = list(_equations(jaxpr))
    results = {v for v in jaxpr.outvars if not isinstance(v, Literal)}
    written = results | {ins[0] for eqn, ins, _ in eqns
                         if eqn is not None and eqn.primitive.name == "name" and ins}
    # what each fused link (a name, a passed-through result) reads: the
    # written values its reader must still find
    reads: Dict[Any, frozenset] = {}
    last: Dict[Any, int] = {}
    fused = []
    for i, (eqn, ins, outs) in enumerate(eqns):
        prim = None if eqn is None else eqn.primitive.name
        link = prim in (None, "name") or (
            prim in _FUSED and not any(v in written for v in outs))
        fused.append(link)
        sources = frozenset().union(*(reads.get(v, (v,)) for v in ins))
        if link:
            for v in outs:
                reads[v] = sources
        else:
            for v in sources:
                last[v] = i
    for v in results:
        for source in reads.get(v, (v,)):
            last[source] = len(eqns)
    dies: Dict[int, list] = {}
    for v, at in last.items():
        dies.setdefault(at, []).append(v)
    held: Dict[Any, int] = {}
    live = peak = 0
    for i, (eqn, ins, outs) in enumerate(eqns):
        if fused[i]:
            continue
        inside = 0 if eqn.primitive.name == "pallas_call" else max(
            (live_bytes(getattr(sub, "jaxpr", sub))
             for sub in jax.core.jaxprs_in_params(eqn.params)), default=0)
        peak = max(peak, live + max(sum(map(_aval_bytes, outs)), inside))
        for v in outs:
            if v in last:                # a result nothing reads is gone at once
                held[v] = _aval_bytes(v)
                live += held[v]
        for v in dies.get(i, ()):
            live -= held.pop(v, 0)       # an input of the jaxpr was never held
    return peak


def _backward_of(block: Callable, carried: int) -> Callable:
    """``block``'s forward and backward as one function of its arguments
    (the cotangents are ones: a traced shape, no value). Its results are the
    cotangents of the first ``carried`` arguments, the carry's: a layer's
    weight gradients are made in it and leave it for the step's gradients,
    whose bytes the room has set aside already."""
    def both(*args):
        out, pull = jax.vjp(block, *args)
        return pull(jax.tree.map(
            lambda o: jnp.ones(o.shape, o.dtype)
            if jnp.issubdtype(o.dtype, jnp.inexact)
            else np.zeros(o.shape, jax.dtypes.float0), out))[:carried]
    return both


class _KeptBlock:
    """``block_fn(carry, layer)`` under ``KEEP_PRODUCTS``: the kind of block
    that ``layers`` of a step's blocks are, held to ``budget`` under
    ``label``. The block is traced once for each shape it is given: to its
    own jaxpr, and that jaxpr's forward and backward to one more, off which
    the names, their bytes and the block's live bytes are read (a custom
    VJP's residuals are named in its forward rule, which only
    differentiation traces). :meth:`reckon` does that much and may be called
    ahead for every kind of block of a step, so that the step's one decision
    (:meth:`Budget.saved`) counts them all; calling the block applies the
    policy: the names of that decision this kind has."""

    def __init__(self, block_fn: Callable, layers: int, budget: Optional[Budget],
                 label: str):
        self.block_fn, self.layers, self.budget = block_fn, layers, budget
        self.label = label
        self._traced: Dict[Any, Any] = {}

    def reckon(self, carry, layer):
        args = jax.tree.leaves((carry, layer))
        key = (jax.tree.structure((carry, layer)),
               tuple((x.shape, str(x.dtype)) for x in args))
        if key not in self._traced:
            closed, out = jax.make_jaxpr(self.block_fn, return_shape=True)(carry, layer)
            # the block's own trace, above, the step needs anyway; what the
            # plan adds to it is a span (``setup_totals["remat_plan_s"]``;
            # the choice itself, in Budget.saved, is a few dict operations)
            with setup_spans.remat_plan():
                both = jax.make_jaxpr(_backward_of(
                    jaxpr_as_fun(closed), len(jax.tree.leaves(carry))))(*args).jaxpr
                named = named_bytes(both)
                self._traced[key] = closed, out, named
                if self.budget is not None:
                    # of its results the carry: what a layer stacks or hands
                    # on beside it is the model's to charge (``handed_bytes``)
                    held = _bytes((args, out[0])) + live_bytes(both)
                    kind = Kind(self.layers, _bytes(carry), held, named)
                    if self.budget.kinds.setdefault(self.label, kind) != kind:
                        raise ValueError(
                            f"remat budget: the kind of block {self.label!r} is traced at a "
                            "second shape; give each shape a label of its own, with its layers")
                    self.budget.block_bytes = max(self.budget.block_bytes, held)
        return self._traced[key]

    def __call__(self, carry, layer):
        closed, out, named = self.reckon(carry, layer)
        if self.budget is None:
            saved = choose_saved(named, None)
        else:
            saved = tuple(n for n in self.budget.saved() if n in named)
        block = jax.checkpoint(jaxpr_as_fun(closed), policy=jax.checkpoint_policies
                               .save_only_these_names(*saved))
        return jax.tree.unflatten(jax.tree.structure(out),
                                  block(*jax.tree.leaves((carry, layer))))


def checkpointed(block_fn: Callable, policy: Optional[str], layers: int,
                 budget: Optional[Budget] = None, label: str = "block") -> Callable:
    """``jax.checkpoint(block_fn)`` under the named policy, for ``layers``
    blocks ``block_fn(carry, layer)`` of a step (a ``lax.scan``'s, or run one
    by one): the one builder of the models' block policy
    (``TransformerLM.apply`` builds one a kind of block with that kind's own
    count of layers, the pipeline stage one). Every explicit name is
    :func:`resolve_policy`'s. ``KEEP_PRODUCTS`` saves the named values that
    the step's one decision admits (:meth:`Budget.saved`; ``label`` is this
    kind's line in its report: one label, one shape)."""
    if policy != KEEP_PRODUCTS:
        return jax.checkpoint(block_fn, policy=resolve_policy(policy or "full"))
    return _KeptBlock(block_fn, layers, budget, label)


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
