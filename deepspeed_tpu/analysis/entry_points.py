"""Registered entry points: the framework's real traced hot paths.

Each entry point is declared ONCE as an :class:`EntrySpec` — the callable,
its representative (sharded) arguments, its donation contract, the mesh it
runs under, and its compiled-layer expectations — and BOTH analysis layers
consume the same spec:

- **Layer B** (``dstpu lint --jaxpr``) traces the spec with
  :func:`trace_and_check` and walks the jaxpr (collective axis binding,
  donation aliasing, retrace signatures).
- **Layer C** (``dstpu lint --spmd``, :mod:`.spmd_audit`) lowers and
  compiles the spec with its real mesh/shardings and audits the
  post-SPMD artifact (GSPMD-inserted collectives, replicated
  intermediates, remat residuals, actual aliasing, memory budgets).

These run on the CPU host platform (``JAX_PLATFORMS=cpu`` with
``--xla_force_host_platform_device_count=8``, the same virtual mesh the
unit tests use); nothing executes, only traces and compiles.

``audit_entry_points()`` is what ``dstpu lint --jaxpr`` and the
``test_lint_clean`` CI gate call.

Layer-C expectations on a spec:

- ``expected_spmd`` — HLO collective kinds the entry point's sharding
  design legitimately lets GSPMD insert (beyond the kinds implied by the
  source jaxpr's own collective primitives). This is the *declared
  contract* the ``implicit-reshard`` rule enforces: any other kind
  appearing in the compiled program is a finding.
- ``param_shapes`` — full (unpartitioned) parameter shapes, set only on
  the ZeRO-partitioned schedules where "residuals must never contain full
  params" is a design invariant (docs/ZERO_OVERLAP.md); the
  ``remat-residual-full-param`` rule walks scan residuals against it.
- ``gate_cheap`` — True for the specs the tier-1 CI gate compiles
  (no engine build, sub-second compiles); the full set runs via
  ``dstpu lint --spmd`` off-gate. See docs/STATIC_ANALYSIS.md.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Sequence, Tuple

from .findings import Finding, SEVERITY_ERROR
from .trace_harness import check_retrace, trace_and_check

_TINY = dict(max_seq_len=32, vocab_size=256, remat=False)


@dataclasses.dataclass
class EntrySpec:
    """One registered entry point, shared by Layers B and C."""
    name: str
    fn: Callable
    args: Tuple[Any, ...]
    donate_argnums: Tuple[int, ...] = ()
    mesh: Any = None                     # context manager; None = no mesh ctx
    retrace_args: Optional[Sequence[Tuple]] = None   # arg sets for check_retrace
    max_signatures: int = 1
    # --- Layer C contracts ---
    #: the production jit's extra arguments (in_shardings/out_shardings) —
    #: Layer C must compile the program production runs, or donation and
    #: partitioning drift from reality
    jit_kwargs: Optional[Dict[str, Any]] = None
    expected_spmd: FrozenSet[str] = frozenset()
    param_shapes: FrozenSet[Tuple[Tuple[int, ...], str]] = frozenset()
    gate_cheap: bool = False
    #: Layer D contract (docs/STATIC_ANALYSIS.md): the entry's schedule is
    #: DESIGNED to overlap its collectives — exposed bytes beyond the
    #: committed exposure budget escalate from a budget regression to the
    #: hard ``exposed-collective`` finding. Declared on the pipelined
    #: ZeRO micro and the ragged serving wave.
    overlap_contract: bool = False
    # bespoke Layer-B checks run by the builder (e.g. telemetry parity)
    extra_findings: List[Finding] = dataclasses.field(default_factory=list)

    def mesh_ctx(self):
        import contextlib
        return self.mesh if self.mesh is not None else contextlib.nullcontext()


#: the active candidate overrides (installed by :func:`candidate_overrides`,
#: consulted by ``_tiny_engine`` / ``_batch``): ``{"config": nested config
#: overrides, "model": gpt2_model kwargs, "batch": {"size", "seq"}}``.
#: Empty = HEAD defaults, which is every path except `dstpu plan`.
_CANDIDATE: Dict[str, Dict[str, Any]] = {}

#: the entries whose spec builders synthesize an engine from a config dict
#: — the only ones a candidate config can re-parameterize. The rest build
#: fixed toy programs; `dstpu plan` rejects candidates targeting them
#: rather than silently auditing the default program.
CANDIDATE_ENTRY_POINTS: Tuple[str, ...] = (
    "engine-train-step", "zero-gather-partition", "zeropp-micro-overlap",
    "telemetry-off-parity", "guardian-step-parity")


@contextlib.contextmanager
def candidate_overrides(config=None, model=None, batch=None):
    """Install candidate overrides for the duration of a spec build:
    ``config`` deep-merges over the builder's engine config (the same
    :func:`~deepspeed_tpu.runtime.config.deep_update` semantics the
    engine build validates under), ``model`` overrides the tiny-model
    kwargs (e.g. ``remat``), ``batch`` overrides the representative batch
    shape (``size``/``seq``). This is how `dstpu plan` re-parameterizes
    the EXISTING registry builders instead of growing a parallel set."""
    global _CANDIDATE
    old = _CANDIDATE
    _CANDIDATE = {"config": config or {}, "model": model or {},
                  "batch": batch or {}}
    try:
        yield
    finally:
        _CANDIDATE = old


def _tiny_engine(config_extra=None, **model_kw):
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2_model
    from deepspeed_tpu.runtime.config import deep_update

    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
    }
    deep_update(config, config_extra)
    deep_update(config, _CANDIDATE.get("config"))
    model_args = dict(_TINY)
    model_args.update(model_kw)
    model_args.update(_CANDIDATE.get("model", {}))
    model = gpt2_model("gpt2-tiny", **model_args)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    return engine


def _batch(engine, batch=8, seq=16):
    import numpy as np
    over = _CANDIDATE.get("batch", {})
    batch = int(over.get("size", batch))
    seq = int(over.get("seq", seq))
    ids = np.zeros((batch, seq), dtype=np.int32)
    return engine._prepare_batch({"input_ids": ids})


def _full_param_shapes(model) -> FrozenSet[Tuple[Tuple[int, ...], str]]:
    """Full (unpartitioned) parameter shapes of ``model`` — what a gathered
    layer weight looks like. The remat-residual rule flags scan residuals
    matching any of these."""
    import jax

    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return frozenset((tuple(l.shape), str(l.dtype))
                     for l in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# spec builders — one per registered entry point
# ---------------------------------------------------------------------------

def build_engine_step() -> EntrySpec:
    """The fused train step: collectives bound, state donated, and the step
    must not retrace across steps (same shapes -> one signature). The step
    is GSPMD-sharded (jit + shardings, no shard_map): the data-parallel
    gradient all-reduce and the ZeRO-1 sharded-optimizer gather/exchange
    are partitioner-inserted BY DESIGN — the declared expected_spmd set."""
    import jax.numpy as jnp

    engine = _tiny_engine()
    batch = _batch(engine)
    lr = jnp.asarray(1e-3, jnp.float32)
    args = (engine.state, batch, lr)
    return EntrySpec(
        name="engine-train-step", fn=engine._train_step_fn, args=args,
        donate_argnums=(0,), mesh=engine.mesh,
        jit_kwargs=_fused_step_jit_kwargs(engine),
        retrace_args=[args, args],
        expected_spmd=frozenset({"all-reduce", "all-gather", "all-to-all"}))


def _fused_step_jit_kwargs(engine) -> Dict[str, Any]:
    """The fused step's production jit arguments (engine._build_fused_jit):
    state shardings in and out, replicated scalars."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    shardings = engine._state_shardings()
    rep = NamedSharding(engine.mesh, P())
    return dict(in_shardings=(shardings, None, None),
                out_shardings=(shardings, rep, rep, rep))


def _zeropp_micro_jit_kwargs(engine) -> Dict[str, Any]:
    """The explicit ZeRO++ micro's production jit arguments
    (engine._build_jits, _explicit_micro branch): only grad_acc flows
    donated; scale replicated; params/batch placed by the caller."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    shardings = engine._state_shardings()
    rep = NamedSharding(engine.mesh, P())
    return dict(in_shardings=(shardings["grad_acc"], rep, None, None),
                out_shardings=(shardings["grad_acc"], rep))


def build_zero_gather_partition() -> EntrySpec:
    """ZeRO++ micro step — the whole-tree BARRIER schedule, the
    ``overlap_comm: false`` escape hatch (engine._build_zeropp_micro_barrier):
    every collective must ride the canonical dp axes and the donated grad
    accumulator must alias. Gathers/scatters are EXPLICIT shard_map
    collectives, so the compiled program may contain no collective kind
    the source jaxpr doesn't already name (psum lowers to all-reduce)."""
    engine = _tiny_engine(config_extra={"zero_optimization": {
        "stage": 3, "stage3_param_persistence_threshold": 0,
        "zero_quantized_weights": True, "overlap_comm": False}})
    assert engine._zeropp, "config did not enable the ZeRO++ path"
    batch = _batch(engine)
    micro = engine._build_zeropp_micro()
    assert not engine._overlap_active, \
        "overlap_comm: false must select the barrier schedule"
    args = (engine.state["grad_acc"], engine.state["loss_scale"]["cur_scale"],
            engine.state["params"], batch)
    return EntrySpec(
        name="zero-gather-partition", fn=micro, args=args,
        donate_argnums=(0,), mesh=engine.mesh,
        jit_kwargs=_zeropp_micro_jit_kwargs(engine),
        param_shapes=_full_param_shapes(engine.model))


def build_zeropp_micro_overlap() -> EntrySpec:
    """The layer-granular pipelined ZeRO++ micro step (ISSUE 3 tentpole,
    engine._build_zeropp_micro_overlap + models/transformer.py
    scan_blocks_pipelined + runtime/zero/overlap.py): double-buffered
    param prefetch in the forward scan carry, backward-interleaved
    gradient reduce-scatter. The audit enforces axis binding (every
    collective in both scan bodies rides canonical dp axes), donation
    aliasing on the grad accumulator, and a stable retrace signature —
    the schedule recompiling per step would erase the win it exists for.
    ``param_shapes`` arms the remat-residual rule: the prefetch CARRY may
    hold one gathered layer (by design), stacked scan residuals may not."""
    engine = _tiny_engine(config_extra={"zero_optimization": {
        "stage": 3, "stage3_param_persistence_threshold": 0,
        "zero_quantized_weights": True, "zero_quantized_gradients": True}})
    assert engine._zeropp, "config did not enable the ZeRO++ path"
    batch = _batch(engine)
    micro = engine._build_zeropp_micro()
    assert engine._overlap_active, (
        "overlap_comm (stage-3 default true) must select the pipelined "
        f"schedule; fell back: {engine._overlap_fallback}")
    gacc = engine.state["grad_acc"]
    scale = engine.state["loss_scale"]["cur_scale"]
    args = (gacc, scale, engine.state["params"], batch)
    return EntrySpec(
        name="zeropp-micro-overlap", fn=micro, args=args,
        donate_argnums=(0,), mesh=engine.mesh,
        jit_kwargs=_zeropp_micro_jit_kwargs(engine),
        retrace_args=[args, args],
        param_shapes=_full_param_shapes(engine.model),
        overlap_contract=True)


def build_moe_dispatch() -> EntrySpec:
    """MoE dispatch/combine: the expert exchange is expressed as sharding
    constraints over the expert axis — those specs must name canonical axes
    of the configured topology, and the partitioner materializes the
    exchange (all-to-all/permute/gather + the combine all-reduce), which is
    the declared expected_spmd set. Since ISSUE 9 the input rides the data
    axis (the production layout, where dispatch is a REAL exchange) and
    the overlap planner's scan-carry chunking pipelines that exchange
    under expert compute — the entry declares an ``overlap_contract``:
    the dispatch-side bytes must stay hidden, the combine-side epilogue
    is the budget-justified edge (tools/exposure_budgets.json)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.moe.layer import MoE
    from deepspeed_tpu.runtime import topology as topo_mod
    from deepspeed_tpu.runtime.topology import DATA_AXIS, TopologyConfig

    topo = topo_mod.initialize(TopologyConfig(expert=2, data=-1), force=True)
    # intermediate 64: a representative FFN-to-exchange ratio (real MoE
    # FFNs are 2-4x hidden) — the dispatch chunk must have enough expert
    # compute beside it to classify overlapped on the audit mesh
    moe = MoE(hidden_size=16, intermediate_size=64, num_experts=4, top_k=2)
    params = moe.init(jax.random.PRNGKey(0))
    x = jax.device_put(jnp.zeros((4, 8, 16), jnp.float32),
                       NamedSharding(topo.mesh, P(DATA_AXIS)))
    args = (params, x)
    return EntrySpec(
        name="moe-dispatch", fn=lambda p, t: moe(p, t)[0], args=args,
        mesh=topo.mesh, retrace_args=[args, args], gate_cheap=True,
        overlap_contract=True,
        expected_spmd=frozenset({"all-reduce", "all-gather", "all-to-all",
                                 "collective-permute"}))


def build_ring_attention() -> EntrySpec:
    """Ring attention: the K/V rotation must ppermute over the canonical
    seq axis inside a shard_map whose mesh matches the global topology.
    All collectives are explicit (collective-permute from ppermute):
    expected_spmd is empty — a partitioner-inserted gather here means the
    sequence sharding broke."""
    import jax.numpy as jnp
    from deepspeed_tpu.runtime import topology as topo_mod
    from deepspeed_tpu.runtime.topology import TopologyConfig
    from deepspeed_tpu.sequence.ring_attention import ring_attention

    topo_mod.initialize(TopologyConfig(seq=2, data=-1), force=True)
    q = jnp.zeros((4, 8, 4, 8), jnp.float32)
    args = (q, q, q)
    return EntrySpec(name="ring-attention", fn=ring_attention, args=args,
                     retrace_args=[args, args], gate_cheap=True)


def build_ulysses_attention() -> EntrySpec:
    """Ulysses: the head-scatter/seq-gather all-to-alls over the seq axis —
    explicit in the source jaxpr, so expected_spmd is empty. Since ISSUE 9
    the exchanges ride the transport planner's activation-kind bf16 wire
    (half the exposed bytes) and the entry declares an
    ``overlap_contract``: the reshard is a dependence chain, so its
    remaining exposure is budget-pinned rather than hideable — a byte
    REGRESSION (e.g. the wire silently reverting to full width) is the
    hard ``exposed-collective`` finding."""
    import jax.numpy as jnp
    from deepspeed_tpu.runtime import topology as topo_mod
    from deepspeed_tpu.runtime.topology import TopologyConfig
    from deepspeed_tpu.sequence.layer import ulysses_attention

    topo_mod.initialize(TopologyConfig(seq=2, data=-1), force=True)

    def attn(q, k, v):
        import jax
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    # at this toy size the exchange sits below the transport planner's
    # min_bytes floor, so the audited wire is full width by DESIGN (tiny
    # exchanges are latency-bound; narrowing buys nothing) — the bf16
    # activation wire is pinned by tests/unit/runtime/test_ulysses.py,
    # whose payloads clear the floor
    q = jnp.zeros((4, 8, 4, 8), jnp.float32)
    args = (q, q, q)
    # attn is a static callable, not a traced array — close over it.
    return EntrySpec(name="ulysses-attention",
                     fn=lambda q, k, v: ulysses_attention(attn, q, k, v),
                     args=args, retrace_args=[args, args], gate_cheap=True,
                     overlap_contract=True)


def build_flash_kernel() -> EntrySpec:
    """The in-repo Pallas flash training kernel (r6 tentpole,
    ops/transformer/pallas_flash.py): the jaxpr audit covers the wrapper's
    graph — the kernel must bind no collective and alias no donation. The
    scalar-prefetch contract (``q_offset``/``window`` are OPERANDS, not
    static config) is enforced by tracing them as ABSTRACT i32 scalars
    here: a regression that bakes either into the kernel's static
    configuration cannot concretize a tracer and surfaces as a hard
    trace-failed finding (and the numerics side is pinned by
    tests/unit/ops/test_pallas_flash_parity.py::test_traced_q_offset_and_window,
    which feeds one jitted trace multiple values)."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops.transformer.pallas_flash import \
        flash_attention_kernel

    q = jnp.zeros((1, 64, 4, 16), jnp.float32)
    k = jnp.zeros((1, 64, 2, 16), jnp.float32)

    def fn(q, k, v, off, w):
        return flash_attention_kernel(q, k, v, causal=True, q_offset=off,
                                      window=w, interpret=True)

    i32 = lambda x: jnp.asarray(x, jnp.int32)
    args = (q, k, k, i32(0), i32(0))
    return EntrySpec(name="flash-attention-kernel", fn=fn, args=args,
                     retrace_args=[args, args])


def build_paged_decode() -> EntrySpec:
    """The paged-decode serving step (inference/v2 paged_attention): one
    new token per sequence against a blocked KV cache. Batch rides the
    data axis; the page pool is replicated (every rank serves its own
    requests against shared pages on the CPU audit mesh). The gather is
    per-rank local — NO collective belongs in the compiled program, so
    expected_spmd is empty: any partitioner-inserted gather/reduce means
    the serving sharding regressed (the 24-request serving wall is a
    memory/reshard problem, not a FLOPs one)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.inference.v2.kernels.paged_attention import \
        paged_decode_attention
    from deepspeed_tpu.runtime import topology as topo_mod
    from deepspeed_tpu.runtime.topology import DATA_AXIS, TopologyConfig

    topo = topo_mod.initialize(TopologyConfig(data=-1), force=True)
    mesh = topo.mesh
    B, H, D, kvH, pages, page = 8, 4, 16, 2, 16, 8
    put = lambda x, *spec: jax.device_put(x, NamedSharding(mesh, P(*spec)))
    q = put(jnp.zeros((B, H, D), jnp.float32), DATA_AXIS)
    k_pages = put(jnp.zeros((kvH, pages, page, D), jnp.float32))
    v_pages = put(jnp.zeros((kvH, pages, page, D), jnp.float32))
    context_lens = put(jnp.ones((B,), jnp.int32), DATA_AXIS)
    block_tables = put(jnp.zeros((B, 4), jnp.int32), DATA_AXIS)
    args = (q, k_pages, v_pages, context_lens, block_tables)
    return EntrySpec(name="paged-decode", fn=paged_decode_attention,
                     args=args, mesh=mesh, retrace_args=[args, args],
                     gate_cheap=True)


def build_ragged_paged_attention() -> EntrySpec:
    """The ragged serving wave (ISSUE 6 tentpole): ragged paged attention
    dispatched through ``shard_map`` over the data axis against a
    DATA-SHARDED page pool — the production composition
    ``engine_v2._wave_sharded_fn`` runs (each rank's sub-wave against its
    local pool slice). The zero-collective decode contract carries over
    from ``paged-decode``: everything is rank-local by construction, so
    ``expected_spmd`` is empty and ANY partitioner-inserted collective
    means the pool sharding or the local-id discipline regressed.

    The ragged wave descriptors (``cu_q_lens`` / ``kv_lens`` /
    ``page_indices``) are traced as ABSTRACT i32 arrays: a regression
    that bakes wave composition into static kernel configuration cannot
    concretize a tracer and surfaces as a hard trace-failed finding
    (numerics pinned by tests/unit/inference/test_ragged_paged_attention
    .py). The kernel path itself is traced in interpret mode, the same
    program the CPU parity suite validates."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.inference.v2.kernels.ragged_paged_attention import \
        ragged_paged_attention
    from deepspeed_tpu.runtime import topology as topo_mod
    from deepspeed_tpu.runtime.topology import DATA_AXIS, TopologyConfig
    from deepspeed_tpu.utils.jax_compat import shard_map

    topo = topo_mod.initialize(TopologyConfig(data=-1), force=True)
    mesh = topo.mesh
    dp = mesh.shape[DATA_AXIS]
    # per-rank sub-wave: 16 flat tokens, 8 atoms, 4-page tables against a
    # 4-pages-per-rank pool slice (global pool dp*4 pages)
    H, D, kvH, ps = 4, 16, 2, 8
    Nr, Ar, MP = 16, 8, 4

    def wave_attn(q, k_pages, v_pages, cu_q_lens, kv_lens, page_indices):
        return ragged_paged_attention(
            q, k_pages, v_pages, kv_lens, page_indices, cu_q_lens,
            block_q=8, use_pallas=True, interpret=True)

    d = DATA_AXIS
    fn = shard_map(wave_attn, mesh=mesh,
                   in_specs=(P(d), P(None, d), P(None, d),
                             P(d), P(d), P(d, None)),
                   out_specs=P(d), check_vma=False)
    put = lambda x, *spec: jax.device_put(x, NamedSharding(mesh, P(*spec)))
    q = put(jnp.zeros((dp * Nr, H, D), jnp.float32), d)
    k_pages = put(jnp.zeros((kvH, dp * 4, ps, D), jnp.float32), None, d)
    v_pages = put(jnp.zeros((kvH, dp * 4, ps, D), jnp.float32), None, d)
    cu = put(jnp.zeros((dp * (Ar + 1),), jnp.int32), d)
    kv_lens = put(jnp.ones((dp * Ar,), jnp.int32), d)
    tables = put(jnp.zeros((dp * Ar, MP), jnp.int32), d)
    args = (q, k_pages, v_pages, cu, kv_lens, tables)
    return EntrySpec(name="ragged-paged-attention", fn=fn, args=args,
                     mesh=mesh, retrace_args=[args, args], gate_cheap=True,
                     overlap_contract=True)


def build_quantized_transport() -> EntrySpec:
    """The transport planner's quantized + hierarchical collective paths
    (ISSUE 8, comm/comm.py + ops/quantizer): an explicit shard_map region
    over the two-tier audit mesh (mics=2 intra-tier x data=4 cross-tier)
    running the planner-resolved grad reduce-scatter (int8 wire,
    hierarchical decomposition) and the EQuARX-style quantized
    all-reduce. Layer B enforces collective axis binding on the quantized
    wire legs; every collective is explicit in the source jaxpr, so
    ``expected_spmd`` is empty; Layers C/D pin the wire bytes per kind
    and the exposure budget (docs/COLLECTIVES.md)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.runtime import topology as topo_mod
    from deepspeed_tpu.runtime.topology import (DATA_AXIS, MICS_AXIS,
                                                TopologyConfig)
    from deepspeed_tpu.utils.jax_compat import shard_map

    topo = topo_mod.initialize(TopologyConfig(mics=2, data=-1), force=True)
    axes = (DATA_AXIS, MICS_AXIS)

    def local(g, a):
        rs = dist.reduce_scatter(g, axis=axes, kind="grad")
        ar = dist.all_reduce(a, axis=axes, kind="grad")
        return rs, ar

    fn = shard_map(local, mesh=topo.mesh,
                   in_specs=(P(axes), P(axes)),
                   out_specs=(P(axes), P(None)),  # rs shards; ar replicates
                   check_vma=False)
    g = jnp.zeros((2048, 16), jnp.float32)
    a = jnp.zeros((4096,), jnp.float32)
    args = (g, a)
    return EntrySpec(name="quantized-transport", fn=fn, args=args,
                     mesh=topo.mesh, retrace_args=[args, args],
                     gate_cheap=True)


def build_fused_optimizer_step() -> EntrySpec:
    """The fused Pallas optimizer step (ISSUE 10 tentpole,
    ops/adam/pallas_adam.py via ``Optimizer.update(kernel='pallas')``):
    one launch per flat bucket over a ZeRO-1-style dp-sharded state with
    bf16 SR moments and the in-pass bf16 param cast — the program every
    step path dispatches under ``DSTPU_OPT_KERNEL`` on TPU. ``step``
    (inside the donated state) and ``lr`` trace ABSTRACT, so a regression
    that bakes either into the kernel's static configuration cannot
    concretize a tracer (the flash/ragged scalar-prefetch discipline).

    DONATED MOMENT BUFFERS are the machine-checked contract: the kernel
    wrapper aliases master/moment operands in place
    (``input_output_aliases``) and the spec donates the state, so a
    layout change that breaks the aliasing chain (a pad or concat
    creeping into the single-leaf path) surfaces as a hard
    ``dead-donation`` finding — without it the fp32+bf16 moments exist
    twice at peak, exactly the copy the fused step exists to avoid.

    The step runs as a ``shard_map`` over the dp axis with LOCAL flat
    shards — the multi-chip composition the engine's mesh-aware auto
    refinement defers to (engine ``_opt_kernel_choice``; under plain
    GSPMD the flat-bucket layout makes the partitioner rematerialize the
    sharded state, which is the finding this entry would raise). The
    update is per-rank elementwise math, so NO collective belongs in the
    compiled program (``expected_spmd`` empty, zero-byte collective map
    committed — the paged-decode discipline)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.runtime import topology as topo_mod
    from deepspeed_tpu.runtime.optimizers import Optimizer
    from deepspeed_tpu.runtime.topology import DATA_AXIS, TopologyConfig
    from deepspeed_tpu.utils.jax_compat import shard_map

    topo = topo_mod.initialize(TopologyConfig(data=-1), force=True)
    mesh = topo.mesh
    d = DATA_AXIS
    opt = Optimizer(name="adamw", lr=1e-3, weight_decay=0.01,
                    moment_dtype=jnp.bfloat16, moment_sq_dtype=jnp.bfloat16)
    put = lambda x, *spec: jax.device_put(x, NamedSharding(mesh, P(*spec)))
    # a dp-sharded matmul-weight leaf + a replicated bias leaf — the two
    # sharding classes a ZeRO-1 optimizer state mixes
    spec_of = {"w": P(d), "b": P()}
    tree_spec = lambda: dict(spec_of)
    params = {"w": put(jnp.zeros((2048, 128), jnp.float32), d),
              "b": put(jnp.zeros((128,), jnp.float32))}
    state = opt.init(params)
    place = lambda t: {k: put(v, *(spec_of[k] or ()))
                       for k, v in t.items()}
    state = {"step": put(state["step"]),
             "master": place(state["master"]),
             "exp_avg": place(state["exp_avg"]),
             "exp_avg_sq": place(state["exp_avg_sq"])}
    grads = {"w": put(jnp.zeros((2048, 128), jnp.bfloat16), d),
             "b": put(jnp.zeros((128,), jnp.bfloat16))}

    def local_update(g, opt_state, lr):
        # bucket_elems=1: every leaf stands alone = the alias (in-place)
        # path — the donation contract under machine check. Replicated
        # leaves step identically on every rank (the SR stream is a pure
        # function of (step, slot, bucket) x element index).
        return opt.update(g, opt_state, lr, param_dtype=jnp.bfloat16,
                          kernel="pallas", bucket_elems=1)

    state_specs = {"step": P(), "master": tree_spec(),
                   "exp_avg": tree_spec(), "exp_avg_sq": tree_spec()}
    fn = shard_map(local_update, mesh=mesh,
                   in_specs=(tree_spec(), state_specs, P()),
                   out_specs=(tree_spec(), state_specs),
                   check_vma=False)
    lr = jnp.asarray(1e-3, jnp.float32)
    args = (grads, state, lr)
    sh = lambda tree: jax.tree.map(lambda x: x.sharding, tree)
    return EntrySpec(
        name="fused-optimizer-step", fn=fn, args=args,
        donate_argnums=(1,), mesh=mesh, retrace_args=[args, args],
        jit_kwargs=dict(in_shardings=(sh(grads), sh(state), None),
                        out_shardings=(sh(grads), sh(state))),
        gate_cheap=True)


def build_fused_moe_dispatch() -> EntrySpec:
    """The fused Pallas MoE dispatch/combine kernel pair (ISSUE 11,
    ops/transformer/pallas_moe.py via ``MoE(kernel='pallas')``): route
    select + capacity scatter, the slot gather + wire cast, and the
    grouped expert-FFN + combine-scatter as hand launches, traced in
    interpret mode (the CPU parity suite's program — the flash/ragged
    discipline).

    The audited composition is a ``shard_map`` over the data axis: each
    rank runs the kernel forward on its LOCAL token slice against
    replicated expert weights — the dead-EP data-parallel regime the
    kernel serves (a live expert/pipeline axis keeps the GSPMD exchange
    path, ``moe/layer.py``). Everything is rank-local by construction,
    so NO collective belongs in the compiled program: ``expected_spmd``
    is empty and the committed collective map is zero-byte (the
    paged-decode / fused-optimizer-step discipline) — any
    partitioner-inserted gather here means the wrapper's sharding
    regressed into exactly the rematerialization the auto-gate guards
    against.

    ``n_chunks=2`` exercises the overlap planner's scan-carry placement
    on the kernel path (chunk c+1's gather+cast prefetched from the
    carry under chunk c's FFN+combine). The token/logits operands trace
    ABSTRACT — a regression that concretizes a routing tracer into the
    kernels' static configuration surfaces as a hard trace-failed
    finding. DONATED TOKEN BUFFER is the machine-checked capacity-buffer
    contract: the token-major output reuses the donated input's buffer
    (same shape/dtype/sharding) while the capacity-slot payload and the
    expert outputs stay internal to the launches — a layout change that
    breaks the alias (the output growing a pad, the payload escaping to
    HBM as a program output) surfaces as a hard ``dead-donation``
    finding."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.moe.layer import MoE
    from deepspeed_tpu.ops.transformer import pallas_moe
    from deepspeed_tpu.runtime import topology as topo_mod
    from deepspeed_tpu.runtime.topology import DATA_AXIS, TopologyConfig
    from deepspeed_tpu.utils.jax_compat import shard_map

    topo = topo_mod.initialize(TopologyConfig(data=-1), force=True)
    mesh = topo.mesh
    dp = mesh.shape[DATA_AXIS]
    d = DATA_AXIS
    # intermediate 64: the representative FFN-to-dispatch ratio the
    # moe-dispatch entry uses (real MoE FFNs are 2-4x hidden)
    moe = MoE(hidden_size=16, intermediate_size=64, num_experts=4, top_k=2)
    fwd = pallas_moe.make_moe_forward(
        top_k=2, capacity=10, activation="silu_gated", mask_pad=False,
        n_chunks=2, interpret=True)
    fn = shard_map(lambda p, t: fwd(p, t)[0], mesh=mesh,
                   in_specs=(jax.tree.map(lambda _: P(), moe.specs(),
                                          is_leaf=lambda s: s is None
                                          or isinstance(s, P)), P(d)),
                   out_specs=P(d), check_vma=False)
    put = lambda x, *spec: jax.device_put(x, NamedSharding(mesh, P(*spec)))
    params = jax.tree.map(put, moe.init(jax.random.PRNGKey(0)))
    tokens = put(jnp.zeros((dp * 32, 16), jnp.float32), d)
    args = (params, tokens)
    sh = lambda tree: jax.tree.map(lambda x: x.sharding, tree)
    return EntrySpec(
        name="fused-moe-dispatch", fn=fn, args=args,
        donate_argnums=(1,), mesh=mesh, retrace_args=[args, args],
        jit_kwargs=dict(in_shardings=(sh(params), tokens.sharding),
                        out_shardings=tokens.sharding),
        gate_cheap=True)


def build_offload_step_pipeline() -> EntrySpec:
    """The per-bucket traced compute of the double-buffered offload
    pipeline (ISSUE 15, ``engine._apply_step_offload``): the D2H fetch
    side's 2-D flatten (``DeepSpeedEngine._to_flat`` — dp dim first, any
    model dim major of the second, a LOCAL transpose by design) and the
    H2D push side's unflatten (``_from_flat`` — the engine's push jit
    traces the SAME function, so the audited program cannot drift).

    Contracts under machine check:

    - **Donated swap-in buffer** (``dead-donation``): the pushed flat
      master segment is dead once the param leaf is rebuilt; for an
      identity-order dp-sharded leaf the unflatten is a pure bitcast and
      the donated buffer MUST alias the output — a pad/concat/reshard
      creeping into the push path surfaces as a hard finding (the
      fused-optimizer-step discipline).
    - **Zero-collective data path** (``expected_spmd`` empty, zero-byte
      committed map): the whole point of the 2-D flat layout is that the
      SPMD partitioner never rematerializes — a GSPMD-inserted collective
      here means the layout contract regressed. (The per-leaf sq-norm
      stat programs are scalar reductions outside this contract; they
      all-reduce ~4 bytes by construction and run once per leaf.)
    - **No host-sync prims in the traced bucket compute** (Layer B's
      callback/sync walk): every fence in the pipeline is host-side
      BETWEEN programs, never inside one."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.runtime import topology as topo_mod
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.runtime.topology import DATA_AXIS, TopologyConfig

    topo = topo_mod.initialize(TopologyConfig(data=-1), force=True)
    mesh = topo.mesh
    d = DATA_AXIS
    # one dp-sharded matrix leaf + one replicated bias leaf — the two
    # layout classes the offload flat machinery handles (a tp-sharded
    # leaf adds an mp dim on the flat's second axis, same local-transpose
    # argument); identity flat order for the matrix, so the push-side
    # donation contract is checkable
    lay_w = (0, (d,), None, ())
    lay_b = (None, (), None, ())
    shape_w, shape_b = (2048, 128), (128,)
    wire = jnp.bfloat16

    def bucket_step(grads, push_flat):
        gw, gb = grads
        flats = [DeepSpeedEngine._to_flat(gw, lay_w),
                 DeepSpeedEngine._to_flat(gb, lay_b)]
        new_w = DeepSpeedEngine._from_flat(push_flat, lay_w, shape_w, wire)
        return flats, new_w

    put = lambda x, *spec: jax.device_put(x, NamedSharding(mesh, P(*spec)))
    grads = (put(jnp.zeros(shape_w, wire), d),
             put(jnp.zeros(shape_b, wire)))
    push_flat = put(jnp.zeros(shape_w, wire), d)
    args = (grads, push_flat)
    w_sh = NamedSharding(mesh, P(d, None))
    b_flat_sh = NamedSharding(mesh, P(None, None))
    return EntrySpec(
        name="offload-step-pipeline", fn=bucket_step, args=args,
        donate_argnums=(1,), mesh=mesh, retrace_args=[args, args],
        jit_kwargs=dict(
            in_shardings=((grads[0].sharding, grads[1].sharding),
                          push_flat.sharding),
            out_shardings=([w_sh, b_flat_sh], w_sh)),
        gate_cheap=True)


def build_telemetry_off_parity() -> EntrySpec:
    """The telemetry zero-overhead contract (docs/OBSERVABILITY.md): the
    engine step entry point's jaxpr must be IDENTICAL with telemetry off
    and on — instrumentation is host-side spans around dispatches, never
    graph edits — and neither graph may contain a host-callback primitive
    (the auditor's ``host-callback-in-graph`` rule covers that part).
    The parity diff runs at build time and lands in ``extra_findings``;
    the spec's fn is the telemetry-ON step, so the Layer-C artifact (and
    its budget) must match engine-train-step's — drift between those two
    budget lines is itself a parity smell."""
    import tempfile

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.telemetry import NULL_TELEMETRY, reset_telemetry

    from .trace_harness import TELEMETRY_GRAPH_DRIFT, JaxprAuditor

    lr = jnp.asarray(1e-3, jnp.float32)
    # ONE engine, traced twice: telemetry enabled (handle + global live),
    # then forced off — if the step graph consults either, the jaxprs
    # diverge. One build keeps the audit cheap inside the tier-1 gate.
    tmpdir = tempfile.mkdtemp(prefix="dstpu_telemetry_audit_")
    try:
        engine = _tiny_engine(config_extra={"telemetry": {
            "enabled": True, "watchdog": {"enabled": False},
            "trace": {"output_path": tmpdir}}})
        assert engine.telemetry.enabled, \
            "telemetry config block did not enable the subsystem"
        batch = _batch(engine)
        with engine.mesh:
            jaxpr_on = jax.make_jaxpr(engine._train_step_fn)(
                engine.state, batch, lr)
    finally:
        reset_telemetry()  # the audit must not leak a live recorder
        import shutil
        shutil.rmtree(tmpdir, ignore_errors=True)
    engine.telemetry = NULL_TELEMETRY
    with engine.mesh:
        jaxpr_off = jax.make_jaxpr(engine._train_step_fn)(
            engine.state, batch, lr)
    auditor = JaxprAuditor("telemetry-off-parity")
    auditor.walk(jaxpr_on.jaxpr)
    extra = auditor.findings
    if str(jaxpr_off) != str(jaxpr_on):
        extra.append(Finding(
            rule_id=TELEMETRY_GRAPH_DRIFT.rule_id,
            path="<trace:telemetry-off-parity>", line=0,
            severity=SEVERITY_ERROR,
            message="engine train-step jaxpr differs between telemetry "
                    "disabled and enabled",
            fix_hint=TELEMETRY_GRAPH_DRIFT.fix_hint))
    return EntrySpec(
        name="telemetry-off-parity", fn=engine._train_step_fn,
        args=(engine.state, batch, lr), donate_argnums=(0,),
        mesh=engine.mesh, extra_findings=extra,
        jit_kwargs=_fused_step_jit_kwargs(engine),
        expected_spmd=frozenset({"all-reduce", "all-gather", "all-to-all"}))


def build_guardian_step_parity() -> EntrySpec:
    """The guardian zero-overhead contract (ISSUE 13, docs/RESILIENCE.md):
    a guardian-OFF engine's fused step jaxpr must be IDENTICAL to the
    pre-guardian program — the sentinels exist only behind the
    ``spike_thresh`` gate — and the guardian-ON step may add NOTHING
    beyond the packed anomaly word riding the reductions the step
    already computes. Three traces:

    1. a pristine engine (guardian never configured) — the baseline;
    2. a guardian-armed engine force-disarmed — must print the SAME
       jaxpr as (1), else ``guardian-graph-drift`` fires;
    3. the armed step (``_train_step_fn_guardian``) — the spec's fn, so
       Layers B/C/D audit the SENTINEL path: collective axis binding,
       donation, and a committed collective map that must stay
       zero-delta against engine-train-step's (the anomaly word may not
       launch new collectives; a tier-1 test diffs the two maps).

    The threshold traces as an ABSTRACT f32 scalar — the rolling-stat
    side stays on the host by construction (baking a concrete threshold
    into the program would recompile every step the stats move)."""
    import jax
    import jax.numpy as jnp

    from .trace_harness import GUARDIAN_GRAPH_DRIFT, JaxprAuditor

    lr = jnp.asarray(1e-3, jnp.float32)
    # the pre-guardian baseline: an engine that never saw the config
    base = _tiny_engine()
    base_batch = _batch(base)
    with base.mesh:
        jaxpr_base = jax.make_jaxpr(base._train_step_fn)(
            base.state, base_batch, lr)
    # the guardian-armed engine, traced ON then force-disarmed for OFF
    engine = _tiny_engine(config_extra={"guardian": {"enabled": True}})
    assert engine._guardian is not None, \
        "guardian config block did not arm the subsystem"
    batch = _batch(engine)
    thresh = jnp.asarray(float("inf"), jnp.float32)
    with engine.mesh:
        jaxpr_on = jax.make_jaxpr(engine._train_step_fn_guardian)(
            engine.state, batch, lr, thresh)
    guardian, engine._guardian = engine._guardian, None
    with engine.mesh:
        jaxpr_off = jax.make_jaxpr(engine._train_step_fn)(
            engine.state, batch, lr)
    engine._guardian = guardian
    auditor = JaxprAuditor("guardian-step-parity")
    auditor.walk(jaxpr_on.jaxpr)
    extra = auditor.findings
    if str(jaxpr_off) != str(jaxpr_base):
        extra.append(Finding(
            rule_id=GUARDIAN_GRAPH_DRIFT.rule_id,
            path="<trace:guardian-step-parity>", line=0,
            severity=SEVERITY_ERROR,
            message="engine train-step jaxpr with the guardian disabled "
                    "differs from the pre-guardian program",
            fix_hint=GUARDIAN_GRAPH_DRIFT.fix_hint))
    args = (engine.state, batch, lr, thresh)
    return EntrySpec(
        name="guardian-step-parity", fn=engine._train_step_fn_guardian,
        args=args, donate_argnums=(0,), mesh=engine.mesh,
        retrace_args=[args, args], extra_findings=extra,
        jit_kwargs=_guardian_step_jit_kwargs(engine),
        expected_spmd=frozenset({"all-reduce", "all-gather", "all-to-all"}))


def _guardian_step_jit_kwargs(engine) -> Dict[str, Any]:
    """The guardian-armed fused jit's production arguments
    (engine._build_fused_jit, guardian branch): +1 replicated scalar in,
    the anomaly word out."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    shardings = engine._state_shardings()
    rep = NamedSharding(engine.mesh, P())
    return dict(in_shardings=(shardings, None, None, None),
                out_shardings=(shardings, rep, rep, rep, rep))


SPEC_BUILDERS: Dict[str, Callable[[], EntrySpec]] = {
    "engine-train-step": build_engine_step,
    "zero-gather-partition": build_zero_gather_partition,
    "zeropp-micro-overlap": build_zeropp_micro_overlap,
    "moe-dispatch": build_moe_dispatch,
    "fused-moe-dispatch": build_fused_moe_dispatch,
    "ring-attention": build_ring_attention,
    "ulysses-attention": build_ulysses_attention,
    "flash-attention-kernel": build_flash_kernel,
    "paged-decode": build_paged_decode,
    "quantized-transport": build_quantized_transport,
    "ragged-paged-attention": build_ragged_paged_attention,
    "fused-optimizer-step": build_fused_optimizer_step,
    "offload-step-pipeline": build_offload_step_pipeline,
    "telemetry-off-parity": build_telemetry_off_parity,
    "guardian-step-parity": build_guardian_step_parity,
}


def build_spec(name: str) -> EntrySpec:
    """Build one entry point's spec with a clean topology (builders that
    configure the global MeshTopology get a fresh slate)."""
    from deepspeed_tpu.runtime import topology as topo_mod

    topo_mod.reset()
    return SPEC_BUILDERS[name]()


def run_entry_audit(spec: EntrySpec) -> List[Finding]:
    """Layer B over one spec: jaxpr walk + donation + retrace + any bespoke
    findings the builder produced."""
    with spec.mesh_ctx():
        findings = trace_and_check(
            spec.fn, *spec.args, donate_argnums=spec.donate_argnums,
            name=spec.name)
    if spec.retrace_args is not None:
        findings += check_retrace(spec.name, spec.retrace_args,
                                  max_signatures=spec.max_signatures)
    return list(spec.extra_findings) + findings


def _make_audit(name: str) -> Callable[[], List[Finding]]:
    def audit() -> List[Finding]:
        return run_entry_audit(build_spec(name))
    audit.__name__ = f"audit_{name.replace('-', '_')}"
    return audit


ENTRY_POINTS: Dict[str, Callable[[], List[Finding]]] = {
    name: _make_audit(name) for name in SPEC_BUILDERS
}

#: the subset the tier-1 CI gate COMPILES (Layer C). Cheap by construction:
#: no engine build, sub-second compiles on the CPU mesh. The full set runs
#: via `dstpu lint --spmd` (docs/STATIC_ANALYSIS.md, "Tier-1 cost control").
#: Pinned rather than computed — building every spec just to read its
#: gate_cheap flag would boot engines; a test asserts the two agree.
GATE_SPMD_ENTRY_POINTS: Tuple[str, ...] = (
    "fused-moe-dispatch", "fused-optimizer-step", "moe-dispatch",
    "offload-step-pipeline", "paged-decode", "quantized-transport",
    "ragged-paged-attention", "ring-attention", "ulysses-attention")


def audit_entry_points(names=None) -> List[Finding]:
    """Run the named audits (default: all). An audit that cannot even trace
    is itself a hard finding — a broken hot path must not pass silently."""
    from deepspeed_tpu.runtime import topology as topo_mod

    if names:
        unknown = sorted(set(names) - set(ENTRY_POINTS))
        if unknown:
            raise ValueError(
                f"unknown entry point(s): {', '.join(unknown)} "
                f"(known: {', '.join(sorted(ENTRY_POINTS))})")
    findings: List[Finding] = []
    for name, fn in ENTRY_POINTS.items():
        if names and name not in names:
            continue
        topo_mod.reset()
        try:
            findings.extend(fn())
        except Exception as e:  # noqa: BLE001 - any trace failure is a finding
            findings.append(Finding(
                rule_id="trace-failed", path=f"<trace:{name}>", line=0,
                severity=SEVERITY_ERROR,
                message=f"entry point failed to trace: {type(e).__name__}: {e}",
                fix_hint="run the audit under JAX_PLATFORMS=cpu with "
                         "xla_force_host_platform_device_count>=8"))
    topo_mod.reset()
    return findings
