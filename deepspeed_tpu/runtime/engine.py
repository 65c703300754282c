"""Training engine.

Counterpart of the reference ``DeepSpeedEngine`` (``runtime/engine.py:179``):
one object wrapping model + optimizer + parallelism + precision + checkpointing
behind ``forward/backward/step`` and ``train_batch``.

TPU-first redesign. The reference mutates torch modules and registers autograd
hooks; here training state is an explicit pytree and the train step is a pure
jitted function with declared input/output shardings:

- ``_micro_step``  : value_and_grad of the model loss, gradient accumulation
  into a (possibly ZeRO-sharded) buffer. XLA emits the grad all-reduce
  (stage<2) or reduce-scatter (stage>=2) that the reference's
  ``allreduce_gradients``/``average_tensor`` (engine.py:1903,
  stage_1_and_2.py:1004) performs manually — and overlaps it with the
  backward computation, which is what ``overlap_comm`` approximates.
- ``_apply_step``  : overflow check → unscale → global-norm clip → optimizer
  update on the (sharded) fp32 master state → recast to model dtype with the
  params' sharding, which at stage 1/2 makes XLA re-materialize full params
  (the reference's ``all_gather_dp_groups``, runtime/utils.py:967), and at
  stage 3 keeps them sharded.

The DeepSpeed ``forward()/backward()/step()`` imperative API is preserved on
top: ``forward`` runs loss+grad in one fused jit call (a JAX program cannot
retroactively differentiate a stored loss), ``backward`` folds the cached
grads into the accumulator, ``step`` applies at gradient-accumulation
boundaries exactly like the reference
(``is_gradient_accumulation_boundary``, engine.py:1510).
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import json
import os
import tempfile
from typing import Any, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..resilience.fault_plan import (GUARDIAN_EXIT_CODE, STALL_EXIT_CODE,
                                     fault_point, maybe_install_from_env,
                                     parse_elastic_env)
from ..resilience.guardian import build_guardian, pack_anomaly_word
from ..telemetry import NULL_TELEMETRY, clock, setup_spans
from ..telemetry import memory as step_memory
from ..utils.logging import log_dist, logger
from ..utils.scope import scoped
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER,
                           NoopTimer, SynchronizedWallClockTimer, ThroughputTimer)
from .activation_checkpointing import checkpointing as remat
from .config import DeepSpeedConfig
from .fp16.loss_scaler import (dynamic_loss_scale_state, has_overflow, static_loss_scale_state,
                               update_scale)
from .lr_schedules import build_lr_schedule
from .optimizers import Optimizer, build_optimizer
from . import topology as topo_mod
from .topology import (BATCH_AXES, DATA_AXIS, SEQ_AXIS, MeshTopology,
                       TopologyConfig)
from .zero.partition import ZeroPartitionPlan

DATA_SPEC = P(BATCH_AXES)  # batches shard their leading dim over both dp axes

# Is a profiler session running? One flag test (jaxlib's TraceMe); where a
# jaxlib lacks it, the step writes its ``engine_totals`` every time.
try:
    from jax._src.lib import _profiler
except ImportError:  # pragma: no cover - another jaxlib layout
    _profiler = None
_profiler_on = getattr(getattr(_profiler, "TraceMe", None), "is_enabled",
                       lambda: True)


def _norm_dt(value) -> str:
    """Normalize a data_types.* knob to the param-stream runner's
    vocabulary, preserving unsupported values so IT rejects them loudly."""
    if value in (None, "fp32", "float32"):
        return "fp32"
    if value in ("bf16", "bfloat16"):
        return "bf16"
    return str(value)


def grad_accum_dtype(knob):
    """``data_types.grad_accum_dtype`` -> the dtype gradients accumulate in."""
    return jnp.bfloat16 if knob in ("bf16", "bfloat16") else jnp.float32


def gradients_bytes(params, grad_dtype) -> int:
    """The bytes of ``params``' gradients, each as wide as ``grad_dtype`` or
    as its parameter, whichever is more (what the remat room sets aside;
    ``tools/remat_plan.py`` reckons a cell's by the same rule)."""
    width = jnp.dtype(grad_dtype).itemsize
    return sum(p.size * max(p.dtype.itemsize, width) for p in jax.tree.leaves(params))


class DeepSpeedEngine:

    def __init__(self,
                 model,
                 config: Optional[DeepSpeedConfig] = None,
                 config_dict: Optional[Dict[str, Any]] = None,
                 topology: Optional[MeshTopology] = None,
                 seed: int = 42,
                 init_params: Optional[Any] = None):
        # Set-up's own account (telemetry/setup_spans.py), plain values kept
        # with telemetry off: the seconds of the import, of ``initialize``
        # and its parts, of every program's first call by what it was spent
        # on (trace, lower, compile or cache load, run), and what compiled
        # after set-up. docs/OBSERVABILITY.md has it key by key.
        self._setup = setup_spans.take()
        self.setup_totals = self._setup.totals
        self._totals_flat = (-1, {})    # (record version, ``engine_totals``)
        self.telemetry = NULL_TELEMETRY     # until _build_telemetry
        with self._setup.parts() as part:
            self._construct(part, model, config, config_dict, topology, seed,
                            init_params)

    def _construct(self, part, model, config, config_dict, topology, seed,
                   init_params):
        """The constructor's body; ``part(name)`` opens the next of
        set-up's spans (``setup_spans.INITIALIZE_PARTS``)."""
        part("config_topology")
        if config is None:
            # topology must exist before batch resolution
            topo_cfg = (config_dict or {}).get("topology", {})
            topology = topology or MeshTopology(TopologyConfig(**topo_cfg))
            config = DeepSpeedConfig(config_dict or {}, mesh_topology=topology)
        self.config = config
        self.topology = topology or MeshTopology(TopologyConfig(
            **{k: getattr(config.topology, k, 1 if k == "mics" else None)
               for k in ("pipe", "data", "mics", "expert", "seq", "model")}))
        self.model = model
        self.mesh = self.topology.mesh
        # Publish as the process-global topology so model-side code traced
        # without an engine handle (ulysses_attention, MoE dispatch) sees the
        # same mesh via get_topology().
        topo_mod.set_topology(self.topology)

        # -- precision policy (reference _configure_distributed_model dtype
        #    casts, engine.py:1085) ------------------------------------------
        if config.fp16.enabled:
            self.param_dtype = jnp.float16
        elif config.bf16.enabled:
            self.param_dtype = jnp.bfloat16
        else:
            self.param_dtype = jnp.float32
        self.grad_dtype = grad_accum_dtype(config.data_types_grad_accum_dtype)

        # -- optimizer + schedule -------------------------------------------
        self.optimizer: Optimizer = build_optimizer(config.optimizer)
        # optimizer-state precision knobs (reference config.py:171
        # fp16_master_weights_and_grads; moments knob is the TPU-native
        # extension that lets a full-depth 1.1B AdamW run fit 16 GB HBM)
        _opt_dtypes = {}
        if config.fp16_master_weights_and_grads:
            _opt_dtypes["master_dtype"] = self.param_dtype
        if config.data_types_optimizer_moment_dtype in ("bf16", "bfloat16"):
            _opt_dtypes["moment_dtype"] = jnp.bfloat16
        elif config.data_types_optimizer_moment_dtype in ("fp16", "float16"):
            _opt_dtypes["moment_dtype"] = jnp.float16
        elif config.data_types_optimizer_moment_dtype not in (None, "fp32",
                                                              "float32"):
            raise ValueError(
                "data_types.optimizer_moment_dtype must be bf16/fp16/fp32, got "
                f"{config.data_types_optimizer_moment_dtype!r}")
        # second moments narrow ONLY through this explicit knob — bf16
        # stores freeze a beta2=0.999 EMA without stochastic rounding, so
        # moment_dtype alone no longer touches exp_avg_sq (ADVICE r4;
        # tradeoff documented in runtime/optimizers.py)
        if config.data_types_optimizer_moment_sq_dtype in ("bf16",
                                                           "bfloat16"):
            _opt_dtypes["moment_sq_dtype"] = jnp.bfloat16
        elif config.data_types_optimizer_moment_sq_dtype in ("fp16",
                                                             "float16"):
            _opt_dtypes["moment_sq_dtype"] = jnp.float16
        elif config.data_types_optimizer_moment_sq_dtype not in (
                None, "fp32", "float32"):
            raise ValueError(
                "data_types.optimizer_moment_sq_dtype must be bf16/fp16/"
                f"fp32, got {config.data_types_optimizer_moment_sq_dtype!r}")
        if _opt_dtypes:
            if config.zero_config.offload_optimizer is not None:
                # the host runner steps flat fp32 chunks through the C++ SIMD
                # optimizer — narrowed stored state is a device-resident knob
                raise ValueError(
                    "optimizer-state dtype knobs compose with the device "
                    "optimizer only, not offload_optimizer (the host runner "
                    "owns flat fp32 state)")
            self.optimizer = dataclasses.replace(self.optimizer, **_opt_dtypes)
        self.lr_scheduler = build_lr_schedule(config.scheduler, self.optimizer.lr)

        # -- ZeRO-Offload / Infinity (reference engine.py:1219: offload mode
        #    selects the CPU optimizer; stage3 nvme pages moments) -----------
        oc = config.zero_config.offload_optimizer
        self._offload_device = (str(getattr(oc.device, "value", oc.device))
                                if oc is not None else "none")
        self._offload = None  # created after state init (needs master leaves)
        # Twin-Flow partial offload (reference stage3.py:814 partial_offload;
        # blogs/deepspeed-offloadpp): ratio of master/optimizer elements on
        # the host, the rest stepped on device by the jitted optimizer
        self._offload_ratio = float(oc.ratio) if oc is not None else 1.0
        if self._offload_device != "none" and self._offload_ratio == 0.0:
            raise ValueError(
                "offload_optimizer ratio=0.0 keeps the whole optimizer on "
                "device — remove the offload_optimizer block instead")
        # -- ZeRO-Infinity parameter offload (reference
        #    partitioned_param_swapper.py:36): bf16 param shards page to
        #    host/NVMe for out-of-core phases (offload_param_cache /
        #    reload_param_cache), freeing HBM between train/generate flips --
        pc = config.zero_config.offload_param
        self._param_offload_device = (str(getattr(pc.device, "value", pc.device))
                                      if pc is not None else "none")
        if self._param_offload_device != "none":
            if config.zero_config.stage != 3:
                raise ValueError(
                    "offload_param requires ZeRO stage 3 (params must be "
                    "partitioned to page per-shard); got stage "
                    f"{config.zero_config.stage}")
            self._param_offload_cfg = pc
        self._param_swapper = None   # NVMe swapper, created on first use
        self._param_host_store = {}  # device=cpu: host-RAM shard store
        self._pcache = None          # metadata while params are paged out
        # -- ZeRO-Infinity IN-TRAINING param streaming (zero/param_stream.py):
        #    params stay host-resident and page through HBM one layer at a
        #    time inside the step, so trainable size is no longer capped by
        #    params+grads <= HBM ------------------------------------------
        self._param_stream = None
        self._paged_training = bool(pc is not None and pc.paged_training
                                    and self._param_offload_device != "none")
        if self._paged_training:
            t = self.topology
            if config.fp16.enabled:
                raise ValueError("offload_param.paged_training supports "
                                 "bf16/fp32 (no dynamic loss scaling on the "
                                 "host-streamed gradient path)")
            if oc is not None:
                raise ValueError("offload_param.paged_training already runs "
                                 "the optimizer on the host — remove "
                                 "offload_optimizer")
            if (t.pipe_parallel_size * t.expert_parallel_size) != 1:
                raise ValueError("offload_param.paged_training composes with "
                                 "dp/tp/sp meshes, not pipe/expert; got "
                                 f"{t}")
            for attr in ("embed", "head", "block_apply"):
                if not hasattr(model, attr):
                    raise ValueError(
                        "offload_param.paged_training needs a model with "
                        "embed/block_apply/head entry points (TransformerLM "
                        f"family); {type(model).__name__} lacks .{attr}")
            _pd = getattr(config, "_param_dict", {})
            for feature in ("progressive_layer_drop", "quantize_training"):
                if _pd.get(feature, {}).get("enabled"):
                    raise ValueError(f"offload_param.paged_training does not "
                                     f"compose with {feature}")
            zc = config.zero_config
            if (zc.zero_quantized_gradients or zc.zero_quantized_weights
                    or zc.zero_hpz_partition_size > 1):
                raise ValueError("offload_param.paged_training does not "
                                 "compose with ZeRO++ knobs")
            if self.optimizer.name in ("onebit_adam", "onebit_lamb",
                                       "zero_one_adam"):
                raise ValueError("offload_param.paged_training uses the host "
                                 "CPU optimizer; 1-bit optimizers are "
                                 "device-side")

        # -- 1-bit optimizers (reference runtime/fp16/onebit): explicit
        #    shard_map DP step so gradients stay local for compression -------
        self._onebit_opt = None
        if self.optimizer.name in ("onebit_adam", "onebit_lamb", "zero_one_adam"):
            t = self.topology
            if (t.model_parallel_size * t.sequence_parallel_size
                    * t.pipe_parallel_size * t.expert_parallel_size
                    * t.mics_shard_size) != 1:
                raise ValueError("1-bit optimizers support pure data parallelism "
                                 "(the reference's supported regime)")
            self._onebit_opt = self._build_onebit_optimizer(config)

        # -- ZeRO++ (reference stage3.py:119, partition_parameters.py:1551,
        #    coalesced_collectives.py:31): quantized collectives need the
        #    gradient/param comm EXPLICIT (shard_map), so the knobs select a
        #    dedicated micro-step build. Reject unsupported compositions
        #    loudly instead of silently ignoring the knobs. ----------------
        zc = config.zero_config
        self._zeropp = (zc.zero_quantized_gradients or zc.zero_quantized_weights
                        or zc.zero_hpz_partition_size > 1)
        if self._zeropp:
            t = self.topology
            if (t.model_parallel_size * t.sequence_parallel_size
                    * t.pipe_parallel_size * t.expert_parallel_size) != 1:
                raise ValueError(
                    "ZeRO++ (zero_quantized_weights/gradients, hpZ) requires a "
                    "pure data-parallel mesh (plus the mics axis for hpZ); got "
                    f"{t}")
            if zc.stage < 2:
                raise ValueError("ZeRO++ requires zero stage >= 2")
            if zc.zero_quantized_weights and zc.stage < 3:
                raise ValueError("zero_quantized_weights requires zero stage 3 "
                                 "(params must be sharded to gather)")
            if zc.zero_hpz_partition_size > 1 and zc.stage < 3:
                raise ValueError("zero_hpz_partition_size > 1 requires zero "
                                 "stage 3 (params must be dp-sharded to have "
                                 "a secondary partition)")
            if zc.zero_hpz_partition_size > 1 and \
                    t.mics_shard_size != zc.zero_hpz_partition_size:
                raise ValueError(
                    f"zero_hpz_partition_size={zc.zero_hpz_partition_size} needs "
                    f"a mesh with mics={zc.zero_hpz_partition_size} (the "
                    f"secondary-partition group); got mics={t.mics_shard_size}")
            if self._onebit_opt is not None:
                raise ValueError("ZeRO++ and 1-bit optimizers are mutually "
                                 "exclusive compression schemes")

        # -- layer-granular overlap schedule (runtime/zero/overlap.py) ------
        # ZeRO++ engines take it whenever overlap_comm is true (the stage-3
        # default). Plain stage-3 engines switch from the declarative path
        # to the explicit pipelined shard_map micro only on an EXPLICIT
        # `overlap_comm: true` — same pure-dp envelope as ZeRO++, and none
        # of the engine modes that own their own micro structure.
        t = self.topology
        self._stage3_overlap = (
            not self._zeropp and zc.stage == 3
            and bool(zc.overlap_comm)
            and bool(getattr(zc, "overlap_comm_explicit", False))
            and (t.model_parallel_size * t.sequence_parallel_size
                 * t.pipe_parallel_size * t.expert_parallel_size) == 1
            and self._offload_device == "none"
            and not self._paged_training
            and self._onebit_opt is None)
        # every engine mode that steps through the explicit shard_map
        # micro (ZeRO++ barrier or pipelined, stage-3 pipelined)
        self._explicit_micro = self._zeropp or self._stage3_overlap
        self._overlap_active = False      # set when the micro is built
        self._overlap_fallback = ""       # reason the overlap path was skipped

        # -- ZeRO plan -------------------------------------------------------
        part("zero_plan")
        param_specs = model.specs()
        shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), self.param_dtype))
        self._param_struct = shapes  # abstract param tree, reused throughout
        shape_tree = jax.tree.map(lambda x: x.shape, shapes)
        self.zero_plan = ZeroPartitionPlan(self.topology, config.zero_config,
                                           param_specs, shape_tree)
        self._param_shardings = self.zero_plan.param_shardings()
        self._grad_shardings = self.zero_plan.grad_shardings()
        log_dist(self.zero_plan.summary(), ranks=[0])

        # Twin-Flow leaf split: host gets ~ratio of the master elements
        # (largest-first greedy), device keeps the rest with a jitted
        # optimizer step. Computed here (not in _init_offload_runner) because
        # _state_shardings needs the device subset's optimizer shardings.
        self._offload_host_idx: list = []
        self._offload_device_idx: list = []
        self._offload_leaf_names: list = []
        if self._offload_device != "none":
            leaves_paths = jax.tree_util.tree_flatten_with_path(shapes)[0]
            names = ["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                              for p in path) for path, _ in leaves_paths]
            sizes = [int(np.prod(leaf.shape)) or 1 for _, leaf in leaves_paths]
            self._offload_leaf_names = names
            target = self._offload_ratio * sum(sizes)
            acc = 0.0
            host = set()
            for i in sorted(range(len(sizes)), key=lambda j: -sizes[j]):
                if abs(acc + sizes[i] - target) <= abs(acc - target):
                    host.add(i)
                    acc += sizes[i]
            if not host:  # ratio>0 guarantees at least one host leaf
                host.add(min(range(len(sizes)), key=lambda j: sizes[j]))
            self._offload_host_idx = [i for i in range(len(sizes)) if i in host]
            self._offload_device_idx = [i for i in range(len(sizes))
                                        if i not in host]

        # -- state init (sharded at init like reference zero.Init,
        #    partition_parameters.py:734) ------------------------------------
        # gas==1 fused-eligible engines keep NO persistent gradient buffer:
        # the fused program's gradients are XLA temporaries (see
        # _train_step_fn). The split forward/backward path allocates the
        # buffer lazily on first use (_ensure_grad_acc).
        # (offload engines qualify too: their gas==1 micro step REPLACES
        # the empty tree with the fresh gradients instead of accumulating —
        # params + grad buffer + fresh grads would be 3x model bytes, the
        # difference between a 3B step compiling on one chip and OOM)
        part("init_state")
        self._gradacc_lazy = (
            config.gradient_accumulation_steps == 1
            and not self._explicit_micro
            and self._onebit_opt is None
            and os.environ.get("DSTPU_FUSED_STEP", "1") != "0")
        if self._paged_training:
            # params never materialize on device as a full tree — the
            # runner owns host params + host optimizer state
            from .zero.param_stream import ParamStreamRunner
            pc = self.config.zero_config.offload_param
            self._param_stream = ParamStreamRunner(
                model, self.mesh,
                optimizer_cfg=config.optimizer,
                param_dtype=self.param_dtype,
                gradient_clipping=config.gradient_clipping,
                buffer_count=pc.buffer_count,
                nvme_path=pc.nvme_path,
                device=self._param_offload_device,
                seed=seed, init_params=init_params,
                # the same precision knobs the device optimizer honors:
                # bf16 moments (stochastic-rounded store) and bf16 grad
                # accumulators halve the HOST state — what fits a 7B-dims
                # paged train state in 125 GB RAM. Raw values pass through
                # so the runner rejects fp16 loudly instead of a silent
                # fp32 downgrade.
                moment_dtype=_norm_dt(
                    config.data_types_optimizer_moment_dtype),
                grad_acc_dtype=_norm_dt(config.data_types_grad_accum_dtype))
            self.state = {"params": None, "opt": None,
                          "loss_scale": self._loss_scale_state()}
        else:
            self.state = self._init_state(seed, init_params)

        part(None)
        # -- bookkeeping -----------------------------------------------------
        self.global_steps = 0
        self.skipped_steps = 0
        self.micro_steps = 0
        self._cached_grads = None
        self._cached_loss = None
        self._last_prepared_batch = None  # abstract struct for MFU flops
        self.gradient_accumulation_steps = config.gradient_accumulation_steps
        self.train_micro_batch_size_per_gpu = config.train_micro_batch_size_per_gpu
        self.train_batch_size = config.train_batch_size
        self.gradient_clipping = config.gradient_clipping

        self.timers = SynchronizedWallClockTimer() if config.wall_clock_breakdown else NoopTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size,
            steps_per_output=config.steps_per_print,
            logging_fn=lambda msg: log_dist(msg, ranks=[0]))

        from ..monitor.monitor import MonitorMaster
        self.monitor = MonitorMaster(config.monitor_config)

        from ..profiling.flops_profiler.profiler import FlopsProfiler
        self.flops_profiler = FlopsProfiler(model=model, ds_engine=self)

        # -- telemetry (telemetry/): span tracing, MFU/goodput, memory
        #    watermarks, stall watchdog. Disabled (the default) this is the
        #    NULL object — every hook a constant no-op, nothing in traced
        #    code (enforced by the telemetry-off-parity Layer-B audit). The
        #    MonitorMaster is ONE sink of the derived metrics; a JSONL sink
        #    feeds tools/trace_view.py. ---------------------------------
        self.telemetry = self._build_telemetry()
        self._step_tokens = 0       # host-counted tokens of the open step

        # -- numerics guardian (resilience/guardian.py, ISSUE 13): None
        #    when off — the step functions then trace the exact
        #    pre-guardian program (machine-checked by the
        #    guardian-step-parity lint entry). When armed, the traced
        #    step packs the anomaly word beside the overflow scalar and
        #    the host policy escalates deterministically. --------------
        self._guardian = build_guardian(
            config.guardian_config, telemetry=self.telemetry,
            # fp16 DYNAMIC scaling: overflow-only anomalies are the
            # scaler's routine calibration (skip + backoff), not a
            # rollback signal — see GuardianPolicy.scaler_owns_overflow
            scaler_owns_overflow=(config.fp16.enabled
                                  and config.fp16.loss_scale == 0))
        #: outputs of the last guardian-armed step (host bookkeeping)
        self._last_anomaly_word = 0

        # -- resilience: a DSTPU_FAULT_PLAN env installs the deterministic
        #    chaos schedule (resilience/fault_plan.py) — host-side seams
        #    only, one None-check per step when absent -------------------
        maybe_install_from_env()
        # where the last save landed — the watchdog-escalation path
        # checkpoints there (or checkpoint.escalation_dir) before exiting
        self._last_save_dir: Optional[str] = None
        self._escalation_exit = os._exit  # injectable for tests

        # -- checkpoint engine: sync npz writes, or write-behind when
        #    checkpoint: {async_save: true} (the previously-dead
        #    AsyncCheckpointEngine) — see save_checkpoint ---------------
        self._ckpt_async = bool(self.config.checkpoint_config.get(
            "async_save", False))
        if self._ckpt_async and jax.process_count() > 1:
            log_dist("checkpoint.async_save: multi-host saves keep the "
                     "synchronous barrier path (per-rank shard files need "
                     "the collective commit fence)", ranks=[0])
            self._ckpt_async = False
        from ..checkpoint.checkpoint_engine import (AsyncCheckpointEngine,
                                                    NpzCheckpointEngine)
        self.checkpoint_engine = (AsyncCheckpointEngine()
                                  if self._ckpt_async
                                  else NpzCheckpointEngine())

        # curriculum learning (reference engine.py:339,1813: difficulty ->
        # forward kwargs; here difficulty == sequence length truncation)
        self.curriculum_scheduler = None
        if config.curriculum_enabled_legacy:
            from .data_pipeline.curriculum_scheduler import CurriculumScheduler
            self.curriculum_scheduler = CurriculumScheduler(
                config.curriculum_params_legacy)

        # progressive layer drop (reference engine.py:339: PLD theta fed into
        # forward kwargs; here a per-layer keep mask through the scan)
        self.progressive_layer_drop = None
        pld_cfg = getattr(config, "_param_dict", {}).get("progressive_layer_drop", {})
        if pld_cfg.get("enabled"):
            from .progressive_layer_drop import ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=pld_cfg.get("theta", 0.5), gamma=pld_cfg.get("gamma", 0.001))
            self._pld_rng = np.random.default_rng(seed)

        # MoQ: engine-scheduled quantization-aware training (reference
        # engine.py quantizer + runtime/quantize.py:14)
        self.quantizer = None
        self.eigenvalue = None
        qt_cfg = getattr(config, "_param_dict", {}).get("quantize_training", {})
        if qt_cfg.get("enabled"):
            from .quantize import MoQQuantizer
            self.quantizer = MoQQuantizer(qt_cfg)
            if self.quantizer.eigenvalue_enabled:
                from .eigenvalue import Eigenvalue
                eig = qt_cfg.get("eigenvalue", {})
                self.eigenvalue = Eigenvalue(
                    verbose=eig.get("verbose", False),
                    max_iter=eig.get("max_iter", 10),
                    tol=eig.get("tol", 1e-2),
                    stability=eig.get("stability", 1e-6))
        self._last_batch = None

        from .. import comm as dist
        if config.comms_logger_enabled:
            dist.configure(config=config)
        # install the overlap-planner config flag process-wide so the
        # engineless consumers (moe/layer.py, sequence/layer.py) honor
        # `overlap_plan: false` too — engine call sites still pass their
        # own config explicitly
        from .overlap_planner import configure_planner
        configure_planner(config.overlap_plan)
        if config.comm_transport:
            # install the transport-planner policy BEFORE any micro step
            # traces (plans are resolved at trace time); invalid keys or
            # widths raise here, at engine build
            dist.configure_transport(**config.comm_transport)
            if config.comm_transport.get("error_feedback"):
                # the overlap planner threads the residual state through
                # the pipelined micro's scan carries (ISSUE 9, closing the
                # ROADMAP item 1(a) deferral) — but ONLY there: the
                # barrier schedule, the fused GSPMD step and a disabled
                # planner still leave EF to explicit
                # TreeComm.scatter(err=...) callers. Whether the carry is
                # actually LIVE is known only when the micro builds
                # (overlap eligibility, int8-eligible buckets) — the
                # builder logs the definitive slot count then; this is
                # only the definite-no warning.
                from .overlap_planner import planner_enabled
                may_carry = (self._explicit_micro
                             and bool(self.config.zero_config.overlap_comm)
                             and planner_enabled(self.config.overlap_plan))
                if not may_carry:
                    logger.warning(
                        "comm_transport.error_feedback: this engine's "
                        "schedule does not carry the residual state "
                        "(pipelined micro + overlap planner required); "
                        "error feedback is active only for explicit "
                        "TreeComm.scatter(err=...) callers")

        self._jit_micro_step = None
        self._jit_apply_step = None
        self._jit_train_step = None
        # What the model launches, plain values kept with telemetry off
        # (docs/OBSERVABILITY.md has every key): the model's own records
        # (``expert_records`` / ``attention_records``), from its configuration
        # now and from the launches' plans once a step is traced (None until
        # then); the engine adds the experts' path and the fused steps so far.
        # What a step leaves on the device beside the loss,
        # ``moe_expert_rows()``, ``diffusion_last_step()`` and
        # ``attn_last_step()`` fetch.
        self.moe_totals = {"path": getattr(self.model, "moe_path", None),
                           "steps": 0, **(self._model_records("expert_records") or {}),
                           "grouped_matmul_route": None,
                           "products_kernel": None, "products_xla": None,
                           "combine_route": None, "combine_rows_moved": 0}
        # whether the fused step returns the model's device-side statistics as
        # a last output (the model says); every other program is as it was
        self._step_has_stats = bool(getattr(self.model, "returns_step_stats", False))
        self._step_stats = None
        self.attn_totals, self.diffusion_totals = (
            self._model_records("attention_records") or ({}, None))
        if self.diffusion_totals is not None:
            self.diffusion_totals["steps"] = 0
        # Optimizer-kernel counters, kept with telemetry off and filled from
        # the static bucket plan when a step that updates is traced: the
        # path ("pallas" / "xla"; None until then), and on the kernel path
        # the launches and elements handed over in the leaf's own layout
        # (native) against those flattened to rows of 128 (flat).
        self.opt_kernel_totals = {"path": None, "launches_native": 0,
                                  "launches_flat": 0, "elements_native": 0,
                                  "elements_flat": 0}
        # What ``remat=True`` kept, kept with telemetry off and written
        # while a step is traced (checkpointing.KEEP_PRODUCTS): the
        # policy, the names the STEP saves (one choice over every kind of
        # block), their bytes over the layers that make them, the bytes of
        # every candidate, the room the device gave, the budget they were
        # held to (None where the backend reports no memory) and what they
        # take of it (``saved_cost_bytes``: a byte under the layer scan
        # costs more than one in a layer that runs by itself), and what the
        # budget was charged first: every block's input (``carries_bytes``)
        # and the working set, the larger of the largest block's forward
        # and backward (``block_bytes``) and what lives outside the blocks
        # (``outside_bytes``) less the gradients (``grads_bytes``), with
        # what layers hand on beside either (``handed_bytes``);
        # ``saved_by_kind``: kind of block -> its layers, its block's bytes,
        # the names of the choice it has and their bytes. ``policy`` None:
        # no block was differentiated under that policy (remat off, an
        # explicit policy, the ZeRO-3 overlap schedule).
        self.remat_totals = {"policy": None, "saved": (), "saved_bytes": 0,
                             "candidate_bytes": 0, "room_bytes": None,
                             "budget_bytes": None, "working_bytes": 0,
                             "block_bytes": 0, "outside_bytes": 0,
                             "grads_bytes": 0, "handed_bytes": 0,
                             "carries_bytes": 0, "saved_cost_bytes": 0,
                             "saved_by_kind": {}}
        # A step's memory, kept with telemetry off and written ONCE, when the
        # first optimizer step has returned (``_account_memory``), from two
        # readings of the allocator: ``limit_bytes``, ``resident_bytes``,
        # ``step_extra_bytes`` (what the running step needs beyond what is
        # resident before it: the runtime's reservation, where the step's own
        # programs raised it), ``step_peak_bytes`` = residents + extra,
        # ``headroom_bytes``. Every key is there from construction, None.
        # Under a profiler session the residents are renewed once, so a trace
        # holds the traced steps'.
        self.memory_totals = step_memory.empty_totals()
        self._memory_before = None      # the allocator before the step's first call
        self._profiler_seen = False     # was the last step under a session?
        # overlap-planner state (set for real when the pipelined micro
        # builds; defaults keep non-overlap engines on the plain carry)
        self._ef_carry_active = False
        self._ef_state = None
        self._overlap_plan = None

    # ------------------------------------------------------------------
    # telemetry construction
    # ------------------------------------------------------------------
    def _build_telemetry(self):
        from ..telemetry import JsonlMetricsSink, build_telemetry
        cfg = self.config.telemetry_config
        sinks = [self.monitor] if self.monitor.enabled else []
        tele = build_telemetry(cfg, sinks=sinks)
        if not tele.enabled:
            return tele
        # set-up's spans that closed before this recorder existed
        self._setup.replay(tele)
        if tele.flush_every <= 1 and (cfg is None or not cfg.flush_interval):
            tele.flush_every = max(1, self.config.steps_per_print)
        if jax.process_index() == 0:
            os.makedirs(tele.output_dir, exist_ok=True)
            tele.sinks.append(JsonlMetricsSink(
                os.path.join(tele.output_dir, "metrics.jsonl")))
        # model FLOPs for MFU resolve lazily at the first flush, through
        # the SAME cost-analysis machinery the flops profiler reports — the
        # two surfaces cannot disagree about the model's arithmetic. The
        # paged-training runner owns its own step programs (no engine jit
        # to cost), so MFU stays unavailable there rather than erroring.
        if self._param_stream is None:
            tele.set_flops_fn(self._telemetry_flops)
        if tele.watchdog is not None:
            from .. import comm as dist
            tele.watchdog.dump_fns.append(lambda: dist.comms_log_tail())
            # hard-deadline escalation (watchdog.escalate_after_s):
            # checkpoint-and-exit so a supervising elastic agent restarts
            tele.escalation_handler = self._escalate_stall
        return tele

    def _telemetry_flops(self) -> float:
        """Model FLOPs per optimizer step for the MFU metric, from the
        same XLA cost-analysis machinery the flops profiler reports.
        Engines on the split path cost the micro step x accumulation
        steps (the profiler's exact number); gas==1 fused engines cost
        the one fused program (fwd+bwd+update — the arithmetic the step
        actually runs). Needs one traced batch; raises until a step ran."""
        if self._last_prepared_batch is None:
            raise RuntimeError("no batch seen yet")
        if self._fused_step_eligible() and \
                not jax.tree.leaves(self.state["grad_acc"]):
            self._build_fused_jit()
            args = (self.state, self._last_prepared_batch,
                    jax.ShapeDtypeStruct((), jnp.float32))
            if self._guardian is not None:
                # the guardian-armed fused jit takes the spike threshold
                # as a 4th (host-scalar) argument
                args = args + (jax.ShapeDtypeStruct((), jnp.float32),)
            flops = self._program_flops(self._jit_train_step, args)
        else:
            self._build_jits()
            flops = self._micro_step_flops(self._last_prepared_batch) \
                * self.gradient_accumulation_steps
        if flops <= 0:
            raise RuntimeError("cost analysis returned no flops")
        return flops

    def _program_flops(self, jitted, args) -> float:
        """XLA's count of the FLOPs of ``jitted`` at ``args``' shapes, over
        the whole mesh: the cost analysis of the LOWERED module, which
        compiles nothing. A backend that cannot analyse a module it has not
        compiled (the TPU's client answers None) gets the compile, as a
        ``first_call`` of the program ``flops_probe`` so that it shows; the
        compiled module counts one device's share."""
        lowered = jitted.lower(*jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
        cost = lowered.cost_analysis()
        if cost and cost.get("flops", 0.0) > 0:
            return float(cost["flops"])
        with self._first_call("flops_probe"):
            cost = lowered.compile().cost_analysis()
        if isinstance(cost, list):
            cost = cost[0] if cost else {}
        return float(cost.get("flops", 0.0)) * self.mesh.size

    # ------------------------------------------------------------------
    # 1-bit optimizer construction
    # ------------------------------------------------------------------
    def _build_onebit_optimizer(self, config):
        from .fp16.onebit import OnebitAdam, OnebitLamb, ZeroOneAdam
        from .topology import DATA_AXIS as AX
        p = dict(config.optimizer.params) if config.optimizer is not None else {}
        dp = self.topology.data_parallel_size
        common = dict(lr=p.get("lr", 1e-3),
                      betas=tuple(p.get("betas", (0.9, 0.999))),
                      eps=p.get("eps", 1e-8),
                      weight_decay=p.get("weight_decay", 0.0),
                      axis=AX, axis_size=dp)
        name = self.optimizer.name
        if name == "onebit_adam":
            return OnebitAdam(freeze_step=p.get("freeze_step", 100), **common)
        if name == "onebit_lamb":
            return OnebitLamb(freeze_step=p.get("freeze_step", 100),
                              max_coeff=p.get("max_coeff", 10.0),
                              min_coeff=p.get("min_coeff", 0.01), **common)
        return ZeroOneAdam(
            var_freeze_step=p.get("var_freeze_step", 100),
            var_update_scaler=p.get("var_update_scaler", 16),
            local_step_scaler=p.get("local_step_scaler", 4), **common)

    # ------------------------------------------------------------------
    # state construction
    # ------------------------------------------------------------------
    def _loss_scale_state(self):
        if self.config.fp16.enabled:
            if self.config.fp16.loss_scale > 0:
                return static_loss_scale_state(self.config.fp16.loss_scale)
            return dynamic_loss_scale_state(self.config.fp16.initial_scale_power,
                                            self.config.fp16.hysteresis)
        return static_loss_scale_state(1.0)

    def _state_shardings(self) -> Dict[str, Any]:
        opt_spec = self.zero_plan.optimizer_spec_tree()
        mesh = self.mesh
        named = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                          is_leaf=lambda s: isinstance(s, P))
        opt_named = named(opt_spec)
        rep = NamedSharding(mesh, P())
        if self._onebit_opt is not None:
            return self._onebit_state_shardings()
        if self._offload_device != "none":
            opt_shardings = {}
            if self._offload_device_idx:
                # Twin-Flow: the device-resident subset keeps a jitted
                # optimizer; its state is a name-keyed dict (names match the
                # params tree paths so opt/master/<name> lines up for
                # zero_to_fp32)
                spec_leaves = jax.tree.leaves(
                    opt_spec, is_leaf=lambda s: isinstance(s, P))
                param_leaves = jax.tree.leaves(self._param_struct)
                dev = {self._offload_leaf_names[i]: param_leaves[i]
                       for i in self._offload_device_idx}
                dev_named = {self._offload_leaf_names[i]:
                             NamedSharding(mesh, spec_leaves[i])
                             for i in self._offload_device_idx}
                opt_template = jax.eval_shape(
                    lambda: self.optimizer.init(
                        {k: jnp.zeros(v.shape, v.dtype)
                         for k, v in dev.items()}))
                for key in opt_template:
                    opt_shardings[key] = rep if key == "step" else dev_named
        else:
            opt_template = jax.eval_shape(
                lambda: self.optimizer.init(
                    jax.tree.map(jnp.zeros_like, self._param_struct)))
            opt_shardings = {}
            for key in opt_template:
                opt_shardings[key] = rep if key == "step" else opt_named
        return {
            "params": self._param_shardings,
            "grad_acc": {} if self._gradacc_lazy else self._grad_shardings,
            "opt": opt_shardings,
            "loss_scale": jax.tree.map(lambda _: rep, self._loss_scale_state()),
        }

    def _onebit_state_shardings(self) -> Dict[str, Any]:
        from .topology import DATA_AXIS as AX
        mesh = self.mesh
        rep = NamedSharding(mesh, P())
        dp_sharded = lambda tree: jax.tree.map(
            lambda _: NamedSharding(mesh, P(AX)), tree)
        template = jax.eval_shape(
            lambda: self._onebit_opt.init(
                self.model.init(jax.random.PRNGKey(0), self.param_dtype)))
        opt_shardings = {k: (dp_sharded(v) if k in ("worker_error", "server_error")
                             else jax.tree.map(lambda _: rep, v))
                         for k, v in template.items()}
        params_tmpl = jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0), self.param_dtype))
        return {
            "params": self._param_shardings,
            "grad_acc": dp_sharded(params_tmpl),
            "opt": opt_shardings,
            "loss_scale": jax.tree.map(lambda _: rep, self._loss_scale_state()),
        }

    def _init_state(self, seed: int, init_params: Optional[Any]) -> Dict[str, Any]:
        shardings = self._state_shardings()

        offload = self._offload_device != "none"
        dp = self.topology.data_parallel_size

        def make_opt(params):
            if self._onebit_opt is not None:
                opt = self._onebit_opt.init(params)
                # per-worker error feedback: leading dp dim, sharded over data
                for key in ("worker_error", "server_error"):
                    opt[key] = jax.tree.map(
                        lambda e: jnp.zeros((dp,) + e.shape, e.dtype), opt[key])
                return opt
            if offload:
                if not self._offload_device_idx:
                    return {}
                leaves = jax.tree.leaves(params)
                return self.optimizer.init(
                    {self._offload_leaf_names[i]: leaves[i]
                     for i in self._offload_device_idx})
            return self.optimizer.init(params)

        def make_grad_acc(params):
            if self._gradacc_lazy:
                return {}  # fused gas==1: gradients never persist in HBM
            if self._onebit_opt is not None:  # local per-device accumulators
                return jax.tree.map(
                    lambda p: jnp.zeros((dp,) + p.shape, self.grad_dtype), params)
            return jax.tree.map(lambda p: jnp.zeros(p.shape, self.grad_dtype), params)

        def make_state(rng):
            params = self.model.init(rng, self.param_dtype)
            return {
                "params": params,
                "grad_acc": make_grad_acc(params),
                "opt": make_opt(params),
                "loss_scale": self._loss_scale_state(),
            }

        with self.mesh:
            if init_params is not None:
                params = jax.tree.map(lambda x: jnp.asarray(x, self.param_dtype), init_params)
                make = lambda p: {
                    "params": p,
                    "grad_acc": make_grad_acc(p),
                    "opt": make_opt(p),
                    "loss_scale": self._loss_scale_state(),
                }
                with self._first_call("init_state"):
                    state = jax.jit(make, out_shardings=shardings)(params)
            else:
                rng = jax.random.PRNGKey(seed)
                with self._first_call("init_state"):
                    state = jax.jit(make_state, out_shardings=shardings)(rng)
        if offload:
            log_dist("state initialized; building offload runner", ranks=[0])
            self._init_offload_runner(state)
        return state

    # elements per NVMe-paged optimizer-state chunk (each chunk's read
    # overlaps the previous chunk's CPU step — double-buffered)
    _OFFLOAD_CHUNK_ELEMS = 4 << 20

    def _offload_bucket_elems(self) -> int:
        """Effective offload bucket/chunk size in ELEMENTS: the fused-buffer
        planner's ``reduce_bucket_size`` discipline (overlap.py binds the
        same knob for collective launches) bounded by the streaming default
        — an explicit smaller ``reduce_bucket_size`` shrinks the offload
        buckets with it, so one knob governs both tiers. Chunk boundaries
        are a CHECKPOINT LAYOUT contract (m/v state is chunked), so this is
        resolved once and recorded in the sidecar."""
        zc = self.config.zero_config
        rb = int(getattr(zc, "reduce_bucket_size", 0) or 0)
        eff = self._OFFLOAD_CHUNK_ELEMS
        if rb > 0:
            eff = min(eff, rb)
        return max(1, eff)

    def _chunked(self, a: np.ndarray):
        c = getattr(self, "_offload_chunk_elems", None) \
            or self._offload_bucket_elems()
        return [a[i:i + c] for i in range(0, max(a.size, 1), c)]

    def _offload_ckpt_path(self, dirname: str) -> str:
        """Per-process file: each host owns only its local master segment."""
        if jax.process_count() == 1:
            return os.path.join(dirname, "offload_optimizer.npz")
        return os.path.join(dirname,
                            f"offload_optimizer.rank{jax.process_index()}.npz")

    def _leaf_flat_layouts(self, spec_tree):
        """Per-leaf flat layout from the optimizer partitioning spec:
        ``(dp_dim, dp_axes, mp_dim, mp_axes)``. The flat form is 2-D —
        ``[dp_dim, mp_dim*rest]`` with the dp-sharded dim first and any
        model/tensor-sharded dim as the MAJOR component of the second —
        both LOCAL transposes, so the SPMD partitioner never has to
        rematerialize, and a tp/sp-sharded leaf keeps its model sharding
        on dim 1 while the host master partitions over dim 0 (offload x
        model parallel, reference stage_1_and_2.py:96 init with mpu)."""
        from .topology import EXPERT_AXIS, MICS_AXIS, SEQ_AXIS
        dp_set = (DATA_AXIS, MICS_AXIS, EXPERT_AXIS, SEQ_AXIS)
        layouts = []
        for spec in jax.tree.leaves(spec_tree,
                                    is_leaf=lambda s: isinstance(s, P)):
            dp_dim, dp_axes = self._dp_axes_in(spec)
            dp_axes = tuple(a for a in dp_axes
                            if self.topology.axis_size(a) > 1)
            mp_dim, mp_axes = None, ()
            for dim, entry in enumerate(spec):
                if entry is None or dim == dp_dim:
                    continue
                ax = entry if isinstance(entry, (tuple, list)) else (entry,)
                mp = tuple(a for a in ax if a not in dp_set
                           and self.topology.axis_size(a) > 1)
                if mp:
                    if mp_dim is not None:
                        raise ValueError(
                            f"optimizer leaf spec {spec} shards two "
                            "non-data dims — no 2-D flat host layout")
                    mp_dim, mp_axes = dim, mp
            layouts.append((dp_dim if dp_axes else None, dp_axes,
                            mp_dim, mp_axes))
        return layouts

    @staticmethod
    def _flat_order(ndim, dp_dim, mp_dim):
        order = [d for d in (dp_dim, mp_dim) if d is not None]
        return order + [d for d in range(ndim) if d not in order]

    @staticmethod
    def _to_flat(x, layout):
        """[...] -> 2-D [dp, rest] per the leaf layout, in the LEAF's own
        dtype: the fp32 widening happens on the HOST after the fetch (both
        consumers already np.asarray(..., float32)). Widening on device
        would double the HBM transient and the D2H bytes — at 3B params
        the fp32 flat copy (13.7 GB) next to the bf16 params cannot even
        fit the chip, which is what stalled the first full-depth 3B
        attempt."""
        dp_dim, _, mp_dim, _ = layout
        if x.ndim == 0:
            return x.reshape(1, 1)
        x = x.transpose(DeepSpeedEngine._flat_order(x.ndim, dp_dim, mp_dim))
        lead = x.shape[0] if dp_dim is not None else 1
        return x.reshape(lead, -1)

    @staticmethod
    def _flat2_sharding_spec(layout) -> P:
        dp_dim, dp_axes, mp_dim, mp_axes = layout
        return P(dp_axes if dp_axes else None, mp_axes if mp_axes else None)

    @staticmethod
    def _leaf_local_groups(arr):
        """Host-local shards of a 2-D flat array grouped by global offset:
        sorted [((row_start, col_start), [devices], device_data)] with
        replicated copies deduplicated (every device in the group gets the
        same data back on push). ``device_data`` stays on device — batch
        the D2H pull with one ``jax.device_get`` over all groups, not
        per-shard copies."""
        groups = {}
        for s in arr.addressable_shards:
            key = tuple((sl.start or 0) for sl in s.index) if s.index else ()
            key = (key + (0, 0))[:2]
            groups.setdefault(key, []).append(s)
        return [(key, [s.device for s in groups[key]], groups[key][0].data)
                for key in sorted(groups)]

    def _init_offload_runner(self, state) -> None:
        """Host master copy + CPU/NVMe optimizer, PARTITIONED over devices.

        Master/optimizer state lives in per-leaf flat fp32 vectors sharded
        over the dp mesh axes (the reference's flat partitioned buffers,
        stage_1_and_2.py:1771 — each DP rank owns 1/dp). Each host holds
        only the segments of its addressable devices, so on a multi-host
        mesh the per-host master memory, gradient fetch bytes, and CPU
        optimizer work all scale as 1/n_hosts instead of being replicated.
        """
        from .zero.offload_optimizer import OffloadedOptimizerRunner
        oc = self.config.zero_config.offload_optimizer
        t = self.topology
        if (t.pipe_parallel_size * t.expert_parallel_size) != 1:
            raise ValueError(
                "offload_optimizer composes with tensor/sequence parallel "
                "meshes but not pipe/expert (a leaf sharded over two "
                f"non-data dims has no 2-D flat host layout); got {t}")

        leaves_paths, self._offload_treedef = \
            jax.tree_util.tree_flatten_with_path(state["params"])
        host_idx = self._offload_host_idx
        all_names, all_shapes = [], []
        for path, leaf in leaves_paths:
            all_names.append("/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                                      for p in path))
            all_shapes.append(leaf.shape)
        # full-tree metadata (unflatten rebuilds EVERY leaf); host-subset
        # metadata for the flat master/moments the host runner owns
        self._offload_full_shapes = all_shapes
        all_layouts = self._leaf_flat_layouts(
            self.zero_plan.optimizer_spec_tree())
        self._offload_all_layouts = all_layouts
        names = [all_names[i] for i in host_idx]
        shapes = [all_shapes[i] for i in host_idx]
        sizes = [int(np.prod(s)) or 1 for s in shapes]
        self._offload_names = names
        self._offload_shapes = shapes
        self._offload_layouts = [all_layouts[i] for i in host_idx]
        self._offload_layout = {"sizes": sizes, "total": sum(sizes)}
        self._offload_flat_shardings = tuple(
            NamedSharding(self.mesh, self._flat2_sharding_spec(lay))
            for lay in self._offload_layouts)

        layouts = self._offload_layouts

        # phase markers: at multi-GiB model sizes each of these phases can
        # take minutes through a slow host<->device link — a silent stall
        # here is indistinguishable from a hang without them. Flattening is
        # one small program PER LEAF (shared cache with the step path).
        import time as _time
        _t0 = _time.perf_counter()
        # Flatten -> fetch -> RELEASE one leaf at a time: holding every
        # flat copy at once would put params + grad buffer + flats
        # (3x model bytes) on the chip together — 20.4 GB at 3B params,
        # which cannot fit 15.75 GiB HBM. Peak here is 2x model bytes plus
        # ONE flat leaf. spans: (leaf_idx, (row0, col0), piece_shape,
        # [devices]) in local processing order — THE layout contract for
        # fetch/step/push/ckpt.
        param_leaves = jax.tree.leaves(state["params"])
        self._offload_flat_shapes = []
        self._offload_direct = []  # per host leaf: raw-C-order move ok?
        self._offload_spans = []
        pieces = []
        total_b = 0
        with self.mesh:
            for k, (i, lay, sh) in enumerate(zip(
                    host_idx, layouts, self._offload_flat_shardings)):
                leaf = param_leaves[i]
                direct = self._offload_leaf_direct(leaf.shape, lay)
                self._offload_direct.append(direct)
                if direct:
                    fshape = self._flat_shape(leaf.shape, lay)
                    self._offload_flat_shapes.append(fshape)
                    self._offload_spans.append(
                        (k, (0, 0), fshape, list(leaf.devices())))
                    total_b += leaf.nbytes
                    pieces.append(np.asarray(jax.device_get(leaf),
                                             np.float32).reshape(-1))
                    continue
                flat = self._flat_leaf_jit(leaf.shape, leaf.dtype, lay, sh)(leaf)
                self._offload_flat_shapes.append(flat.shape)
                datas = []
                for key, devices, data in self._leaf_local_groups(flat):
                    self._offload_spans.append((k, key, data.shape, devices))
                    datas.append(data)
                total_b += sum(d.nbytes for d in datas)
                pieces.extend(np.asarray(p, np.float32).reshape(-1)
                              for p in jax.device_get(datas))
                del flat, datas
        log_dist(f"offload init: flatten+fetch {total_b / 1e9:.1f} GB in "
                 f"{_time.perf_counter() - _t0:.1f}s", ranks=[0])
        local_master = (np.concatenate(pieces) if pieces
                        else np.zeros(0, np.float32))
        # chunk the local segment so NVMe paging streams fixed-size blocks
        # (chunk i+1's read overlaps chunk i's CPU step); resolved ONCE —
        # the chunk layout is a checkpoint contract
        self._offload_chunk_elems = self._offload_bucket_elems()
        chunks = self._chunked(local_master)
        # -- pipelined-schedule metadata (ISSUE 15): per-leaf span ranges
        # and leaf-bucket fetch groups. Spans are recorded per leaf in
        # order, so a bucket (a contiguous leaf run) is a contiguous span
        # run — the prefix property the chunk feed relies on. Grouping
        # rides the overlap.py fused-buffer planner: small leaves pack
        # greedily under the bucket, at-cap leaves stand alone.
        from .zero.partition import plan_comm_buckets
        self._offload_leaf_spans = []
        s = 0
        for k in range(len(host_idx)):
            e = s
            while e < len(self._offload_spans) and \
                    self._offload_spans[e][0] == k:
                e += 1
            self._offload_leaf_spans.append((s, e))
            s = e
        local_sizes = [sum(int(np.prod(self._offload_spans[j][2]))
                           for j in range(a, b))
                       for a, b in self._offload_leaf_spans]
        entries, _ = plan_comm_buckets(
            local_sizes, ["offload"] * len(local_sizes),
            [1] * len(local_sizes), self._offload_chunk_elems)
        # the planner may pack around a standalone at-cap leaf; the feed
        # needs CONTIGUOUS leaf runs (runner chunks consume a prefix), so
        # split each bucket at discontinuities and order by first leaf
        runs = []
        for e in entries:
            ls = sorted(e.leaves)
            run = [ls[0]]
            for x in ls[1:]:
                if x == run[-1] + 1:
                    run.append(x)
                else:
                    runs.append(run)
                    run = [x]
            runs.append(run)
        runs.sort(key=lambda r: r[0])
        self._offload_fetch_buckets = runs

        opt_cfg = self.config.optimizer
        self._offload = OffloadedOptimizerRunner(
            opt_type=opt_cfg.type if opt_cfg is not None else "adamw",
            opt_params=dict(opt_cfg.params) if opt_cfg is not None else {},
            leaves=chunks,
            device=self._offload_device,
            nvme_path=oc.nvme_path,
            pipeline=oc.pipeline_read or oc.pipeline_write)
        twin = ""
        if self._offload_device_idx:
            dev_elems = sum(int(np.prod(all_shapes[i])) or 1
                            for i in self._offload_device_idx)
            twin = (f", Twin-Flow ratio {self._offload_ratio}: "
                    f"{dev_elems / 1e6:.1f}M elements stay device-stepped")
        log_dist(f"ZeRO-Offload: optimizer on {self._offload_device} "
                 f"(local {local_master.size / 1e6:.1f}M of "
                 f"{self._offload_layout['total'] / 1e6:.1f}M master params, "
                 f"{len(chunks)} chunks{twin})", ranks=[0])

    # ------------------------------------------------------------------
    # jitted step functions
    # ------------------------------------------------------------------
    def moe_expert_rows(self):
        """The last fused step's assignments per expert, ``[layers,
        experts]`` int32, fetched now (the step itself never syncs on
        them); None off the no-drop MoE path or before a step."""
        if not self._step_stats or "moe_expert_rows" not in self._step_stats:
            return None
        return np.asarray(self._step_stats["moe_expert_rows"])

    def diffusion_last_step(self) -> Optional[Dict[str, float]]:
        """The last fused step's ``masked_share`` (masked positions over all)
        and ``mean_weight`` (the mean 1 / t of a masked position), fetched
        now; None for another objective or before a step."""
        if not self._step_stats or "diffusion_masked_share" not in self._step_stats:
            return None
        return {"masked_share": float(self._step_stats["diffusion_masked_share"]),
                "mean_weight": float(self._step_stats["diffusion_mean_weight"])}

    def attn_last_step(self) -> Optional[Dict[str, Any]]:
        """The last fused step's count of flash tiles under its rows' packed
        documents, fetched now: ``{kind: {"forward" | "backward":
        {"position": the tiles the position test alone runs, "run": the
        tiles run}}}`` for one launch, a head (``pallas_flash.tiles_run`` at
        the tiles the kernel route takes; kinds ``window``, ``full``,
        ``blockdiff``, ``dsa``: ``TransformerLM.attn_tile_kinds``), and beside
        them every scalar the model left under ``attn_<name>``, as ``name``
        (a learned selection's ``selected_share``, selected over visible
        pairs, ``indexer_kl`` and ``lm_loss``, the two terms of its loss).
        None for a model that leaves neither, or before a step."""
        stats = self._step_stats or {}
        out = {name[len("attn_"):]: float(value) for name, value in stats.items()
               if name.startswith("attn_") and name != "attn_tiles"}
        if "attn_tiles" in stats:
            tiles = np.asarray(stats["attn_tiles"])
            out.update({
                kind: {kernel: {"position": int(by_position), "run": int(run)}
                       for kernel, (by_position, run) in zip(("forward", "backward"), line)}
                for (kind, _), line in zip(self.model.attn_tile_kinds, tiles)})
        return out or None

    @functools.cached_property
    def _remat_room_bytes(self) -> Optional[int]:
        """What one device can give a step's activations, read ONCE, when a
        step is first traced with the training state on the device: what the
        mesh's fullest device reports free, less the gradients. Whatever
        else is resident then (an evaluation batch, a harness's buffers)
        counts as staying. None where the backend reports no memory (the
        CPU): everything named is saved. Across processes every host must
        decide alike, so until the reading is exchanged a multi-process run
        gets 0: the block recomputed whole."""
        fullest = step_memory.device_memory(self.mesh.devices.flat)
        if fullest is None:
            return None
        if jax.process_count() > 1:
            return 0
        free = fullest["bytes_limit"] - fullest["bytes_in_use"]
        grads = self._grads_bytes
        log_dist(f"remat room: {free / 1e9:.2f} GB free a device, "
                 f"{grads / 1e9:.2f} GB of gradients", ranks=[0])
        return max(0, free - grads)

    @functools.cached_property
    def _grads_bytes(self) -> int:
        """The gradients' bytes on one device, as `_remat_room_bytes` takes
        them out of what is free."""
        return gradients_bytes(self.state["params"], self.grad_dtype)

    def _remat_kw(self, local: bool = False) -> Dict[str, Any]:
        """The ``remat_budget=`` argument for a differentiated call of the
        model's loss, where it takes one (``checkpointing.Budget``: what its
        blocks' backward may keep, and ``remat_totals`` to write the
        decision into). A model's bytes are whole-batch, so the device's
        room counts once for each way the mesh splits an activation;
        ``local``: the call sits inside a ``shard_map`` and sees one
        device's share."""
        if "remat_budget" not in inspect.signature(self.model.loss).parameters:
            return {}
        room = self._remat_room_bytes
        if room is None:
            return {"remat_budget": remat.Budget(None, self.remat_totals)}
        ways = 1 if local else int(np.prod([self.mesh.shape[a]
                                            for a in BATCH_AXES + (SEQ_AXIS,)
                                            if a in self.mesh.shape]))
        return {"remat_budget": remat.Budget(ways * room, self.remat_totals,
                                             grads_bytes=ways * self._grads_bytes)}

    def _loss_and_stats(self, params, batch):
        """(loss, stats): the model's loss and, as a tuple of one, its
        device-side step statistics where the fused step returns them
        (``_step_has_stats``); else the empty tuple."""
        traced = self.remat_totals["policy"] is not None
        self._count_launches(batch)
        if self._step_has_stats:
            loss, stats = self.model.loss_and_stats(params, batch,
                                                    **self._remat_kw())
            out = loss, (stats,)
            if "moe_expert_rows" in stats:   # the no-drop path's layers, now known
                self.moe_totals.update(self._model_records(
                    "expert_records", batch,
                    expert_layers=stats["moe_expert_rows"].shape[0],
                    dtype=self.param_dtype, devices=self.mesh.size,
                    kept=self.remat_totals["saved"]))
        else:
            out = self.model.loss(params, batch, **self._remat_kw()), ()
        kept = self.remat_totals
        if kept["policy"] is not None and not traced:
            log_dist(f"remat keeps {', '.join(kept['saved']) or 'nothing'}: "
                     f"{kept['saved_bytes'] / 1e6:.1f} of "
                     f"{kept['candidate_bytes'] / 1e6:.1f} MB"
                     + ("" if kept["budget_bytes"] is None else
                        f" (costing {kept['saved_cost_bytes'] / 1e6:.1f} of a"
                        f" budget of {kept['budget_bytes'] / 1e6:.1f} MB"
                        f" of a room of {kept['room_bytes'] / 1e6:.1f})")
                     + f"; working set {kept['working_bytes'] / 1e6:.1f} MB"
                       f" of a block's {kept['block_bytes'] / 1e6:.1f}"
                       f" and outside them {kept['outside_bytes'] / 1e6:.1f}"
                       f" less gradients {kept['grads_bytes'] / 1e6:.1f}"
                       f" with {kept['handed_bytes'] / 1e6:.1f} handed on;"
                       f" the blocks' inputs {kept['carries_bytes'] / 1e6:.1f}; "
                     + "; ".join(
                         f"{kind['layers']} x {label}: {', '.join(kind['saved']) or 'nothing'}"
                         f" ({kind['saved_bytes'] / 1e6:.1f} MB)"
                         for label, kind in kept["saved_by_kind"].items()),
                     ranks=[0])
        return out

    def _micro_step_fn(self, state, batch, with_stats: bool = False):
        """Scaled loss + grads, accumulated. Returns (state, loss), and
        the model's step statistics third when asked and there are any."""
        scale = state["loss_scale"]["cur_scale"]
        gas = self.gradient_accumulation_steps

        def scaled_loss(params):
            loss, stats = self._loss_and_stats(params, batch)
            return loss * (scale / gas), (loss, stats)

        grads_fn = jax.grad(scaled_loss, has_aux=True)
        grads, (loss, stats) = grads_fn(state["params"])
        if jax.tree.leaves(state["grad_acc"]):
            new_acc = jax.tree.map(lambda a, g: a + g.astype(self.grad_dtype),
                                   state["grad_acc"], grads)
        else:
            # bufferless gas==1 (offload engines): the fresh gradients ARE
            # the accumulator — no add against a persistent zeros tree
            new_acc = jax.tree.map(lambda g: g.astype(self.grad_dtype), grads)
        state = dict(state)
        state["grad_acc"] = new_acc
        return (state, loss) + (stats if with_stats else ())

    def _opt_kernel_choice(self) -> Optional[str]:
        """The engine's mesh-aware refinement of the ``DSTPU_OPT_KERNEL``
        auto default: forced values ('xla'/'pallas') pass through
        untouched; on auto, a MULTI-device mesh pins the XLA tree even on
        TPU — the fused path's flat-bucket layout would make GSPMD
        reshard (fully rematerialize) the ZeRO-sharded optimizer state
        every step, the exact copy the kernel exists to avoid. The
        single-chip meshes both benchmark cells run on take the
        kernel; the multi-chip enablement needs a shard_map'd local
        flat-partition layout (docs/KERNELS.md). Returning ``None``
        lets ``Optimizer.update`` resolve the env (TPU -> pallas,
        CPU -> xla)."""
        mode = os.environ.get("DSTPU_OPT_KERNEL", "").strip().lower()
        if mode in ("xla", "pallas"):
            return mode
        if self.mesh.size > 1:
            return "xla"
        return None

    def _optimizer_update(self, grads, opt_state, lr, grad_scale):
        """``Optimizer.update`` as every step path calls it: the
        compute-param cast happens INSIDE update — in-kernel on the fused
        Pallas path (DSTPU_OPT_KERNEL, one write instead of a separate
        recast program), the identical astype composition on the XLA path
        (bitwise pre-PR). Runs while a step is traced, so the plan's
        counters (``opt_kernel_totals``) are host values from shapes."""
        kernel = self._opt_kernel_choice()
        self.opt_kernel_totals = self.optimizer.kernel_totals(grads, kernel)
        return self.optimizer.update(grads, opt_state, lr,
                                     grad_scale=grad_scale,
                                     param_dtype=self.param_dtype,
                                     kernel=kernel)

    def _apply_step_fn(self, state, lr):
        """Optimizer boundary: unscale, clip, update, recast, scale bookkeeping."""
        return self._apply_from_grads(state, state["grad_acc"], lr)

    def _apply_step_fn_guardian(self, state, lr, spike_thresh):
        """The guardian-armed apply boundary (split + pipelined ZeRO micro
        paths): same program plus the packed anomaly word as a 4th
        output. The loss bit folds in host-side (the split apply never
        sees the loss in-graph)."""
        return self._apply_from_grads(state, state["grad_acc"], lr,
                                      spike_thresh=spike_thresh)

    @scoped("optimizer")
    def _apply_from_grads(self, state, grads, lr, spike_thresh=None,
                          loss=None, stats=()):
        """The apply boundary with the gradient source explicit: the split
        path passes the persistent ``grad_acc`` buffer; the fused gas==1
        path passes the backward's output directly — those gradients are
        program-internal temporaries, so no persistent buffer exists.

        ``spike_thresh`` arms the guardian sentinels: the anomaly word
        packs from scalars this body already computes (overflow flag,
        raw/unscaled grad norms) plus the host-fed threshold — zero new
        reductions/collectives — and returns as an extra output; the
        in-graph skip generalizes from the fp16 overflow to any anomaly
        bit (``skip_on_anomaly``). ``spike_thresh=None`` (guardian off)
        traces the exact pre-guardian program — the
        ``guardian-step-parity`` lint entry machine-checks that.

        ``stats``: the step's statistics as ``_loss_and_stats`` returns
        them (the fused step has them, the split path's apply does not). A
        model with a router bias (``has_router_bias``) has that one leaf
        taken out of the optimizer's hands here: its master and its weight
        come from the old master moved by the step's load alone, or left
        where they were without ``stats``."""
        scale = state["loss_scale"]["cur_scale"]
        overflow = has_overflow(grads) if self.config.fp16.enabled else jnp.asarray(False)

        # unscale + clip as ONE scalar folded into the optimizer's per-leaf
        # fp32 cast (optimizers.py update grad_scale) — pre-multiplying the
        # tree here would have XLA materialize a full fp32 gradient copy
        # (4.4 GiB at 1.1B params) between backward and update. gnorm of the
        # scaled grads is inv * the raw norm, so the reduction runs on the
        # stored (bf16/fp32) grads without a cast copy.
        inv = jnp.where(overflow, 0.0, 1.0 / scale)
        raw_norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                for g in jax.tree.leaves(grads)))
        # on overflow raw_norm is inf and inv is 0 — select 0.0 instead of
        # computing inf * 0 = NaN (the pre-fold code zeroed grads first)
        gnorm = jnp.where(overflow, 0.0, raw_norm * inv)
        factor = inv
        if self.gradient_clipping > 0:
            clip = jnp.minimum(1.0, self.gradient_clipping / (gnorm + 1e-6))
            factor = inv * clip

        def do_update(_):
            params, opt = self._optimizer_update(grads, state["opt"], lr, factor)
            if getattr(self.model, "has_router_bias", False):
                load = stats[0].get("moe_router_load") if stats else None
                old = state["opt"]["master"]
                hold = lambda new: self.model.hold_router_bias(old, new, load)
                params, opt = hold(params), {**opt, "master": hold(opt["master"])}
            return params, opt

        def skip_update(_):
            return state["params"], state["opt"]

        new_params, new_opt = jax.lax.cond(overflow, skip_update, do_update, None)

        if spike_thresh is not None:
            word = pack_anomaly_word(overflow=overflow, raw_norm=raw_norm,
                                     gnorm=gnorm, spike_thresh=spike_thresh,
                                     loss=loss)
            if self._guardian.config.skip_on_anomaly:
                # the anomaly skip beyond overflow is an ELEMENTWISE
                # select against the pre-update state — NOT a widened
                # cond predicate: the overflow cond keeps its exact
                # pre-guardian provenance, so GSPMD partitions the
                # program identically (the committed guardian map must
                # stay zero-delta vs engine-train-step; a predicate
                # change measurably re-decomposed the grad reductions)
                extra_skip = (word != 0) & jnp.logical_not(overflow)
                keep = lambda new, old: jnp.where(extra_skip, old, new)
                new_params = jax.tree.map(keep, new_params, state["params"])
                new_opt = jax.tree.map(keep, new_opt, state["opt"])

        fp16c = self.config.fp16
        new_scale_state = update_scale(
            state["loss_scale"], overflow,
            scale_window=fp16c.loss_scale_window,
            min_scale=fp16c.min_loss_scale,
            hysteresis=fp16c.hysteresis,
            consecutive_hysteresis=fp16c.consecutive_hysteresis)

        new_state = {
            "params": new_params,
            "grad_acc": jax.tree.map(jnp.zeros_like, state["grad_acc"]),
            "opt": new_opt,
            "loss_scale": new_scale_state,
        }
        if spike_thresh is not None:
            return new_state, overflow, gnorm, word
        return new_state, overflow, gnorm

    def _train_step_fn(self, state, batch, lr, spike_thresh=None):
        """Fused micro + apply: ONE XLA program per optimizer step when
        gradient_accumulation_steps == 1. The gradients flow straight from
        the backward into the optimizer update without a grad_acc
        materialization between two dispatches — saving one host->device
        dispatch and a full fp32-gradient HBM round trip per step
        (measured 7-12 ms/step on the attached v5e for bert-large).

        When the engine was built gas==1-fused-eligible, ``grad_acc`` is an
        EMPTY tree: the backward's gradients feed the update as program
        temporaries and no persistent gradient buffer occupies HBM at all —
        2.2 GiB back at 1.1B params, the margin that lifts the full-depth
        TinyLlama bench from micro 8 to 12 on one chip. (The split
        forward/backward path lazily allocates the buffer on first use.)

        ``spike_thresh`` arms the guardian sentinels (the
        ``_apply_from_grads`` convention): the loss is in-graph here, so
        its non-finite bit packs in the same program, and the anomaly
        word returns as a 5th output. ONE body serves both modes —
        guardian-off and the armed program cannot drift apart."""
        guardian = spike_thresh is not None
        # the model's step statistics, where it has any, are the LAST output
        if jax.tree.leaves(state["grad_acc"]):
            # a live buffer exists (split path was used on this engine):
            # keep accumulate-then-zero semantics
            state, loss, *stats = self._micro_step_fn(state, batch,
                                                      with_stats=True)
            res = self._apply_from_grads(
                state, state["grad_acc"], lr, spike_thresh=spike_thresh,
                loss=loss if guardian else None, stats=tuple(stats))
            return (res[0], loss) + res[1:] + tuple(stats)
        scale = state["loss_scale"]["cur_scale"]

        def scaled_loss(params):
            loss, stats = self._loss_and_stats(params, batch)
            return loss * scale, (loss, stats)  # gas == 1: no /gas

        grads, (loss, stats) = jax.grad(scaled_loss, has_aux=True)(
            state["params"])
        grads = jax.tree.map(lambda g: g.astype(self.grad_dtype), grads)
        res = self._apply_from_grads(state, grads, lr,
                                     spike_thresh=spike_thresh,
                                     loss=loss if guardian else None,
                                     stats=stats)
        return (res[0], loss) + res[1:] + stats

    def _train_step_fn_guardian(self, state, batch, lr, spike_thresh):
        """The guardian-armed fused step: ``_train_step_fn`` with the
        threshold REQUIRED — a distinct callable so the jit cache, the
        lint entry and stack traces name the armed program explicitly."""
        return self._train_step_fn(state, batch, lr, spike_thresh)

    # ------------------------------------------------------------------
    # 1-bit step functions: explicit shard_map over the data axis so each
    # device's gradients stay local for compression (reference
    # runtime/fp16/onebit + runtime/comm/nccl.py backends)
    # ------------------------------------------------------------------
    def _build_onebit_jits(self, shardings, rep):
        from ..utils.jax_compat import shard_map
        from .topology import DATA_AXIS as AX
        mesh = self.mesh
        gas = self.gradient_accumulation_steps
        model = self.model
        onebit = self._onebit_opt
        fp16_enabled = self.config.fp16.enabled
        fp16c = self.config.fp16

        p_rep = jax.tree.map(lambda _: P(), self.state["params"])
        gacc_sp = jax.tree.map(lambda _: P(AX), self.state["grad_acc"])
        opt_sp = {k: jax.tree.map(lambda _: P(AX) if k in ("worker_error",
                                                           "server_error") else P(), v)
                  for k, v in self.state["opt"].items()}

        def local_micro(params, gacc, scale, batch):
            def scaled_loss(p):
                loss = model.loss(p, batch, **self._remat_kw(local=True))
                return loss * (scale / gas), loss

            grads, loss = jax.grad(scaled_loss, has_aux=True)(params)
            gacc = jax.tree.map(
                lambda a, g: a + g.astype(self.grad_dtype)[None], gacc, grads)
            return gacc, jax.lax.pmean(loss, AX)

        def micro_step(state, batch):
            batch_sp = {k: (P() if k in self._REPLICATED_BATCH_KEYS else P(AX))
                        for k in batch}
            sm = shard_map(local_micro, mesh=mesh,
                           in_specs=(p_rep, gacc_sp, P(), batch_sp),
                           out_specs=(gacc_sp, P()), check_vma=False)
            gacc, loss = sm(state["params"], state["grad_acc"],
                            state["loss_scale"]["cur_scale"], batch)
            state = dict(state)
            state["grad_acc"] = gacc
            return state, loss

        def local_apply(params, gacc, opt, scale, lr):
            g_local = jax.tree.map(lambda g: g[0].astype(jnp.float32), gacc)
            if fp16_enabled:
                finite = jnp.all(jnp.stack([jnp.all(jnp.isfinite(g))
                                            for g in jax.tree.leaves(g_local)]))
                overflow = jax.lax.pmax((~finite).astype(jnp.int32), AX) > 0
            else:
                overflow = jnp.asarray(False)
            inv = jnp.where(overflow, 0.0, 1.0 / scale)
            g_local = jax.tree.map(lambda g: g * inv, g_local)
            # reporting only: pmean of local sq-norms (global norm needs sync)
            gnorm = jnp.sqrt(jax.lax.pmean(
                sum(jnp.sum(g * g) for g in jax.tree.leaves(g_local)), AX))

            opt_local = dict(opt)
            for key in ("worker_error", "server_error"):
                if key in opt_local:
                    opt_local[key] = jax.tree.map(lambda e: e[0], opt_local[key])
            master = opt_local["master"]

            def do(_):
                return onebit.update(g_local, opt_local, lr)

            def skip(_):
                return master, opt_local

            new_master, new_opt = jax.lax.cond(overflow, skip, do, None)
            new_params = jax.tree.map(lambda m_: m_.astype(self.param_dtype),
                                      new_master)
            for key in ("worker_error", "server_error"):
                if key in new_opt:
                    new_opt[key] = jax.tree.map(lambda e: e[None], new_opt[key])
            new_gacc = jax.tree.map(jnp.zeros_like, gacc)
            return new_params, new_gacc, new_opt, overflow, gnorm

        def apply_step(state, lr):
            sm = shard_map(local_apply, mesh=mesh,
                           in_specs=(p_rep, gacc_sp, opt_sp, P(), P()),
                           out_specs=(p_rep, gacc_sp, opt_sp, P(), P()),
                           check_vma=False)
            new_params, new_gacc, new_opt, overflow, gnorm = sm(
                state["params"], state["grad_acc"], state["opt"],
                state["loss_scale"]["cur_scale"], lr)
            new_scale = update_scale(state["loss_scale"], overflow,
                                     scale_window=fp16c.loss_scale_window,
                                     min_scale=fp16c.min_loss_scale,
                                     hysteresis=fp16c.hysteresis,
                                     consecutive_hysteresis=fp16c.consecutive_hysteresis)
            return ({"params": new_params, "grad_acc": new_gacc,
                     "opt": new_opt, "loss_scale": new_scale}, overflow, gnorm)

        return micro_step, apply_step

    # ------------------------------------------------------------------
    # ZeRO++ explicit micro step: qwZ int8 param all-gather, qgZ int8
    # gradient reduce-scatter, hpZ secondary shard on the 'mics' axis
    # (reference partition_parameters.py:1101/1551, coalesced_collectives.py:31)
    # ------------------------------------------------------------------
    @staticmethod
    def _dp_axes_in(spec):
        """(dim, dp_axes) of the ZeRO-sharded dim of ``spec`` (or (None, ()))."""
        from .zero.partition import dp_axes_in
        return dp_axes_in(spec)

    def _zeropp_micro_env(self):
        """The shared geometry of both explicit micro schedules."""
        from .topology import MICS_AXIS
        zc = self.config.zero_config
        hpz = zc.zero_hpz_partition_size > 1
        all_dp = tuple(a for a in (DATA_AXIS, MICS_AXIS)
                       if self.topology.axis_size(a) > 1) or (DATA_AXIS,)
        n_dp = self.topology.axis_size(all_dp)
        param_specs = self.zero_plan.param_spec_tree()
        grad_specs = self.zero_plan.grad_spec_tree()
        # hpZ: the micro step reads from the SECONDARY partition — sharded
        # over 'mics' only (intra-group gathers), refreshed from the primary
        # once per optimizer step.
        if hpz:
            gather_src_specs = jax.tree.map(
                lambda s: self._hpz_secondary_spec(s), param_specs,
                is_leaf=lambda s: isinstance(s, P))
        else:
            gather_src_specs = param_specs
        return zc, all_dp, n_dp, param_specs, grad_specs, gather_src_specs

    def _zero_overlap_eligibility(self, grad_specs) -> str:
        """'' when the layer-granular schedule can run, else the reason
        for falling back to the barrier schedule."""
        if os.environ.get("DSTPU_ZERO_OVERLAP", "1") == "0":
            return "DSTPU_ZERO_OVERLAP=0"
        for attr in ("embed", "block_apply", "head", "scan_blocks_pipelined",
                     "derive_labels", "head_loss", "combine_aux"):
            if not hasattr(self.model, attr):
                return (f"model {type(self.model).__name__} lacks .{attr} "
                        "(TransformerLM family required)")
        if not (isinstance(self._param_struct, dict)
                and "blocks" in self._param_struct):
            return "param tree has no stacked 'blocks' subtree"
        # a block leaf dp-sharded over its LAYER dim has no per-layer shard
        # to gather — the pipelined schedule cannot exist for it
        for specs in (grad_specs["blocks"],
                      self.zero_plan.param_spec_tree()["blocks"]):
            for spec in jax.tree.leaves(specs,
                                        is_leaf=lambda s: isinstance(s, P)):
                dim, axes = self._dp_axes_in(spec)
                axes = tuple(a for a in axes
                             if self.topology.axis_size(a) > 1)
                if axes and dim == 0:
                    return (f"block leaf sharded over the layer dim ({spec})")
        return ""

    def _build_zeropp_micro(self):
        """The explicit shard_map micro step. Dispatches between the
        layer-granular pipelined schedule (overlap_comm true, default for
        ZeRO++) and the whole-tree barrier schedule — ``overlap_comm:
        false`` is an exact escape hatch back to the latter."""
        zc = self.config.zero_config
        self._overlap_active = False
        if zc.overlap_comm:
            reason = self._zero_overlap_eligibility(
                self.zero_plan.grad_spec_tree())
            if not reason:
                self._overlap_active = True
                self._overlap_fallback = ""
                return self._build_zeropp_micro_overlap()
            self._overlap_fallback = reason
            log_dist(f"zero overlap_comm: falling back to the barrier "
                     f"schedule ({reason})", ranks=[0])
        return self._build_zeropp_micro_barrier()

    def _build_zeropp_micro_barrier(self):
        from ..utils.jax_compat import shard_map
        from .. import comm as dist
        from ..comm.comm import (ALGO_HIERARCHICAL, KIND_GRAD, KIND_PARAM,
                                 WIDTH_FP8, WIDTH_INT8, _hier_psum_scatter,
                                 resolve_transport)
        from ..ops.quantizer.quantizer import (fp8_all_gather,
                                               fp8_reduce_scatter,
                                               quantized_all_gather,
                                               quantized_reduce_scatter)

        mesh = self.mesh
        gas = self.gradient_accumulation_steps
        model = self.model
        grad_dtype = self.grad_dtype
        (zc, all_dp, n_dp, param_specs, grad_specs,
         gather_src_specs) = self._zeropp_micro_env()
        axis_sizes = dict(self.topology.mesh.shape)

        def gather_full(x, spec):
            dim, axes = self._dp_axes_in(spec)
            if dim is None:
                return x
            axes = tuple(a for a in axes if self.topology.axis_size(a) > 1)
            if not axes:
                return x
            tp = resolve_transport(
                KIND_PARAM, "all_gather", x.size * x.dtype.itemsize, axes,
                axis_sizes=axis_sizes,
                requested=WIDTH_INT8 if zc.zero_quantized_weights else None)
            if tp.algo == ALGO_HIERARCHICAL:
                # the barrier gather executes flat — record it flat
                import dataclasses as _dc
                tp = _dc.replace(tp, algo="flat", inner=(), outer=())
            xm = jnp.moveaxis(x, dim, 0)
            # whole-tree gather before the loss: fully EXPOSED collective
            # time (what the overlap schedule exists to hide)
            dist.record_collective("all_gather", x.size * x.dtype.itemsize,
                                   axes, overlapped=False,
                                   wire_bytes=tp.wire_bytes(
                                       x.size, x.dtype.itemsize))
            if tp.width == WIDTH_INT8:
                g = quantized_all_gather(xm, axis=axes)
            elif tp.width == WIDTH_FP8:
                g = fp8_all_gather(xm, axes)
            else:
                g = jax.lax.all_gather(xm, axes, axis=0, tiled=True)
            return jnp.moveaxis(g, 0, dim)

        def scatter_grad(g, spec):
            dim, axes = self._dp_axes_in(spec)
            axes = tuple(a for a in axes if self.topology.axis_size(a) > 1)
            if dim is None or not axes:
                dist.record_collective("all_reduce",
                                       g.size * g.dtype.itemsize, all_dp,
                                       overlapped=False)
                return jax.lax.psum(g, all_dp) / n_dp
            # per-leaf transport plan (docs/COLLECTIVES.md): grads default
            # to the int8 wire; qgZ stays an explicit width request;
            # multi-axis dp decomposes hierarchically
            tp = resolve_transport(
                KIND_GRAD, "reduce_scatter", g.size * 4, axes,
                axis_sizes=axis_sizes,
                requested=(WIDTH_INT8 if zc.zero_quantized_gradients
                           else None))
            gm = jnp.moveaxis(g.astype(jnp.float32), dim, 0)
            dist.record_collective(
                "all_to_all" if tp.quantized else "reduce_scatter",
                g.size * 4, axes, overlapped=False,
                wire_bytes=tp.wire_bytes(g.size, 4))
            if tp.algo == ALGO_HIERARCHICAL:
                q_inner = None
                if tp.width == WIDTH_INT8:
                    q_inner = lambda x, ax: quantized_reduce_scatter(
                        x, axis=ax, group_size=tp.group_size)
                elif tp.width == WIDTH_FP8:
                    q_inner = lambda x, ax: fp8_reduce_scatter(
                        x, ax, group_size=tp.group_size)
                r = _hier_psum_scatter(gm, axes, tp.inner, tp.outer,
                                       quantized_inner=q_inner)
            elif tp.width == WIDTH_INT8:
                r = quantized_reduce_scatter(gm, axis=axes,
                                             group_size=tp.group_size)
            elif tp.width == WIDTH_FP8:
                r = fp8_reduce_scatter(gm, axes, group_size=tp.group_size)
            else:
                r = jax.lax.psum_scatter(gm, axes, scatter_dimension=0, tiled=True)
            # Batch is sharded over ALL dp axes but under MiCS the grad spec
            # carries only the sub-group ('mics') axis — the sum over the
            # remaining data groups must still happen (cheap: it runs on the
            # 1/axes-sized shard, the reference's hierarchical reduction).
            rest = tuple(a for a in all_dp if a not in axes)
            if rest:
                r = jax.lax.psum(r, rest)
            return jnp.moveaxis(r, 0, dim) / n_dp

        batch_rep = self._REPLICATED_BATCH_KEYS

        def local_micro(param_shards, gacc_shards, scale, batch):
            full = jax.tree.map(gather_full, param_shards, gather_src_specs,
                                is_leaf=lambda s: isinstance(s, P))

            def scaled_loss(p):
                loss = model.loss(p, batch, **self._remat_kw(local=True))
                return loss * (scale / gas), loss

            grads, loss = jax.grad(scaled_loss, has_aux=True)(full)
            gshard = jax.tree.map(scatter_grad, grads, grad_specs,
                                  is_leaf=lambda s: isinstance(s, P))
            gacc = jax.tree.map(lambda a, g: a + g.astype(grad_dtype),
                                gacc_shards, gshard)
            return gacc, jax.lax.pmean(loss, all_dp)

        gacc_specs = grad_specs

        def micro_step(gacc_in, cur_scale, secondary, batch):
            batch_specs = {k: (P() if k in batch_rep else P(BATCH_AXES))
                           for k in batch}
            sm = shard_map(local_micro, mesh=mesh,
                           in_specs=(gather_src_specs, gacc_specs, P(), batch_specs),
                           out_specs=(gacc_specs, P()), check_vma=False)
            return sm(secondary, gacc_in, cur_scale, batch)

        return micro_step

    def _build_zeropp_micro_overlap(self):
        """The layer-granular pipelined micro step (ISSUE 3 tentpole;
        ISSUE 9 made it the overlap PLANNER's first client).

        Same shard_map signature and gradient math as the barrier schedule,
        but the block-stack gather/compute/scatter is restructured around
        the model's ``scan_blocks_pipelined``: layer *l+1*'s (optionally
        quantized) all-gather is issued during layer *l*'s forward compute
        from the scan carry (double-buffered, freed after use), the
        backward re-gathers per layer with the same one-ahead prefetch, and
        layer *l*'s gradient reduce-scatter is issued during layer *l−1*'s
        backward compute. Collectives are bucket-planned
        (``reduce_bucket_size``/``allgather_bucket_size``) so small leaves
        fuse into one launch and huge leaves split for pipelining.

        The schedule's parameters now come from the map-driven
        :class:`~..runtime.overlap_planner.OverlapPlan` for
        ``zeropp-micro-overlap`` (runtime/overlap_planner.py,
        docs/OVERLAP_PLANNER.md) instead of being hand-pinned:

        - **edge split** (``split_edge_leaves``): head-side rest leaves
          (final norm, an untied LM head — often the step's largest
          reduce, i.e. the optimizer-step reduce) gather BEFORE the
          forward scan and scatter BEFORE the backward scan, so the
          scans' FLOPs hide them; only the embed-side leaves keep truly
          exposed edge launches.
        - **deferred replicated flush** (``defer_replicated``):
          replicated-w.r.t.-dp block leaves stop paying one psum per
          scan iteration — their grads leave the scan locally and fuse
          into ONE flat boundary all-reduce (exact).
        - **error-feedback carry** (``carry_error_feedback`` + the
          ``comm_transport.error_feedback`` policy): the PR 8 residual
          state rides the backward scan's xs/ys and the micro-step
          carry, closing the ROADMAP item 1(a) deferral.

        ``DSTPU_OVERLAP_PLAN=0`` / ``overlap_plan: false`` pins the
        identity plan — the hand-written PR 3 schedule, bitwise.
        """
        from ..models.transformer import head_slices
        from ..utils.jax_compat import shard_map
        from .. import comm as dist
        from . import overlap_planner as op_mod
        from .zero.overlap import build_tree_comm

        mesh = self.mesh
        gas = self.gradient_accumulation_steps
        model = self.model
        grad_dtype = self.grad_dtype
        (zc, all_dp, n_dp, param_specs, grad_specs,
         gather_src_specs) = self._zeropp_micro_env()
        axis_sizes = dict(self.topology.mesh.shape)
        is_p = lambda s: isinstance(s, P)

        plan = op_mod.plan_for("zeropp-micro-overlap",
                               config_flag=self.config.overlap_plan)
        planned = plan.placement == op_mod.PLACEMENT_SCAN_CARRY
        self._overlap_plan = plan
        ag_bucket = plan.allgather_bucket or zc.allgather_bucket_size
        rs_bucket = plan.reduce_bucket or zc.reduce_bucket_size

        # one layer a step of the pipelined scan
        n_steps = L = int(model.config.num_layers)

        def split(tree):
            rest = {k: v for k, v in tree.items() if k != "blocks"}
            return rest, tree["blocks"]

        def bundle_tree(tree, drop_layer_dim):
            """Stacked [L, ...] leaves -> a step's view [1, ...] (the comm
            tree's buckets and error-feedback state are laid out for a
            leading dimension of the layers a step): specs drop the layer
            dim and gain a leading None; structs lose the layer dim for the
            per-layer shape."""
            if drop_layer_dim == "spec":
                return jax.tree.map(lambda s: P(*((None,) + tuple(s)[1:])),
                                    tree, is_leaf=is_p)
            return jax.tree.map(
                lambda l: jax.ShapeDtypeStruct((1,) + tuple(l.shape)[1:],
                                               l.dtype), tree)

        rest_src_specs, blk_src_specs = split(gather_src_specs)
        rest_grad_specs, blk_grad_specs = split(grad_specs)
        rest_struct, blk_struct = split(self._param_struct)

        blk_comm = build_tree_comm(
            bundle_tree(blk_src_specs, "spec"),
            bundle_tree(blk_grad_specs, "spec"),
            bundle_tree(blk_struct, "struct"),
            axis_sizes=axis_sizes, all_dp=all_dp, n_dp=n_dp,
            quant_weights=zc.zero_quantized_weights,
            quant_grads=zc.zero_quantized_gradients,
            allgather_bucket=ag_bucket, reduce_bucket=rs_bucket,
            overlapped=True, name="blocks",
            defer_replicated=planned and plan.defer_replicated)

        # the MODEL declares which rest leaves its embed() reads
        # (TransformerLM.embed_param_keys — defined next to embed so the
        # two cannot silently drift); a model family without the
        # declaration gets no edge split rather than a wrong one
        embed_keys = getattr(model, "embed_param_keys", None)
        head_keys = (tuple(k for k in rest_struct if k not in embed_keys)
                     if embed_keys is not None else ())
        use_split = (planned and plan.split_edge_leaves and bool(head_keys))
        pick = lambda tree, keys: {k: tree[k] for k in tree if k in keys}
        drop = lambda tree, keys: {k: tree[k] for k in tree
                                   if k not in keys}

        def rest_tree_comm(subtree_of, overlapped, name):
            return build_tree_comm(
                subtree_of(rest_src_specs), subtree_of(rest_grad_specs),
                subtree_of(rest_struct),
                axis_sizes=axis_sizes, all_dp=all_dp, n_dp=n_dp,
                quant_weights=zc.zero_quantized_weights,
                quant_grads=zc.zero_quantized_gradients,
                allgather_bucket=ag_bucket, reduce_bucket=rs_bucket,
                overlapped=overlapped, name=name)

        if use_split:
            # head-side leaves HOIST across the scans (straight-line
            # placement): gathered before the forward scan / scattered
            # before the backward scan, their launches sit beside
            # independent scan compute — recorded (and, in the compiled
            # schedule, classified) overlapped
            embed_comm = rest_tree_comm(
                lambda t: drop(t, head_keys), False, "rest-embed")
            head_comm = rest_tree_comm(
                lambda t: pick(t, head_keys), True, "rest-head")
            rest_comms = (embed_comm, head_comm)
        else:
            rest_comm = rest_tree_comm(lambda t: t, False, "rest")
            rest_comms = (rest_comm,)

        oversize = blk_comm.oversize + sum(
            (cm.oversize for cm in rest_comms), [])
        if oversize and not getattr(self, "_bucket_warned", False):
            # warn ONCE instead of silently ignoring the knob (satellite):
            # these leaves exceed the bucket even after the best split
            self._bucket_warned = True
            logger.warning(
                f"zero bucket plan: {len(oversize)} leaves exceed "
                f"allgather/reduce bucket sizes even after splitting "
                f"(first: {oversize[0]}) — raise the bucket knobs or "
                f"accept single oversized launches")
        log_dist(
            f"zero overlap schedule ({'plan: ' + plan.summary() if planned else 'hand'}): "
            f"{L} layers x 1/step; {blk_comm.plan_summary()}; "
            + "; ".join(cm.plan_summary() for cm in rest_comms), ranks=[0])

        # --- error-feedback residual carry (the planner owns the scan
        # carries, so the PR 8 state can finally ride them) -------------
        ef_on = (planned and plan.carry_error_feedback
                 and bool(dist.transport_config()["error_feedback"]))
        ef_local_struct = None
        if ef_on:
            stack_step = lambda s: (None if s is None else
                                    jax.ShapeDtypeStruct(
                                        (n_steps,) + tuple(s.shape), s.dtype))
            ef_local_struct = {"blocks": [stack_step(s)
                                          for s in blk_comm.err_struct()]}
            if use_split:
                ef_local_struct["rest_embed"] = embed_comm.err_struct()
                ef_local_struct["rest_head"] = head_comm.err_struct()
            else:
                ef_local_struct["rest"] = rest_comm.err_struct()
            if not jax.tree.leaves(ef_local_struct):
                ef_on = False   # nothing EF-eligible (kill switch / fp8 /
                ef_local_struct = None  # hierarchical-only buckets)
        self._ef_carry_active = ef_on
        # device-local state across shard_map calls: a leading dp axis
        # (the 1-bit optimizers' worker_error precedent) makes each
        # device's residual its own shard of one global array
        self._ef_struct = None
        self._ef_spec = None
        if ef_on:
            self._ef_struct = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n_dp,) + tuple(s.shape),
                                               s.dtype), ef_local_struct)
            self._ef_spec = jax.tree.map(lambda s: P(all_dp),
                                         ef_local_struct)
            log_dist("zero overlap schedule: error-feedback residuals ride "
                     "the micro-step carry "
                     f"({len(jax.tree.leaves(self._ef_struct))} slots)",
                     ranks=[0])

        batch_rep = self._REPLICATED_BATCH_KEYS

        def local_micro(param_shards, gacc_shards, ef, scale, batch):
            rest_shards, blocks = split(param_shards)
            input_ids = batch["input_ids"]
            # loss ingredients SHARED with model.loss (derive_labels /
            # head_loss / combine_aux) so both schedules train the same
            # objective by construction
            labels = model.derive_labels(batch)
            # (the head in slices of the rows where one device's room does
            # not hold its float32 logits, as the fused step has it)
            head_rows = head_slices(
                model.config, self._remat_kw(local=True).get("remat_budget"), input_ids)
            ef_local = (jax.tree.map(lambda a: a[0], ef)
                        if ef is not None else None)
            if use_split:
                # head-side leaves launch EARLY — consumed only after the
                # forward scan, whose compute hides them
                head_full = head_comm.gather(pick(rest_shards, head_keys))
                embed_full = embed_comm.gather(drop(rest_shards, head_keys))
                rest_full = {**embed_full, **head_full}
            else:
                # edge-of-step leaves: gathered once, exposed (no compute
                # yet)
                rest_full = rest_comm.gather(rest_shards)
            positions = jnp.arange(input_ids.shape[1])[None, :]

            if use_split:
                def embed_f(ef_tree):
                    x, _ = model.embed({**ef_tree, **head_full}, input_ids,
                                       batch.get("token_type_ids"))
                    return x
                x0, embed_vjp = jax.vjp(embed_f, embed_full)
            else:
                def embed_f(rf):
                    x, _ = model.embed(rf, input_ids,
                                       batch.get("token_type_ids"))
                    return x
                x0, embed_vjp = jax.vjp(embed_f, rest_full)

            layer_mask = batch.get("layer_mask")
            x_out, aux_sum, pullback = model.scan_blocks_pipelined(
                blocks, x0, positions,
                gather=blk_comm.gather, scatter=blk_comm.scatter,
                keep=layer_mask, attn_mask=batch.get("attention_mask"),
                # the plan deepens to 2 when the committed map still
                # shows exposed in-scan bytes at depth 1 (ISSUE 11);
                # plan-off keeps the hand schedule's depth 1 bitwise
                prefetch_depth=(plan.prefetch_depth if planned else 1),
                comm_scope=blk_comm.trace_executions,
                comm_edge=blk_comm.schedule_class,
                scatter_err=(ef_local["blocks"] if ef_local is not None
                             else None))

            s_ = (scale / gas).astype(jnp.float32)
            # d(loss)/d(aux) derived FROM combine_aux so a changed aux
            # weighting can never drift between the two schedules
            daux = s_ * jax.grad(
                lambda a: model.combine_aux(jnp.zeros(()), a))(
                    jnp.zeros(()))
            new_ef = {}
            if use_split:
                def head_f(ef_tree, hf, xx):
                    return model.head_loss({**ef_tree, **hf}, xx, labels,
                                           extra_mask=batch.get("loss_mask"),
                                           slices=head_rows)
                ce, head_vjp = jax.vjp(head_f, embed_full, head_full,
                                       x_out)
                loss = model.combine_aux(ce, aux_sum)
                drf_e_h, drf_head, dx_out = head_vjp(s_)
                # head-side grads scatter NOW, before the backward scan —
                # its compute hides the launch (an untied LM head makes
                # this the optimizer-step's dominant reduce)
                if ef_local is not None:
                    dhead, new_ef["rest_head"] = head_comm.scatter(
                        drf_head, err=ef_local["rest_head"])
                else:
                    dhead = head_comm.scatter(drf_head)
                pb = pullback(dx_out, daux)
                if ef_local is not None:
                    dblocks, dx0, new_ef["blocks"] = pb
                else:
                    dblocks, dx0 = pb
                (drf_e_e,) = embed_vjp(dx0)
                drest_embed = jax.tree.map(jnp.add, drf_e_h, drf_e_e)
                if ef_local is not None:
                    dembed, new_ef["rest_embed"] = embed_comm.scatter(
                        drest_embed, err=ef_local["rest_embed"])
                else:
                    dembed = embed_comm.scatter(drest_embed)
                grads = {**dembed, **dhead}
            else:
                def head_f(rf, xx):
                    return model.head_loss(rf, xx, labels,
                                           extra_mask=batch.get("loss_mask"),
                                           slices=head_rows)
                ce, head_vjp = jax.vjp(head_f, rest_full, x_out)
                loss = model.combine_aux(ce, aux_sum)
                drf_h, dx_out = head_vjp(s_)
                pb = pullback(dx_out, daux)
                if ef_local is not None:
                    dblocks, dx0, new_ef["blocks"] = pb
                else:
                    dblocks, dx0 = pb
                (drf_e,) = embed_vjp(dx0)
                drest_full = jax.tree.map(jnp.add, drf_h, drf_e)
                if ef_local is not None:
                    drest, new_ef["rest"] = rest_comm.scatter(
                        drest_full, err=ef_local["rest"])
                else:
                    drest = rest_comm.scatter(drest_full)
                grads = dict(drest)
            # deferred replicated-leaf reduction: ONE fused flat boundary
            # launch instead of one psum per scan iteration (exact)
            with blk_comm.schedule_class(False):
                dblocks = blk_comm.flush_deferred(dblocks)
            grads["blocks"] = dblocks
            gacc = jax.tree.map(lambda a, g: a + g.astype(grad_dtype),
                                gacc_shards, grads)
            loss_out = jax.lax.pmean(loss, all_dp)
            if ef_local is not None:
                return gacc, jax.tree.map(lambda a: a[None], new_ef), \
                    loss_out
            return gacc, loss_out

        gacc_specs = grad_specs

        if ef_on:
            ef_specs = self._ef_spec

            def micro_step(carry, cur_scale, secondary, batch):
                gacc_in, ef_in = carry
                batch_specs = {k: (P() if k in batch_rep else P(BATCH_AXES))
                               for k in batch}
                sm = shard_map(local_micro, mesh=mesh,
                               in_specs=(gather_src_specs, gacc_specs,
                                         ef_specs, P(), batch_specs),
                               out_specs=((gacc_specs, ef_specs, P())),
                               check_vma=False)
                gacc, ef_out, loss = sm(secondary, gacc_in, ef_in,
                                        cur_scale, batch)
                return (gacc, ef_out), loss

            return micro_step

        def micro_step(gacc_in, cur_scale, secondary, batch):
            batch_specs = {k: (P() if k in batch_rep else P(BATCH_AXES))
                           for k in batch}
            local = lambda p, g, sc, b: local_micro(p, g, None, sc, b)
            sm = shard_map(local, mesh=mesh,
                           in_specs=(gather_src_specs, gacc_specs, P(),
                                     batch_specs),
                           out_specs=(gacc_specs, P()), check_vma=False)
            return sm(secondary, gacc_in, cur_scale, batch)

        return micro_step

    @staticmethod
    def _hpz_secondary_spec(spec: P) -> P:
        """Replace the ZeRO dp-sharding of a leaf with 'mics'-only sharding
        (the hpZ secondary partition, reference _partition_param_sec,
        partition_parameters.py:1551)."""
        from .topology import MICS_AXIS
        dim, dp = DeepSpeedEngine._dp_axes_in(spec)
        if dim is None:
            return P(*spec)
        entries = list(spec)
        entry = entries[dim]
        ax = entry if isinstance(entry, (tuple, list)) else (entry,)
        keep = tuple(a for a in ax if a not in dp) + (MICS_AXIS,)
        entries[dim] = keep if len(keep) > 1 else keep[0]
        return P(*entries)

    def _refresh_secondary(self):
        """Rebuild the hpZ secondary partition from the primary params —
        the once-per-optimizer-step inter-group all-gather. The reshard jit
        is cached: this runs on the per-step hot path."""
        if not getattr(self, "_explicit_micro", False):
            return
        if self.config.zero_config.zero_hpz_partition_size > 1:
            if getattr(self, "_jit_hpz_reshard", None) is None:
                specs = jax.tree.map(self._hpz_secondary_spec,
                                     self.zero_plan.param_spec_tree(),
                                     is_leaf=lambda s: isinstance(s, P))
                shardings = jax.tree.map(
                    lambda s: NamedSharding(self.mesh, s), specs,
                    is_leaf=lambda s: isinstance(s, P))
                self._jit_hpz_reshard = jax.jit(lambda p: p,
                                                out_shardings=shardings)
            with self.mesh:
                self._secondary = self._jit_hpz_reshard(self.state["params"])
        else:
            self._secondary = self.state["params"]

    def _build_jits(self):
        if self._jit_micro_step is not None and self._jit_apply_step is not None:
            return
        if getattr(self, "_cached_shardings", None) is None:
            self._cached_shardings = self._state_shardings()
        shardings = self._cached_shardings
        rep = NamedSharding(self.mesh, P())
        if self._onebit_opt is not None:
            micro_step, apply_step = self._build_onebit_jits(shardings, rep)
            self._jit_micro_step = jax.jit(
                micro_step, donate_argnums=(0,),
                in_shardings=(shardings, None),
                out_shardings=(shardings, rep))
            self._jit_apply_step = jax.jit(
                apply_step, donate_argnums=(0,),
                in_shardings=(shardings, rep),
                out_shardings=(shardings, rep, rep))
            return
        if self._explicit_micro:
            if getattr(self, "_secondary", None) is None:
                self._refresh_secondary()
            if self._jit_micro_step is None:
                # Only grad_acc flows through the jit (donated) — passing the
                # whole state would copy params + fp32 optimizer state every
                # micro step. The secondary (params at hpz=1) is a plain
                # non-donated input, so the aliasing stays valid.
                micro = self._build_zeropp_micro()
                if getattr(self, "_ef_carry_active", False):
                    # planner EF carry: the residual state rides the donated
                    # micro carry next to grad_acc (device-local via the
                    # leading dp axis; persists across optimizer steps so
                    # the quantization error telescopes)
                    ef_sh = jax.tree.map(
                        lambda s: NamedSharding(self.mesh, s),
                        self._ef_spec, is_leaf=lambda s: isinstance(s, P))
                    if getattr(self, "_ef_state", None) is None:
                        with self.mesh:
                            self._ef_state = jax.jit(
                                lambda: jax.tree.map(
                                    lambda s: jnp.zeros(s.shape, s.dtype),
                                    self._ef_struct),
                                out_shardings=ef_sh)()
                    self._jit_micro_step = jax.jit(
                        micro, donate_argnums=(0,),
                        in_shardings=((shardings["grad_acc"], ef_sh), rep,
                                      None, None),
                        out_shardings=((shardings["grad_acc"], ef_sh), rep))
                else:
                    self._jit_micro_step = jax.jit(
                        micro, donate_argnums=(0,),
                        in_shardings=(shardings["grad_acc"], rep, None, None),
                        out_shardings=(shardings["grad_acc"], rep))
            if self._jit_apply_step is None:
                self._jit_apply_step = self._make_apply_jit(shardings, rep)
            return
        if self._jit_micro_step is None:
            # batch in_shardings None: inherit _device_batch placement (data
            # leaves sharded over BATCH_AXES, aux leaves like layer_mask
            # replicated)
            micro_out = shardings
            if self._gradacc_lazy and self._offload_device != "none":
                # bufferless offload micro: input grad_acc is the empty
                # tree, output carries the fresh gradients
                micro_out = dict(shardings)
                micro_out["grad_acc"] = self._grad_shardings
            self._jit_micro_step = jax.jit(
                self._micro_step_fn,
                donate_argnums=(0,),
                in_shardings=(shardings, None),
                out_shardings=(micro_out, rep),
            )
        if self._jit_apply_step is None:
            self._jit_apply_step = self._make_apply_jit(shardings, rep)

    def _make_apply_jit(self, shardings, rep):
        """The split/pipelined-micro apply-step jit — guardian-armed when
        the policy is live (extra replicated spike-threshold input, the
        anomaly word as a 4th output), the exact pre-guardian program
        otherwise. One builder so both _build_jits branches agree."""
        if self._guardian is not None:
            return jax.jit(
                self._apply_step_fn_guardian, donate_argnums=(0,),
                in_shardings=(shardings, rep, None),
                out_shardings=(shardings, rep, rep, rep))
        return jax.jit(
            self._apply_step_fn,
            donate_argnums=(0,),
            in_shardings=(shardings, rep),
            out_shardings=(shardings, rep, rep),
        )

    def _fused_step_eligible(self) -> bool:
        """The fused one-program step covers the common jitted path; the
        shard_map (1-bit, ZeRO++) and host-optimizer (offload) paths keep
        their own dispatch structure. DSTPU_FUSED_STEP=0 opts out."""
        return (self.gradient_accumulation_steps == 1
                and self._offload is None
                and not self._explicit_micro
                and self._onebit_opt is None
                and os.environ.get("DSTPU_FUSED_STEP", "1") != "0")

    def _build_fused_jit(self):
        if self._jit_train_step is not None:
            return
        if getattr(self, "_cached_shardings", None) is None:
            self._cached_shardings = self._state_shardings()
        shardings = self._cached_shardings
        rep = NamedSharding(self.mesh, P())
        stats = (rep,) if self._step_has_stats else ()
        if self._guardian is not None:
            # guardian-armed program: +1 replicated host-scalar input
            # (spike threshold) and the anomaly word as a 5th output
            self._jit_train_step = jax.jit(
                self._train_step_fn_guardian,
                donate_argnums=(0,),
                in_shardings=(shardings, None, None, None),
                out_shardings=(shardings, rep, rep, rep, rep) + stats,
            )
            return
        self._jit_train_step = jax.jit(
            self._train_step_fn,
            donate_argnums=(0,),
            in_shardings=(shardings, None, None),
            out_shardings=(shardings, rep, rep, rep) + stats,
        )

    def _prepare_batch(self, batch):
        """Host-side batch pipeline shared by forward() and the fused step:
        validation, curriculum truncation, PLD layer mask, device placement,
        and the MoQ eigenvalue batch capture."""
        with self.telemetry.phase("prepare_batch", phase="data",
                                  step=self.global_steps):
            self._validate_batch(batch)
            if self.curriculum_scheduler is not None:
                batch = self._apply_curriculum(batch)
            if self.progressive_layer_drop is not None and "layer_mask" not in batch:
                self.progressive_layer_drop.update_state(self.global_steps)
                batch = dict(batch)
                batch["layer_mask"] = self.progressive_layer_drop.layer_mask(
                    self._pld_rng, self.model.config.num_layers)
            batch = self._device_batch(batch)
        if self.quantizer is not None and self.quantizer.eigenvalue_enabled:
            self._last_batch = batch  # MoQ eigenvalue pass reuses it
        if self.telemetry.enabled:
            # host-side token accounting (global batch) + the abstract
            # batch the MFU flops resolution lowers against
            ids = batch.get("input_ids")
            if ids is not None:
                self._step_tokens += int(np.prod(ids.shape))
            self._last_prepared_batch = {
                k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in batch.items()}
        return batch

    def _train_batch_fused(self, batch) -> jax.Array:
        """One-dispatch optimizer step: the forward() bookkeeping followed
        by the step() bookkeeping, around a single fused program. The
        phase timers cannot see inside the fused program, so the whole
        dispatch is accounted to the step timer."""
        topo_mod.set_topology(self.topology)
        self._build_fused_jit()
        # the profiler's step marker: a trace can be cut by step, and the
        # program's spans below lie inside it
        with jax.profiler.StepTraceAnnotation("train_step",
                                              step_num=self.global_steps):
            return self._fused_step(batch)

    def _fused_step(self, batch) -> jax.Array:
        """The body of ``_train_batch_fused``, inside its step marker."""
        # prepare BEFORE the timer AND the telemetry step span: a rejected
        # batch must not leave the step timer running — or the watchdog
        # armed — into the next call (same rule as forward())
        batch = self._prepare_batch(batch)
        self.telemetry.step_begin(self.global_steps)
        # chaos seam: an injected stall sleeps INSIDE the open step span
        # (host side) so the watchdog sees exactly what a wedged dispatch
        # looks like; `step` is the step this dispatch will complete
        fault_point("step_begin", step=self.global_steps + 1)
        # SDC-injection seam (grad_bitflip / loss_spike): host-side param
        # corruption BEFORE the dispatch — what a flipped HBM bit looks
        # like to the step the sentinels watch
        fault_point("numerics", step=self.global_steps + 1,
                    payload=self._inject_numerics_fault)
        self.timers(STEP_GLOBAL_TIMER).start()
        lr = jnp.asarray(self.lr_scheduler.get_lr(), jnp.float32)
        anomaly = None
        with self.telemetry.phase("fused_dispatch", phase="step",
                                  step=self.global_steps), \
                self._first_call("train_step", batch):
            with self.mesh:
                if self._guardian is not None:
                    thresh = jnp.asarray(self._guardian.spike_threshold(),
                                         jnp.float32)
                    probe_in = self._stage_replay_inputs(batch, lr, thresh)
                    self.state, loss, overflow, gnorm, anomaly, *stats = \
                        self._jit_train_step(self.state, batch, lr, thresh)
                    if probe_in is not None:
                        anomaly = self._run_replay_probe(
                            probe_in, (loss, gnorm, anomaly))
                else:
                    self.state, loss, overflow, gnorm, *stats = \
                        self._jit_train_step(self.state, batch, lr)
        self._count_moe(stats)
        self._cached_loss = loss
        self.micro_steps += 1
        with self.telemetry.phase("post_step", phase="step",
                                  step=self.global_steps):
            self._post_step(overflow, gnorm, anomaly=anomaly, loss=loss)
        self._after_step()
        return loss

    def _first_call(self, program: str, batch=None):
        """The first-call door (``setup_spans.SetupTotals.first_call``): a
        ``first_call`` span around the call that compiles ``program`` for
        shapes not met before, a constant no-op on every later call. A
        program compiled after set-up comes through here too."""
        key = () if batch is None else tuple(v.shape for v in batch.values())
        if self._setup.open and program in ("train_step", "micro_step") \
                and self._memory_before is None:
            # the step's first program is about to be loaded (``memory_totals``)
            self._memory_before = step_memory.device_memory(self.mesh.devices.flat)
        return self._setup.first_call(program, key, self.telemetry)

    def _drop_jits(self, *names: str) -> None:
        """Forget jitted programs whose captured shardings or donated
        buffers are stale; each one's next call is a first call again."""
        for name in names:
            setattr(self, f"_jit_{name}", None)
        self._setup.seen = {k: v for k, v in self._setup.seen.items()
                            if k[0] not in names}

    def _account_memory(self) -> None:
        """``memory_totals``, once, when the first optimizer step has
        returned: the allocator as it reads now against how it read before
        the step's first program was called (``_first_call``). No sync: the
        allocator's bytes in use are the live arrays, which a step that is
        still running has already written over its donated state, and the
        reservation is made when a program is loaded."""
        t0 = clock.now()
        self.memory_totals.update(step_memory.step_totals(
            self._memory_before, step_memory.device_memory(self.mesh.devices.flat)))
        self.memory_totals["account_s"] = clock.now() - t0
        log_dist(step_memory.describe(self.memory_totals), ranks=[0])

    def _after_step(self) -> None:
        """The end of an optimizer step: set-up ends with the first one, and
        while a profiler session is running the engine's counters go into
        its trace as one zero-length ``engine_totals`` annotation, a stat a
        scalar (docs/OBSERVABILITY.md)."""
        if self._setup.open:
            self._setup.finish()
            self._count_traced_rows()
            log_dist("set-up: " + json.dumps(self.setup_totals), ranks=[0])
            self._account_memory()
        traced, seen = _profiler_on(), self._profiler_seen
        self._profiler_seen = traced
        if traced:
            if not seen:
                # a session's first step: the residents are the TRACED
                # steps' (a harness may have freed buffers since the first)
                step_memory.with_residents(
                    self.memory_totals,
                    step_memory.device_memory(self.mesh.devices.flat))
                self._totals_flat = (-1, {})
            version, flat = self._totals_flat
            if version != self._setup.version:
                flat = setup_spans.flat_totals(
                    setup=self.setup_totals, moe=self.moe_totals,
                    attn=self.attn_totals, opt_kernel=self.opt_kernel_totals,
                    remat=self.remat_totals, diffusion=self.diffusion_totals or {},
                    memory=self.memory_totals)
                self._totals_flat = (self._setup.version, flat)
            if "moe.steps" in flat:     # the counters a step writes
                flat["moe.steps"] = self.moe_totals["steps"]
            if "diffusion.steps" in flat:
                flat["diffusion.steps"] = self.diffusion_totals["steps"]
            with jax.profiler.TraceAnnotation("engine_totals", **flat):
                pass

    def _model_records(self, name: str, batch=None, **how):
        """The model's record ``name`` (``TransformerLM.attention_records`` /
        ``expert_records``): of its configuration, or of the ``batch`` a step
        is being traced for (host arithmetic from static shapes). None for a
        model without, or a batch without ids."""
        records = getattr(self.model, name, None)
        if records is None or (batch is not None and "input_ids" not in batch):
            return None
        return records(*(() if batch is None else batch["input_ids"].shape[:2]), **how)

    def _count_launches(self, batch) -> None:
        """``attn_totals`` and ``diffusion_totals`` while a step is traced."""
        attn, diffusion = self._model_records("attention_records", batch) or ({}, None)
        self.attn_totals.update(attn)
        if diffusion is not None:
            self.diffusion_totals.update(diffusion)

    def _count_traced_rows(self) -> None:
        """What the model's records say of the rows the first step ran
        (``TransformerLM.traced_rows_records`` over that step's statistics,
        fetched this once, as set-up ends), into ``attn_totals``."""
        records = getattr(self.model, "traced_rows_records", None)
        if records is not None and self._step_stats:
            for kind, found in records(self._step_stats).items():
                self.attn_totals[kind].update(found)

    def _count_moe(self, stats) -> None:
        """The fused step's MoE counters; the step's statistics are kept
        as the device arrays they are."""
        if self.diffusion_totals is not None:
            self.diffusion_totals["steps"] += 1
        if self.moe_totals["path"] is not None:
            self.moe_totals["steps"] += 1
        self._step_stats = stats[0] if stats else None

    # ------------------------------------------------------------------
    # public API (reference engine.py forward :1781 / backward :1922 / step :2120)
    # ------------------------------------------------------------------
    _REPLICATED_BATCH_KEYS = ("layer_mask",)  # per-layer/global aux inputs

    def _validate_batch(self, batch: Dict[str, Any]) -> None:
        """Host-side input_ids checks — an out-of-range id would CLIP
        silently in the embedding lookup (nn/layers.py gather mode), so
        blame the data here, with the offending values. One cheap pass
        over small int arrays; device arrays are pulled back (tiny)."""
        ids = batch.get("input_ids")
        cfg = getattr(self.model, "config", None)
        vocab = getattr(cfg, "vocab_size", None)
        if ids is None or vocab is None:
            return
        arr = np.asarray(ids)
        mn, mx = int(arr.min()), int(arr.max())
        if mx >= vocab or mn < 0:
            raise ValueError(
                f"input_ids out of range for vocab_size={vocab}: "
                f"min id {mn}, max id {mx} (negative masking ids belong in "
                f"'labels', not input_ids)")
        if getattr(cfg, "position", None) == "learned":
            max_len = getattr(cfg, "max_seq_len", None)
            if max_len is not None and arr.shape[-1] > max_len:
                raise ValueError(
                    f"sequence length {arr.shape[-1]} exceeds the learned "
                    f"position table ({max_len}); positions would clip")

    def _device_batch(self, batch: Dict[str, Any]) -> Dict[str, jax.Array]:
        sharding = NamedSharding(self.mesh, DATA_SPEC)
        rep = NamedSharding(self.mesh, P())
        return {k: jax.device_put(jnp.asarray(v),
                                  rep if k in self._REPLICATED_BATCH_KEYS else sharding)
                for k, v in batch.items()}

    def _apply_curriculum(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        """Truncate sequences to the scheduled difficulty (reference
        curriculum kwargs injection, engine.py:1813-1826). Difficulty is
        quantized by the schedule's difficulty_step, bounding the number of
        distinct compiled shapes."""
        seqlen = self.curriculum_scheduler.update_difficulty(self.global_steps)
        out = {}
        for k, v in batch.items():
            v = np.asarray(v)
            out[k] = v[:, :seqlen] if v.ndim >= 2 and v.shape[1] > seqlen else v
        return out

    def _ensure_grad_acc(self) -> None:
        """Allocate the persistent gradient buffer on first use of the
        split forward/backward path when the engine was built without one
        (gas==1 fused-eligible). Invalidate jits/shardings built against
        the empty tree.

        Offload engines NEVER allocate it at gas==1: their micro step
        replaces the empty tree with the fresh gradients (see
        _micro_step_fn) and the offload apply consumes + drops them —
        a persistent buffer would put 3x model bytes on the chip."""
        if not self._gradacc_lazy:
            return
        if self._offload_device != "none":
            if jax.tree.leaves(self.state["grad_acc"]):
                raise RuntimeError(
                    "offload engines at gradient_accumulation_steps == 1 "
                    "hold gradients only between forward and step; call "
                    "step() before the next forward (set "
                    "gradient_accumulation_steps > 1 for accumulation)")
            return
        if jax.tree.leaves(self.state["grad_acc"]):
            return
        self._gradacc_lazy = False
        with self.mesh:
            self.state["grad_acc"] = jax.jit(
                lambda p: jax.tree.map(
                    lambda x: jnp.zeros(x.shape, self.grad_dtype), p),
                out_shardings=self._grad_shardings)(self.state["params"])
        self._cached_shardings = None
        self._drop_jits("train_step", "micro_step", "apply_step")

    def _reject_paged(self, op: str) -> None:
        if self._param_stream is not None:
            raise RuntimeError(
                f"{op}() is not available with offload_param.paged_training "
                "— the paged step fuses forward/backward/apply around the "
                "per-layer param pipeline; use train_batch() (training) or "
                "eval_batch() (loss only)")

    def forward(self, batch: Dict[str, Any]):
        """Compute loss (and gradients — fused; see module docstring)."""
        self._reject_paged("forward")
        self._require_params("forward")
        self._ensure_grad_acc()
        # retraces (new shapes) must see THIS engine's mesh, not whichever
        # engine was constructed last
        topo_mod.set_topology(self.topology)
        self._build_jits()
        # prepare before the timer and the telemetry step span: a rejected
        # batch must not leave FORWARD_GLOBAL_TIMER running — or the
        # watchdog armed — into the next step
        batch = self._prepare_batch(batch)
        self.telemetry.step_begin(self.global_steps)
        fault_point("step_begin", step=self.global_steps + 1)
        fault_point("numerics", step=self.global_steps + 1,
                    payload=self._inject_numerics_fault)
        self.timers(FORWARD_GLOBAL_TIMER).start()
        with self.telemetry.phase("micro_dispatch", phase="fwd",
                                  step=self.global_steps), \
                self._first_call("micro_step", batch):
            with self.mesh:
                if self._explicit_micro:
                    if getattr(self, "_ef_carry_active", False):
                        (gacc, ef), loss = self._jit_micro_step(
                            (self.state["grad_acc"], self._ef_state),
                            self.state["loss_scale"]["cur_scale"],
                            self._secondary, batch)
                        self._ef_state = ef
                    else:
                        gacc, loss = self._jit_micro_step(
                            self.state["grad_acc"],
                            self.state["loss_scale"]["cur_scale"],
                            self._secondary, batch)
                    self.state["grad_acc"] = gacc
                else:
                    self.state, loss = self._jit_micro_step(self.state, batch)
        self._cached_loss = loss
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss

    def backward(self, loss=None):
        """Gradients were produced in forward; this marks the micro-step
        boundary (reference engine.backward, engine.py:1922)."""
        self._reject_paged("backward")
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        # gradients were fused into the forward dispatch; this span marks
        # the micro boundary so the trace shows accumulation structure
        with self.telemetry.phase("micro_boundary", phase="bwd",
                                  step=self.global_steps):
            self.micro_steps += 1
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return self._cached_loss

    def is_gradient_accumulation_boundary(self) -> bool:
        return self.micro_steps % self.gradient_accumulation_steps == 0

    def step(self):
        """Apply the optimizer at accumulation boundaries (engine.py:2120)."""
        self._reject_paged("step")
        self._require_params("step")
        if not self.is_gradient_accumulation_boundary():
            return
        self._build_jits()
        self.timers(STEP_GLOBAL_TIMER).start()
        lr = jnp.asarray(self.lr_scheduler.get_lr(), jnp.float32)
        anomaly = None
        with self.telemetry.phase("apply_step", phase="optimizer",
                                  step=self.global_steps), \
                self._first_call("apply_step"):
            if self._offload is not None:
                overflow, gnorm = self._apply_step_offload(float(lr))
                if self._guardian is not None:
                    # the offload boundary already resolved everything on
                    # the host — the word is pure host arithmetic there
                    anomaly = self._last_anomaly_word
            else:
                with self.mesh:
                    if self._guardian is not None and \
                            self._onebit_opt is None:
                        thresh = jnp.asarray(
                            self._guardian.spike_threshold(), jnp.float32)
                        self.state, overflow, gnorm, anomaly = \
                            self._jit_apply_step(self.state, lr, thresh)
                    else:
                        self.state, overflow, gnorm = self._jit_apply_step(
                            self.state, lr)
        with self.telemetry.phase("post_step", phase="step",
                                  step=self.global_steps):
            self._post_step(overflow, gnorm, anomaly=anomaly)
        self._after_step()

    def _post_step(self, overflow, gnorm, anomaly=None, loss=None) -> None:
        """Host-side bookkeeping after the optimizer update (shared by the
        split and fused step paths). ``anomaly`` is the traced anomaly
        word when the guardian armed this path (None otherwise); the
        guardian's verdict — observe, maybe roll back — runs at the end,
        after the step's accounting is consistent."""
        word = int(anomaly) if anomaly is not None else 0
        self._last_anomaly_word = word
        self.global_steps += 1
        if self.quantizer is not None:
            # MUST run before _refresh_secondary: quantize() donates the
            # param buffers, and at hpz==1 the ZeRO++ secondary ALIASES
            # them — refreshing afterwards re-points it at the quantized
            # arrays (and makes the forward actually see the QAT weights)
            eigenvalues = None
            if (self.eigenvalue is not None and self._last_batch is not None
                    and "blocks" in self.state["params"]
                    and self.global_steps %
                    self.quantizer.gas_boundary_resolution == 0):
                L = int(jax.tree.leaves(
                    self.state["params"]["blocks"])[0].shape[0])
                with self.mesh:
                    eigenvalues = self.eigenvalue.compute_layer_eigenvalues(
                        self.model.loss, self.state["params"],
                        self._last_batch,
                        jax.random.PRNGKey(self.global_steps), L)
            with self.mesh:
                self.state["params"] = self.quantizer.quantize(
                    self.state["params"], bool(overflow), eigenvalues)
        if self._explicit_micro:
            self._refresh_secondary()
        guardian_skip = (word != 0 and self._guardian is not None
                         and self._guardian.config.skip_on_anomaly)
        if self.config.fp16.enabled and bool(overflow):
            # skipped update does not consume schedule (reference engine.py:2053)
            self.skipped_steps += 1
            log_dist(f"step {self.global_steps}: fp16 overflow, skipping update "
                     f"(new scale {float(self.state['loss_scale']['cur_scale'])})", ranks=[0])
        elif guardian_skip:
            # the in-graph anomaly skip generalizes the overflow skip:
            # the update did not apply, so the schedule is not consumed
            self.skipped_steps += 1
            log_dist(f"step {self.global_steps}: guardian anomaly "
                     f"(word={word}), update skipped", ranks=[0])
        else:
            self.lr_scheduler.step()
        self.timers(STEP_GLOBAL_TIMER).stop()
        self._last_grad_norm = gnorm
        if self.telemetry.enabled:
            tokens, self._step_tokens = self._step_tokens, 0
            # global_steps already incremented; the open span began at N
            self.telemetry.step_end(self.global_steps - 1, tokens=tokens)
            if self.global_steps % self.telemetry.flush_every == 0:
                # fence point: derived metrics (step percentiles, MFU,
                # goodput, overlap efficiency, memory watermarks) to every
                # sink — the monitor's 3-scalar flush grew into this
                self.telemetry.flush(self.global_steps)
        if self.monitor.enabled and self.global_steps % self.config.steps_per_print == 0:
            self.monitor.write_events([
                ("Train/lr", self.lr_scheduler.get_lr(), self.global_steps),
            ])
        if self._guardian is not None:
            # the guardian verdict: loss/gnorm are tiny scalars the caller
            # fetches anyway; the policy ladder is pure host arithmetic
            lossf = None
            src = loss if loss is not None else self._cached_loss
            if src is not None:
                lossf = float(src)
            gn = float(gnorm)
            self.telemetry.record_numerics(self.global_steps, lossf, gn)
            verdict = self._guardian.observe(self.global_steps, lossf, gn,
                                             word)
            if verdict.action == "rollback":
                self._guardian_rollback(verdict)
        # chaos seam: a crash injected "at step k" kills the process HERE,
        # after step k's bookkeeping and before any checkpoint the caller
        # would write for it — the preemption the elastic agent recovers
        fault_point("step_end", step=self.global_steps)

    def _offload_jit(self, kind, key, build):
        """Per-leaf program cache for the offload path. The offload data
        movement is deliberately MANY SMALL programs, not one monolithic
        flatten/unflatten over every leaf: per-leaf dispatch overhead is
        noise next to the multi-GiB host<->device transfers these models
        imply. The whole-tree form is not measured on the current
        machine."""
        if not hasattr(self, "_offload_jits"):
            self._offload_jits = {}
        full = (kind,) + key
        if full not in self._offload_jits:
            self._offload_jits[full] = build()
        return self._offload_jits[full]

    def _flat_leaf_jit(self, shape, dtype, lay, sharding):
        return self._offload_jit(
            "flat", (shape, str(dtype), lay, str(sharding)),
            lambda: jax.jit(lambda x, _l=lay: self._to_flat(x, _l),
                            out_shardings=sharding))

    @staticmethod
    def _flat_shape(shape, lay):
        """Shape _to_flat would produce, without tracing."""
        if len(shape) == 0:
            return (1, 1)
        dp_dim, _, mp_dim, _ = lay
        order = DeepSpeedEngine._flat_order(len(shape), dp_dim, mp_dim)
        t = tuple(shape[d] for d in order)
        lead = t[0] if dp_dim is not None else 1
        total = 1
        for d in t:
            total *= d
        return (lead, total // max(lead, 1))

    def _offload_leaf_direct(self, shape, lay) -> bool:
        """True when the leaf's flat layout is its C-order view on a
        1-device mesh: fetch/push then move the RAW leaf (device_get /
        device_put) with ZERO device-side transient — no transpose
        program, no flat copy. At 3B params on one 16 GB chip the flat
        copy (even one leaf's) next to params + grad buffer is the
        difference between fitting and RESOURCE_EXHAUSTED. Multi-device
        meshes keep the sharded flat machinery."""
        if self.mesh.size != 1:
            return False
        if len(shape) == 0:
            return True
        dp_dim, _, mp_dim, _ = lay
        order = self._flat_order(len(shape), dp_dim, mp_dim)
        return list(order) == list(range(len(shape)))

    def _stat_leaf_jit(self, shape, dtype, fp16):
        def build():
            def stat(x):
                sq = jnp.sum(jnp.square(x.astype(jnp.float32)))
                fin = jnp.all(jnp.isfinite(x)) if fp16 else jnp.asarray(True)
                return sq, fin
            return jax.jit(stat)
        return self._offload_jit("stat", (shape, str(dtype), fp16), build)

    @staticmethod
    def _from_flat(f, lay, shape, dtype):
        """Inverse of :meth:`_to_flat`: 2-D flat → the leaf's own shape,
        cast to the param dtype. The single statement of the unflatten
        math — the push jit and the ``offload-step-pipeline`` lint entry
        both trace THIS function, so the audited program cannot drift
        from production."""
        if len(shape) == 0:
            a = f.reshape(())
        else:
            dp_dim, _, mp_dim, _ = lay
            order = DeepSpeedEngine._flat_order(len(shape), dp_dim, mp_dim)
            a = f.reshape(tuple(shape[d] for d in order))
            a = a.transpose([order.index(d) for d in range(len(shape))])
        return a.astype(dtype)

    def _unflat_leaf_jit(self, lay, shape, sharding):
        dtype = self.param_dtype

        def build():
            # DONATE the pushed flat buffer when the unflatten is a pure
            # reshape (identity order) and the push dtype matches: the
            # swap-in buffer is dead after this program, and the alias
            # lets XLA build the new param leaf in place (machine-checked
            # dead-donation in the offload-step-pipeline lint entry). A
            # transposing layout cannot alias — no donation there.
            dp_dim, _, mp_dim, mp_axes = lay
            order = self._flat_order(max(len(shape), 1), dp_dim, mp_dim)
            # inputs arrive pre-cast to the param dtype (push_dt), so the
            # unflat is a pure bitcast when the order is identity AND the
            # in/out shardings agree (a ZeRO-3 dp-sharded matrix leaf —
            # the out-of-core production case). Replicated-param stages
            # reshard on the way out and cannot alias; donating there
            # only buys a 'donation unusable' warning per leaf.
            donate = False
            if (order == list(range(max(len(shape), 1))) and not mp_axes
                    and len(shape) == 2):
                fsh = NamedSharding(self.mesh, self._flat2_sharding_spec(lay))
                try:
                    donate = sharding.is_equivalent_to(fsh, 2)
                except (TypeError, ValueError):
                    donate = False
            return jax.jit(lambda f: self._from_flat(f, lay, shape, dtype),
                           out_shardings=sharding,
                           donate_argnums=(0,) if donate else ())
        return self._offload_jit("unflat", (lay, shape, str(sharding)), build)

    def _offload_grad_feed(self, leaves, mult, ph, grad_buf, span_offs,
                           span_lens, chunk_bounds):
        """Lazily yield runner grad chunks as their D2H transfers land —
        the fetch half of the double-buffered offload pipeline (ISSUE 15).

        Bucket k+1's flatten programs and async host copies are ISSUED
        before the blocking landing of bucket k (``copy_to_host_async``
        starts the wire transfer; the later ``device_get`` merely
        completes it), so at most two buckets of flat grad copies are
        device-resident and the landing wait — charged to the
        ``h2d_prefetch`` phase — shrinks toward transfer-minus-compute.
        The runner pulls chunks between bucket computes, which is what
        puts bucket k's host step under bucket k+1's wire time."""
        import time as _time
        host_idx = self._offload_host_idx
        layouts = self._offload_layouts
        buckets = self._offload_fetch_buckets
        staged: Dict[int, list] = {}

        def issue(bk):
            for k in buckets[bk]:
                i = host_idx[k]
                if self._offload_direct[k]:
                    datas = [leaves[i]]
                else:
                    flat = self._flat_leaf_jit(
                        leaves[i].shape, leaves[i].dtype, layouts[k],
                        self._offload_flat_shardings[k])(leaves[i])
                    datas = [d for _, _, d in self._leaf_local_groups(flat)]
                for d in datas:
                    try:
                        d.copy_to_host_async()
                    except AttributeError:
                        pass  # older jaxlib: device_get still lands it
                staged[k] = datas

        filled = 0
        next_chunk = 0
        if buckets:
            issue(0)
        for bk in range(len(buckets)):
            if bk + 1 < len(buckets):
                issue(bk + 1)  # next bucket's wire time under this landing
            t0 = _time.perf_counter()
            for k in buckets[bk]:
                datas = staged.pop(k)
                got = jax.device_get(datas)
                s0, s1 = self._offload_leaf_spans[k]
                for j, p in zip(range(s0, s1), got):
                    seg = grad_buf[span_offs[j]:span_offs[j] + span_lens[j]]
                    seg[...] = np.asarray(p, np.float32).reshape(-1)
                    if mult != 1.0:
                        np.multiply(seg, np.float32(mult), out=seg)
                filled = span_offs[s1 - 1] + span_lens[s1 - 1] \
                    if s1 > s0 else filled
                del got, datas
            ph["h2d_prefetch"] += _time.perf_counter() - t0
            while next_chunk < len(chunk_bounds) \
                    and chunk_bounds[next_chunk][1] <= filled:
                a, b = chunk_bounds[next_chunk]
                next_chunk += 1
                yield grad_buf[a:b]
        # tail: everything has landed (zero-size locals land here too)
        while next_chunk < len(chunk_bounds):
            a, b = chunk_bounds[next_chunk]
            next_chunk += 1
            yield grad_buf[a:b]

    def _apply_step_offload(self, lr: float):
        """Optimizer boundary on the host (ZeRO-Offload): fetch the LOCAL
        shard of the flat gradient (each host reads only its addressable
        1/n_hosts, in the GRAD dtype — fp32 widening, unscale and clip all
        happen on the host), native CPU optimizer on the local master
        segment (NVMe chunks stream through the pipelined swapper), then
        scatter the updated master back into the sharded param tree, one
        small program per leaf (see _offload_jit).

        Since ISSUE 15 the three streams run as a double-buffered
        leaf-bucket pipeline (fetch of bucket k+1 under host compute of
        bucket k, pushes async behind both — docs/OFFLOAD.md);
        ``DSTPU_OFFLOAD_PIPELINE=0`` restores the serial barrier
        schedule bitwise. Either way the step records the 4-way stall
        decomposition in ``last_offload_phase_s``."""
        host_idx = self._offload_host_idx
        dev_idx = self._offload_device_idx
        dev_names = [self._offload_leaf_names[i] for i in dev_idx]
        layouts = self._offload_layouts
        fp16 = self.config.fp16.enabled

        leaves = jax.tree.leaves(self.state["grad_acc"])
        with self.mesh:
            dev_grads = {n: leaves[i] for n, i in zip(dev_names, dev_idx)}
            # sq-norm and finiteness on the RAW leaves (both are
            # layout-invariant) — the flat copies don't exist yet, and
            # materializing them all at once would not fit (see below)
            stats = [self._stat_leaf_jit(leaves[i].shape, leaves[i].dtype,
                                         fp16)(leaves[i])
                     for i in host_idx]
            stats += [self._stat_leaf_jit(v.shape, v.dtype, fp16)(v)
                      for v in dev_grads.values()]
        # ONE host round trip for every scalar (sq-norms, finite flags, the
        # loss scale): gnorm/overflow/clip resolve on the host
        fetched = jax.device_get(
            [self.state["loss_scale"]["cur_scale"]] + list(stats))
        scale = float(fetched[0])
        sq = float(sum(s for s, _ in fetched[1:]))
        finite = all(bool(f) for _, f in fetched[1:])
        overflow = bool(fp16 and not finite)
        inv = 0.0 if overflow else 1.0 / scale
        # on overflow sq is often inf and inf*0.0 is NaN in Python floats;
        # the device path reports 0.0 (jnp.where) — match it
        gnorm = 0.0 if overflow else (sq ** 0.5) * inv
        mult = inv
        if self.gradient_clipping > 0:
            mult = inv * min(1.0, self.gradient_clipping / (gnorm + 1e-6))
        skip = overflow
        if self._guardian is not None:
            # the offload boundary resolves every scalar on the host
            # already — the anomaly word here is plain Python arithmetic
            # over the same fetched stats (zero extra device work)
            from ..resilience.guardian import (ANOMALY_GNORM_SPIKE,
                                               ANOMALY_GRAD_NONFINITE,
                                               ANOMALY_GRAD_ZERO)
            # like pack_anomaly_word: non-finiteness also derives from
            # the norm itself, so bf16/fp32 runs (overflow pinned False)
            # still catch NaN/inf grads
            word = (ANOMALY_GRAD_NONFINITE
                    if (overflow or not np.isfinite(sq)) else 0)
            if sq == 0.0:
                word |= ANOMALY_GRAD_ZERO
            if gnorm > self._guardian.spike_threshold():
                word |= ANOMALY_GNORM_SPIKE
            self._last_anomaly_word = word
            if word and self._guardian.config.skip_on_anomaly:
                skip = True
        if not skip:
            dev_params = {}
            if dev_idx:
                # Twin-Flow device partition: dispatch the jitted optimizer
                # step FIRST (async) so it overlaps the host D2H + CPU step
                # below; unscale/clip fold into the update's per-leaf cast
                # (grad_scale), so the raw grads never widen on device
                if getattr(self, "_jit_offload_devstep", None) is None:
                    param_sh_leaves = jax.tree.leaves(self._param_shardings)
                    dev_param_sh = {n: param_sh_leaves[i]
                                    for n, i in zip(dev_names, dev_idx)}
                    opt_sh = self._state_shardings()["opt"]

                    # donate the optimizer state: it is replaced by the
                    # returned tree, and without donation the fp32 moments
                    # exist twice at peak (device-partition leaves are the
                    # large ones under Twin-Flow)
                    self._jit_offload_devstep = jax.jit(
                        self._optimizer_update, donate_argnums=(1,),
                        out_shardings=(dev_param_sh, opt_sh))
                with self.mesh:
                    dev_params, self.state["opt"] = \
                        self._jit_offload_devstep(
                            dev_grads, self.state["opt"],
                            jnp.asarray(lr, jnp.float32),
                            jnp.asarray(mult, jnp.float32))
            # Grad fetch (device → host). Two schedules (ISSUE 15):
            #
            # - PIPELINED (default): the chunk feed below issues bucket
            #   k+1's flatten programs + async host copies before blocking
            #   on bucket k, so the landing wait overlaps the host step of
            #   the previous bucket. At most two buckets of flat copies
            #   are device-resident (double buffer) — the per-leaf memory
            #   argument still holds, bounded by the bucket size.
            # - SERIAL (DSTPU_OFFLOAD_PIPELINE=0): flatten → pull →
            #   RELEASE one leaf at a time, every leaf fetched before any
            #   host compute (the pre-ISSUE-15 schedule, kept BITWISE —
            #   same chunk boundaries, same arithmetic order). Direct
            #   leaves move raw with no device transient at all. fp32
            #   widening and unscale × clip happen HOST-side either way.
            from .zero.offload_optimizer import offload_pipeline_enabled
            import time as _time
            pipelined = offload_pipeline_enabled()
            ph = {"h2d_prefetch": 0.0, "bucket_compute": 0.0,
                  "d2h_writeback": 0.0, "nvme_io": 0.0}
            span_lens = [int(np.prod(sh))
                         for _, _, sh, _ in self._offload_spans]
            span_offs = []
            off = 0
            for ln in span_lens:
                span_offs.append(off)
                off += ln
            total_local = off
            if pipelined:
                grad_buf = np.empty(total_local, np.float32)
                c = self._offload_chunk_elems
                chunk_bounds = [(a, min(a + c, total_local))
                                for a in range(0, max(total_local, 1), c)]
                grad_feed = self._offload_grad_feed(
                    leaves, mult, ph, grad_buf, span_offs, span_lens,
                    chunk_bounds)
            else:
                _t0 = _time.perf_counter()
                pieces = []
                with self.mesh:
                    for k, (i, lay, sh) in enumerate(zip(
                            host_idx, layouts, self._offload_flat_shardings)):
                        if self._offload_direct[k]:
                            pieces.append(np.asarray(
                                jax.device_get(leaves[i]),
                                np.float32).reshape(-1))
                            continue
                        flat = self._flat_leaf_jit(
                            leaves[i].shape, leaves[i].dtype, lay, sh)(leaves[i])
                        datas = [d for _, _, d in self._leaf_local_groups(flat)]
                        pieces.extend(np.asarray(p, np.float32).reshape(-1)
                                      for p in jax.device_get(datas))
                        del flat, datas
                if mult != 1.0:
                    for j, pc in enumerate(pieces):
                        if pc.flags.writeable:
                            np.multiply(pc, np.float32(mult), out=pc)
                        else:  # zero-copy device_get views are read-only
                            pieces[j] = pc * np.float32(mult)
                local_grad = (np.concatenate(pieces) if pieces
                              else np.zeros(0, np.float32))
                grad_feed = self._chunked(local_grad)
                ph["h2d_prefetch"] = _time.perf_counter() - _t0
            # the OLD params are dead from here on (their gradients are
            # consumed, their replacement is rebuilt from the host master
            # and dev_params): drop the tree BEFORE the first push so the
            # incoming flats + rebuilt leaves fit beside the grad buffer
            # at 3B scale
            self.state["params"] = None
            # Host step INTERLEAVED with the param push (reference overlap
            # pattern, stage_1_and_2.py:1005): step_iter yields each master
            # chunk as its update lands, and every span that chunk completes
            # is device_put immediately (async H2D) — the upload of chunk
            # k's params rides under chunk k+1's NVMe paging + CPU step
            # instead of serializing after the whole host phase.
            # Direct leaves upload straight as the new param leaf; sharded
            # leaves rebuild their flat array and unflatten one small
            # program per leaf after the loop.
            per_leaf: Dict[int, list] = {}
            # push in the PARAM dtype, not fp32: the unflatten casts to
            # param dtype anyway, so uploading wide only doubles H2D
            # bytes (at 3B params: 13.7 GB vs 6.8)
            push_dt = np.dtype(self.param_dtype)
            param_sh_leaves = jax.tree.leaves(self._param_shardings)
            outs = [None] * len(self._offload_full_shapes)
            master_buf = np.empty(total_local, np.float32)
            done = 0
            next_span = 0

            def _flush_spans(limit):
                nonlocal next_span
                t0 = _time.perf_counter()
                while next_span < len(self._offload_spans):
                    leaf_idx, _, pshape, devices = \
                        self._offload_spans[next_span]
                    o = span_offs[next_span]
                    length = span_lens[next_span]
                    if o + length > limit:
                        break
                    seg = master_buf[o:o + length]
                    i = host_idx[leaf_idx]
                    if self._offload_direct[leaf_idx]:
                        leaf_shape = self._offload_shapes[leaf_idx]
                        outs[i] = jax.device_put(
                            seg.reshape(leaf_shape).astype(push_dt),
                            param_sh_leaves[i])
                    else:
                        per_leaf.setdefault(leaf_idx, []).extend(
                            jax.device_put(seg.reshape(pshape).astype(push_dt),
                                           d)
                            for d in devices)
                    next_span += 1
                # dispatch wall of the async H2D pushes (device_put returns
                # before the copy completes — the transfer itself rides
                # under the next bucket's paging + CPU step)
                ph["d2h_writeback"] += _time.perf_counter() - t0

            with self.mesh:
                for _, mchunk in self._offload.step_iter(grad_feed, lr=lr):
                    flat = np.asarray(mchunk).reshape(-1)
                    master_buf[done:done + flat.size] = flat
                    done += flat.size
                    _flush_spans(done)
                _flush_spans(done)
                t0 = _time.perf_counter()
                for leaf_idx, arrs in per_leaf.items():
                    flat = jax.make_array_from_single_device_arrays(
                        self._offload_flat_shapes[leaf_idx],
                        self._offload_flat_shardings[leaf_idx], arrs)
                    i = host_idx[leaf_idx]
                    outs[i] = self._unflat_leaf_jit(
                        layouts[leaf_idx], self._offload_shapes[leaf_idx],
                        param_sh_leaves[i])(flat)
                    del flat
                ph["d2h_writeback"] += _time.perf_counter() - t0
            # paging-stall visibility: seconds the host step spent BLOCKED
            # on NVMe fences (0 for device=cpu), and its total wall time —
            # the bench reports stall_frac from these. The 4-way phase
            # split (docs/OBSERVABILITY.md "Offload stall decomposition")
            # is the honest decomposition the pipeline is judged by.
            if pipelined:
                # the feed charged its landing waits as it ran; fold in
                # any residual pull-wait the runner saw on top of them
                ph["h2d_prefetch"] = max(ph["h2d_prefetch"],
                                         self._offload.last_fetch_s)
            ph["bucket_compute"] = self._offload.last_compute_s
            ph["nvme_io"] = self._offload.last_stall_s
            self.last_offload_stall_s = self._offload.last_stall_s
            self.last_offload_compute_s = self._offload.last_compute_s
            self.last_offload_phase_s = dict(ph)
            self.telemetry.record_offload_phases(self.global_steps, ph)
            for n, i in zip(dev_names, dev_idx):
                outs[i] = dev_params[n]
            self.state["params"] = jax.tree.unflatten(
                self._offload_treedef, outs)

        if self._gradacc_lazy:
            # bufferless mode: the per-step gradients were consumed above —
            # restore the empty-tree invariant the micro jit was traced
            # with (the epilogue's zeros-of-{} is then a no-op)
            self.state["grad_acc"] = {}
        # zero the accumulator + update loss scale on device
        if getattr(self, "_jit_offload_epilogue", None) is None:
            shardings = self._cached_shardings
            fp16c = self.config.fp16

            def epilogue(grad_acc, scale_state, ovf):
                new_acc = jax.tree.map(jnp.zeros_like, grad_acc)
                new_scale = update_scale(scale_state, ovf,
                                         scale_window=fp16c.loss_scale_window,
                                         min_scale=fp16c.min_loss_scale,
                                         hysteresis=fp16c.hysteresis,
                                         consecutive_hysteresis=fp16c.consecutive_hysteresis)
                return new_acc, new_scale

            self._jit_offload_epilogue = jax.jit(
                epilogue, donate_argnums=(0,),
                out_shardings=(shardings["grad_acc"], shardings["loss_scale"]))
        with self.mesh:
            self.state["grad_acc"], self.state["loss_scale"] = \
                self._jit_offload_epilogue(self.state["grad_acc"],
                                           self.state["loss_scale"],
                                           jnp.asarray(overflow))
        return overflow, gnorm

    def _train_batch_paged(self, data_iter_or_batch) -> jax.Array:
        """ZeRO-Infinity param-streaming step: the runner pages params
        through HBM per layer; the engine keeps schedule/bookkeeping."""
        self.tput_timer.start()
        gas = self.gradient_accumulation_steps
        if isinstance(data_iter_or_batch, dict):
            if gas > 1 and not getattr(self, "_gas_replay_warned", False):
                self._gas_replay_warned = True
                log_dist(
                    f"train_batch(dict) with gradient_accumulation_steps="
                    f"{gas} REPLAYS the same micro-batch for every "
                    "accumulation step — pass an iterator for real "
                    "training semantics", ranks=[0])
            batches = [data_iter_or_batch] * gas
        else:
            batches = [next(data_iter_or_batch) for _ in range(gas)]
        # prepare before the step span: a rejected batch must not leave
        # the watchdog armed (same rule as the fused/split paths)
        with self.telemetry.phase("prepare_batch", phase="data",
                                  step=self.global_steps):
            for b in batches:
                self._validate_batch(b)
            if self.curriculum_scheduler is not None:
                batches = [self._apply_curriculum(b) for b in batches]
            dev = [self._device_batch(b) for b in batches]
        self.telemetry.step_begin(self.global_steps)
        fault_point("step_begin", step=self.global_steps + 1)
        lr = float(self.lr_scheduler.get_lr())
        with self.telemetry.phase("paged_step", phase="step",
                                  step=self.global_steps):
            loss = self._param_stream.train_step(dev, lr)
        # paged-path stall decomposition (ISSUE 15): device-side waits on
        # host futures (the pipeline interlock) and main-thread waits on
        # NVMe read futures — both already accumulated by the runner
        self.telemetry.record_offload_phases(self.global_steps, {
            "h2d_prefetch": self._param_stream.last_fetch_wait_s,
            "nvme_io": getattr(self._param_stream, "last_nvme_wait_s", 0.0),
        })
        self.micro_steps += gas
        self.global_steps += 1
        fault_point("step_end", step=self.global_steps)
        self.lr_scheduler.step()
        self._last_grad_norm = self._param_stream.last_grad_norm
        self.tput_timer.stop(global_step=True)
        if self.telemetry.enabled:
            tokens = sum(int(np.prod(b["input_ids"].shape))
                         for b in dev if "input_ids" in b)
            self.telemetry.step_end(self.global_steps - 1, tokens=tokens)
            if self.global_steps % self.telemetry.flush_every == 0:
                self.telemetry.flush(self.global_steps)
        return loss

    def train_batch(self, data_iter_or_batch) -> jax.Array:
        """One full optimizer step: gas micro-steps + apply (the
        PipelineEngine-style entry, pipe/engine.py:321)."""
        if self._param_stream is not None:
            return self._train_batch_paged(data_iter_or_batch)
        self._require_params("training")
        fp_cfg = self.config.flops_profiler_config
        profiling = fp_cfg.enabled and self.global_steps == fp_cfg.profile_step
        if profiling:
            self.flops_profiler.start_profile()
        self.tput_timer.start()
        if isinstance(data_iter_or_batch, dict):
            if self.gradient_accumulation_steps > 1 and \
                    not getattr(self, "_gas_replay_warned", False):
                self._gas_replay_warned = True
                log_dist(
                    f"train_batch(dict) with gradient_accumulation_steps="
                    f"{self.gradient_accumulation_steps} REPLAYS the same "
                    "micro-batch for every accumulation step — pass an "
                    "iterator for real training semantics", ranks=[0])
            batches = [data_iter_or_batch] * self.gradient_accumulation_steps
        else:
            batches = [next(data_iter_or_batch) for _ in range(self.gradient_accumulation_steps)]
        # the profiler costs the micro-step program, so it needs the split
        # path; everything else with gas==1 takes the one-dispatch step
        if not profiling and self._fused_step_eligible():
            loss = self._train_batch_fused(batches[0])
            self.tput_timer.stop(global_step=True)
            return loss
        losses = []
        for batch in batches:
            losses.append(self.forward(batch))
            self.backward()
        self.step()
        self.tput_timer.stop(global_step=True)
        if profiling:
            self.flops_profiler.stop_profile()
            self.flops_profiler.set_flops(
                self._micro_step_flops(batches[0]) * len(batches))
            self.flops_profiler.print_model_profile(
                profile_step=fp_cfg.profile_step, output_file=fp_cfg.output_file)
            self.flops_profiler.end_profile()
        return jnp.mean(jnp.stack(losses))

    def _micro_step_flops(self, batch) -> float:
        """XLA's cost analysis of the micro-step (``_program_flops``; the
        hook-based estimate of the reference's profiler.py:228). ``batch``
        leaves may be arrays or ``ShapeDtypeStruct``s (the telemetry MFU
        path keeps only the abstract batch)."""
        try:
            dev_batch = (batch if all(isinstance(v, jax.ShapeDtypeStruct)
                                      for v in batch.values())
                         else self._device_batch(batch))
            if self._explicit_micro:
                args = (self.state["grad_acc"],
                        self.state["loss_scale"]["cur_scale"],
                        self._secondary, dev_batch)
            else:
                args = (self.state, dev_batch)
            return self._program_flops(self._jit_micro_step, args)
        except Exception:
            return 0.0

    def eval_batch(self, batch: Dict[str, Any]) -> jax.Array:
        if self._param_stream is not None:
            self._validate_batch(batch)
            return self._param_stream.forward_loss(self._device_batch(batch))
        self._require_params("eval_batch")
        topo_mod.set_topology(self.topology)
        if getattr(self, "_jit_eval", None) is None:
            self._jit_eval = jax.jit(self.model.loss)
        self._validate_batch(batch)
        batch = self._device_batch(batch)
        with self._first_call("eval", batch), self.mesh:
            return self._jit_eval(self.state["params"], batch)

    # ------------------------------------------------------------------
    # introspection (reference engine getters)
    # ------------------------------------------------------------------
    def get_lr(self):
        return [self.lr_scheduler.get_lr()]

    def get_global_grad_norm(self) -> float:
        return float(getattr(self, "_last_grad_norm", 0.0))

    def loss_scale(self) -> float:
        return float(self.state["loss_scale"]["cur_scale"])

    def zero_optimization_stage(self) -> int:
        return self.config.zero_config.stage

    def get_model_parallel_world_size(self) -> int:
        return self.topology.model_parallel_size

    def get_data_parallel_world_size(self) -> int:
        return self.topology.data_parallel_size

    def module_state_dict(self):
        """Gathered (replicated) params as a host pytree — reference
        ``_zero3_consolidated_16bit_state_dict`` (engine.py:3477)."""
        if self._param_stream is not None:
            return self._param_stream.params_host_tree()
        self._require_params("module_state_dict")
        with self.mesh:
            gathered = jax.jit(
                lambda p: p,
                out_shardings=jax.tree.map(lambda _: NamedSharding(self.mesh, P()),
                                           self.state["params"]))(self.state["params"])
        return jax.device_get(gathered)

    # ------------------------------------------------------------------
    # ZeRO-Infinity parameter offload (reference
    # partitioned_param_swapper.py:36 + parameter_offload.py:201): page the
    # bf16 param shards out of HBM between phases (train <-> generate in the
    # hybrid engine, checkpoint export, serving restarts) and back. Under
    # jit every param must be device-resident DURING a step, so paging
    # happens at phase boundaries — the TPU-native shape of fetch/release.
    # ------------------------------------------------------------------
    def _require_params(self, op: str) -> None:
        if self._pcache is not None:
            raise RuntimeError(
                f"params are paged out (offload_param_cache); call "
                f"reload_param_cache() before {op}")

    def _get_param_swapper(self):
        if self._param_swapper is None:
            from .swap_tensor.partitioned_param_swapper import \
                AsyncPartitionedParameterSwapper
            cfg = self._param_offload_cfg
            swap_dir = cfg.nvme_path or os.path.join(
                tempfile.gettempdir(), f"dstpu_param_swap_{os.getpid()}")
            self._param_swapper = AsyncPartitionedParameterSwapper(
                os.path.join(swap_dir, f"rank{jax.process_index()}"))
        return self._param_swapper

    def device_state_bytes(self) -> int:
        """Actual device-resident bytes of the training state on THIS host
        (sums every addressable shard, so replication is counted)."""
        total = 0
        for leaf in jax.tree.leaves(self.state):
            if isinstance(leaf, jax.Array) and hasattr(leaf, "addressable_shards"):
                total += sum(s.data.nbytes for s in leaf.addressable_shards)
        return total

    def offload_param_cache(self) -> None:
        """Page every param shard to host/NVMe and FREE its HBM (reference
        ``swap_out_and_release``). ``reload_param_cache`` restores them."""
        if self._param_offload_device == "none":
            raise ValueError(
                "offload_param_cache requires zero_optimization.offload_param "
                "with device cpu|nvme (got none)")
        if self._pcache is not None:
            return  # already paged out
        leaves, treedef = jax.tree_util.tree_flatten(self.state["params"])
        nvme = self._param_offload_device == "nvme"
        swapper = self._get_param_swapper() if nvme else None
        meta = []
        for idx, leaf in enumerate(leaves):
            pieces = []
            groups = {}
            for s in leaf.addressable_shards:
                key = tuple((sl.start or 0) for sl in s.index) \
                    if s.index else ()
                groups.setdefault(key, []).append(s)
            for key in sorted(groups):
                shards = groups[key]
                name = f"p{idx}__" + "_".join(map(str, key))
                host = np.asarray(jax.device_get(shards[0].data))
                if nvme:
                    swapper.swap_out(name, host)  # async; fenced below
                else:
                    self._param_host_store[name] = host
                pieces.append((name, [s.device for s in shards]))
            meta.append({"shape": leaf.shape, "dtype": leaf.dtype,
                         "sharding": leaf.sharding, "pieces": pieces})
        if nvme:
            swapper.synchronize_writes()
        for leaf in leaves:
            leaf.delete()  # the actual HBM release
        self._pcache = {"treedef": treedef, "meta": meta}
        self.state["params"] = None
        # old programs captured donated buffers — both step entry points
        self._drop_jits("micro_step", "train_step")

    def reload_param_cache(self) -> None:
        """Rebuild the device-sharded param tree from the paged shards."""
        if self._pcache is None:
            return
        nvme = self._param_offload_device == "nvme"
        swapper = self._param_swapper
        if nvme:
            # prefetch everything. Pipelined (ISSUE 15) the swapper lands
            # the bulk read in byte-bounded GROUPS on its worker queue, so
            # each get() below blocks only on its own group and the H2D
            # device_put dispatch of group k overlaps group k+1's disk
            # reads; serial mode keeps the single-queue prefetch (the
            # first get drains it whole — one handle, one wait).
            swapper.swap_in([n for m in self._pcache["meta"]
                             for n, _ in m["pieces"]], async_op=True)
        leaves = []
        for m in self._pcache["meta"]:
            arrs = []
            for name, devices in m["pieces"]:
                host = swapper.get(name) if nvme \
                    else self._param_host_store[name]
                arrs.extend(jax.device_put(host, d) for d in devices)
            leaves.append(jax.make_array_from_single_device_arrays(
                m["shape"], m["sharding"], arrs))
        self.state["params"] = jax.tree_util.tree_unflatten(
            self._pcache["treedef"], leaves)
        if nvme:
            # fence the H2D transfers BEFORE pooling: device_put may alias
            # or still be streaming the host buffer after returning, and a
            # pooled buffer would be overwritten by the next same-size
            # swap_in's async_pread mid-transfer (ADVICE r4). Once every
            # leaf is ready no consumer of the host memory remains, so the
            # buffers can re-enter the free list (donate=True) and the
            # steady-state page-out/page-in cycle allocates no new host
            # memory (reference SwapBufferManager reuse).
            jax.block_until_ready(self.state["params"])
        for m in self._pcache["meta"]:
            for name, _ in m["pieces"]:
                if nvme:
                    swapper.release(name, donate=True)
                else:
                    self._param_host_store.pop(name, None)
        self._pcache = None

    # ------------------------------------------------------------------
    # checkpointing (reference engine.py:3050 save / :2688 load)
    # ------------------------------------------------------------------
    def _paged_ckpt_path(self, dirname: str) -> str:
        return os.path.join(dirname,
                            f"param_stream.rank{jax.process_index()}.npz")

    def _save_checkpoint_paged(self, save_dir, tag, client_state,
                               save_latest) -> None:
        from .. import comm as dist
        d = os.path.join(save_dir, tag)
        os.makedirs(d, exist_ok=True)
        sd = self._param_stream.state_dict()
        # atomic per-rank file (with the store's retry/fault seams);
        # 'latest' flips only after EVERY rank's file is complete
        # (barrier), so a crash mid-save never strands 'latest' on a tag
        # with truncated shards
        from ..checkpoint.store import _atomic_json, _atomic_savez, \
            write_latest
        _atomic_savez(self._paged_ckpt_path(d), sd)
        if jax.process_index() == 0:
            _atomic_json(os.path.join(d, "client_state.json"), client_state)
        dist.barrier()
        if save_latest and jax.process_index() == 0:
            write_latest(save_dir, tag)
        log_dist(f"saved param-stream checkpoint {d}", ranks=[0])

    def _load_checkpoint_paged(self, load_dir, tag, load_optimizer_states):
        import json
        if tag is None:
            latest = os.path.join(load_dir, "latest")
            if not os.path.exists(latest):
                return None, {}
            with open(latest) as f:
                tag = f.read().strip()
        d = os.path.join(load_dir, tag)
        sd = dict(np.load(self._paged_ckpt_path(d)))
        if not load_optimizer_states:
            import re
            # weights derive from the masters regardless; moments reset
            for k in list(sd):
                if re.match(r"^[gb]_m\d+/", k):
                    sd[k] = np.zeros_like(sd[k])
        self._param_stream.load_state_dict(sd)
        with open(os.path.join(d, "client_state.json")) as f:
            client_state = json.load(f)
        self.global_steps = int(client_state.get("global_steps", 0))
        self.skipped_steps = int(client_state.get("skipped_steps", 0))
        self.micro_steps = int(client_state.get("micro_steps", 0))
        if "lr_scheduler" in client_state:
            self.lr_scheduler.load_state_dict(client_state["lr_scheduler"])
        return tag, client_state

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict[str, Any]] = None,
                        save_latest: bool = True) -> None:
        if self._param_stream is None:
            self._require_params("save_checkpoint")
        from ..checkpoint.store import save_checkpoint as _save
        tag = tag or f"global_step{self.global_steps}"
        self._last_save_dir = save_dir   # watchdog escalation target
        client_state = dict(client_state or {})
        client_state.update({
            "global_steps": self.global_steps,
            "skipped_steps": self.skipped_steps,
            "micro_steps": self.micro_steps,
            "lr_scheduler": self.lr_scheduler.state_dict(),
        })
        if self._param_stream is not None:
            with self.telemetry.checkpoint_span("save_checkpoint", tag=tag):
                self._save_checkpoint_paged(save_dir, tag, client_state,
                                            save_latest)
            return
        if self.quantizer is not None:
            client_state["moq_quantizer"] = self.quantizer.state_dict()
        if self._ckpt_async:
            # Write-behind (the Nebula slot, checkpoint_engine.py): the
            # synchronous part is ONLY the host staging — the next step may
            # donate these device buffers. IO runs on the engine's worker;
            # `latest` repoints LAST in the same task, so a reader never
            # sees the tag before its data+meta are durable (the commit
            # fence). load_checkpoint commits pending saves first.
            from ..checkpoint.store import stage_state, write_latest, \
                write_staged
            # a still-in-flight previous save would interleave file writes
            self.checkpoint_engine.commit(tag)
            with self.telemetry.checkpoint_span("checkpoint_stage", tag=tag):
                keys, host = stage_state(self.state)
                sidecar = (self._offload_sidecar_arrays()
                           if self._offload is not None else None)

            # guardian pin decision AND its inputs are captured
            # SYNCHRONOUSLY: the clean window, the step number and the
            # stat snapshot all describe the state being staged right
            # now — the worker thread must neither read a counter the
            # training thread has advanced nor iterate deques the next
            # observe() is appending to
            pin_clean = (save_latest and self._guardian is not None
                         and self._guardian.pin_ready())
            pin_step = self.global_steps
            pin_stats = (self._guardian.stats_snapshot()
                         if pin_clean else None)

            def _write():
                # sidecar FIRST: meta.json (inside write_staged) is the
                # commit record — a tag whose meta verifies must have
                # every file a load needs, or the corrupt-`latest`
                # fallback could select a half-written tag. Its crc32
                # rides the commit record (extra_checksums) so the
                # CRC-verified-load contract covers the offload master
                # state, not just the device tree.
                extra = (self._write_offload_sidecar(save_dir, tag, sidecar)
                         if sidecar is not None else None)
                write_staged(save_dir, tag, keys, host, client_state,
                             save_latest=False, extra_checksums=extra)
                if save_latest:
                    write_latest(save_dir, tag)
                if pin_clean:
                    self._pin_known_good(save_dir, tag, step=pin_step,
                                         stats=pin_stats)
                self._retire_old_checkpoints(save_dir, tag)

            self.checkpoint_engine.submit(tag, _write)
            log_dist(f"staged checkpoint {save_dir}/{tag} "
                     "(async write-behind)", ranks=[0])
            return
        with self.telemetry.checkpoint_span("save_checkpoint", tag=tag):
            # offload sidecar FIRST: meta.json (inside _save) is the
            # commit record and `latest` repoints after it — a crash at
            # any instruction leaves either an uncommitted tag or a
            # complete one, never a committed tag missing its sidecar
            # (the corrupt-`latest` fallback trusts committed tags)
            extra = (self._write_offload_sidecar(
                         save_dir, tag, self._offload_sidecar_arrays())
                     if self._offload is not None else None)
            _save(save_dir, tag, self.state, client_state,
                  save_latest=save_latest, extra_checksums=extra)
            if jax.process_index() == 0:
                if save_latest and self._guardian is not None and \
                        self._guardian.pin_ready():
                    self._pin_known_good(save_dir, tag)
                self._retire_old_checkpoints(save_dir, tag)
        log_dist(f"saved checkpoint {save_dir}/{tag}", ranks=[0])

    def _write_offload_sidecar(self, save_dir: str, tag: str,
                               arrays) -> Optional[Dict[str, int]]:
        """Write this process's offload sidecar atomically and return the
        checksums to fold into the commit record — ONE definition for the
        sync and async-staged save paths, so their durability contracts
        cannot drift. Multi-host: every rank drops a ``.crc`` sidecar
        next to its file and rank 0 folds them post-barrier (the
        ``state.rank*.npz`` precedent in checkpoint/store.py), so
        ``verify_tag`` covers every rank's master state, not just this
        host's."""
        from ..checkpoint.store import _atomic_savez, _atomic_text
        tag_dir = os.path.join(save_dir, tag)
        os.makedirs(tag_dir, exist_ok=True)
        spath = self._offload_ckpt_path(tag_dir)
        crc = _atomic_savez(spath, arrays)
        if jax.process_count() == 1:
            return {os.path.basename(spath): crc}
        from .. import comm as dist
        _atomic_text(spath + ".crc", str(crc))
        dist.barrier()  # every rank's sidecar + crc before the commit
        if jax.process_index() != 0:
            return None
        extra = {}
        for p in range(jax.process_count()):
            fn = f"offload_optimizer.rank{p}.npz"
            cp = os.path.join(tag_dir, fn + ".crc")
            with open(cp) as f:
                extra[fn] = int(f.read().strip())
            os.remove(cp)
        return extra

    def _pin_known_good(self, save_dir: str, tag: str, step=None,
                        stats=None) -> None:
        """Commit ``tag`` as the guardian's rollback target — only
        reached after a verified-clean window (``pin_ready``), so a tag
        written mid-anomaly-streak can never become the target
        ``keep_last_n`` retention must preserve. The async-save worker
        passes ``step``/``stats`` captured at staging time; the sync
        path reads them live (same thread)."""
        from ..checkpoint.store import pin_known_good
        pin_known_good(save_dir, tag)
        self._guardian.bind_ledger_dir(save_dir)
        self._guardian.note_pinned(
            tag, self.global_steps if step is None else step, stats=stats)

    def _retire_old_checkpoints(self, save_dir: str, tag: str) -> None:
        """keep-last-N retention (checkpoint: {keep_last_n: N}); 0 (the
        default) keeps everything. Runs after the commit point, never
        removes what `latest` names NOR the tag just written (a
        save_latest=False milestone snapshot is not `latest` but must
        survive its own save), and never fails a save."""
        keep = int(self.config.checkpoint_config.get("keep_last_n", 0))
        if keep > 0:
            from ..checkpoint.store import retire_old_tags
            retire_old_tags(save_dir, keep, protect=(tag,))

    def _escalate_stall(self, step: int, elapsed: float) -> None:
        """Watchdog escalation (telemetry.watchdog.escalate_after_s): a
        step past the HARD deadline is declared dead — checkpoint what
        the host still holds (the last completed step's state; best
        effort, a truly wedged device cannot be drained) and exit with
        STALL_EXIT_CODE so the elastic agent's restart loop takes over.
        Runs on the watchdog thread: graceful degradation instead of a
        hung world burning its allocation."""
        target = self.config.checkpoint_config.get("escalation_dir") \
            or self._last_save_dir
        logger.error(
            f"watchdog escalation: step {step} stalled {elapsed:.1f}s past "
            f"the hard deadline; "
            + (f"checkpointing to {target} and exiting"
               if target else "no checkpoint dir known (no prior "
               "save_checkpoint and no checkpoint.escalation_dir); exiting")
            + f" with code {STALL_EXIT_CODE}")
        if target is not None:
            # the save itself can hang on the very runtime being escalated
            # (device_get / multi-host barrier against a wedged peer) — a
            # hang is not an Exception, so bound it with a daemon worker
            # and a hard timeout: the EXIT is the guarantee, the
            # checkpoint is best-effort
            import threading

            def _try_save():
                try:
                    self.save_checkpoint(
                        target, tag=f"escalation_step{self.global_steps}")
                    self.checkpoint_engine.commit("")  # async: fence
                except Exception as e:  # noqa: BLE001 - must still exit
                    logger.error(f"watchdog escalation: checkpoint failed "
                                 f"({e}); exiting anyway")

            budget = float(self.config.checkpoint_config.get(
                "escalation_save_timeout_s", 120.0))
            # the saver's exclusion is protocol-level, invisible to the
            # lint's lock analysis: it only runs once the watchdog has
            # declared the main thread wedged past the hard deadline, and
            # the process exits immediately after — best-effort by design
            saver = threading.Thread(  # dstpu: ignore[unguarded-shared-mutation]
                target=_try_save, daemon=True,
                name="dstpu-escalation-save")
            saver.start()
            saver.join(timeout=budget)
            if saver.is_alive():
                logger.error(
                    f"watchdog escalation: checkpoint did not finish in "
                    f"{budget:.0f}s (checkpoint.escalation_save_timeout_s) "
                    "— runtime is wedged; exiting without it")
        try:
            self.telemetry.close()  # flush spans/metrics for the autopsy
        except Exception:  # noqa: BLE001
            pass
        self._escalation_exit(STALL_EXIT_CODE)

    # ------------------------------------------------------------------
    # numerics guardian plumbing (resilience/guardian.py, ISSUE 13)
    # ------------------------------------------------------------------
    def _inject_numerics_fault(self, e) -> None:
        """Mutator for the ``numerics`` fault seam (grad_bitflip /
        loss_spike events): corrupt ONE param leaf host-side before the
        step dispatch — exactly what a flipped bit in HBM weights looks
        like to the sentinels. Deterministic in the event's
        (leaf_match | leaf, index, bit | factor): ``leaf_match`` is a
        glob over the flattened param path (``wte*`` reaches the logits
        un-normalized — a flip inside a pre-LN block is absorbed by the
        next LayerNorm, the textbook SILENT corruption only the replay
        probe would see); ``leaf == -1`` selects the largest leaf (or,
        for loss_spike, scales the whole tree)."""
        import fnmatch
        with_paths = jax.tree_util.tree_flatten_with_path(
            self.state["params"])[0]
        keys = ["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                         for p in path) for path, _ in with_paths]
        leaves, treedef = jax.tree_util.tree_flatten(self.state["params"])
        if not leaves:
            logger.warning("numerics fault: no param leaves to corrupt")
            return
        matched = None
        if e.leaf_match:
            hits = [j for j, k in enumerate(keys)
                    if fnmatch.fnmatch(k, e.leaf_match)]
            if not hits:
                logger.warning(f"numerics fault: no param leaf matches "
                               f"{e.leaf_match!r}; falling back to leaf "
                               f"selection by index")
            else:
                matched = hits[0]
        if matched is None and e.kind == "loss_spike" and e.leaf == -1:
            # the divergence case: EVERY weight scaled — pre-LN blocks
            # normalize a single scaled leaf away, but a whole-tree scale
            # blows the logits (and the gradients) up finitely, which is
            # exactly the loss-spike signature the sentinels watch
            def scale(x):
                a = np.array(jax.device_get(x))
                a = (a.astype(np.float32) * np.float32(e.factor)).astype(
                    a.dtype)
                return jax.device_put(a, x.sharding)
            leaves = [scale(x) for x in leaves]
            i = "ALL"
        else:
            if matched is not None:
                i = matched
            elif e.leaf == -1:
                i = max(range(len(leaves)), key=lambda j: leaves[j].size)
            else:
                i = e.leaf % len(leaves)
            src = leaves[i]
            arr = np.array(jax.device_get(src))  # writable host copy
            if e.kind == "grad_bitflip":
                flat = arr.reshape(-1)
                iview = flat.view({2: np.int16, 4: np.int32,
                                   8: np.int64}[arr.dtype.itemsize])
                bit = min(int(e.bit), 8 * arr.dtype.itemsize - 2)
                idx = int(e.index) % flat.size
                iview[idx] ^= iview.dtype.type(1) << bit
            else:  # loss_spike on one explicit leaf
                arr = (arr.astype(np.float32) * np.float32(e.factor)).astype(
                    arr.dtype)
            leaves[i] = jax.device_put(arr, src.sharding)
        self.state["params"] = jax.tree_util.tree_unflatten(treedef, leaves)
        if self._explicit_micro:
            # the ZeRO++ secondary caches (a resharding of) the params —
            # the corruption must be visible to the very next micro step
            self._refresh_secondary()
        name = keys[i] if isinstance(i, int) else i
        logger.error(f"numerics fault injected: {e.kind} on param leaf "
                     f"{name} (step {self.global_steps + 1})")

    def _stage_replay_inputs(self, batch, lr, thresh):
        """SDC replay probe, stage half: when a probe is due this step,
        pull a host copy of the full pre-step state (the step donates its
        device buffers, so the copy must exist BEFORE the dispatch).
        Returns ``None`` on non-probe steps — the common case costs one
        modulo."""
        g = self._guardian
        interval = g.config.replay_probe_interval if g is not None else 0
        if not interval or (self.global_steps + 1) % interval:
            return None
        host_state = jax.tree.map(lambda x: np.array(jax.device_get(x)),
                                  self.state)
        return (host_state, batch, lr, thresh)

    def _run_replay_probe(self, probe_in, outputs):
        """SDC replay probe, compare half: re-run the SAME compiled step
        on the staged inputs and compare the (loss, gnorm, anomaly-word)
        outputs BITWISE. XLA is deterministic on fixed inputs, so any
        drift means the hardware corrupted data somewhere between the two
        executions — reported as ANOMALY_SDC_REPLAY on the step's word
        (escalating through the normal policy ladder) instead of
        silently poisoning the run. Costs one extra step per probe
        interval, by design."""
        from ..resilience.guardian import ANOMALY_SDC_REPLAY
        host_state, batch, lr, thresh = probe_in
        shardings = self._cached_shardings
        replay_state = jax.tree.map(
            lambda h, s: jax.device_put(h, s), host_state, shardings)
        _, r_loss, _, r_gnorm, r_word = self._jit_train_step(
            replay_state, batch, lr, thresh)[:5]
        loss, gnorm, word = outputs
        mismatch = (
            np.asarray(r_loss).tobytes() != np.asarray(loss).tobytes()
            or np.asarray(r_gnorm).tobytes() != np.asarray(gnorm).tobytes()
            or int(r_word) != int(word))
        if mismatch:
            logger.error(
                f"guardian replay probe MISMATCH at step "
                f"{self.global_steps + 1}: loss {float(loss)!r} vs replay "
                f"{float(r_loss)!r}, gnorm {float(gnorm)!r} vs "
                f"{float(r_gnorm)!r} — silent data corruption")
            return jnp.asarray(int(word) | ANOMALY_SDC_REPLAY, jnp.int32)
        return word

    def _guardian_rollback(self, verdict) -> None:
        """Escalation rung 3: roll the run back to the last-known-good
        checkpoint. Under an elastic agent this RIDES the PR 12 restart
        path — repoint ``latest`` at the pinned tag, exit with
        GUARDIAN_EXIT_CODE, and the restarted attempt auto-resumes from
        the pin (rollback IS a resumed attempt; injected numerics faults
        are attempt-scoped, so the replay runs clean). Without an agent
        the engine reloads the pin in-process and continues — the
        training loop keyed on ``engine.global_steps`` replays the span
        naturally."""
        target = self.config.checkpoint_config.get("escalation_dir") \
            or self._last_save_dir
        if target is None:
            # nothing to roll back to: degrade LOUDLY but keep training —
            # killing a run over an anomaly it has no checkpoint for
            # would convert detection into destruction. The cooldown
            # stops the window from re-escalating every step.
            logger.error(
                f"guardian rollback requested at step {self.global_steps} "
                f"({', '.join(verdict.kinds) or 'anomaly window'}) but no "
                "checkpoint was ever saved and no "
                "checkpoint.escalation_dir is configured — continuing "
                "WITHOUT rollback; save checkpoints (or set "
                "checkpoint.escalation_dir) to arm recovery")
            self._guardian.reset_after_rollback(self.global_steps)
            return
        from ..checkpoint.store import rollback_to_known_good
        self._guardian.bind_ledger_dir(target)
        # repoint `latest` at the pin (no-op when nothing was pinned yet:
        # resume then loads plain `latest`, which still precedes the
        # anomalous step whenever the anomaly fired before its save)
        tag = rollback_to_known_good(target)
        self._guardian.note_rollback(self.global_steps, verdict, tag)
        logger.error(
            f"guardian ROLLBACK at step {self.global_steps} "
            f"({', '.join(verdict.kinds) or 'anomaly window'}): target "
            f"{target}/{tag or '<latest>'}")
        if parse_elastic_env():
            try:
                self.telemetry.close()
            except Exception:  # noqa: BLE001 - the exit is the guarantee
                pass
            self._escalation_exit(GUARDIAN_EXIT_CODE)
            return  # tests stub the exit; fall through like a restart
        loaded, _ = self.load_checkpoint(target, tag=tag)
        if loaded is None:
            raise RuntimeError(
                f"guardian rollback: no loadable checkpoint under {target}")
        self._guardian.reset_after_rollback(self.global_steps)
        log_dist(f"guardian rollback complete: resumed tag {loaded} at "
                 f"step {self.global_steps}", ranks=[0])

    def _offload_sidecar_arrays(self) -> Dict[str, Any]:
        """Host arrays of the offload optimizer sidecar file. Name-keyed
        flat layout: master/state are this host's local segments plus span
        metadata, so readers (zero_to_fp32) can slice params out by NAME
        instead of positional guessing."""
        sd = self._offload.state_dict()
        lay = self._offload_layout
        return dict(
            step=sd["step"],
            master_flat=np.concatenate(
                [m.reshape(-1) for m in sd["master"]]),
            state_flat=np.concatenate(
                [s.reshape(-1) for s in sd["state"]]),
            names=np.array(self._offload_names),
            sizes=np.array(lay["sizes"], np.int64),
            total=lay["total"],
            chunk_elems=self._offload_chunk_elems,
            # per-leaf 2-D flat form: dp dim first, model dim (if
            # any) major of the second (-1 = absent)
            shard_dims=np.array(
                [-1 if lay[0] is None else lay[0]
                 for lay in self._offload_layouts], np.int64),
            mp_dims=np.array(
                [-1 if lay[2] is None else lay[2]
                 for lay in self._offload_layouts], np.int64),
            span_leaf=np.array(
                [i for i, _, _, _ in self._offload_spans], np.int64),
            span_starts=np.array(
                [k for _, k, _, _ in self._offload_spans], np.int64),
            span_lens=np.array(
                [int(np.prod(sh))
                 for _, _, sh, _ in self._offload_spans], np.int64),
            span_shapes=np.array(
                [sh for _, _, sh, _ in self._offload_spans],
                np.int64))

    def save_16bit_model(self, save_dir: str, save_filename: str = "pytorch_model.npz") -> None:
        """Gathered bit16 weights for deployment (reference
        ``save_16bit_model``/``_zero3_consolidated_16bit_state_dict``,
        engine.py:3546,3477)."""
        sd = self.module_state_dict()
        flat = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(sd)[0]:
            key = ".".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            flat[key] = np.asarray(leaf)
        os.makedirs(save_dir, exist_ok=True)
        np.savez(os.path.join(save_dir, save_filename), **flat)
        log_dist(f"saved 16-bit model to {save_dir}/{save_filename}", ranks=[0])

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True) -> Tuple[Optional[str], Dict[str, Any]]:
        # an in-flight async save must land before `latest` is read —
        # the load side of the write-behind commit fence
        self.checkpoint_engine.commit(tag or "")
        if self._param_stream is not None:
            return self._load_checkpoint_paged(load_dir, tag,
                                               load_optimizer_states)
        self._require_params("load_checkpoint")
        from ..checkpoint.store import load_checkpoint as _load
        shardings = self._state_shardings()
        with self.telemetry.checkpoint_span("load_checkpoint"), self.mesh:
            state, client_state, tag = _load(load_dir, tag, self.state, shardings,
                                             load_optimizer_states=load_optimizer_states)
        if state is None:
            return None, {}
        self.state = state
        # ZeRO++: the secondary partition caches (a resharding of) the
        # params — a stale cache would train against pre-checkpoint weights
        self._refresh_secondary()
        if self._offload is not None and load_optimizer_states:
            path = self._offload_ckpt_path(os.path.join(load_dir, tag or ""))
            if not os.path.exists(path):
                raise ValueError(
                    f"offload optimizer state not found at {path} — the "
                    "checkpoint was saved without offload or on a different "
                    "host count (files are per-process); pass "
                    "load_optimizer_states=False to load weights only")
            z = np.load(path)
            if "master_flat" not in z:
                raise ValueError(
                    f"{path} is in the legacy per-leaf offload format "
                    "(master_{i} keys); load weights only with "
                    "load_optimizer_states=False, or extract fp32 weights "
                    "with the version that wrote it")
            saved_chunk = int(z["chunk_elems"]) if "chunk_elems" in z else None
            if saved_chunk is None:
                raise ValueError(
                    "offload checkpoint records no chunk_elems — the m/v "
                    "state layout is chunked and cannot be parsed; re-save "
                    "with a current version")
            starts = np.asarray(z["span_starts"])
            if starts.ndim == 1:
                # legacy 1-D flat layout (pure-dp): element offset ->
                # (row, 0) on the 2-D flat whose row width is the leaf's
                # trailing extent
                conv = []
                for leaf, st, ln in zip(z["span_leaf"], starts,
                                        z["span_lens"]):
                    cols = self._offload_flat_shapes[int(leaf)][1]
                    conv.append((int(leaf), (int(st) // max(cols, 1), 0),
                                 (int(ln) // max(cols, 1), cols)))
                saved = conv
            else:
                saved = [(int(l), tuple(int(x) for x in st),
                          tuple(int(x) for x in sh))
                         for l, st, sh in zip(z["span_leaf"], starts,
                                              z["span_shapes"])]
            cur = [(i, tuple(k), tuple(sh))
                   for i, k, sh, _ in self._offload_spans]
            if saved != cur:
                raise ValueError(
                    "offload checkpoint was saved on a different "
                    f"host/device layout (spans {saved[:3]}... vs "
                    f"{cur[:3]}...); per-host segments must match")
            master, state = z["master_flat"], z["state_flat"]
            slots = self._offload._slots
            if saved_chunk != self._offload_chunk_elems:
                # RE-CHUNK a tag written at a different chunk size (e.g. a
                # pre-reduce_bucket_size-binding checkpoint, or the knob
                # changed): state_flat is per-SAVED-chunk [m|v] blocks, so
                # rebuild the full per-slot vectors and re-split at the
                # current boundaries — the master itself is one flat concat
                # either way
                log_dist(
                    f"offload checkpoint chunk size {saved_chunk} != "
                    f"current {self._offload_chunk_elems}; re-chunking the "
                    "m/v state (docs/OFFLOAD.md)", ranks=[0])
                full = [np.empty(master.size, state.dtype)
                        for _ in range(slots)]
                off = 0
                for a in range(0, max(master.size, 1), saved_chunk):
                    ln = min(saved_chunk, master.size - a)
                    for s in range(slots):
                        full[s][a:a + ln] = state[off:off + ln]
                        off += ln
                masters = self._chunked(np.asarray(master))
                states, a = [], 0
                for m in masters:
                    states.append(np.concatenate(
                        [full[s][a:a + m.size] for s in range(slots)]))
                    a += m.size
            else:
                masters = self._chunked(master)
                states, off = [], 0
                for m in masters:
                    states.append(state[off:off + m.size * slots])
                    off += m.size * slots
            self._offload.load_state_dict({
                "step": int(z["step"]), "master": masters, "state": states,
            })
        self.global_steps = client_state.get("global_steps", 0)
        self.skipped_steps = client_state.get("skipped_steps", 0)
        self.micro_steps = client_state.get("micro_steps", 0)
        if "lr_scheduler" in client_state:
            self.lr_scheduler.load_state_dict(client_state["lr_scheduler"])
        if self.quantizer is not None and "moq_quantizer" in client_state:
            self.quantizer.load_state_dict(client_state["moq_quantizer"])
        return tag, client_state
