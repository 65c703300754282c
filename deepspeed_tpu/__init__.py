"""DeepSpeed-TPU: a TPU-native training & inference framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of DeepSpeed
(reference ``deepspeed/__init__.py``): ZeRO-style memory partitioning,
tensor/sequence/expert/pipeline parallelism, mixed precision with loss
scaling, checkpointing, monitoring and profiling, and ragged-batch inference
— expressed as sharding specs over a ``jax.sharding.Mesh`` instead of NCCL
process groups and CUDA kernels.

Front door (reference ``deepspeed/__init__.py:64``):

    engine, optimizer, dataloader, lr_scheduler = deepspeed_tpu.initialize(
        model=model, config=config_dict)
"""

from __future__ import annotations

import time as _time

_IMPORT_T0 = _time.perf_counter()   # set-up's first phase: this import

import json  # noqa: E402
import os  # noqa: E402
from typing import Any, Dict, Optional, Tuple  # noqa: E402

__version__ = "0.1.0"

from . import comm  # noqa: F401
from .accelerator import get_accelerator  # noqa: F401
from .runtime.config import DeepSpeedConfig, DeepSpeedConfigError  # noqa: F401
from .runtime.engine import DeepSpeedEngine  # noqa: F401
from .runtime.topology import MeshTopology, TopologyConfig  # noqa: F401
from .comm.comm import init_distributed  # noqa: F401
from .utils.compile_cache import enable_compile_cache  # noqa: F401
from .telemetry import setup_spans as _setup_spans

# ``engine.setup_totals["import_s"]``: this package's import less whatever
# the process had imported before it (docs/OBSERVABILITY.md)
_setup_spans.import_s = _time.perf_counter() - _IMPORT_T0


def maybe_apply_tuned_config(config: Optional[Any]) -> Optional[Any]:
    """The ``DSTPU_TUNE`` overlay (docs/AUTOTUNING.md): when the env var
    is ``1``, deep-merge the pinned tune winner's config overrides
    (``tools/autotune/best.json``, written by ``dstpu tune --apply``)
    over the caller's config dict; any other non-empty, non-``0`` value
    is read as an explicit path to a ``best.json`` or trial ledger.

    Unset or ``0`` returns ``config`` UNCHANGED — the very same object,
    so opted-out engine construction is byte-identical to a build that
    never heard of the autotuner."""
    gate = os.environ.get("DSTPU_TUNE", "")
    if gate in ("", "0"):
        return config
    from .autotuning.cli import default_best_path
    path = default_best_path() if gate == "1" else gate
    try:
        with open(path, "r", encoding="utf-8") as fh:
            doc = json.load(fh)
    except (OSError, ValueError) as e:
        from .utils.logging import logger
        logger.warning(f"DSTPU_TUNE={gate}: no usable tuned config at "
                       f"{path} ({e}) — building untuned")
        return config
    best = doc.get("best") if "best" in doc else doc
    overrides = ((best or {}).get("overrides") or {}).get("config") or {}
    if not overrides or not isinstance(config, dict):
        return config
    from .runtime.config import deep_update
    from .utils.logging import log_dist
    merged = deep_update(json.loads(json.dumps(config)), overrides)
    log_dist(f"DSTPU_TUNE: overlaid tuned config "
             f"{(best or {}).get('label')} from {path}", ranks=[0])
    return merged


def _initialize_engine(config, model, topology, seed, model_parameters):
    """``initialize``'s engine: the compile cache, the distributed start,
    the engine class the configuration asks for and an elastic resume."""
    enable_compile_cache()
    init_distributed()

    # engine selection (reference deepspeed/__init__.py:156-193: hybrid_engine
    # config -> DeepSpeedHybridEngine, else DeepSpeedEngine)
    engine_cls = DeepSpeedEngine
    if isinstance(config, dict) and config.get("hybrid_engine", {}).get("enabled"):
        from .runtime.hybrid_engine import DeepSpeedHybridEngine
        engine_cls = DeepSpeedHybridEngine

    engine = engine_cls(
        model=model,
        config_dict=config if isinstance(config, dict) else None,
        config=config if isinstance(config, DeepSpeedConfig) else None,
        topology=topology,
        seed=seed,
        init_params=model_parameters,
    )

    # elastic resume (dstpu-resilience, docs/RESILIENCE.md): a world
    # (re)started by DSElasticAgent(checkpoint_dir=...) carries the
    # checkpoint dir in DSTPU_ELASTIC — resume from the last committed
    # tag so a restart (possibly at a different dp width; the store
    # re-buckets shards on load) continues instead of re-initializing.
    # No committed tag yet → fresh start; a corrupt `latest` falls back
    # to the newest verified tag inside load_checkpoint.
    from .resilience import parse_elastic_env
    _ckpt_dir = parse_elastic_env().get("checkpoint_dir")
    if _ckpt_dir:
        tag, _ = engine.load_checkpoint(_ckpt_dir)
        from .utils.logging import log_dist
        log_dist(
            "elastic resume: "
            + (f"resumed tag {tag} at step {engine.global_steps}" if tag
               else "no committed checkpoint yet — fresh start")
            + f" (dir {_ckpt_dir})", ranks=[0])
    return engine


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               distributed_port: int = 29500,
               topology: Optional[MeshTopology] = None,
               dist_init_required: Optional[bool] = None,
               collate_fn=None,
               config: Optional[Any] = None,
               config_params: Optional[Dict[str, Any]] = None,
               seed: int = 42):
    """Build a ready-to-train engine (reference ``deepspeed.initialize``,
    ``deepspeed/__init__.py:64``).

    ``model`` is a module object exposing ``init(rng, dtype) -> params``,
    ``specs() -> PartitionSpec tree``, ``loss(params, batch) -> scalar``
    (e.g. ``deepspeed_tpu.models.TransformerLM``). Returns the same 4-tuple
    as the reference: (engine, optimizer_descriptor, dataloader, lr_scheduler).
    """
    assert model is not None, "deepspeed_tpu.initialize: model is required"
    config = config if config is not None else config_params
    if isinstance(config, str):  # JSON path (reference-supported form)
        with open(config) as f:
            config = json.load(f)
    # DSTPU_TUNE overlay: off (unset/"0") this returns `config` itself —
    # engine construction stays byte-identical to an autotuner-free build
    config = maybe_apply_tuned_config(config)

    # the whole call as one span; the engine built inside takes the span's
    # record as its ``setup_totals`` (telemetry/setup_spans.py)
    with _setup_spans.initializing():
        engine = _initialize_engine(config, model, topology, seed,
                                    model_parameters)

    dataloader = None
    if training_data is not None:
        from .runtime.dataloader import DeepSpeedDataLoader
        dp = engine.topology.data_parallel_size
        dataloader = DeepSpeedDataLoader(
            training_data,
            batch_size=engine.train_micro_batch_size_per_gpu * dp,
            collate_fn=collate_fn)

    return engine, engine.optimizer, dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, model_path: Optional[str] = None, **kwargs):
    """Reference ``deepspeed.init_inference`` (``deepspeed/__init__.py:269``).

    ``model_path`` loads a real HF checkpoint directory (safetensors or
    torch-bin, gpt2/llama/mistral/mixtral) and places the weights sharded
    per the model's TP specs — the reference's checkpoint-loading path
    (``inference/engine.py:254`` + ``module_inject/load_checkpoint.py``).
    """
    from .inference.engine import InferenceEngine
    enable_compile_cache()
    if model_path is not None:
        if model is not None:
            raise ValueError("init_inference: pass either model or model_path, "
                             "not both (which weights would win is ambiguous)")
        if "params" in kwargs:
            raise ValueError("init_inference: params cannot be combined with "
                             "model_path (the checkpoint provides the params)")
        from .inference.engine import InferenceConfig
        from .runtime.state_dict_factory import load_hf_model
        icfg = config if isinstance(config, InferenceConfig) else InferenceConfig(config, **kwargs)
        model, kwargs["params"] = load_hf_model(model_path, dtype=icfg.dtype)
    return InferenceEngine(model=model, config=config, **kwargs)
