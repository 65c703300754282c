"""Engine-v2 configuration (reference ``inference/v2/config_v2.py`` and
``inference/v2/ragged/manager_configs.py``)."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp


@dataclasses.dataclass
class DeepSpeedTPStateManagerConfig:
    """Ragged state-manager knobs (reference ``manager_configs.py:145,151``)."""
    max_tracked_sequences: int = 2048
    max_ragged_batch_size: int = 768       # token budget per forward
    max_ragged_sequence_count: int = 512   # sequences per forward
    max_context: int = 8192                # longest trackable sequence
    memory_config_mode: str = "reserve"    # 'reserve' | 'allocate'
    memory_reserve_fraction: float = 0.85


@dataclasses.dataclass
class RaggedInferenceEngineConfig:
    """Top-level engine config (reference ``config_v2.py:19``)."""
    tensor_parallel_degree: int = 1
    state_manager: DeepSpeedTPStateManagerConfig = dataclasses.field(
        default_factory=DeepSpeedTPStateManagerConfig)
    kv_block_size: int = 16                # tokens per KV block (page)
    num_kv_blocks: Optional[int] = None    # None => derived from max_context budget
    kv_cache_dtype: Any = jnp.bfloat16
    max_prefill_chunk: int = 256           # SplitFuse prefill chunk cap
    quantization_mode: Optional[str] = None
    # Page-pool placement across the mesh (ISSUE 6: the pool stops being
    # replicated). "auto": a pool whose size the engine DERIVES is sharded
    # over the data axis whenever tp == 1 and the data axis has > 1 device
    # (each rank owns num_blocks/dp pages + its own null block; sequences
    # are pinned to one shard, waves dispatch through shard_map with zero
    # collectives); an explicitly-sized pool keeps the legacy layout so
    # existing configs do not silently change dispatch. "data" forces the
    # sharded layout (raises if the shape cannot shard); "replicated"
    # forces the legacy layout.
    kv_pool_sharding: str = "auto"
    # Atom tile of the ragged wave program: every scheduled sequence-chunk
    # is split into atoms of <= ragged_block_q query tokens (8 = the fp32
    # MXU sublane minimum, so a decode atom costs the same tile as the old
    # per-sequence decode kernel).
    ragged_block_q: int = 8
    # Wave dispatch: "wave" = the unified ragged-wave program (ONE atom
    # class, any composition per launch); "legacy" = the previous
    # two-class (decode rows + prefill grid) dispatch, kept as the A/B
    # denominator and escape hatch (DSTPU_WAVE=legacy overrides).
    wave_dispatch: str = "wave"
    # decode-only engine steps fuse up to this many tokens per sequence in
    # one compiled program (on-device sampling between steps); 1 disables.
    # The scheduler falls back to single-token SplitFuse steps whenever
    # prefill work is pending, so TTFT is unaffected. Amortizes the
    # per-dispatch host overhead while bounding how long a newly-arrived
    # prompt waits behind a running burst; 32 is not measured on the
    # current machine.
    decode_burst: int = 32
