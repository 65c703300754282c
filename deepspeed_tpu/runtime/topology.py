"""Device-mesh topology: the single source of truth for parallel dimensions.

TPU-native counterpart of the reference's process-group machinery
(``deepspeed/utils/groups.py:51`` ``initialize``; ``runtime/pipe/topology.py:244``
``PipeModelDataParallelTopology``). Instead of creating NCCL process groups per
parallel dimension, we build ONE ``jax.sharding.Mesh`` whose named axes *are*
the groups:

    ('pipe', 'data', 'mics', 'expert', 'seq', 'model')

- ``model``  : tensor parallelism (reference: mpu model-parallel group) —
  innermost so TP collectives ride nearest-neighbor ICI links.
- ``seq``    : Ulysses sequence parallelism (reference ``groups.py:452-491``).
- ``expert`` : expert parallelism (reference ``_create_expert_and_data_parallel``
  ``groups.py:113``). Non-expert parameters treat it as extra data parallelism.
- ``data``   : the outer data-parallel axis (expert-data-parallel in MoE terms).
- ``mics``   : MiCS sub-group axis (reference ``zero/mics.py:62``): size 1
  normally; with ``mics_shard_size`` ZeRO states shard over THIS axis only,
  so shards stay inside a sub-group (intra-ICI) and are replicated across
  ``data`` groups — the hierarchical-allgather layout of MiCS. Batches and
  gradient sync always span ``('data','mics')``.
- ``pipe``   : pipeline stages (reference ``PipelineParallelGrid``).

The *effective* data-parallel group of a non-expert parameter is the compound
axis tuple ``('data', 'expert', 'seq')`` — gradients are averaged over all
three, exactly like the reference divides ZeRO reductions by
``sequence_parallel_size`` (``stage_1_and_2.py:1038``) and treats expert ranks
as data-parallel for dense params. Expert parameters sync grads over
``('data', 'seq')`` only.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

PIPE_AXIS = "pipe"
DATA_AXIS = "data"
MICS_AXIS = "mics"
EXPERT_AXIS = "expert"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"

MESH_AXES: Tuple[str, ...] = (PIPE_AXIS, DATA_AXIS, MICS_AXIS, EXPERT_AXIS, SEQ_AXIS, MODEL_AXIS)

# Batch leading-dim sharding spans both data-parallel axes.
BATCH_AXES: Tuple[str, ...] = (DATA_AXIS, MICS_AXIS)

# Compound axes used for gradient sync / ZeRO partitioning.
DENSE_GRAD_AXES: Tuple[str, ...] = (DATA_AXIS, MICS_AXIS, EXPERT_AXIS, SEQ_AXIS)
EXPERT_GRAD_AXES: Tuple[str, ...] = (DATA_AXIS, MICS_AXIS, SEQ_AXIS)


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Parallel degrees. Any degree left at -1 is inferred so that the product
    covers all available devices (only ``data`` may be inferred)."""
    pipe: int = 1
    data: int = -1
    mics: int = 1
    expert: int = 1
    seq: int = 1
    model: int = 1

    def resolve(self, n_devices: int) -> "TopologyConfig":
        known = self.pipe * self.mics * self.expert * self.seq * self.model
        data = self.data
        if data == -1:
            if n_devices % known != 0:
                raise ValueError(
                    f"Cannot infer data-parallel degree: {n_devices} devices not divisible "
                    f"by pipe*mics*expert*seq*model={known}")
            data = n_devices // known
        total = known * data
        if total != n_devices:
            raise ValueError(
                f"Topology {dataclasses.replace(self, data=data)} needs {total} devices, "
                f"but {n_devices} are available")
        return dataclasses.replace(self, data=data)


class MeshTopology:
    """Owns the jax Mesh and answers the group-membership questions the
    reference answers with ``_get_*_parallel_group()`` accessors."""

    def __init__(self, config: Optional[TopologyConfig] = None, devices: Optional[Sequence[jax.Device]] = None):
        devices = list(devices) if devices is not None else jax.devices()
        config = (config or TopologyConfig()).resolve(len(devices))
        self.config = config
        shape = (config.pipe, config.data, config.mics, config.expert, config.seq,
                 config.model)
        self._mesh = Mesh(self._device_grid(devices, shape), MESH_AXES)

    @staticmethod
    def _hybrid_dcn_shape(shape: Tuple[int, ...],
                          n_slices: int) -> Optional[Tuple[int, ...]]:
        """Which mesh axis absorbs the data-center network (multi-slice)
        dimension. Replica-style axes whose collectives are bandwidth-light
        per step — ``data``, then ``mics``, then ``pipe`` (stage boundary
        crossings are point-to-point) — may span DCN; ``model``/``seq``/
        ``expert`` collectives must stay on ICI (reference concern:
        topology-aware process-group placement, pipe/topology.py:244).
        Returns the dcn mesh shape, or None if no eligible axis divides."""
        if n_slices <= 1:
            return None
        dcn = [1] * len(shape)
        for axis in (DATA_AXIS, MICS_AXIS, PIPE_AXIS):
            i = MESH_AXES.index(axis)
            if shape[i] % n_slices == 0:
                dcn[i] = n_slices
                return tuple(dcn)
        return None

    @staticmethod
    def _device_grid(devices: Sequence[jax.Device], shape: Tuple[int, ...]) -> np.ndarray:
        if len(devices) > 1 and devices[0].platform == "tpu":
            # a TPU mesh follows the physical torus; a device set that
            # mesh_utils cannot lay out is an error, never a silent
            # enumeration-order mesh with ICI-hostile neighbours
            from jax.experimental import mesh_utils
            n_slices = len({getattr(d, "slice_index", 0) for d in devices})
            if n_slices > 1:
                # multi-slice (v5p pods over DCN): data-like axes ride DCN,
                # model/seq/expert stay inside each slice's ICI torus
                dcn = MeshTopology._hybrid_dcn_shape(shape, n_slices)
                if dcn is not None:
                    ici = tuple(s // d for s, d in zip(shape, dcn))
                    return mesh_utils.create_hybrid_device_mesh(
                        ici, dcn, devices=devices)
            return mesh_utils.create_device_mesh(shape, devices=devices)
        return np.asarray(devices).reshape(shape)

    # -- mesh access ---------------------------------------------------------
    @property
    def mesh(self) -> Mesh:
        return self._mesh

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self._mesh, PartitionSpec(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self._mesh, PartitionSpec())

    # -- degrees (reference: groups.get_*_parallel_world_size) --------------
    @property
    def world_size(self) -> int:
        return int(np.prod(self._mesh.devices.shape))

    def axis_size(self, axis) -> int:
        if isinstance(axis, (tuple, list)):
            return int(np.prod([self.axis_size(a) for a in axis]))
        return self._mesh.shape[axis]

    @property
    def data_parallel_size(self) -> int:
        """Full data-parallel degree for dense parameters."""
        return self.axis_size(DENSE_GRAD_AXES)

    @property
    def mics_shard_size(self) -> int:
        return self.axis_size(MICS_AXIS)

    @property
    def expert_parallel_size(self) -> int:
        return self.axis_size(EXPERT_AXIS)

    @property
    def expert_data_parallel_size(self) -> int:
        return self.axis_size(EXPERT_GRAD_AXES)

    @property
    def model_parallel_size(self) -> int:
        return self.axis_size(MODEL_AXIS)

    @property
    def pipe_parallel_size(self) -> int:
        return self.axis_size(PIPE_AXIS)

    @property
    def sequence_parallel_size(self) -> int:
        return self.axis_size(SEQ_AXIS)

    def __repr__(self) -> str:
        c = self.config
        return (f"MeshTopology(pipe={c.pipe}, data={c.data}, mics={c.mics}, "
                f"expert={c.expert}, seq={c.seq}, model={c.model})")


_TOPOLOGY: Optional[MeshTopology] = None


def initialize(config: Optional[TopologyConfig] = None, devices: Optional[Sequence[jax.Device]] = None, force: bool = False) -> MeshTopology:
    """Create (or return) the process-global topology.

    Counterpart of ``deepspeed.utils.groups.initialize`` (groups.py:51).
    """
    global _TOPOLOGY
    if _TOPOLOGY is None or force:
        _TOPOLOGY = MeshTopology(config, devices)
    return _TOPOLOGY


def set_topology(topology: MeshTopology) -> MeshTopology:
    """Publish ``topology`` as the process-global instance.

    The engine calls this for whatever topology it resolves (including one
    passed explicitly to ``deepspeed_tpu.initialize``) so that code without an
    engine handle — e.g. ``ulysses_attention`` inside the traced model —
    observes the same mesh through ``get_topology()``.
    """
    global _TOPOLOGY
    _TOPOLOGY = topology
    return topology


def get_topology() -> MeshTopology:
    if _TOPOLOGY is None:
        return initialize()
    return _TOPOLOGY


def is_initialized() -> bool:
    return _TOPOLOGY is not None


def reset() -> None:
    global _TOPOLOGY
    _TOPOLOGY = None
