"""ZeRO-Infinity parameter streaming: train models whose parameters exceed
device memory.

Counterpart of the reference's in-training parameter paging — the
``AsyncPartitionedParameterSwapper`` (reference
``runtime/swap_tensor/partitioned_param_swapper.py:36``) plus the NVMe/host
prefetch in ``partitioned_param_coordinator.py:503`` — whose flagship claim
is training 40B params on a single 32 GB device. The torch version hooks
module pre/post-forward to fetch/release each submodule's partitions. The
TPU-native shape of the same idea, given that a jit program needs its
operands resident:

- Parameters live on the HOST (numpy, wire dtype), one stacked array per
  block leaf plus the embedding/head ("globals") leaves.
- The train step is a Python-orchestrated pipeline of SMALL jit programs
  (one compile each, reused for every layer): embed → block×L → head
  (loss + top gradient) → reversed block backward × L → embed backward.
- Layer k+1's host→device fetch is issued before layer k's compute is
  dispatched, so the transfer rides under the matmuls (the coordinator's
  ``__prefetch_nvme_param_partitions``); block k's params are dropped as
  soon as its compute is dispatched, so at most ``buffer_count`` block
  buffers are ever resident.
- Backward recomputes each block from its saved input (layer-granular
  rematerialisation — the save/recompute structure the reference gets from
  activation checkpointing) and streams each block's gradients device→host
  on an IO thread while earlier layers are still computing.
- The optimizer is entirely host-resident (fp32 master + moments stepped
  by the C++ SIMD CPU optimizer, csrc/optimizers/cpu_optimizers.cpp). Host
  optimizer steps for unit k are scheduled as futures; the NEXT step's
  fetch of unit k waits on its future — so host optimizer compute overlaps
  the next step's forward instead of stalling the device (the reference's
  overlap pattern, stage_1_and_2.py:1005).

Steady-state device residency is O(buffer_count · block_bytes + globals +
activations), independent of depth — params+grads no longer need to fit
HBM, which is the whole point.

Supported envelope (loud rejections elsewhere): bf16/fp32 training,
dense blocks (no MoE), dp/tp/sp meshes. fp16 loss-scaling, pipeline and
expert parallelism compose with the resident-param engine paths instead.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ...ops.adam.cpu_adam import (DeepSpeedCPUAdagrad, DeepSpeedCPUAdam,
                                  DeepSpeedCPULion)
from ...telemetry import memory as step_memory
from ...utils.logging import log_dist
from ..activation_checkpointing import checkpointing as remat

GLOBALS_UNIT = 0  # unit index of the embedding/head leaves; blocks are 1..L


def _flatten_named(tree) -> Tuple[List[str], List[Any], Any]:
    """(names, leaves, treedef) with stable path-derived names."""
    leaves_paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path) for path, _ in leaves_paths]
    return names, [leaf for _, leaf in leaves_paths], treedef


class ParamStreamRunner:
    """Owns host parameter + optimizer state and the paged train step."""

    def __init__(self, model, mesh, *,
                 optimizer_cfg,            # engine config.optimizer (may be None)
                 param_dtype,              # device/wire dtype (bf16/fp32)
                 gradient_clipping: float = 0.0,
                 buffer_count: int = 2,
                 nvme_path: Optional[str] = None,
                 device: str = "cpu",
                 seed: int = 42,
                 init_params: Optional[Any] = None,
                 moment_dtype: str = "fp32",
                 grad_acc_dtype: str = "fp32"):
        c = model.config
        if c.moe is not None:
            raise ValueError("offload_param.paged_training does not support "
                             "MoE blocks (use the resident-param engine)")
        # device == "nvme": the bf16 param store lives on DISK as one blob
        # per unit, read ahead through the C++ AIO engine (reference
        # AsyncPartitionedParameterSwapper, partitioned_param_swapper.py:36)
        # and written back by the host optimizer step. Host RAM then holds
        # only master/moments/grad-acc.
        self.model = model
        self.mesh = mesh
        self.param_dtype = param_dtype
        self.gradient_clipping = float(gradient_clipping or 0.0)
        self.buffer_count = max(2, int(buffer_count))
        self.num_layers = int(c.num_layers)
        self.step_count = 0
        self.last_grad_norm = 0.0
        # instrumentation: the honest residency/overlap record
        self.peak_param_bytes = 0      # max device param bytes ever resident
        self._live_param_bytes = 0
        self.total_param_bytes = 0     # full host tree, for the ratio
        self.last_fetch_wait_s = 0.0   # device-side stall on host futures
        self.last_host_step_s = 0.0    # host optimizer wall (overlapped)
        self.last_nvme_wait_s = 0.0    # main-thread stall on NVMe futures
        self._lock = threading.Lock()

        # -- host parameter store (wire dtype) --------------------------
        params = init_params
        if params is None:
            cpu = jax.local_devices(backend="cpu")[0]
            with jax.default_device(cpu):
                params = self.model.init(jax.random.PRNGKey(seed), param_dtype)
        params = jax.tree.map(np.asarray, params)
        blocks = params.pop("blocks")
        self._np_dtype = np.dtype(param_dtype)
        self._gnames, gleaves, self._gtreedef = _flatten_named(params)
        self._bnames, bleaves, self._btreedef = _flatten_named(blocks)
        # np.array copies: device_get views are read-only, the store is
        # written in place by every host optimizer step
        self._gstore = [np.array(l, dtype=self._np_dtype) for l in gleaves]
        self._bstore = [np.array(l, dtype=self._np_dtype) for l in bleaves]
        for leaf in self._bstore:
            if leaf.shape[0] != self.num_layers:
                raise ValueError("paged_training expects stacked block "
                                 f"leaves [L, ...]; got {leaf.shape}")
        # release the init tree before allocating masters/moments: at 7B
        # dims the source leaves are 13.5 GB that would otherwise stay
        # referenced through __init__
        del gleaves, bleaves, params, blocks
        self.total_param_bytes = (
            sum(l.nbytes for l in self._gstore)
            + sum(l.nbytes for l in self._bstore))
        self._block_bytes = sum(l.nbytes // self.num_layers
                                for l in self._bstore)
        self._global_bytes = sum(l.nbytes for l in self._gstore)

        # -- host optimizer (fp32 master + moments, flat per leaf) ------
        opt_type = (optimizer_cfg.type if optimizer_cfg is not None
                    else "adamw").lower()
        opt_params = dict(optimizer_cfg.params) if optimizer_cfg is not None \
            else {}
        self.lr_default = float(opt_params.get("lr", 1e-3))
        if opt_type in ("adam", "adamw", "fusedadam", "fusedadamw",
                        "torchadam"):
            self._opt = DeepSpeedCPUAdam(
                lr=self.lr_default,
                betas=tuple(opt_params.get("betas", (0.9, 0.999))),
                eps=opt_params.get("eps", 1e-8),
                weight_decay=opt_params.get("weight_decay", 0.0),
                adamw_mode="w" in opt_type, _sanctioned=True)
            self._slots = 2
        elif opt_type in ("lion", "fusedlion"):
            self._opt = DeepSpeedCPULion(
                lr=self.lr_default,
                betas=tuple(opt_params.get("betas", (0.9, 0.99))),
                weight_decay=opt_params.get("weight_decay", 0.0),
                _sanctioned=True)
            self._slots = 1
        elif opt_type == "adagrad":
            self._opt = DeepSpeedCPUAdagrad(
                lr=self.lr_default, eps=opt_params.get("eps", 1e-8),
                weight_decay=opt_params.get("weight_decay", 0.0),
                _sanctioned=True)
            self._slots = 1
        else:
            raise ValueError(f"paged_training host optimizer supports "
                             f"adam/adamw/lion/adagrad, got '{opt_type}'")
        # masters: globals flat fp32 per leaf; blocks [L, size] so layer k's
        # slice steps independently. Moments/grad-accumulators can store
        # bf16 to halve host RAM (the knob that fits a 7B-dims host state
        # in 125 GB): moments use STOCHASTIC ROUNDING on the store (same
        # EMA-freeze argument as runtime/optimizers._sr_to_bf16 — with
        # beta2=0.999 the per-step v increment is below bf16 resolution),
        # grad accumulators round deterministically (wire is bf16 anyway;
        # exact at gas=1).
        if moment_dtype not in ("fp32", "bf16"):
            raise ValueError(f"moment_dtype must be fp32|bf16, got "
                             f"{moment_dtype!r}")
        if grad_acc_dtype not in ("fp32", "bf16"):
            raise ValueError(f"grad_acc_dtype must be fp32|bf16, got "
                             f"{grad_acc_dtype!r}")
        import ml_dtypes
        self._bf16 = np.dtype(ml_dtypes.bfloat16)
        self._mdt = np.float32 if moment_dtype == "fp32" else self._bf16
        self._gadt = np.float32 if grad_acc_dtype == "fp32" else self._bf16
        # SR noise generators are PER THREAD (numpy Generators are not
        # thread-safe; the optimizer pool runs 4 workers) — each worker
        # spawns an independent child stream off one SeedSequence
        self._sr_seed = np.random.SeedSequence(seed ^ 0x51AB)
        self._sr_local = threading.local()
        self._gmaster = [np.ascontiguousarray(l, np.float32).reshape(-1)
                         for l in self._gstore]
        self._bmaster = [np.ascontiguousarray(l, np.float32)
                         .reshape(self.num_layers, -1) for l in self._bstore]
        self._gm = [[np.zeros(m.shape, self._mdt) for m in self._gmaster]
                    for _ in range(self._slots)]
        self._bm = [[np.zeros(m.shape, self._mdt) for m in self._bmaster]
                    for _ in range(self._slots)]
        # gradient accumulators, zeroed after each applied step
        self._ggrad = [np.zeros(m.shape, self._gadt) for m in self._gmaster]
        self._bgrad = [np.zeros(m.shape, self._gadt) for m in self._bmaster]

        # -- shardings ---------------------------------------------------
        specs = self.model.specs()
        bspecs = specs.pop("blocks")
        # strip the stacked layer dim from block specs
        bspecs = jax.tree.map(lambda s: P(*s[1:]), bspecs,
                              is_leaf=lambda s: isinstance(s, P))
        ns = lambda s: NamedSharding(self.mesh, s)
        _, gspec_leaves, _ = _flatten_named(specs)
        _, bspec_leaves, _ = _flatten_named(bspecs)
        self._gshard = [ns(s) for s in gspec_leaves]
        self._bshard = [ns(s) for s in bspec_leaves]
        from ..topology import BATCH_AXES, SEQ_AXIS
        self._act_shard = ns(P(BATCH_AXES, SEQ_AXIS, None))

        # -- pipelines ---------------------------------------------------
        # one IO thread: serial device→host landings keep the fp32
        # accumulation race-free; host optimizer steps fan out over cores
        # (the C++ kernel releases the GIL / uses OpenMP internally)
        self._io = ThreadPoolExecutor(max_workers=1,
                                      thread_name_prefix="pstream-io")
        self._cpu = ThreadPoolExecutor(max_workers=4,
                                       thread_name_prefix="pstream-opt")
        self._unit_futs: Dict[int, Future] = {}
        self._land_futs: List[Future] = []
        self._jits: Dict[Any, Any] = {}

        # -- NVMe param store (reference partitioned_param_swapper.py:36):
        # block-unit params live on disk as one bf16 blob per layer, read
        # ahead through the C++ AIO engine; globals (embeddings/head —
        # needed at both ends of every step) stay in RAM.
        self._aio = None               # non-None IS the nvme-mode flag
        self._nvme_pending = None  # (unit_index, buffer) of in-flight read
        self._nvme_last = None
        # NVMe worker queue (ISSUE 15): in pipelined mode ONE worker
        # thread owns the AIO handle during steady state and every
        # read/write runs as a queued task, so `_nvme_take` /
        # `_flush_nvme_dirty` never block the device dispatch loop on an
        # `aio.wait()` — the main thread only ever waits on a FUTURE,
        # and only when the prefetch genuinely has not landed (the
        # honest `nvme_io` stall). DSTPU_OFFLOAD_PIPELINE=0 restores the
        # main-thread-fenced schedule bitwise.
        self._nvme_exec = None
        self._nvme_futs: Dict[int, Future] = {}
        self._nvme_flush_fut: Optional[Future] = None
        # write-behind cache: optimizer-pool threads STAGE updated blobs
        # here (the AIO handle is not thread-safe — wait()'s pin-drop
        # would free a buffer a pool thread just queued); ONLY the main
        # thread queues AIO ops, flushing at step start / fetch / fence
        self._nvme_dirty: Dict[int, np.ndarray] = {}
        if device == "nvme":
            import tempfile
            from ...ops.aio import AsyncIOHandle
            from .offload_optimizer import offload_pipeline_enabled
            if offload_pipeline_enabled():
                self._nvme_exec = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="pstream-nvme")
            base = nvme_path or tempfile.gettempdir()
            # per-instance subdir: two runners sharing an nvme_path must
            # not clobber each other's store (same convention as
            # offload_optimizer's opt_{id:x})
            self._nvme_dir = os.path.join(base, f"pstream_{id(self):x}")
            os.makedirs(self._nvme_dir, exist_ok=True)
            self._aio = AsyncIOHandle(num_threads=2)
            # blob layout: per-leaf (byte offset, nbytes, row shape);
            # identical for every layer (leaves are stacked [L, ...])
            self._blob_meta = []
            off = 0
            for leaf in self._bstore:
                row = leaf[0]
                self._blob_meta.append((off, row.nbytes, row.shape))
                off += row.nbytes
            assert off == self._block_bytes, (off, self._block_bytes)
            self._bstore = None  # disk is canonical for block params
            for k in range(self.num_layers):
                # masters == store at init, so _pack_unit is exact — ONE
                # definition of the blob layout
                self._aio.sync_pwrite(self._pack_unit(k),
                                      self._unit_path(k))

        log_dist(
            f"param-stream: {self.total_param_bytes / 1e9:.2f} GB params "
            f"{'NVMe' if device == 'nvme' else 'host'}-resident "
            f"({self.num_layers} blocks × "
            f"{self._block_bytes / 1e6:.1f} MB + "
            f"{self._global_bytes / 1e6:.1f} MB globals in RAM); "
            f"steady-state device residency ≈ 2 block buffers + globals",
            ranks=[0])

    def _unit_path(self, k: int) -> str:
        return os.path.join(self._nvme_dir, f"unit{k}.bin")

    def _pack_unit(self, k: int) -> np.ndarray:
        """One layer's bf16 blob assembled from the masters — the single
        definition of the blob layout (init write, step write-back, and
        checkpoint rewrite all call this)."""
        blob = np.empty(self._block_bytes, np.uint8)
        for (o, nb, shape), m in zip(self._blob_meta, self._bmaster):
            blob[o:o + nb] = (m[k].reshape(shape)
                              .astype(self._np_dtype).reshape(-1)
                              .view(np.uint8))
        return blob

    def _flush_nvme_dirty(self) -> None:
        """Queue the staged write-backs. Called at step start and at
        fence. Pipelined: the flush is a TASK on the NVMe worker queue —
        the main thread returns immediately instead of sitting on the
        AIO submit path — and any prefetch futures from the previous
        step are invalidated first (their units are about to be
        re-stepped, so a held read would serve one-step-old params).
        Serial (DSTPU_OFFLOAD_PIPELINE=0): main-thread submit, the
        pre-ISSUE-15 schedule."""
        if self._nvme_exec is not None:
            self._check_nvme_flush()
            for fut in self._nvme_futs.values():
                fut.cancel() or fut.result()  # drain; buffers are dropped
            self._nvme_futs.clear()
            self._nvme_flush_fut = self._nvme_exec.submit(
                self._flush_nvme_dirty_task)
            return
        self._flush_nvme_dirty_task()

    def _check_nvme_flush(self, wait: bool = False) -> None:
        """Surface a failed async write-back LOUDLY: the flush task pops
        blobs from the dirty cache before writing, so an exception inside
        it (ENOSPC, dead handle) would otherwise vanish in a dropped
        Future while training continues against one-step-old disk state —
        the serial path raised on the main thread, and so must this
        one."""
        fut = self._nvme_flush_fut
        if fut is not None and (wait or fut.done()):
            self._nvme_flush_fut = None
            fut.result()

    def _flush_nvme_dirty_task(self) -> None:
        """AIO-owner context (worker task in pipelined mode, main thread
        in serial mode): pop every staged blob and queue its write."""
        with self._lock:
            items = list(self._nvme_dirty.items())
            self._nvme_dirty.clear()
        for k, blob in items:
            self._aio.async_pwrite(blob, self._unit_path(k))

    def _nvme_read_task(self, k: int) -> np.ndarray:
        """Worker task: the blob for unit ``k``. A staged dirty blob
        serves from RAM (its disk write is queued here — two readers of
        the buffer are safe, same argument as the serial path);
        otherwise the handle's ``wait()`` fences every previously-queued
        write before the disk read, so a read can never race its own
        unit's write-back. Only the worker thread runs this, so the AIO
        handle has exactly one driver during steady state."""
        with self._lock:
            dirty = self._nvme_dirty.pop(k, None)
        if dirty is not None:
            self._aio.async_pwrite(dirty, self._unit_path(k))
            return dirty
        self._aio.wait()
        buf = np.empty(self._block_bytes, np.uint8)
        self._aio.async_pread(buf, self._unit_path(k))
        self._aio.wait()
        return buf

    def _nvme_take_pipelined(self, k: int) -> np.ndarray:
        """Pipelined `_nvme_take`: consume the prefetch future (blocking
        only if the read genuinely has not landed — the honest
        ``nvme_io`` stall, accumulated in ``last_nvme_wait_s``), then
        queue the next unit's read on the worker. The prefetch guard is
        the serial path's: only units whose host optimizer step is fully
        done may be read ahead (an in-flight step is about to stage a
        dirty blob; the read task's own dirty check closes the
        staged-after-submit window because the worker runs strictly
        after the flush task that would carry it)."""
        self._check_nvme_flush()
        L = self.num_layers
        d = -1 if (self._nvme_last is not None and k < self._nvme_last) else 1
        self._nvme_last = k
        fut = self._nvme_futs.pop(k, None)
        if fut is None:
            fut = self._nvme_exec.submit(self._nvme_read_task, k)
        t0 = time.perf_counter()
        buf = fut.result()
        self.last_nvme_wait_s += time.perf_counter() - t0
        nxt = k + d
        if 0 <= nxt < L and nxt != k and nxt not in self._nvme_futs:
            hostfut = self._unit_futs.get(1 + nxt)
            with self._lock:
                nxt_dirty = nxt in self._nvme_dirty
            if not nxt_dirty and (hostfut is None or hostfut.done()):
                self._nvme_futs[nxt] = self._nvme_exec.submit(
                    self._nvme_read_task, nxt)
        return buf

    def _nvme_take(self, k: int) -> np.ndarray:
        """Blob for layer k (MAIN THREAD ONLY): a staged dirty blob serves
        directly (its disk write is queued here, and reading from the
        buffer while AIO writes it out is two readers — safe); otherwise
        consume the in-flight prefetch or sync-read. Fresh buffers per
        fetch — the device_put may still be reading the previous one
        asynchronously. The aio.wait() fences every previously-queued
        write, so a read can never race its own unit's write-back."""
        if self._nvme_exec is not None:
            return self._nvme_take_pipelined(k)
        L = self.num_layers
        d = 1
        if self._nvme_last is not None and k < self._nvme_last:
            d = -1
        self._nvme_last = k
        with self._lock:
            dirty = self._nvme_dirty.pop(k, None)
        nxt = k + d
        with self._lock:
            nxt_dirty = nxt in self._nvme_dirty
        # prefetch only units whose host step is fully done AND whose
        # write-back (if any) was queued before the wait below — a unit
        # still dirty will be served from RAM anyway
        fut = self._unit_futs.get(1 + nxt)
        can_prefetch = (0 <= nxt < L and not nxt_dirty
                        and (fut is None or fut.done()))
        pend, self._nvme_pending = self._nvme_pending, None
        self._aio.wait()
        if dirty is not None:
            self._aio.async_pwrite(dirty, self._unit_path(k))
            buf = dirty
        elif pend is not None and pend[0] == k:
            buf = pend[1]
        else:
            buf = np.empty(self._block_bytes, np.uint8)
            self._aio.async_pread(buf, self._unit_path(k))
            self._aio.wait()
        if can_prefetch and nxt != k:
            nbuf = np.empty(self._block_bytes, np.uint8)
            self._aio.async_pread(nbuf, self._unit_path(nxt))
            self._nvme_pending = (nxt, nbuf)
        return buf

    # ------------------------------------------------------------------
    # device program cache (one compile per signature, reused every layer)
    # ------------------------------------------------------------------
    def _jit(self, key, build):
        if key not in self._jits:
            self._jits[key] = build()
        return self._jits[key]

    def _block_tree(self, leaves):
        return jax.tree_util.tree_unflatten(self._btreedef, leaves)

    def _global_tree(self, leaves):
        return jax.tree_util.tree_unflatten(self._gtreedef, leaves)

    def _positions(self, S):
        return jnp.arange(S)[None, :]

    def _embed_fwd(self, keys):
        def build():
            def f(gleaves, batch):
                gp = self._global_tree(gleaves)
                x, _ = self.model.embed(gp, batch["input_ids"],
                                        batch.get("token_type_ids"))
                return x
            return jax.jit(f, out_shardings=self._act_shard)
        return self._jit(("embed", keys), build)

    def _block_fwd(self, window: bool):
        def build():
            def f(bleaves, x, w):
                blk = self._block_tree(bleaves)
                pos = self._positions(x.shape[1])
                y, _ = self.model.block_apply(blk, x, pos, window=w)
                return y

            def f_nw(bleaves, x):
                blk = self._block_tree(bleaves)
                pos = self._positions(x.shape[1])
                y, _ = self.model.block_apply(blk, x, pos)
                return y
            return jax.jit(f if window else f_nw,
                           out_shardings=self._act_shard)
        return self._jit(("bfwd", window), build)

    def _block_bwd(self, window: bool):
        def build():
            wire = self.param_dtype

            def core(bleaves, x, dy, w):
                blk = self._block_tree(bleaves)
                pos = self._positions(x.shape[1])
                if w is None:
                    fn = lambda b, xx: self.model.block_apply(b, xx, pos)[0]
                else:
                    fn = lambda b, xx: self.model.block_apply(
                        b, xx, pos, window=w)[0]
                _, vjp = jax.vjp(fn, blk, x)
                db, dx = vjp(dy)
                # norms are NOT computed here: with gas > 1 the clip norm
                # must be of the ACCUMULATED gradient, which only exists on
                # the host — see train_step's fence
                return dx, [g.astype(wire) for g in jax.tree.leaves(db)]

            shard = (self._act_shard, list(self._bshard))
            if window:
                f = lambda bl, x, dy, w: core(bl, x, dy, w)
            else:
                f = lambda bl, x, dy: core(bl, x, dy, None)
            return jax.jit(f, out_shardings=shard)
        return self._jit(("bbwd", window), build)

    @functools.cached_property
    def _head_room_bytes(self) -> Optional[int]:
        """What the fullest device has free when the head is first traced:
        the room its float32 logits and their gradient are measured against
        (``transformer.head_row_slices``). None where the backend reports no
        memory (the CPU): the head whole."""
        fullest = step_memory.device_memory(self.mesh.devices.flat)
        return (None if fullest is None
                else fullest["bytes_limit"] - fullest["bytes_in_use"])

    def _head_fwd_bwd(self, keys):
        def build():
            from ...models.transformer import head_slices
            wire = self.param_dtype

            def f(gleaves, x, batch, inv_gas):
                ids = batch["input_ids"]
                labels = batch.get("labels")
                if labels is None:
                    labels = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)),
                                     constant_values=-100)
                slices = head_slices(self.model.config,
                                     remat.Budget(self._head_room_bytes), ids)

                def loss_fn(gl, xx):
                    return self.model.head_loss(
                        self._global_tree(gl), xx, labels,
                        extra_mask=batch.get("loss_mask"), slices=slices)
                loss, vjp = jax.vjp(loss_fn, gleaves, x)
                # 1/gas cotangent: micro gradients accumulate to the MEAN
                # over micro-batches, matching the resident engine's
                # loss * (scale/gas) convention (engine.py micro step)
                dgl, dx = vjp(inv_gas.astype(jnp.float32))
                return loss, dx, [g.astype(jnp.float32) for g in dgl]
            shard = (None, self._act_shard,
                     [NamedSharding(self.mesh, s.spec) for s in self._gshard])
            return jax.jit(f, out_shardings=shard)
        return self._jit(("head", keys), build)

    def _head_loss_only(self, keys):
        """Forward-only head + loss (eval path — no VJP, no grad buffers)."""
        def build():
            from ...models.transformer import masked_cross_entropy

            def f(gleaves, x, batch):
                ids = batch["input_ids"]
                labels = batch.get("labels")
                if labels is None:
                    labels = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)),
                                     constant_values=-100)
                logits = self.model.head(self._global_tree(gleaves), x)
                return masked_cross_entropy(logits, labels,
                                            extra_mask=batch.get("loss_mask"))
            return jax.jit(f)
        return self._jit(("headfwd", keys), build)

    def _embed_bwd(self, keys):
        def build():
            def f(gleaves, batch, dx):
                def fn(gl):
                    x, _ = self.model.embed(self._global_tree(gl),
                                            batch["input_ids"],
                                            batch.get("token_type_ids"))
                    return x
                _, vjp = jax.vjp(fn, gleaves)
                (dgl,) = vjp(dx)
                return [g.astype(jnp.float32) for g in dgl]
            return jax.jit(f)
        return self._jit(("embbwd", keys), build)

    def _acc_globals(self):
        def build():
            return jax.jit(lambda a, b: [x + y for x, y in zip(a, b)])
        return self._jit(("gacc",), build)

    # ------------------------------------------------------------------
    # fetch / residency accounting
    # ------------------------------------------------------------------
    def _track(self, delta: int):
        with self._lock:
            self._live_param_bytes += delta
            if self._live_param_bytes > self.peak_param_bytes:
                self.peak_param_bytes = self._live_param_bytes

    def _wait_unit(self, unit: int):
        fut = self._unit_futs.pop(unit, None)
        if fut is not None:
            t0 = time.perf_counter()
            fut.result()
            self.last_fetch_wait_s += time.perf_counter() - t0

    def _fetch_globals(self):
        self._wait_unit(GLOBALS_UNIT)
        leaves = [jax.device_put(h, s)
                  for h, s in zip(self._gstore, self._gshard)]
        self._track(self._global_bytes)
        return leaves

    def _fetch_block(self, k: int):
        """Device copy of layer k's params; waits for a pending host
        optimizer step of that layer first (the pipeline interlock)."""
        self._wait_unit(1 + k)
        if self._aio is not None:
            blob = self._nvme_take(k)
            leaves = [jax.device_put(
                blob[o:o + nb].view(self._np_dtype).reshape(shape), s)
                for (o, nb, shape), s in zip(self._blob_meta, self._bshard)]
        else:
            leaves = [jax.device_put(h[k], s)
                      for h, s in zip(self._bstore, self._bshard)]
        self._track(self._block_bytes)
        return leaves

    def _release(self, bytes_: int):
        self._track(-bytes_)

    # ------------------------------------------------------------------
    # gradient landing (IO thread)
    # ------------------------------------------------------------------
    @staticmethod
    def _acc_into(acc: np.ndarray, g32: np.ndarray) -> None:
        """acc += g32 across storage dtypes (bf16 acc upcasts, adds,
        rounds back — exact at gas=1 since the wire is bf16 anyway)."""
        if acc.dtype == np.float32:
            acc += g32
        else:
            acc[...] = (acc.astype(np.float32) + g32).astype(acc.dtype)

    def _land_block_grads(self, k: int, db_leaves):
        host = jax.device_get(db_leaves)
        for acc, g in zip(self._bgrad, host):
            self._acc_into(acc[k], np.asarray(g, np.float32).reshape(-1))

    def _land_global_grads(self, dg_leaves):
        host = jax.device_get(dg_leaves)
        for acc, g in zip(self._ggrad, host):
            self._acc_into(acc, np.asarray(g, np.float32).reshape(-1))

    def _accumulated_sqnorm(self) -> float:
        """||accumulated grad||² over every unit — computed on the HOST
        after all landings so the clip norm is of the actual applied
        gradient, not a sum of per-micro norms (those differ under
        gas > 1). Row-wise so a bf16 accumulator upcasts one layer at a
        time, never the whole stack."""
        sq = 0.0
        for acc in self._ggrad:
            a = acc.astype(np.float32) if acc.dtype != np.float32 else acc
            sq += float(a @ a)
        for acc in self._bgrad:
            for row in acc:
                r = (row.astype(np.float32) if row.dtype != np.float32
                     else row)
                sq += float(r @ r)
        return sq

    # ------------------------------------------------------------------
    # host optimizer step (cpu pool; futures gate next step's fetches)
    # ------------------------------------------------------------------
    def _sr_gen(self) -> np.random.Generator:
        g = getattr(self._sr_local, "gen", None)
        if g is None:
            with self._lock:
                child = self._sr_seed.spawn(1)[0]
            g = np.random.default_rng(child)
            self._sr_local.gen = g
        return g

    def _np_sr_bf16(self, x32: np.ndarray) -> np.ndarray:
        """Stochastically round fp32 → bf16 on the host (numpy twin of
        runtime/optimizers._sr_to_bf16): add uniform low bits, truncate."""
        bits = np.ascontiguousarray(x32, np.float32).view(np.uint32)
        noise = self._sr_gen().integers(0, 1 << 16, size=bits.shape,
                                        dtype=np.uint32)
        return ((bits + noise) >> 16).astype(np.uint16).view(self._bf16)

    def _host_step_unit(self, unit: int, mult: float, lr: float, step: int):
        if unit == GLOBALS_UNIT:
            for parts in zip(self._gmaster, self._ggrad, self._gstore,
                             *self._gm):
                master, grad, store = parts[0], parts[1], parts[2]
                self._step_one(master, grad, parts[3:], mult, lr, step)
                store[...] = master.reshape(store.shape).astype(store.dtype)
            return
        k = unit - 1
        if self._aio is not None:
            for i, (master, grad) in enumerate(
                    zip(self._bmaster, self._bgrad)):
                slots = [self._bm[s][i][k] for s in range(self._slots)]
                self._step_one(master[k], grad[k], slots, mult, lr, step)
            # STAGE the write-back — this runs on a pool thread and the
            # AIO handle is main-thread-only (wait()'s pin-drop would
            # free a concurrently-queued buffer mid-write)
            blob = self._pack_unit(k)
            with self._lock:
                self._nvme_dirty[k] = blob
            return
        for i, (master, grad, store) in enumerate(
                zip(self._bmaster, self._bgrad, self._bstore)):
            slots = [self._bm[s][i][k] for s in range(self._slots)]
            self._step_one(master[k], grad[k], slots, mult, lr, step)
            store[k] = master[k].reshape(store.shape[1:]).astype(store.dtype)

    def _step_one(self, master, grad, slots, mult, lr, step):
        """One leaf/row update across storage dtypes: bf16 grad/moments
        widen to fp32 scratch for the C++ kernel; moments SR back."""
        g32 = (grad if grad.dtype == np.float32
               else grad.astype(np.float32))
        if mult != 1.0:
            np.multiply(g32, np.float32(mult), out=g32)
        narrow = slots and slots[0].dtype != np.float32
        s32 = ([np.ascontiguousarray(s, np.float32) for s in slots]
               if narrow else list(slots))
        if self._slots == 2:
            self._opt.step(master, g32, s32[0], s32[1], step=step, lr=lr)
        elif self._slots == 1:
            self._opt.step(master, g32, s32[0], lr=lr)
        else:
            self._opt.step(master, g32, lr=lr)
        if narrow:
            for dst, src in zip(slots, s32):
                dst[...] = self._np_sr_bf16(src)
        grad[...] = 0

    # ------------------------------------------------------------------
    # the paged train step
    # ------------------------------------------------------------------
    def train_step(self, device_batches: List[Dict[str, Any]],
                   lr: Optional[float] = None) -> jax.Array:
        """gas micro fwd+bwd passes + host optimizer apply. Host optimizer
        futures are left pending — the NEXT step's fetch of each unit waits
        on its future, so host compute overlaps the next forward."""
        lr = self.lr_default if lr is None else float(lr)
        L = self.num_layers
        self.last_fetch_wait_s = 0.0
        self.last_nvme_wait_s = 0.0
        windows = getattr(self.model, "_windows", None)
        wkey = windows is not None
        if self._aio is not None:
            self._flush_nvme_dirty()  # queue last step's staged write-backs

        losses = []
        dg_acc = None
        inv_gas = jnp.asarray(1.0 / len(device_batches), jnp.float32)
        with self.mesh:
            gleaves = self._fetch_globals()
            for batch in device_batches:
                keys = tuple(sorted(batch.keys()))
                x = self._embed_fwd(keys)(gleaves, batch)
                xs: List[Any] = []
                cur = self._fetch_block(0)
                fwd = self._block_fwd(wkey)
                for k in range(L):
                    xs.append(x)
                    nxt = self._fetch_block(k + 1) if k + 1 < L else None
                    if wkey:
                        x = fwd(cur, x, jnp.asarray(windows[k], jnp.int32))
                    else:
                        x = fwd(cur, x)
                    cur = nxt
                    self._release(self._block_bytes)
                loss, dx, dgl = self._head_fwd_bwd(keys)(gleaves, x, batch,
                                                         inv_gas)
                losses.append(loss)
                dg_acc = dgl if dg_acc is None \
                    else self._acc_globals()(dg_acc, dgl)
                cur = self._fetch_block(L - 1)
                bwd = self._block_bwd(wkey)
                for k in range(L - 1, -1, -1):
                    nxt = self._fetch_block(k - 1) if k > 0 else None
                    if wkey:
                        dx, db = bwd(cur, xs[k], dx,
                                     jnp.asarray(windows[k], jnp.int32))
                    else:
                        dx, db = bwd(cur, xs[k], dx)
                    xs[k] = None  # free the activation
                    self._land_futs.append(
                        self._io.submit(self._land_block_grads, k, db))
                    cur = nxt
                    self._release(self._block_bytes)
                dge = self._embed_bwd(keys)(gleaves, batch, dx)
                dg_acc = self._acc_globals()(dg_acc, dge)
            self._land_futs.append(
                self._io.submit(self._land_global_grads, dg_acc))
            self._release(self._global_bytes)

        # fence all gradient landings, then resolve clip multiplier on the
        # ACCUMULATED (mean-over-micros) gradient
        for fut in self._land_futs:
            fut.result()
        self._land_futs.clear()
        gnorm = float(np.sqrt(self._accumulated_sqnorm()))
        self.last_grad_norm = gnorm
        mult = 1.0
        if self.gradient_clipping > 0 and gnorm > self.gradient_clipping:
            mult = self.gradient_clipping / (gnorm + 1e-6)

        # schedule host steps; do NOT wait — next step's fetches will
        self.step_count += 1
        t0 = time.perf_counter()
        for unit in range(L + 1):
            self._unit_futs[unit] = self._cpu.submit(
                self._host_step_unit, unit, mult, lr, self.step_count)
        self.last_host_step_s = time.perf_counter() - t0  # dispatch only
        return jnp.mean(jnp.stack(losses))

    def forward_loss(self, batch: Dict[str, Any]) -> jax.Array:
        """Paged forward only (eval)."""
        L = self.num_layers
        windows = getattr(self.model, "_windows", None)
        wkey = windows is not None
        if self._aio is not None:
            self._flush_nvme_dirty()
        keys = tuple(sorted(batch.keys()))
        with self.mesh:
            gleaves = self._fetch_globals()
            x = self._embed_fwd(keys)(gleaves, batch)
            cur = self._fetch_block(0)
            fwd = self._block_fwd(wkey)
            for k in range(L):
                nxt = self._fetch_block(k + 1) if k + 1 < L else None
                if wkey:
                    x = fwd(cur, x, jnp.asarray(windows[k], jnp.int32))
                else:
                    x = fwd(cur, x)
                cur = nxt
                self._release(self._block_bytes)
            loss = self._head_loss_only(keys)(gleaves, x, batch)
            self._release(self._global_bytes)
        return loss

    # ------------------------------------------------------------------
    # state access / checkpointing
    # ------------------------------------------------------------------
    def fence(self):
        """Complete every pending host optimizer step (and land the NVMe
        write-backs they staged)."""
        for unit in list(self._unit_futs):
            self._wait_unit(unit)
        if self._aio is not None:
            self._flush_nvme_dirty()
            if self._nvme_exec is not None:
                # the flush ran as a worker task; the wait must too — the
                # worker owns the handle, and FIFO ordering makes this a
                # full drain of everything queued before it. A failed
                # flush re-raises HERE, not silently in its Future.
                self._check_nvme_flush(wait=True)
                self._nvme_exec.submit(self._aio.wait).result()
            else:
                self._aio.wait()

    def params_host_tree(self):
        """Full parameter tree (host numpy, wire dtype) — state_dict/save.
        Blocks rebuild from the fp32 masters (the store is bf16(master) by
        construction), so the NVMe mode needs no disk round-trip."""
        self.fence()
        tree = jax.tree_util.tree_unflatten(self._gtreedef, list(self._gstore))
        if self._aio is not None:
            bl = [m.reshape((self.num_layers,) + shape).astype(self._np_dtype)
                  for m, (_, _, shape) in zip(self._bmaster, self._blob_meta)]
        else:
            bl = list(self._bstore)
        tree["blocks"] = jax.tree_util.tree_unflatten(self._btreedef, bl)
        return tree

    def _rewrite_nvme_store(self) -> None:
        """Regenerate every unit blob from the masters (checkpoint load).
        Prefetched reads are invalidated first — they hold pre-load
        params."""
        with self._lock:
            self._nvme_dirty.clear()
        self._nvme_pending = None

        def rewrite():
            for k in range(self.num_layers):
                self._aio.async_pwrite(self._pack_unit(k),
                                       self._unit_path(k))
            self._aio.wait()

        if self._nvme_exec is not None:
            for fut in self._nvme_futs.values():
                fut.cancel() or fut.result()
            self._nvme_futs.clear()
            self._nvme_exec.submit(rewrite).result()
        else:
            rewrite()

    def _save_arr(self, a: np.ndarray) -> np.ndarray:
        # npz has no bf16: persist the raw 2-byte payload as uint16 (same
        # convention as the quant cache, engine_v2.py)
        return a.view(np.uint16) if a.dtype == self._bf16 else a

    def _load_into(self, dst: np.ndarray, src) -> None:
        src = np.asarray(src)
        if src.dtype == np.uint16:
            # uint16 is ALWAYS a persisted-bf16 payload — reinterpret
            # before any numeric cast (a bf16-state checkpoint loaded
            # into an fp32-state runner must not astype raw bit patterns)
            src = src.view(self._bf16)
        if src.dtype != dst.dtype:
            dst[...] = src.astype(dst.dtype)
        else:
            dst[...] = src

    def state_dict(self) -> Dict[str, Any]:
        self.fence()
        out: Dict[str, Any] = {"step": self.step_count}
        for i, name in enumerate(self._gnames):
            out[f"g_master/{name}"] = self._gmaster[i]
            for s in range(self._slots):
                out[f"g_m{s}/{name}"] = self._save_arr(self._gm[s][i])
        for i, name in enumerate(self._bnames):
            out[f"b_master/{name}"] = self._bmaster[i]
            for s in range(self._slots):
                out[f"b_m{s}/{name}"] = self._save_arr(self._bm[s][i])
        return out

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        self.fence()
        self.step_count = int(sd["step"])
        for i, name in enumerate(self._gnames):
            self._gmaster[i][...] = sd[f"g_master/{name}"]
            for s in range(self._slots):
                self._load_into(self._gm[s][i], sd[f"g_m{s}/{name}"])
            self._gstore[i][...] = self._gmaster[i].reshape(
                self._gstore[i].shape).astype(self._gstore[i].dtype)
        for i, name in enumerate(self._bnames):
            self._bmaster[i][...] = sd[f"b_master/{name}"]
            for s in range(self._slots):
                self._load_into(self._bm[s][i], sd[f"b_m{s}/{name}"])
            if self._aio is None:
                self._bstore[i][...] = self._bmaster[i].reshape(
                    self._bstore[i].shape).astype(self._bstore[i].dtype)
        if self._aio is not None:
            self._rewrite_nvme_store()

    def close(self):
        self.fence()
        self._io.shutdown(wait=True)
        self._cpu.shutdown(wait=True)
        if self._nvme_exec is not None:
            self._nvme_exec.shutdown(wait=True)
        if self._aio is not None:
            self._aio.wait()
            self._aio.close()
            # the store is derivable from the masters — don't leak a
            # model-sized blob directory per run
            import shutil
            shutil.rmtree(self._nvme_dir, ignore_errors=True)
