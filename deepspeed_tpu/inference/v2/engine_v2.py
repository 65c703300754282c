"""Continuous-batching inference engine.

Counterpart of the reference ``InferenceEngineV2``
(``inference/v2/engine_v2.py:30``): ``put`` schedules new tokens for a set of
UIDs and returns next-token logits, ``query``/``can_schedule`` expose KV
budget for the scheduler, ``flush`` retires sequences.

TPU-first structure: ``put`` dispatches ONE compiled program
(:meth:`RaggedInferenceModel.ragged_forward`) per engine step, mixing two
atom classes — single-token decode rows (paged Pallas attention, never
padded to chunk length) and prefill chunk rows (batched chunk attention) —
with projections/MLP fused over the concatenated token stream and the KV
cache donated. Shapes are bucketed so a serving loop reuses a handful of
compiled programs. This is the XLA expression of Dynamic SplitFuse
(reference atom_builder + flash_attn_by_atoms, ragged_ops.cpp:20-47); the
scheduler (scheduler.py) mixes prompt chunks and generation inside one
token budget per engine step.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ... import comm as dist
from ...models.transformer import TransformerLM
from ...runtime.topology import (DATA_AXIS, MODEL_AXIS, MeshTopology,
                                 TopologyConfig)
from ...telemetry import get_telemetry, setup_spans
from ...telemetry.trace import NULL_SPAN, PHASE_SERVING
from ...utils.compile_cache import enable_compile_cache
from ...utils.logging import log_dist
from .config_v2 import RaggedInferenceEngineConfig
from .model import RaggedInferenceModel
from .ragged.kv_cache import BlockedKVCache
from .ragged.ragged_manager import DSStateManager
from .ragged.ragged_wrapper import _next_bucket
from .ragged.wave import (COUNTER_KEYS, WaveEntry, build_sharded_wave,
                          burst_counters, wave_counters, wave_key)


def _put_chunk_bytes() -> int:
    """Per-transfer byte cap for weight/KV uploads: leaves above it (llama2-7b's
    stacked down_proj is 2.9 GiB dense bf16) upload in slabs. Whether a
    directly attached chip needs the cap is not measured."""
    return int(os.environ.get("DSTPU_PUT_CHUNK_BYTES", 1 << 30))


def _chunked_put(host: np.ndarray, sharding) -> jax.Array:
    """device_put in bounded slabs along axis 0, assembled on device.
    Small arrays (or unsplittable ones) go through in one put."""
    cap = _put_chunk_bytes()
    if host.nbytes <= cap or host.ndim == 0 or host.shape[0] <= 1:
        return jax.device_put(host, sharding)
    rows = max(1, int(cap // max(host.nbytes // host.shape[0], 1)))
    # an axis-0-sharded leaf needs every slab divisible by the partition
    # count; round rows down to a multiple (or give up slabbing)
    spec0 = sharding.spec[0] if sharding.spec else None
    if spec0 is not None:
        axes = spec0 if isinstance(spec0, (tuple, list)) else (spec0,)
        parts = 1
        for a in axes:
            parts *= sharding.mesh.shape[a]
        rows = (rows // parts) * parts
        if rows < parts:
            # a single row-group already exceeds the cap; parts rows is the
            # smallest cleanly-shardable slab — each DEVICE still receives
            # <= cap/parts of it, which is what the per-transfer cap bounds.
            # (Silently falling back to one unslabbed put here would re-hit
            # the cap for exactly the leaves this path exists to handle.)
            rows = parts
    slabs = [jax.device_put(host[i:i + rows], sharding)
             for i in range(0, host.shape[0], rows)]
    # donate the slabs: peak device transient stays ~2x the leaf, not 3x
    return jax.jit(lambda xs: jnp.concatenate(xs, axis=0),
                   out_shardings=sharding, donate_argnums=0)(slabs)


def _place_dense(mesh, specs, params, np_dtype) -> Any:
    """Leaf-wise host->device placement with the transfer cap (used by
    __init__ and update_params for unquantized HOST trees whose leaves
    can exceed the cap). Device-resident leaves (mixed trees) are placed
    directly — never pulled back to host."""
    def place(s_, x):
        sh = NamedSharding(mesh, s_)
        if isinstance(x, jax.Array):
            return jax.device_put(x.astype(np_dtype), sh)
        return _chunked_put(np.asarray(x).astype(np_dtype, copy=False), sh)
    return jax.tree.map(place, specs, params,
                        is_leaf=lambda s_: isinstance(s_, P))


class InferenceEngineV2:

    def __init__(self,
                 model: TransformerLM,
                 config: Optional[RaggedInferenceEngineConfig] = None,
                 params: Optional[Any] = None,
                 topology: Optional[MeshTopology] = None,
                 seed: int = 0,
                 donate_params: bool = False,
                 quant_cache_dir: Optional[str] = None,
                 quant_cache_fingerprint: Optional[Any] = None):
        enable_compile_cache()
        self.config = config or RaggedInferenceEngineConfig()
        self._quant_cache_dir = quant_cache_dir
        self._quant_cache_fingerprint = quant_cache_fingerprint
        c = model.config
        self.topology = topology or MeshTopology(
            TopologyConfig(model=self.config.tensor_parallel_degree, data=-1))
        self.mesh = self.topology.mesh

        sm = self.config.state_manager
        block_size = self.config.kv_block_size
        max_ctx = min(sm.max_context, c.max_seq_len)
        self.max_blocks_per_seq = -(-max_ctx // block_size)
        num_blocks = self.config.num_kv_blocks
        derived_blocks = num_blocks is None
        if num_blocks is None:
            # enough for max_ragged_sequence_count sequences at half context
            num_blocks = 1 + sm.max_ragged_sequence_count * max(
                1, self.max_blocks_per_seq // 2)
        # -- page-pool shard decision (ISSUE 6: sharded, not replicated) --
        # Data-axis sharding splits the PAGE dim: each rank owns
        # num_blocks/dp pages + its own null block, sequences pin to one
        # shard, and waves dispatch through shard_map with no collectives.
        # Requires tp == 1 (with tp > 1 the pool is head-sharded over the
        # model axis below — already "sharded across the mesh", and the
        # per-head KV write must stay GSPMD-placed).
        dp = int(self.mesh.shape.get(DATA_AXIS, 1))
        tp = self.topology.model_parallel_size
        pool_mode = self.config.kv_pool_sharding
        wave_on = (self.config.wave_dispatch != "legacy"
                   and os.environ.get("DSTPU_WAVE") != "legacy")
        self.kv_shards = 1
        if pool_mode not in ("auto", "data", "replicated"):
            raise ValueError(f"kv_pool_sharding must be auto|data|replicated,"
                             f" got {pool_mode!r}")
        if pool_mode != "replicated" and tp == 1 and dp > 1 and wave_on:
            if derived_blocks and pool_mode == "auto":
                # a sequence's blocks all come from ONE shard, so a shard
                # must be able to hold a max-context sequence (plus its
                # null block) or long requests become permanently
                # unschedulable; then round up so the pool shards cleanly
                num_blocks = max(num_blocks,
                                 dp * (self.max_blocks_per_seq + 1))
                num_blocks = -(-num_blocks // dp) * dp
                self.kv_shards = dp
            elif pool_mode == "data":
                if num_blocks % dp or num_blocks // dp < 2:
                    raise ValueError(
                        f"kv_pool_sharding='data' needs num_kv_blocks "
                        f"divisible by the data axis ({dp}) with >= 2 "
                        f"blocks per shard, got {num_blocks}")
                self.kv_shards = dp
        elif pool_mode == "data":
            raise ValueError(
                "kv_pool_sharding='data' requires tensor_parallel_degree 1, "
                "a multi-device data axis, and the wave dispatch")
        self.kv_cache = BlockedKVCache(
            c.num_layers, c.kv_heads, c.head_dim, num_blocks, block_size,
            dtype=self.config.kv_cache_dtype)
        self.state_manager = DSStateManager(sm, self.kv_cache,
                                            num_shards=self.kv_shards)
        # module selection (reference modules/heuristics.py instantiate_*):
        # resolved once here; the chosen names are logged below so kernel
        # fallbacks are visible, never silent
        from .modules import instantiate_attention, instantiate_linear
        self._impls = instantiate_attention(self.config, c)
        self._impls["linear"] = instantiate_linear(self.config, c)
        self._model = RaggedInferenceModel(
            model, block_size, self.max_blocks_per_seq,
            use_pallas=self._impls["decode"].name == "pallas_paged",
            ragged_block_q=self.config.ragged_block_q,
            # MQA/odd head counts under tp: kv_heads can't shard over the
            # model axis, and GSPMD mis-sums the rope'd K page scatter over
            # the data axis (see RaggedInferenceModel.replicate_kv_writes)
            replicate_kv_writes=(tp > 1 and c.kv_heads % tp != 0))
        self.model = model

        specs = model.specs()
        shardings = jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                                 is_leaf=lambda s: isinstance(s, P))
        from ..quantization import QuantizationConfig, quantize_placed
        # the LINEAR slot of the module registry decides dense vs WOQ; the
        # chosen implementation's mode then drives the param transform
        self._qcfg = (QuantizationConfig.from_mode(self.config.quantization_mode)
                      if self._impls["linear"].name != "dense" else None)
        with self.mesh:
            if params is not None and self._qcfg is not None:
                # STREAMING quantized placement: one leaf at a time, host ->
                # device -> int8. Whole-tree placement would put the full
                # dense copy in HBM before quantizing (dense + int8 peak:
                # llama2-7b bf16 is 13.5 GB, + 6.7 GB int8 > a v5e's 16 GB);
                # this path peaks at int8 total + ONE dense leaf.
                self.params = self._place_quantized_streaming(
                    specs, params, donate=donate_params)
            elif params is not None:
                host_leaves = jax.tree.leaves(params)
                # classify PER LEAF: a mixed tree (some device arrays, some
                # oversized host leaves) must still take the slab path for
                # the host leaves — _chunked_put passes device-resident
                # leaves straight through
                if any((not isinstance(x, jax.Array))
                       and x.nbytes > _put_chunk_bytes()
                       for x in host_leaves):
                    self.params = _place_dense(self.mesh, specs, params,
                                               np.dtype(c.dtype))
                else:
                    self.params = jax.jit(
                        lambda p: jax.tree.map(
                            lambda x: jnp.asarray(x, c.dtype), p),
                        out_shardings=shardings)(params)
            else:
                self.params = jax.jit(lambda rng: model.init(rng, c.dtype),
                                      out_shardings=shardings)(jax.random.PRNGKey(seed))
                if self._qcfg is not None:
                    self.params = quantize_placed(self.mesh, specs,
                                                  self.params, self._qcfg)
            # pages layout [L, kvH, P, ps, D]: shard the HEAD dim over the
            # model axis when it divides — attention is then fully local per
            # head (k/v projections are already head-column-sharded, so the
            # per-step KV write lands on the owning rank with no reshard),
            # matching the reference's TP serving layout. MQA/odd head
            # counts (kvH % tp != 0 would be a device_put ERROR, not a slow
            # path) fall back to page-dim sharding: even memory split, XLA
            # inserts the gathers.
            tp = self.topology.model_parallel_size
            if self.kv_shards > 1:
                # data-sharded pool (decided above): each data rank owns a
                # contiguous page range; wave dispatch goes through
                # shard_map so every gather/write is rank-local
                spec = P(None, None, DATA_AXIS)
            elif c.kv_heads % tp == 0:
                spec = P(None, MODEL_AXIS)
            elif self.kv_cache.num_blocks % tp == 0:
                spec = P(None, None, MODEL_AXIS)
            else:  # MQA + indivisible block count: replicate (still correct)
                spec = P()
            kv_spec = NamedSharding(self.mesh, spec)
            # the pools are already DEVICE arrays (jnp.zeros at cache
            # construction) — place() is a device-side reshard, never a
            # host transfer, so no slab cap applies
            self.kv_cache.place(kv_spec, num_shards=self.kv_shards)

        self._burst_fns: Dict[Tuple[int, int, int], Any] = {}
        # what the dispatches counted (docs/OBSERVABILITY.md): running
        # totals since the engine was built, the last put()/decode_burst()'s
        # own, and each bucket key with the seconds its first call took.
        # Plain integer adds, kept with telemetry on or off.
        self.wave_totals: Dict[str, int] = dict.fromkeys(COUNTER_KEYS, 0)
        self.last_counters: Dict[str, Any] = {}
        self._seen_buckets: Dict[Tuple[str, Tuple[int, ...]], Dict[str, Any]] = {}
        log_dist(
            f"InferenceEngineV2: {num_blocks} KV blocks × {block_size} tokens "
            f"({self.kv_cache.mem_bytes() / 2**20:.0f} MiB"
            + (f", {self.kv_shards}-way data-sharded pool"
               if self.kv_shards > 1 else "") + "), "
            f"tp={self.topology.model_parallel_size}, "
            f"attn={self._impls['decode'].name}/{self._impls['prefill'].name}"
            f"/{self._impls['wave'].name}, "
            f"dispatch={'wave' if self._wave_dispatch_on else 'legacy'}, "
            f"linear={self._impls['linear'].name}", ranks=[0])

    def _place_quantized_streaming(self, specs: Any, params: Any,
                                   donate: bool = False) -> Any:
        """Walk the param tree leaf-wise with a PIPELINED upload: targeted
        kernels are quantized on the HOST (bit-identical numpy mirror of
        quantize_kernel) and only the int payload crosses the link — 4-8x
        fewer wire bytes than the dense push — while a worker prepares the
        next leaves so host cast/quantize overlaps the device transfer
        (round-3's serial bf16-then-quantize build took 286 s for 7B; the
        reference streams checkpoints with layered loaders for the same
        reason). ``DSTPU_HOST_QUANTIZE=0`` restores the device-quantize
        path (dense bf16 slabs up, jit quantize, drop dense). With
        ``donate=True`` the caller's host tree is CONSUMED (leaves popped
        as placed) so host RAM is also bounded."""
        import numpy as np
        from jax.sharding import NamedSharding
        from ..quantization import (host_quantize_kernel, quantize_kernel,
                                    quantize_specs)
        c = self.model.config
        cfg = self._qcfg
        targets = set(cfg.targets)
        np_dtype = np.dtype(c.dtype)
        host_quant = os.environ.get("DSTPU_HOST_QUANTIZE", "1") != "0"
        # one compiled quantize program per distinct (shape, sharding) —
        # llama2-7b has ~10 distinct kernel shapes across ~225 leaves
        jit_cache: Dict[Any, Any] = {}

        def host_cast(v):
            host = np.asarray(v)
            return host.astype(np_dtype) if host.dtype != np_dtype else host

        shard_cache: Dict[Any, Any] = {}

        def q_shardings(shape, spec):
            key = (shape, str(spec))
            if key not in shard_cache:
                q_shape = jax.eval_shape(
                    lambda a: quantize_kernel(a, cfg),
                    jax.ShapeDtypeStruct(shape, c.dtype))["q"]
                qs = quantize_specs({"kernel": spec},
                                    {"q": q_shape, "scale": None}, self.mesh)
                shard_cache[key] = {name: NamedSharding(self.mesh, s)
                                    for name, s in qs.items()}
            return shard_cache[key]

        # pass 1: flatten the ordered work list (out-dict, key, kind, ...).
        # A deque consumed by popleft so that with donate=True each leaf's
        # last reference dies once its prepare->place hop completes — host
        # RAM stays bounded at `depth` prepared leaves, as documented.
        from collections import deque
        items: deque = deque()

        def collect(spec_tree, tree, inside_target, out, path):
            if inside_target and "q" in tree and "scale" in tree:
                # PRE-QUANTIZED subtree (quant-cache reload): the int
                # payload uploads directly, no dense read or quantize.
                # Handled as a PAIR before the loop so donate-mode pops
                # cannot double-consume either member regardless of key
                # order.
                qv = tree.pop("q") if donate else tree["q"]
                sv = tree.pop("scale") if donate else tree["scale"]
                items.append((out, "preq", (qv, sv), spec_tree["kernel"],
                              path))
            for k in list(tree):
                if not donate and inside_target and k in ("q", "scale"):
                    continue  # consumed by the pair above
                v = tree.pop(k) if donate else tree[k]
                if k == "kernel" and inside_target:
                    items.append((out, "quant", v, spec_tree["kernel"],
                                  path + "/kernel"))
                elif isinstance(v, dict):
                    out[k] = {}
                    collect(spec_tree[k], v, inside_target or k in targets,
                            out[k], path + "/" + k)
                else:
                    items.append((out, k, v, spec_tree[k], path + "/" + k))

        result: Dict[str, Any] = {}
        collect(specs, params, False, result, "")

        # the cache is only coherent when THIS build quantizes on the host
        # (the device-quantize path never produces host q/scale to persist;
        # writing a dense-only manifest would poison later cache hits)
        cache_dir = self._quant_cache_dir if host_quant else None
        cache_manifest: list = []

        def _cache_file(path, suffix):
            return os.path.join(cache_dir,
                                path.strip("/").replace("/", "__") + suffix)

        def _atomic_save(fname, arr):
            # tmp must end in .npy or np.save appends the extension; the
            # os.replace makes concurrent builders converge on a complete
            # file instead of interleaving writes
            tmp = f"{fname}.{os.getpid()}.tmp.npy"
            np.save(tmp, arr)
            os.replace(tmp, fname)

        # pass 2: prepare (worker thread) || upload (main thread)
        def prepare(item):
            out, key, v, spec, path = item
            if key == "quant" and host_quant:
                q, scale = host_quantize_kernel(np.asarray(v), cfg, np_dtype)
                if cache_dir:
                    try:
                        _atomic_save(_cache_file(path, ".q.npy"), q)
                        _atomic_save(_cache_file(path, ".scale.npy"), scale)
                        cache_manifest.append((path, "quant"))
                    except OSError:
                        pass  # read-only mount: serve uncached
                return (out, "host_q", (q, scale), spec, v.shape)
            if key == "preq":
                return (out, "host_q", v, spec, None)
            host = host_cast(v)
            if cache_dir and key != "quant":
                # npy has no bf16: persist the raw 2-byte payload as uint16
                # (the loader views it back through the manifest dtype)
                sv = host.view(np.uint16) if host.dtype.str == "<V2" or \
                    host.dtype == np.dtype(jnp.bfloat16) else host
                try:
                    _atomic_save(_cache_file(path, ".dense.npy"), sv)
                    cache_manifest.append((path, "dense"))
                except OSError:
                    pass  # read-only mount: serve uncached
            return (out, key, host, spec, None)

        def place(prepared):
            out, key, v, spec, shape = prepared
            if key == "host_q":
                q, scale = v
                if shape is None:  # pre-quantized: derive the dense shape
                    *lead, G, gse, dout = q.shape
                    gs = gse * 2 if q.dtype == np.uint8 else gse
                    shape = (*lead, G * gs, dout)
                shard = q_shardings(shape, spec)
                out["q"] = _chunked_put(np.asarray(q), shard["q"])
                out["scale"] = jax.device_put(np.asarray(scale),
                                              shard["scale"])
            elif key == "quant":  # device-quantize path
                ck = (v.shape, str(spec))
                if ck not in jit_cache:
                    jit_cache[ck] = jax.jit(
                        lambda a: quantize_kernel(a, cfg),
                        out_shardings=q_shardings(v.shape, spec))
                # push 2-byte (not 4), in bounded slabs; the dense device
                # copy is dropped when qp replaces it
                dense = _chunked_put(v, NamedSharding(self.mesh, spec))
                qp = jit_cache[ck](dense)
                del dense
                out["q"], out["scale"] = qp["q"], qp["scale"]
            else:
                out[key] = _chunked_put(v, NamedSharding(self.mesh, spec))

        if cache_dir:
            try:
                os.makedirs(cache_dir, exist_ok=True)
            except OSError:
                cache_dir = None  # read-only checkpoint mount: no cache
        from concurrent.futures import ThreadPoolExecutor
        depth = 5  # bounded: at most `depth` prepared leaves in host RAM
        # 4 workers: the host quantize is numpy (releases the GIL on the
        # big ufuncs), so leaves quantize in parallel while the main
        # thread streams device puts
        with ThreadPoolExecutor(max_workers=4) as ex:
            pending: deque = deque()
            while items:
                pending.append(ex.submit(prepare, items.popleft()))
                if len(pending) >= depth:
                    place(pending.popleft().result())
            while pending:
                place(pending.popleft().result())
        if cache_dir and cache_manifest:
            import json as _json
            manifest = os.path.join(cache_dir, "manifest.json")
            tmp = f"{manifest}.{os.getpid()}.tmp"
            try:
                with open(tmp, "w") as f:
                    _json.dump({"bits": cfg.bits,
                                "group_size": cfg.group_size,
                                "dtype": str(np_dtype),
                                "fingerprint": getattr(
                                    self, "_quant_cache_fingerprint", None),
                                "leaves": cache_manifest}, f)
                # atomic: a concurrent reader never sees a torn manifest
                os.replace(tmp, manifest)
            except OSError:
                pass  # cache is best-effort; serving continues uncached
        return result

    def update_params(self, params: Any) -> None:
        """Rebind weights (hybrid-engine train->generate flip): cast into the
        engine's shardings without touching compiled programs."""
        c = self.model.config
        specs = self.model.specs()
        leaves = jax.tree.leaves(params)
        on_device = bool(leaves) and isinstance(leaves[0], jax.Array)
        with self.mesh:
            if self._qcfg is not None and not on_device:
                # host tree (checkpoint reload): stream leaf-by-leaf so the
                # dense copy never fully materializes in HBM (see
                # _place_quantized_streaming)
                self.params = self._place_quantized_streaming(specs, params)
            elif not on_device and any(x.nbytes > _put_chunk_bytes()
                                       for x in leaves):
                # host tree with oversized leaves: same slab path as init
                self.params = _place_dense(self.mesh, specs, params,
                                           np.dtype(c.dtype))
            else:
                shardings = jax.tree.map(
                    lambda s: NamedSharding(self.mesh, s), specs,
                    is_leaf=lambda s: isinstance(s, P))
                self.params = jax.jit(
                    lambda p: jax.tree.map(lambda x: jnp.asarray(x, c.dtype), p),
                    out_shardings=shardings)(params)
                if self._qcfg is not None:
                    # hybrid-engine flip: the dense tree is already device-
                    # resident (it IS the training copy), so the on-device
                    # quantize stays sharded and never round-trips the host
                    self.params = quantize_placed(self.mesh, specs,
                                                  self.params, self._qcfg)

    # ------------------------------------------------------------------
    # compiled-program cache (jax.jit retraces per (S, T, mp) bucket)
    # ------------------------------------------------------------------
    @functools.cached_property
    def _ragged_fn(self):
        return jax.jit(self._model.ragged_forward, donate_argnums=(1, 2))

    @functools.cached_property
    def _wave_fn(self):
        """The unified ragged-wave program (replicated / model-sharded
        pool): one jit, retraced per (N, A, MP, R) bucket."""
        return jax.jit(self._model.wave_forward, donate_argnums=(1, 2))

    @functools.cached_property
    def _wave_sharded_fn(self):
        """The data-sharded wave dispatch: shard_map over the data axis —
        each rank runs the FULL model (tp == 1, params replicated) on its
        own sub-wave against its LOCAL page-pool slice. Zero collectives
        by construction: gathers, writes and logits are all rank-local
        (the ``ragged-paged-attention`` lint entry point compiles exactly
        this composition and budgets it)."""
        from ...utils.jax_compat import shard_map

        d = DATA_AXIS
        fn = shard_map(
            self._model.wave_forward, mesh=self.mesh,
            in_specs=(P(),                       # params (replicated; tp==1)
                      P(None, None, d), P(None, None, d),   # k/v pages
                      P(d), P(d), P(d),          # tokens, positions, write
                      P(d), P(d), P(d, None),    # cu_q_lens, kv_lens, tables
                      P(d)),                     # last_rows
            out_specs=(P(d), P(None, None, d), P(None, None, d)),
            check_vma=False)
        return jax.jit(fn, donate_argnums=(1, 2))

    # ------------------------------------------------------------------
    # scheduling queries (reference engine_v2.py:153,179)
    # ------------------------------------------------------------------
    def query(self, uid: int) -> Dict[str, int]:
        seq = self.state_manager.get_sequence(uid)
        return {
            "seen_tokens": 0 if seq is None else seq.seen_tokens,
            "cur_allocated_blocks": 0 if seq is None else seq.cur_allocated_blocks,
            "free_blocks": self.state_manager.free_blocks,
        }

    @property
    def max_context(self) -> int:
        """Longest sequence the KV layout can hold (per sequence)."""
        return self.max_blocks_per_seq * self.state_manager.block_size

    def can_schedule(self, uids: Sequence[int], lengths: Sequence[int]) -> bool:
        """Dry-run KV block budgeting (reference ``can_schedule``/
        ``get_length_needed``)."""
        return self._plan_shards(uids, lengths) is not None

    def _plan_shards(self, uids: Sequence[int],
                     lengths: Sequence[int]) -> Optional[Dict[int, int]]:
        """The ONE placement rule both ``can_schedule`` (dry run) and
        ``put`` (commit) evaluate, so they always agree: existing
        sequences grow in their pinned shard; new sequences land on the
        least-loaded shard AT THAT POINT of the plan (ties -> lowest id).
        Returns {uid: shard} or None if the batch does not fit. With one
        shard this degenerates to the original aggregate free-block
        check."""
        sm = self.config.state_manager
        if len(uids) > sm.max_ragged_sequence_count:
            return None
        if sum(lengths) > sm.max_ragged_batch_size:
            return None
        alloc = self.state_manager.allocator
        free = [alloc.shard_free_blocks(r) for r in range(alloc.num_shards)]
        plan: Dict[int, int] = {}
        for uid, n in zip(uids, lengths):
            seq = self.state_manager.get_sequence(uid)
            seen = 0 if seq is None else seq.seen_tokens
            have = 0 if seq is None else seq.cur_allocated_blocks
            if seen + n > self.max_context:
                # growing past the block-table capacity would silently
                # overwrite the sequence's own live KV
                return None
            total_blocks = -(-(seen + n) // self.state_manager.block_size)
            need = max(0, total_blocks - have)
            if seq is not None:
                r = seq.shard
            elif uid in plan:
                r = plan[uid]
            else:
                r = max(range(len(free)), key=lambda i: (free[i], -i))
            if need > free[r]:
                return None
            free[r] -= need
            plan[uid] = r
        return plan

    def flush(self, uid: int) -> None:
        self.state_manager.flush_sequence(uid)

    # -- KV host offload / restore: working form of the reference's
    #    stubbed BlockedKVCache.offload/restore (kv_cache.py:169,179).
    #    Preemption stashes a sequence's KV in host RAM; restore resumes
    #    decoding with one H2D scatter instead of a full re-prefill. -----
    def offload_sequence(self, uid: int) -> None:
        with self.mesh:
            self.state_manager.offload_sequence(uid)

    def can_restore(self, uid: int, headroom: int = 0) -> bool:
        return (self.state_manager.is_offloaded(uid)
                and self.state_manager.can_restore(uid, headroom))

    def is_offloaded(self, uid: int) -> bool:
        return self.state_manager.is_offloaded(uid)

    def restore_sequence(self, uid: int) -> None:
        with self.mesh:
            self.state_manager.restore_sequence(uid)

    # ------------------------------------------------------------------
    # forward (reference engine_v2.py:107 put)
    # ------------------------------------------------------------------
    def put(self, batch_uids: Sequence[int], batch_tokens: Sequence[np.ndarray]) -> np.ndarray:
        """Schedule new tokens for each UID; returns last-token logits
        [len(uids), vocab].

        ONE device dispatch serves the whole ragged batch — mixed prefill
        chunks and decodes in a single compiled program (the SplitFuse
        contract; reference atom_builder + flash_attn_by_atoms). Prompts
        longer than ``max_prefill_chunk`` take one extra dispatch per extra
        chunk wave.

        Default dispatch is the unified ragged-WAVE program (one atom
        class, ragged_paged_attention); ``wave_dispatch="legacy"`` or
        ``DSTPU_WAVE=legacy`` restores the previous two-class program.
        """
        plan = self._plan_shards(batch_uids, [len(t) for t in batch_tokens])
        if plan is None:
            raise RuntimeError("batch does not fit KV/budget; call can_schedule first")
        self.last_counters = {}

        work: List[Tuple[int, np.ndarray]] = []
        for uid, tokens in zip(batch_uids, batch_tokens):
            tokens = np.asarray(tokens, np.int32)
            seq = self.state_manager.get_or_create_sequence(uid,
                                                            shard=plan[uid])
            self.state_manager.allocate_blocks(seq, len(tokens))
            work.append((uid, tokens))

        run = self._run_wave if self._wave_dispatch_on else self._run_ragged
        cap = self.config.max_prefill_chunk
        out_logits: Dict[int, np.ndarray] = {}
        offset = {uid: 0 for uid, _ in work}
        while True:
            wave = [(uid, toks[offset[uid]:offset[uid] + cap])
                    for uid, toks in work if offset[uid] < len(toks)]
            if not wave:
                break
            logits = run(wave)
            for i, (uid, chunk) in enumerate(wave):
                offset[uid] += len(chunk)
                out_logits[uid] = logits[i]
        return np.stack([out_logits[u] for u in batch_uids])

    @property
    def _wave_dispatch_on(self) -> bool:
        """Live env read so an A/B harness can flip mid-process; a
        data-sharded pool REQUIRES the wave program (the legacy two-class
        program indexes the pool globally)."""
        if self.kv_shards > 1:
            return True
        return (self.config.wave_dispatch != "legacy"
                and os.environ.get("DSTPU_WAVE") != "legacy")

    def _run_wave(self, wave: List[Tuple[int, np.ndarray]]) -> np.ndarray:
        """One dispatch of a mixed wave through the unified ragged-wave
        program. wave: [(uid, chunk)] — any composition of decode tokens
        and prefill chunks; the host atom builder (ragged/wave.py)
        flattens it into ONE token stream + per-atom descriptors, sharded
        pools get one equally-bucketed sub-wave per data rank."""
        tele = get_telemetry()
        uids = [uid for uid, _ in wave]
        sm = self.state_manager
        shards = max(self.kv_shards, 1)
        with tele.phase("wave.build", phase=PHASE_SERVING, req=uids):
            per_shard: List[List[WaveEntry]] = [[] for _ in range(shards)]
            for uid, chunk in wave:
                seq = sm.get_sequence(uid)
                r = seq.shard if shards > 1 else 0
                local = [sm.allocator.local_id(b) for b in seq.blocks] \
                    if shards > 1 else list(seq.blocks)
                per_shard[r].append(
                    WaveEntry(uid, chunk, seq.seen_tokens, local))
            desc = build_sharded_wave(per_shard,
                                      block_q=self.config.ragged_block_q,
                                      block_size=sm.block_size)
            key = wave_key(desc)
            self._count("wave", key,
                        wave_counters(desc, len(wave), sm.block_size))
        fn = self._wave_sharded_fn if shards > 1 else self._wave_fn
        # The wave program moves ZERO collective bytes by contract (the
        # sharded pool keeps every gather/write rank-local; lint entry
        # `ragged-paged-attention` compiles and budgets exactly this).
        # Record the dispatch anyway — overlapped, zero bytes — so the
        # overlap ledger COVERS serving instead of silently omitting it,
        # and Layer D's parity test can hold the serving split at 0/0
        # against the static collective map (a future collective creeping
        # into the wave shows up in both ledgers, not neither).
        dist.record_collective("wave_dispatch", 0, (DATA_AXIS,),
                               overlapped=True)
        logits, k_pages, v_pages = self._dispatch(
            "wave", key, uids, lambda: fn(
                self.params, self.kv_cache.k_pages, self.kv_cache.v_pages,
                jnp.asarray(desc.tokens), jnp.asarray(desc.positions),
                jnp.asarray(desc.write_idx), jnp.asarray(desc.cu_q_lens),
                jnp.asarray(desc.kv_lens), jnp.asarray(desc.page_indices),
                jnp.asarray(desc.last_rows)),
            sequences=len(wave), tokens=int(desc.n_tokens), shards=shards)
        self.kv_cache.update(k_pages, v_pages)
        for uid, chunk in wave:
            sm.get_sequence(uid).post_forward(len(chunk))
        with tele.phase("wave.fetch", phase=PHASE_SERVING, req=uids):
            logits = np.asarray(logits)     # waits for the device
        return np.stack([logits[desc.row_of_uid[uid]] for uid, _ in wave])

    # -- what the dispatches counted ----------------------------------------
    def _count(self, program: str, key: Tuple[int, ...],
               counters: Dict[str, int]) -> None:
        """Add one dispatch's counters to the running totals and to the
        current put()/decode_burst()'s own."""
        last = self.last_counters
        for k, v in counters.items():
            self.wave_totals[k] += v
            last[k] = last.get(k, 0) + v
        last.setdefault("buckets", []).append([program, *key])

    def _dispatch(self, program: str, key: Tuple[int, ...],
                  uids: Sequence[int], call, **args):
        """Descriptor upload and the call, as one ``wave.dispatch`` span.
        The first call of a bucket key traces and compiles (or loads the
        compile cache), so it goes through the repository's one first-call
        door (``telemetry/setup_spans.py``): a ``first_call`` span around
        it and an instant ``compile:<program>`` that says which key and
        what the call's seconds went on."""
        tele = get_telemetry()
        seen = self._seen_buckets
        first = (setup_spans.FirstCall(program, tele, PHASE_SERVING, key=list(key))
                 if (program, key) not in seen else NULL_SPAN)
        with tele.phase("wave.dispatch", phase=PHASE_SERVING, req=uids,
                        program=program, **args), first:
            with self.mesh:
                out = call()
        if first is not NULL_SPAN:
            seen[(program, key)] = first.numbers
        return out

    def seen_buckets(self) -> Dict[Tuple[str, Tuple[int, ...]], Dict[str, Any]]:
        """Every ``(program, bucket key)`` dispatched so far (wave ``(N, A,
        MP, R)``, burst ``(B, mp, k)``, legacy ragged ``(Bd, mpd, Sp, T,
        mpp)``) with what its first call took (``wall_s`` and, of it,
        ``trace_s``, ``lower_s``, ``compile_s``, ``run_s``; ``cache``):
        which step recompiled, answered by the program."""
        return dict(self._seen_buckets)

    def can_burst(self, batch_uids: Sequence[int], num_steps: int) -> bool:
        """Burst feasibility: the fused program runs len(uids) tokens PER
        STEP (the ragged token budget applies per step, not to the k-fold
        product), but allocates ``num_steps`` KV slots per sequence up
        front."""
        if self.kv_shards > 1:
            # fused bursts index the pool globally (and scan-carry it
            # whole); under a data-sharded pool decode throughput comes
            # from disaggregated decode waves instead (docs/SERVING.md)
            return False
        sm = self.config.state_manager
        n = len(batch_uids)
        if n > sm.max_ragged_sequence_count or n > sm.max_ragged_batch_size:
            return False
        need = 0
        for uid in batch_uids:
            seq = self.state_manager.get_sequence(uid)
            if seq is None or seq.seen_tokens == 0:
                return False
            if seq.seen_tokens + num_steps > self.max_context:
                return False
            total = -(-(seq.seen_tokens + num_steps)
                      // self.state_manager.block_size)
            need += max(0, total - seq.cur_allocated_blocks)
        return need <= self.state_manager.free_blocks

    def decode_burst(self, batch_uids: Sequence[int],
                     last_tokens: Sequence[int], num_steps: int,
                     temperatures: Optional[Sequence[float]] = None,
                     seed: int = 0) -> np.ndarray:
        """Generate ``num_steps`` tokens for every (already-prefilled) UID
        in one dispatch (see :meth:`RaggedInferenceModel.decode_burst`).
        Returns sampled tokens ``[len(uids), num_steps]``.
        """
        if not self.can_burst(batch_uids, num_steps):
            raise RuntimeError("burst does not fit KV budget; call can_burst")
        tele = get_telemetry()
        uids = list(batch_uids)
        self.last_counters = {}
        sm = self.state_manager
        with tele.phase("wave.build", phase=PHASE_SERVING, req=uids):
            seqs = []
            for uid in batch_uids:
                seq = sm.get_sequence(uid)
                assert seq is not None and seq.seen_tokens > 0, \
                    f"decode_burst requires a prefilled sequence (uid {uid})"
                sm.allocate_blocks(seq, num_steps)
                seqs.append(seq)

            B = _next_bucket(len(batch_uids), lo=16)
            mp = self._bucket_blocks(batch_uids)
            tokens = np.zeros((B,), np.int32)
            positions = np.zeros((B,), np.int32)
            tables = np.zeros((B, mp), np.int32)  # padded rows: null block 0
            temps = np.zeros((B,), np.float32)
            for i, (uid, seq) in enumerate(zip(batch_uids, seqs)):
                tokens[i] = last_tokens[i]
                positions[i] = seq.seen_tokens
                bt = seq.blocks[:mp]
                tables[i, :len(bt)] = bt
                if temperatures is not None:
                    temps[i] = temperatures[i]
            key = (B, mp, num_steps)
            self._count("burst", key, burst_counters(
                [seq.seen_tokens for seq in seqs], num_steps, B, mp,
                sm.block_size))

        if key not in self._burst_fns:
            self._burst_fns[key] = jax.jit(
                functools.partial(self._model.decode_burst, num_steps=num_steps),
                donate_argnums=(1, 2))
        toks, k_pages, v_pages = self._dispatch(
            "burst", key, uids, lambda: self._burst_fns[key](
                self.params, self.kv_cache.k_pages, self.kv_cache.v_pages,
                jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(tables), jax.random.PRNGKey(seed),
                jnp.asarray(temps)),
            sequences=len(batch_uids), k=num_steps)
        self.kv_cache.update(k_pages, v_pages)
        for seq in seqs:
            seq.post_forward(num_steps)
        with tele.phase("wave.fetch", phase=PHASE_SERVING, req=uids):
            return np.asarray(toks)[:len(batch_uids)]   # waits for the device

    def _bucket_blocks(self, uids) -> int:
        need = max((len(self.state_manager.get_sequence(u).blocks) for u in uids),
                   default=1)
        return min(self.max_blocks_per_seq, _next_bucket(max(need, 1), lo=4))

    def _run_ragged(self, wave: List[Tuple[int, np.ndarray]]) -> np.ndarray:
        """One dispatch of the mixed ragged batch. wave: [(uid, chunk)].

        Splits the wave into the two atom classes of ``ragged_forward`` —
        decode rows (1 continuing token) and prefill chunk rows — builds
        their padded metadata, and dispatches once.
        """
        sm = self.state_manager
        decode = [(u, c) for u, c in wave
                  if len(c) == 1 and sm.get_sequence(u).seen_tokens > 0]
        prefill = [(u, c) for u, c in wave
                   if not (len(c) == 1 and sm.get_sequence(u).seen_tokens > 0)]

        # lo=16: padded decode rows are near-free (they attend 1 null-block
        # token), while each distinct Bd bucket costs a full XLA compile —
        # keep the program-shape space tiny for the serving loop
        Bd = _next_bucket(len(decode), lo=16) if decode else 0
        mpd = self._bucket_blocks([u for u, _ in decode]) if decode else 1
        d_tokens = np.zeros((Bd,), np.int32)
        d_positions = np.zeros((Bd,), np.int32)
        d_context = np.ones((Bd,), np.int32)  # padded rows hit the null block
        d_tables = np.zeros((Bd, mpd), np.int32)
        for i, (uid, chunk) in enumerate(decode):
            seq = sm.get_sequence(uid)
            d_tokens[i] = chunk[0]
            d_positions[i] = seq.seen_tokens
            d_context[i] = seq.seen_tokens + 1
            bt = seq.blocks[:mpd]
            d_tables[i, :len(bt)] = bt

        t_max = max((len(c) for _, c in prefill), default=0)
        Sp = _next_bucket(len(prefill), lo=1) if prefill else 0
        T = _next_bucket(t_max, lo=16) if prefill else 1
        mpp = self._bucket_blocks([u for u, _ in prefill]) if prefill else 1
        p_tokens = np.zeros((Sp, T), np.int32)
        p_positions = np.zeros((Sp, T), np.int32)
        p_valid = np.zeros((Sp,), np.int32)
        p_history = np.zeros((Sp,), np.int32)
        p_tables = np.zeros((Sp, mpp), np.int32)
        for i, (uid, chunk) in enumerate(prefill):
            seq = sm.get_sequence(uid)
            k = len(chunk)
            p_tokens[i, :k] = chunk
            p_positions[i, :k] = seq.seen_tokens + np.arange(k, dtype=np.int32)
            p_valid[i] = k
            p_history[i] = seq.seen_tokens
            bt = seq.blocks[:mpp]
            p_tables[i, :len(bt)] = bt

        # the legacy two-class program: spans and bucket keys, no counters
        logits, k_pages, v_pages = self._dispatch(
            "ragged", (Bd, mpd, Sp, T, mpp), [u for u, _ in wave],
            lambda: self._ragged_fn(
                self.params, self.kv_cache.k_pages, self.kv_cache.v_pages,
                jnp.asarray(d_tokens), jnp.asarray(d_positions),
                jnp.asarray(d_context), jnp.asarray(d_tables),
                jnp.asarray(p_tokens), jnp.asarray(p_positions),
                jnp.asarray(p_valid), jnp.asarray(p_history),
                jnp.asarray(p_tables)),
            decode=len(decode), prefill=len(prefill),
            prefill_tokens=int(p_valid.sum()))
        self.kv_cache.update(k_pages, v_pages)
        for uid, chunk in wave:
            sm.get_sequence(uid).post_forward(len(chunk))

        logits = np.asarray(logits)
        by_uid = {}
        for i, (uid, _) in enumerate(decode):
            by_uid[uid] = logits[i]
        for i, (uid, _) in enumerate(prefill):
            by_uid[uid] = logits[Bd + i]
        return np.stack([by_uid[u] for u, _ in wave])


def build_engine(model: TransformerLM,
                 config: Optional[RaggedInferenceEngineConfig] = None,
                 params: Optional[Any] = None,
                 **kwargs) -> InferenceEngineV2:
    """Engine from an in-memory model (reference ``engine_factory.py:28``)."""
    return InferenceEngineV2(model, config=config, params=params, **kwargs)


def _ckpt_fingerprint(model_path: str):
    """(name, size, mtime) of the checkpoint's weight/config files — a
    changed or re-saved checkpoint invalidates the quant cache."""
    names = sorted(n for n in os.listdir(model_path)
                   if n.endswith((".safetensors", ".bin", ".json"))
                   and not n.startswith("."))
    return [(n, os.path.getsize(os.path.join(model_path, n)),
             int(os.path.getmtime(os.path.join(model_path, n))))
            for n in names]


def _quant_cache_load(model_path: str, cache_dir: str, dtype, qcfg):
    """(model, pre-quantized host tree) from a quant cache: int payloads +
    bf16 dense leaves mmap straight off disk — no 2-byte/param dense
    checkpoint read, no quantize. Returns None if the manifest is absent
    or mismatched (dtype, bits, group size, or checkpoint fingerprint) —
    a stale cache must never silently serve old weights."""
    import json as _json
    man_path = os.path.join(cache_dir, "manifest.json")
    if not os.path.exists(man_path):
        return None
    with open(man_path) as f:
        man = _json.load(f)
    if man.get("dtype") != str(np.dtype(dtype)):
        return None
    if qcfg is not None and (man.get("bits") != qcfg.bits
                             or man.get("group_size") != qcfg.group_size):
        return None
    fp = man.get("fingerprint")
    if fp is None or [tuple(e) for e in fp] != _ckpt_fingerprint(model_path):
        return None
    from ...runtime.state_dict_factory import (SDLoaderFactory,
                                               hf_to_transformer_config)
    loader = SDLoaderFactory.get_sd_loader(model_path)  # config.json only
    cfg = hf_to_transformer_config(loader.config, dtype=dtype)
    tree: Dict[str, Any] = {}
    for path, kind in man["leaves"]:
        node = tree
        parts = path.strip("/").split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        stem = os.path.join(cache_dir, path.strip("/").replace("/", "__"))
        if kind == "quant":
            # the pre-quantized {"q", "scale"} subtree replaces {"kernel"}
            target = node if parts[-1] == "kernel" \
                else node.setdefault(parts[-1], {})
            target["q"] = np.load(stem + ".q.npy", mmap_mode="r")
            target["scale"] = np.load(stem + ".scale.npy", mmap_mode="r")
        else:
            arr = np.load(stem + ".dense.npy", mmap_mode="r")
            if arr.dtype == np.uint16:  # bf16 persisted as raw 2-byte words
                arr = arr.view(np.dtype(dtype))
            node[parts[-1]] = arr
    from ...models.transformer import TransformerLM
    return TransformerLM(cfg), tree


def build_hf_engine(model_path: str,
                    config: Optional[RaggedInferenceEngineConfig] = None,
                    dtype: Any = jnp.bfloat16,
                    **kwargs) -> InferenceEngineV2:
    """Serving engine directly from a real HF checkpoint directory
    (reference ``engine_factory.build_hf_engine``, engine_factory.py:65).

    ``dtype`` is the weight/compute dtype; the KV cache dtype is governed
    separately by ``config.kv_cache_dtype``.

    Quantized configs keep a PRE-QUANTIZED cache next to the checkpoint
    (``.dstpu_quant_cache_<mode>/``): the first build writes it while
    quantizing on the host, subsequent builds mmap the 4-8x smaller int
    payload and skip the dense read + quantize entirely (the reference
    ships pre-sharded/quantized checkpoints for the same reason).
    ``DSTPU_QUANT_CACHE=0`` disables."""
    from ..quantization import QuantizationConfig
    from ...runtime.state_dict_factory import load_hf_model
    qmode = getattr(config, "quantization_mode", None) if config else None
    cache_dir = None
    if qmode and os.environ.get("DSTPU_QUANT_CACHE", "1") != "0":
        qcfg = QuantizationConfig.from_mode(qmode)
        cache_dir = os.path.join(model_path, f".dstpu_quant_cache_{qmode}")
        cached = _quant_cache_load(model_path, cache_dir, dtype, qcfg)
        if cached is not None:
            model, params = cached
            log_dist(f"quant cache hit: {cache_dir}", ranks=[0])
            return InferenceEngineV2(model, config=config, params=params,
                                     **kwargs)
        kwargs.setdefault("quant_cache_dir", cache_dir)
        kwargs.setdefault("quant_cache_fingerprint",
                          _ckpt_fingerprint(model_path))
    model, params = load_hf_model(model_path, dtype=dtype)
    # the freshly loaded host tree is owned here: donate it so the
    # quantized streaming load releases host RAM leaf by leaf
    kwargs.setdefault("donate_params", True)
    return InferenceEngineV2(model, config=config, params=params, **kwargs)
