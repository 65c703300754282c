"""Weight-only quantized inference (int8 / int4).

Counterpart of the reference's weight-only quantization for serving:
``deepspeed/inference/quantization/quantization.py`` (``_init_group_wise_weight_quantization``)
and the v2 ``quantization_mode`` plumbing (``inference/v2/config_v2.py:33``) —
weights live in HBM at 8 or 4 bits and are expanded on the fly inside the
matmul, halving/quartering the weight bandwidth that bounds decode.

TPU-first form: SYMMETRIC groupwise quantization over the contraction dim.
int8 stores plain ``jnp.int8``; int4 stores PACKED ``uint8`` — two bias-8
nibbles per byte along the within-group axis — a storage format every
device-transfer path carries (whether ``jnp.int4`` jit inputs work on a
directly attached chip is not measured); the unpack (shift/mask,
XLA-fused into the consumer) happens in-program. The matmul factors the scale OUT of the contraction per group:

    y = sum_g (x_g @ q_g) * scale[g]         # q int, x/scale bf16

so the MXU consumes the int weights directly and no dequantized copy of the
kernel ever materializes in HBM — the property the reference's fused
dequant+GEMM CUDA kernels exist to provide.

A quantized kernel leaf is the subtree ``{"q": int8[G, gs, out]`` (int8)
``| uint8[G, gs/2, out]`` (packed int4)``, "scale": f32[G, 1, out]}`` in
place of ``{"kernel": [in, out]}``; ``nn.Linear`` dispatches on the
presence of ``"q"``, and consumers dispatch packed-vs-plain on
``q.dtype == uint8``. (Distinct from the COLLECTIVE wire format in
``ops/quantizer/quantizer.py`` — last-axis two's-complement nibbles — a
per-message transient, not a storage layout.)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# block-tree kernel names eligible for WOQ (projections; embeddings, norms
# and MoE expert banks are excluded — the reference likewise quantizes the
# injected linear modules only)
DEFAULT_TARGETS = ("q_proj", "k_proj", "v_proj", "o_proj", "fc_in", "fc_out",
                   "gate_proj", "up_proj", "down_proj", "lm_head")


@dataclasses.dataclass(frozen=True)
class QuantizationConfig:
    """Reference ``quantization_config`` (inference config ``quant`` field /
    v2 ``quantization_mode``): 'int8' | 'int4', groupwise over in-features."""
    bits: int = 8               # 8 | 4
    group_size: int = 128       # contraction elements sharing one scale
    targets: Sequence[str] = DEFAULT_TARGETS

    def __post_init__(self):
        if self.bits not in (4, 8):
            raise ValueError(f"weight-only quantization supports 4 or 8 bits, "
                             f"got {self.bits}")
        if self.group_size < 1:
            raise ValueError(f"group_size must be >= 1, got {self.group_size}")

    @staticmethod
    def from_mode(mode: Optional[str]) -> Optional["QuantizationConfig"]:
        if mode in (None, "none", False):
            return None
        if isinstance(mode, QuantizationConfig):
            return mode
        table = {"int8": 8, "wint8": 8, "int4": 4, "wint4": 4}
        if mode not in table:
            raise ValueError(f"unknown quantization_mode {mode!r} "
                             f"(supported: {sorted(table)})")
        return QuantizationConfig(bits=table[mode])


def _pack_int4(q: jax.Array) -> jax.Array:
    """int values in [-8, 7], [..., G, gs, out] -> biased nibbles packed
    two-per-byte along gs: uint8 [..., G, gs/2, out] — the int4 STORAGE
    format (see the module docstring)."""
    b = (q + 8).astype(jnp.uint8)
    return b[..., 0::2, :] | (b[..., 1::2, :] << 4)


def _unpack_int4(p: jax.Array) -> jax.Array:
    """uint8 [..., G, gs/2, out] -> int8 [..., G, gs, out] (in-program:
    XLA fuses the shifts into the consumer, no unpacked copy in HBM
    between calls)."""
    lo = (p & 0xF).astype(jnp.int8) - 8
    hi = (p >> 4).astype(jnp.int8) - 8
    *lead, G, gsp, d_out = p.shape
    return jnp.stack([lo, hi], axis=-2).reshape(*lead, G, 2 * gsp, d_out)


def quantize_kernel(kernel: jax.Array, cfg: QuantizationConfig) -> Dict[str, jax.Array]:
    """[..., in, out] -> {"q": int[..., G, gs, out], "scale": f32[..., G, 1, out]}.

    Leading dims (the scanned layer axis) pass through untouched. int8
    stores plain ``jnp.int8``; int4 stores PACKED uint8 (two biased
    nibbles per byte along gs — see :func:`_pack_int4`), detected
    downstream by ``q.dtype == uint8``.
    """
    *lead, d_in, d_out = kernel.shape
    gs = min(cfg.group_size, d_in)
    while d_in % gs:  # shrink to a divisor (static shapes need exact tiling)
        gs //= 2
    G = d_in // gs
    w = jnp.asarray(kernel, jnp.float32).reshape(*lead, G, gs, d_out)
    qmax = float(2 ** (cfg.bits - 1) - 1)
    absmax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)  # [..., G, 1, out]
    scale = jnp.maximum(absmax, 1e-12) / qmax
    q = jnp.clip(jnp.round(w / scale), -qmax - 1, qmax)
    if cfg.bits == 4 and gs % 2 == 0:
        return {"q": _pack_int4(q.astype(jnp.int8)), "scale": scale}
    # odd-gs int4 degrades to int8 storage (correct, just uncompressed)
    return {"q": q.astype(jnp.int8), "scale": scale}


def host_quantize_kernel(kernel: "np.ndarray", cfg: QuantizationConfig,
                         model_np_dtype,
                         slab_elems: int = 1 << 27) -> Tuple["np.ndarray",
                                                             "np.ndarray"]:
    """Numpy mirror of :func:`quantize_kernel`, bit-identical: cast to the
    model dtype first (matching the device path, which uploads the host
    bf16 cast and quantizes from it), fp32 group math, round-half-even
    (``np.rint`` == ``jnp.round``). Returns (q, scale) as host arrays so
    the engine can upload the 4-8x smaller int payload directly instead of
    pushing dense bf16 and quantizing on device.

    Computes in SLABS along the leading (stacked-layer) dim into
    preallocated outputs: whole-leaf numpy passes on a 2.9 GB leaf spill
    a chain of ~6 GB fp32 temporaries and ran 4x slower than the sum of
    their parts (measured: 105 s vs ~24 s slabbed)."""
    w = np.asarray(kernel)
    *lead, d_in, d_out = w.shape
    gs = min(cfg.group_size, d_in)
    while d_in % gs:
        gs //= 2
    G = d_in // gs
    qmax = float(2 ** (cfg.bits - 1) - 1)
    pack4 = cfg.bits == 4 and gs % 2 == 0
    n_rows = 1
    for d in lead:
        n_rows *= d
    wr = w.reshape(n_rows, d_in, d_out)
    q = np.empty((n_rows, G, gs // 2 if pack4 else gs, d_out),
                 np.uint8 if pack4 else np.int8)
    scale = np.empty((n_rows, G, 1, d_out), np.float32)
    rows = max(1, slab_elems // max(d_in * d_out, 1))
    for r0 in range(0, n_rows, rows):
        r1 = min(r0 + rows, n_rows)
        c = wr[r0:r1]
        if c.dtype != model_np_dtype:
            c = c.astype(model_np_dtype)
        c = c.astype(np.float32).reshape(r1 - r0, G, gs, d_out)
        absmax = np.max(np.abs(c), axis=-2, keepdims=True)
        s = np.maximum(absmax, 1e-12) / qmax
        qc = np.clip(np.rint(c / s), -qmax - 1, qmax)
        scale[r0:r1] = s
        if pack4:
            b = (qc.astype(np.int8) + 8).astype(np.uint8)
            q[r0:r1] = b[..., 0::2, :] | (b[..., 1::2, :] << 4)
        else:
            q[r0:r1] = qc.astype(np.int8)
    gs_out = gs // 2 if pack4 else gs
    return (q.reshape(*lead, G, gs_out, d_out),
            scale.reshape(*lead, G, 1, d_out))


# flip to the G-loop form when the batched partial product [tokens, G, out]
# would exceed this many fp32 elements (the einsum form materializes it:
# a 2048-token wave through llama2-7b's quantized lm_head would be
# 2048*32*32000*4B = 8.4 GB — an HBM OOM the loop form caps at [tokens, out])
_PARTIAL_ELEMS_LIMIT = 64 * 1024 * 1024


def quantized_matmul(x: jax.Array, qp: Dict[str, jax.Array]) -> jax.Array:
    """x [..., in] @ quantized kernel -> [..., out], scales factored out of
    each group's contraction so the int weights feed the MXU directly.

    ``DSTPU_PALLAS_WOQ=1`` routes 2-D int8 kernels through the
    builder-written Pallas kernel (ops/quantizer/pallas_woq_matmul.py) —
    opt-in: it beats this XLA form by ~7% on the attached chip but not
    bf16-dense (numbers in the kernel's docstring).

    NOTE (A/B protocol): the flag is read at TRACE time — a jitted caller
    that already compiled keeps the path it traced with, so flipping the
    env var mid-process has no effect on cached programs. A/B runs must
    use fresh processes or jax.clear_caches()."""
    q, scale = qp["q"], qp["scale"]
    stored_int8 = q.dtype == jnp.int8  # before unpack: the Pallas kernel
    # streams STORED bytes — feeding it unpacked int4 would materialize
    # the int8 copy in HBM as a pallas_call operand (opaque to fusion)
    if q.dtype == jnp.uint8:  # packed int4 storage
        q = _unpack_int4(q)
    G, gs, d_out = q.shape[-3:]
    import os
    if (os.environ.get("DSTPU_PALLAS_WOQ") == "1" and q.ndim == 3
            and stored_int8 and x.dtype == jnp.bfloat16
            and jax.default_backend() == "tpu"
            and d_out % 128 == 0
            # decode-shaped only: the kernel's VMEM accumulator is
            # (M, bn) f32 — a prefill wave's M in the thousands would
            # blow VMEM (and was never the bandwidth-bound case)
            and int(np.prod(x.shape[:-1])) <= 32):
        from ...ops.quantizer.pallas_woq_matmul import woq_matmul
        lead = x.shape[:-1]
        out = woq_matmul(x.reshape(-1, x.shape[-1]), q, scale)
        return out.reshape(*lead, d_out)
    xg = x.reshape(*x.shape[:-1], G, gs)
    wdt = x.dtype
    if jax.default_backend() == "cpu" and x.dtype == jnp.bfloat16:
        # XLA:CPU has no DotThunk for batched bf16 x bf16 -> f32 (G > 1
        # lowers to a batched dot); upcasting is trace-time static, so the
        # TPU program — where bf16 x bf16 -> f32 IS the native MXU mode —
        # is untouched
        xg, wdt = xg.astype(jnp.float32), jnp.float32
    tokens = int(np.prod(x.shape[:-1])) or 1
    if tokens * G * d_out <= _PARTIAL_ELEMS_LIMIT:
        # [..., G, out] partial products, scaled per group then summed
        y = jnp.einsum("...gi,gio->...go", xg, q.astype(wdt),
                       preferred_element_type=jnp.float32)
        y = y * scale.reshape(G, d_out).astype(jnp.float32)
        return jnp.sum(y, axis=-2).astype(x.dtype)

    # large-activation form: accumulate over CHUNKS of groups so the live
    # intermediate stays at [..., Gc, out] <= the limit (instead of G times
    # that), while each chunk still runs as one batched dot on the MXU
    gc = max(1, _PARTIAL_ELEMS_LIMIT // max(tokens * d_out, 1))
    while G % gc:
        gc -= 1
    sc = scale.reshape(G, d_out).astype(jnp.float32)
    xc = jnp.moveaxis(xg.reshape(*x.shape[:-1], G // gc, gc, gs),
                      -3, 0)                       # [nc, ..., gc, gs]
    qc = q.reshape(G // gc, gc, gs, d_out)
    scc = sc.reshape(G // gc, gc, d_out)

    def step(acc, args):
        xk, qk, sk = args
        y = jnp.einsum("...gi,gio->...go", xk, qk.astype(wdt),
                       preferred_element_type=jnp.float32)
        return acc + jnp.sum(y * sk, axis=-2), None

    acc = jnp.zeros(x.shape[:-1] + (d_out,), jnp.float32)
    acc, _ = jax.lax.scan(step, acc, (xc, qc, scc))
    return acc.astype(x.dtype)


def dequantize_kernel(qp: Dict[str, jax.Array], dtype=jnp.float32) -> jax.Array:
    q, scale = qp["q"], qp["scale"]
    if q.dtype == jnp.uint8:  # packed int4 storage
        q = _unpack_int4(q)
    *lead, G, gs, d_out = q.shape
    w = q.astype(jnp.float32) * scale
    return w.reshape(*lead, G * gs, d_out).astype(dtype)


def quantize_param_tree(params: Dict[str, Any], cfg: QuantizationConfig) -> Dict[str, Any]:
    """Replace each targeted ``{"kernel": ...}`` leaf with its quantized
    subtree; biases/norms/embeddings stay in the compute dtype."""

    def walk(tree, inside_target):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                if k == "kernel" and inside_target:
                    qp = quantize_kernel(v, cfg)
                    out["q"] = qp["q"]
                    out["scale"] = qp["scale"]
                else:
                    out[k] = walk(v, inside_target or k in cfg.targets)
            return out
        return tree

    return walk(params, False)


def dequantize_param_tree(params: Dict[str, Any], dtype=jnp.float32) -> Dict[str, Any]:
    def walk(tree):
        if isinstance(tree, dict):
            if "q" in tree and "scale" in tree:
                rest = {k: walk(v) for k, v in tree.items()
                        if k not in ("q", "scale")}
                return {"kernel": dequantize_kernel(
                    {"q": tree["q"], "scale": tree["scale"]}, dtype), **rest}
            return {k: walk(v) for k, v in tree.items()}
        return tree

    return walk(params)


def quantize_specs(specs: Dict[str, Any], params_q: Dict[str, Any],
                   mesh=None) -> Dict[str, Any]:
    """Derive PartitionSpecs for a quantized tree from the dense specs:
    kernel P(*lead, a, b) -> q P(*lead, None, a, b), scale P(*lead, None, None, b).

    The contraction dim [in] becomes [G, gs]; a contraction sharding ``a``
    lands on the WITHIN-GROUP axis gs (each device holds whole groups'
    slices and computes partial group sums — group boundaries never
    straddle shards, which they would on the G axis whenever G is not a
    multiple of the axis size). If gs itself is not divisible by the axis
    size, the leaf is replicated instead."""
    from jax.sharding import PartitionSpec as P

    def axis_size(name) -> int:
        if mesh is None or name is None:
            return 1
        names = (name,) if isinstance(name, str) else tuple(name)
        size = 1
        for n in names:
            size *= mesh.shape[n]
        return size

    def walk(spec_tree, q_tree):
        if isinstance(q_tree, dict) and "q" in q_tree and "scale" in q_tree:
            k = spec_tree["kernel"]
            *lead, a, b = tuple(k)
            gs = q_tree["q"].shape[-2]
            if a is not None and gs % max(axis_size(a), 1):
                a = None  # can't split within-group cleanly: replicate
            out = {"q": P(*lead, None, a, b), "scale": P(*lead, None, None, b)}
            for key, v in spec_tree.items():
                if key != "kernel":
                    out[key] = v
            return out
        if isinstance(q_tree, dict):
            return {key: walk(spec_tree[key], q_tree[key]) for key in q_tree}
        return spec_tree

    return walk(specs, params_q)


def quantize_placed(mesh, specs: Dict[str, Any], params: Dict[str, Any],
                    cfg: QuantizationConfig) -> Dict[str, Any]:
    """Quantize an already-placed param tree ON DEVICE, with output
    shardings derived from the dense specs — the dense tree is freed after
    the jit, so peak HBM is dense + quantized once, then quantized only."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    q_struct = jax.eval_shape(lambda p: quantize_param_tree(p, cfg), params)
    qspecs = quantize_specs(specs, q_struct, mesh)
    qshard = jax.tree.map(lambda s: NamedSharding(mesh, s), qspecs,
                          is_leaf=lambda s: isinstance(s, P))
    return jax.jit(lambda p: quantize_param_tree(p, cfg),
                   out_shardings=qshard, donate_argnums=0)(params)


def quantized_tree_bytes(params: Dict[str, Any]) -> int:
    # packed-int4 leaves are uint8, so plain itemsize accounting is exact;
    # the jnp.int4 branch remains for user-supplied native sub-byte arrays
    return sum(x.size * jnp.dtype(x.dtype).itemsize if x.dtype != jnp.int4
               else (x.size + 1) // 2
               for x in jax.tree.leaves(params))
