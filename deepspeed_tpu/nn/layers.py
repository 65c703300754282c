"""Functional layer library with sharding metadata.

This fills the role the reference fills with raw ``torch.nn`` plus its TP
wrappers (``module_inject/layers.py:16,62`` ``LinearAllreduce``/
``LinearLayer``): every layer is a small dataclass that can ``init`` a params
pytree, report a parallel ``specs`` pytree of ``PartitionSpec`` describing its
tensor-parallel layout over the ``model`` mesh axis, and apply itself purely.

Instead of *replacing* modules to introduce TP (the reference's AutoTP,
``module_inject/auto_tp.py:187``), layers declare ``shard='column'|'row'``
and XLA's SPMD partitioner inserts the all-reduces the reference does by hand
— a row-sharded Linear after a column-sharded one needs exactly one psum,
which XLA places automatically from the sharding constraints.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..runtime.topology import MODEL_AXIS

Params = Dict[str, Any]


def _init_dense(rng, shape, scale: float, dtype) -> jax.Array:
    return (jax.random.normal(rng, shape, dtype=jnp.float32) * scale).astype(dtype)


@dataclasses.dataclass(frozen=True)
class Linear:
    """Dense layer; ``shard='column'`` splits out_features over the model
    axis (reference ``LinearLayer``), ``shard='row'`` splits in_features and
    relies on a following psum (reference ``LinearAllreduce``)."""
    in_features: int
    out_features: int
    use_bias: bool = True
    shard: Optional[str] = None  # None | 'column' | 'row'
    init_scale: float = 0.02

    def init(self, rng, dtype=jnp.float32) -> Params:
        k_rng, _ = jax.random.split(rng)
        params = {"kernel": _init_dense(k_rng, (self.in_features, self.out_features), self.init_scale, dtype)}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.out_features,), dtype=dtype)
        return params

    def specs(self) -> Params:
        if self.shard == "column":
            kernel, bias = P(None, MODEL_AXIS), P(MODEL_AXIS)
        elif self.shard == "row":
            kernel, bias = P(MODEL_AXIS, None), P()
        else:
            kernel, bias = P(None, None), P()
        out = {"kernel": kernel}
        if self.use_bias:
            out["bias"] = bias
        return out

    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        if "q" in params:
            # weight-only-quantized kernel (inference/quantization): int
            # weights feed the matmul directly, scales factored per group
            from ..inference.quantization.quantization import quantized_matmul
            y = quantized_matmul(x, params)
        else:
            y = x @ params["kernel"].astype(x.dtype)
        if self.use_bias:
            y = y + params["bias"].astype(x.dtype)
        return y


@dataclasses.dataclass(frozen=True)
class Embedding:
    """Token embedding, vocab-sharded over the model axis when ``shard``."""
    num_embeddings: int
    features: int
    shard: bool = False
    init_scale: float = 0.02

    def init(self, rng, dtype=jnp.float32) -> Params:
        return {"embedding": _init_dense(rng, (self.num_embeddings, self.features), self.init_scale, dtype)}

    def specs(self) -> Params:
        return {"embedding": P(MODEL_AXIS, None) if self.shard else P(None, None)}

    def __call__(self, params: Params, ids: jax.Array) -> jax.Array:
        # mode="clip": jnp.take's default out-of-bounds mode is "fill",
        # which yields NaN rows for any id >= vocab — a silent poison that
        # surfaces steps later as a NaN loss. Clipping matches torch-side
        # frameworks' observable behavior closely enough while the engine
        # validates ids loudly on the host (engine._device_batch).
        return jnp.take(params["embedding"], ids, axis=0, mode="clip")

    def attend(self, params: Params, x: jax.Array) -> jax.Array:
        """Tied-unembedding logits."""
        return x @ params["embedding"].astype(x.dtype).T


@dataclasses.dataclass(frozen=True)
class LayerNorm:
    features: int
    eps: float = 1e-5
    use_bias: bool = True

    def init(self, rng, dtype=jnp.float32) -> Params:
        p = {"scale": jnp.ones((self.features,), dtype=dtype)}
        if self.use_bias:
            p["bias"] = jnp.zeros((self.features,), dtype=dtype)
        return p

    def specs(self) -> Params:
        out = {"scale": P()}
        if self.use_bias:
            out["bias"] = P()
        return out

    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        # Norm statistics in fp32 regardless of compute dtype (matches the
        # reference's fused LN kernels which accumulate in fp32).
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * params["scale"].astype(jnp.float32)
        if self.use_bias:
            y = y + params["bias"].astype(jnp.float32)
        return y.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class RMSNorm:
    """Pre-norm used by Llama-family models (reference rms_norm.cu).
    ``unit_offset``: the gain is ``1 + scale`` (EvaByte's
    ``norm_add_unit_offset``), so the stored scale starts at 0."""
    features: int
    eps: float = 1e-6
    unit_offset: bool = False

    def init(self, rng, dtype=jnp.float32) -> Params:
        fill = jnp.zeros if self.unit_offset else jnp.ones
        return {"scale": fill((self.features,), dtype=dtype)}

    def specs(self) -> Params:
        return {"scale": P()}

    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        gain = params["scale"].astype(jnp.float32)
        if self.unit_offset:
            gain = 1.0 + gain
        return (y * gain).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class HeadVectors:
    """One learned vector a head, ``[heads, features]``, drawn normal(0,
    ``init_scale``): EVA attention's ``phi`` and ``mu`` of a layer. The
    caller reads the array itself (``params["value"]``)."""
    heads: int
    features: int
    init_scale: float = 0.02

    def init(self, rng, dtype=jnp.float32) -> Params:
        return {"value": _init_dense(rng, (self.heads, self.features),
                                     self.init_scale, dtype)}

    def specs(self) -> Params:
        return {"value": P(MODEL_AXIS, None)}


@dataclasses.dataclass(frozen=True)
class HyperConnection:
    """One sub-layer's hyper-connection coefficients over ``streams`` residual
    streams of ``features`` (mHC, arXiv:2512.24880; ``TransformerLM.
    _hc_coefficients`` reads the arrays itself): ``phi`` ``[streams x features,
    streams^2 + 2 streams]`` (the columns: ``pre`` [n], ``post`` [n], ``res`` [n
    x n] row-major), ``bias`` of as many, ``alpha`` the three scalars (pre,
    post, res). A fresh layer mixes nothing: ``res`` starts at the identity
    (its off-diagonal logits at -8), ``pre`` at a half a stream, ``post`` at 1."""
    streams: int
    features: int
    init_scale: float = 0.02

    @property
    def outputs(self) -> int:
        return self.streams * (self.streams + 2)

    def init(self, rng, dtype=jnp.float32) -> Params:
        n = self.streams
        res = jnp.where(jnp.eye(n, dtype=bool), 0.0, -8.0).reshape(-1)
        return {"phi": _init_dense(rng, (n * self.features, self.outputs),
                                   self.init_scale, dtype),
                "bias": jnp.concatenate([jnp.zeros((2 * n,)), res]).astype(dtype),
                "alpha": jnp.full((3,), 0.01, dtype)}

    def specs(self) -> Params:
        return {"phi": P(None, None), "bias": P(None), "alpha": P(None)}


@dataclasses.dataclass(frozen=True)
class ScanParams:
    """A selective-scan layer's small arrays (Mamba-1, arXiv:2312.00752; the
    caller reads them itself): the depthwise convolution's ``conv`` ``[taps,
    channels]`` (tap k multiplies the input ``taps - 1 - k`` tokens back) and
    ``conv_bias``, ``A_log`` ``[channels, states]`` (``A = -exp(A_log)``, a
    channel's states starting at -1 .. -states), ``D`` (ones) and ``dt_bias``
    (the inverse softplus of a log-uniform draw in [1e-3, 1e-1]): the
    published initialisers."""
    channels: int
    states: int
    taps: int = 4
    #: Mamba-2 (arXiv:2405.21060): ``A_log``, ``D`` and ``dt_bias`` are ``[heads]``,
    #: one scalar a head (``A_log`` the log of a uniform draw in [1, 16]), and
    #: ``channels`` the convolution's alone; 0: Mamba-1's arrays, above
    heads: int = 0

    def init(self, rng, dtype=jnp.float32) -> Params:
        k_conv, k_dt = jax.random.split(rng)
        bound = self.taps ** -0.5
        dt = jnp.exp(jax.random.uniform(k_dt, (self.heads or self.channels,), jnp.float32)
                     * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
        if self.heads:
            return {
                "conv": jax.random.uniform(k_conv, (self.taps, self.channels), jnp.float32,
                                           -bound, bound).astype(dtype),
                "conv_bias": jnp.zeros((self.channels,), dtype),
                "A_log": jnp.log(jax.random.uniform(
                    jax.random.fold_in(k_dt, 1), (self.heads,), jnp.float32, 1.0, 16.0)
                ).astype(dtype),
                "D": jnp.ones((self.heads,), dtype),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)}
        return {
            "conv": jax.random.uniform(k_conv, (self.taps, self.channels), jnp.float32,
                                       -bound, bound).astype(dtype),
            "conv_bias": jnp.zeros((self.channels,), dtype),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, self.states + 1, dtype=jnp.float32)),
                                      (self.channels, self.states)).astype(dtype),
            "D": jnp.ones((self.channels,), dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)}

    def specs(self) -> Params:
        if self.heads:      # (B and C's channels are every head's: nothing divided)
            return {name: P() for name in ("conv", "conv_bias", "A_log", "D", "dt_bias")}
        return {"conv": P(None, MODEL_AXIS), "conv_bias": P(MODEL_AXIS),
                "A_log": P(MODEL_AXIS, None), "D": P(MODEL_AXIS), "dt_bias": P(MODEL_AXIS)}


@dataclasses.dataclass(frozen=True)
class DeltaParams:
    """A Kimi Delta Attention layer's small arrays (arXiv:2510.26692; the caller
    reads them itself): the three depthwise convolutions ``conv_q`` / ``conv_k`` /
    ``conv_v`` ``[taps, channels]`` (tap k multiplies the input ``taps - 1 - k``
    tokens back; no bias), ``A_log`` ``[heads]`` (``A = -exp(A_log)``, the log of a
    uniform draw in [1, 16]) and ``dt_bias`` ``[channels]`` (the inverse softplus of
    a log-uniform draw in [1e-3, 1e-1]): Mamba-2's initialisers, a channel's bias
    where Mamba-2 has a head's."""
    heads: int
    channels: int
    taps: int = 4

    def init(self, rng, dtype=jnp.float32) -> Params:
        k_conv, k_dt = jax.random.split(rng)
        bound = self.taps ** -0.5
        dt = jnp.exp(jax.random.uniform(k_dt, (self.channels,), jnp.float32)
                     * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
        convs = {name: jax.random.uniform(key, (self.taps, self.channels), jnp.float32,
                                          -bound, bound).astype(dtype)
                 for name, key in zip(("conv_q", "conv_k", "conv_v"),
                                      jax.random.split(k_conv, 3))}
        return {**convs,
                "A_log": jnp.log(jax.random.uniform(
                    jax.random.fold_in(k_dt, 1), (self.heads,), jnp.float32, 1.0, 16.0)
                ).astype(dtype),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)}

    def specs(self) -> Params:
        return {name: P() for name in ("conv_q", "conv_k", "conv_v", "A_log", "dt_bias")}


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)


def silu(x: jax.Array) -> jax.Array:
    return jax.nn.silu(x)


def rotary_embedding(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
                     style: str = "half", freqs: Optional[jax.Array] = None,
                     scale: float = 1.0) -> jax.Array:
    """Apply rotary position embeddings.

    x: [..., seq, heads, head_dim]; positions: [..., seq].
    ``style='half'`` pairs dim i with dim i+half (llama/gpt-neox "rotate
    half"); ``style='interleaved'`` pairs adjacent dims (2i, 2i+1) — gpt-j's
    "rotate every two". TPU-native equivalent of the reference's
    ``apply_rotary_pos_emb.cu``; left to XLA fusion (elementwise, fuses into
    the surrounding matmuls). ``freqs`` [head_dim / 2] replaces the plain
    ``theta ** (-2i / head_dim)`` (a scaled rope's), ``scale`` multiplies
    cos and sin.
    """
    head_dim = x.shape[-1]
    half = head_dim // 2
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, half]
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    if style == "interleaved":
        x1, x2 = x[..., 0::2], x[..., 1::2]
        y1 = x1 * cos - x2 * sin
        y2 = x2 * cos + x1 * sin
        # re-interleave: [..., half, 2] -> [..., head_dim]
        return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def dropout(rng, x: jax.Array, rate: float, deterministic: bool) -> jax.Array:
    if deterministic or rate == 0.0:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def init_tree(layers: Dict[str, Any], rng, dtype=jnp.float32) -> Tuple[Params, Params]:
    """Init a dict of layers → (params, specs) trees with per-layer rng split."""
    params, specs = {}, {}
    rngs = jax.random.split(rng, len(layers))
    for r, (name, layer) in zip(rngs, sorted(layers.items())):
        params[name] = layer.init(r, dtype)
        specs[name] = layer.specs()
    return params, specs
