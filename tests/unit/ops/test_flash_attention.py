"""The XLA training attention paths (ops/transformer/attention.py): the
query-chunked route, XLA's memory bound at long sequence, against the
one-shot one — same math, forward and backward, with and without segment
ids. The in-repo kernel is tests/unit/ops/test_pallas_flash_*.py's (one file
a kernel), the route table test_pallas_flash_rules.py's."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import (_xla_attention,
                                                     _xla_attention_chunked)


def _qkv(B=2, S=256, H=4, kvH=2, D=64, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32) * 0.3
    k = jnp.asarray(rng.normal(size=(B, S, kvH, D)), jnp.float32) * 0.3
    v = jnp.asarray(rng.normal(size=(B, S, kvH, D)), jnp.float32) * 0.3
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_chunked_xla_matches_unchunked(eight_devices, causal):
    """One query chunk at a time must equal the one-shot XLA attention
    exactly (same math, bounded memory), forward and backward."""
    q, k, v = _qkv(S=256, kvH=2, seed=5)
    scale = 1.0 / (q.shape[-1] ** 0.5)

    def f_ref(q, k, v):
        return jnp.sum(jnp.square(_xla_attention(q, k, v, causal, scale,
                                                 None)))

    def f_chk(q, k, v):
        return jnp.sum(jnp.square(_xla_attention_chunked(
            q, k, v, causal, scale, None, chunk=64)))

    ref, g_ref = jax.jit(jax.value_and_grad(f_ref, argnums=(0, 1, 2)))(q, k, v)
    got, g_chk = jax.jit(jax.value_and_grad(f_chk, argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    for a, b in zip(g_chk, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_chunked_xla_with_segment_ids(eight_devices):
    q, k, v = _qkv(B=2, S=128, kvH=2, seed=7)
    seg = jnp.asarray(np.random.default_rng(0).integers(0, 2, size=(2, 128)))
    scale = 1.0 / (q.shape[-1] ** 0.5)
    ref = _xla_attention(q, k, v, False, scale, seg)
    got = _xla_attention_chunked(q, k, v, False, scale, seg, chunk=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
