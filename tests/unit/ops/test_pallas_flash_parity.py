"""The plain flash pair (``flash_fwd`` / ``flash_bwd``) against the fp32 XLA
reference over the training feature matrix (`flash_cases.CASES`): the old
`test_pallas_flash.py`'s first section, forward and gradient parity in fp32,
bf16 inputs, ``q_offset`` static and traced, the LSE residual, the ring's
merge, remat, ALiBi's contract and the ``DSTPU_ATTN`` gates. Tolerances:
`flash_cases`."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import (_xla_attention,
                                                     alibi_slopes)
from deepspeed_tpu.ops.transformer.pallas_flash import (
    MASK_VALUE, flash_attention_kernel, flash_attention_with_lse,
    merge_partials)
from tests.unit.ops.flash_cases import (BF16_GRAD_TOL, BF16_TOL, CASES, FP32_TOL,
                                         GRAD_TOL, _qkv, _run_pair, out_and_grads)


@pytest.mark.parametrize("name", sorted(CASES))
@pytest.mark.parametrize("kvH", [1, 2, 8])
def test_forward_parity_fp32(eight_devices, name, kvH):
    q, k, v, reference, kernel = _run_pair(CASES[name], kvH=kvH)
    got, want = jax.jit(lambda q, k, v: (kernel(q, k, v), reference(q, k, v)))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **FP32_TOL)


@pytest.mark.parametrize("name", sorted(CASES))
def test_grad_parity_fp32(eight_devices, name):
    q, k, v, reference, kernel = _run_pair(CASES[name])
    (_, g_ker), (_, g_ref) = out_and_grads((kernel, reference), None, q, k, v)
    for a, b, nm in zip(g_ker, g_ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   err_msg=f"{name}:{nm}", **GRAD_TOL)


@pytest.mark.parametrize("name", ["causal", "window", "alibi",
                                  "segids_causal", "tiles_256x512"])
def test_bf16_inputs_vs_fp32_reference(eight_devices, name):
    """bf16 training inputs against the fp32 reference: the fp32
    accumulation contract (errors stay at input-quantization scale)."""
    q, k, v, reference, kernel = _run_pair(CASES[name], dtype=jnp.bfloat16,
                                           seed=3)
    got, g_ker = out_and_grads(kernel, None, q, k, v)
    want, g_ref = out_and_grads(reference, None, q.astype(jnp.float32),
                                k.astype(jnp.float32), v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(want), **BF16_TOL)
    for a, b, nm in zip(g_ker, g_ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   err_msg=f"{name}:{nm}", **BF16_GRAD_TOL)


def test_q_offset_matches_chunked_contract(eight_devices):
    """q_offset = absolute position of q row 0 (bottom-right alignment):
    a query chunk against the full K must match the XLA path's q_offset
    semantics, forward and grads — this is the contract the Ulysses and
    ring calls rely on."""
    q, k, v = _qkv(S=256, kvH=2, seed=5)
    qc = q[:, 128:]
    scale = 1.0 / (q.shape[-1] ** 0.5)

    def loss_ref(qc, k, v):
        return jnp.sum(jnp.square(_xla_attention(
            qc, k, v, True, scale, None, q_offset=128)))

    def loss_ker(qc, k, v):
        return jnp.sum(jnp.square(flash_attention_kernel(
            qc, k, v, causal=True, scale=scale, q_offset=128,
            interpret=True)))

    ref, g_ref = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(qc, k, v)
    got, g_ker = jax.value_and_grad(loss_ker, argnums=(0, 1, 2))(qc, k, v)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    for a, b in zip(g_ker, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **GRAD_TOL)


def test_traced_q_offset_and_window(eight_devices):
    """q_offset and window ride scalar prefetch, so TRACED values (the
    ring per-hop offsets, gpt-neo's scanned per-layer windows) must work
    under jit without retracing the kernel per value."""
    q, k, v = _qkv(S=128, kvH=2, seed=6)
    scale = 1.0 / (q.shape[-1] ** 0.5)

    @jax.jit
    def f(q, k, v, off, w):
        return flash_attention_kernel(q, k, v, causal=True, scale=scale,
                                      q_offset=off, window=w,
                                      interpret=True)

    for off, w in ((0, 0), (0, 32), (64, 48)):
        qq = q if off == 0 else q[:, :64]
        ref = _xla_attention(qq, k, v, True, scale, None,
                             window=jnp.asarray(w, jnp.int32),
                             q_offset=off)
        got = f(qq, k, v, jnp.asarray(off, jnp.int32),
                jnp.asarray(w, jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   err_msg=f"off={off} w={w}", **FP32_TOL)


def test_lse_matches_reference_logsumexp(eight_devices):
    """The saved LSE residual must be the true per-row logsumexp of the
    masked scaled logits — ring accumulation and the backward both build
    on it."""
    q, k, v = _qkv(B=1, S=128, H=2, kvH=2, D=64, seed=7)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    _, lse = flash_attention_with_lse(q, k, v, causal=True, scale=scale,
                                      interpret=True)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.arange(128)[:, None] >= jnp.arange(128)[None, :]
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    ref = jax.scipy.special.logsumexp(logits, axis=-1)  # [B, H, S]
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # ~21 s: the hop/LSE merge contract is exercised
# end-to-end by tests/unit/runtime/test_ring_attention.py
# (ring_matches_dense, ring_flash_body parity and gradients); this is the
# kernel-level restatement of the same accumulation identity.
def test_ring_lse_accumulation_equivalence(eight_devices):
    """The ring-attention hop contract: per-hop kernel partials merged via
    LSE accumulation (merge_partials) — including hops entirely in the
    future (all-masked: lse == MASK_VALUE sentinel) — must equal one-shot
    attention over the concatenated keys, forward and grads."""
    B, S, H, kvH, D = 2, 128, 4, 2, 64
    q, k, v = _qkv(B=B, S=S, H=H, kvH=kvH, D=D, seed=8)
    scale = 1.0 / (D ** 0.5)
    sp, s = 4, S // 4

    def ring_merged(q, k, v):
        """Emulates _ring_local_flash for the rank holding the LAST q
        shard (sees every block) and rank 0 (sees only its own)."""
        outs = []
        for r in (sp - 1, 0):
            qr = q[:, r * s:(r + 1) * s]
            from deepspeed_tpu.ops.transformer.pallas_flash import (
                flash_attention_with_lse)
            o = jnp.zeros_like(qr)
            lse = jnp.full((B, H, s), MASK_VALUE, jnp.float32)
            for owner in range(sp):
                o_h, lse_h = flash_attention_with_lse(
                    qr, k[:, owner * s:(owner + 1) * s],
                    v[:, owner * s:(owner + 1) * s],
                    causal=True, scale=scale, q_offset=(r - owner) * s,
                    interpret=True)
                o, lse = merge_partials(o, lse, o_h, lse_h)
            outs.append(o)
        return outs

    ref = _xla_attention(q, k, v, True, scale, None)
    got_last, got_first = ring_merged(q, k, v)
    np.testing.assert_allclose(np.asarray(got_last),
                               np.asarray(ref[:, -s:]), **FP32_TOL)
    np.testing.assert_allclose(np.asarray(got_first),
                               np.asarray(ref[:, :s]), **FP32_TOL)

    # grads flow through the merge's LSE weights
    def loss_merged(q, k, v):
        a, b = ring_merged(q, k, v)
        return jnp.sum(jnp.square(a)) + jnp.sum(jnp.square(b))

    def loss_ref(q, k, v):
        r = _xla_attention(q, k, v, True, scale, None)
        return (jnp.sum(jnp.square(r[:, -s:]))
                + jnp.sum(jnp.square(r[:, :s])))

    g_m = jax.grad(loss_merged, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_m, g_r, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   err_msg=nm, **GRAD_TOL)


def test_alibi_slopes_are_nondifferentiable_by_contract(eight_devices):
    """ALiBi slopes are a fixed positional schedule (Press et al. do not
    learn them); the kernel stop-gradients them EXPLICITLY — this test
    pins that contract so the zero cotangent reads as intent, not a bug.
    Training slopes as parameters requires the XLA path."""
    q, k, v = _qkv(S=128, kvH=2, seed=11)
    sl = jnp.asarray(alibi_slopes(q.shape[2]))
    g = jax.grad(lambda s: jnp.sum(jnp.square(flash_attention_kernel(
        q, k, v, causal=True, alibi_slopes=s, interpret=True))))(sl)
    np.testing.assert_array_equal(np.asarray(g), np.zeros_like(g))


def test_unknown_dstpu_attn_rejected(eight_devices, monkeypatch):
    """A typo'd escape hatch must fail loudly, in both dispatch sites."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    q, k, v = _qkv(S=128, kvH=2, seed=12)
    monkeypatch.setenv("DSTPU_ATTN", "XLA")
    with pytest.raises(ValueError, match="DSTPU_ATTN"):
        attn_mod.flash_attention(q, k, v, causal=True)


def test_dispatch_env_gates(eight_devices, monkeypatch):
    """DSTPU_ATTN routes: 'pallas' forces the in-repo kernel on the CPU
    mesh; 'xla' keeps the XLA path; both agree numerically."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    q, k, v = _qkv(S=128, kvH=2, seed=10)
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    got = attn_mod.flash_attention(q, k, v, causal=True)
    monkeypatch.setenv("DSTPU_ATTN", "xla")
    ref = attn_mod.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **FP32_TOL)
