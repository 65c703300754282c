"""Chip rehearsals kept as tests (ISSUE 21; on-chip-measurement guide §2).

1. Every Pallas kernel that is on a default path when ``default_backend()
   == "tpu"`` is compiled by the chip's own compiler for a DESCRIBED
   ``v5e:2x2`` (no chip attached), at the widths ``chip_smoke.py`` runs —
   interpret mode accepts programs Mosaic refuses (rank-1 blocks off the
   128-lane tiling, ``cumsum``, scalar stores into VMEM), and this is where
   such a refusal shows without chip time. Skipped with the reason where the
   topology cannot be described. A compile that passes is not a chip run.
2. ``chip_smoke``'s phase functions are driven at tiny size on the CPU
   mesh (the steering lives here, not in an option of the script).
3. ``python chip_smoke.py`` without a TPU exits non-zero and prints no
   result line.

tests/conftest.py turns the persistent compile cache off for the session: a
program compiled for a described device is written to the cache but cannot
be read back without a chip.
"""

import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

REAL = chip_smoke.Sizes()
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def chip():
    """``sds(shape, dtype)`` placing an abstract array on one described
    v5e chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no compile-only client here
        pytest.skip(f"cannot describe a v5e:2x2 topology: "
                    f"{type(e).__name__}: {str(e)[:200]}")
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def compile_for_chip(fn, *args):
    """Raises what the chip's compiler would raise."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


class TestCompilesForV5e:

    @pytest.mark.parametrize("shape", [
        REAL.flash,                 # TinyLlama GQA heads at long sequence
        (1, 4096, 32, 32, 128),     # llama2-7b MHA heads
        REAL.flash_train,           # gpt2-large micro 4: the benchmark cell
    ])
    def test_flash_fwd_bwd(self, chip, shape):
        from deepspeed_tpu.ops.transformer.pallas_flash import \
            flash_attention_kernel
        B, S, H, kvH, D = shape

        def loss(q, k, v):
            return jnp.sum(flash_attention_kernel(
                q, k, v, causal=True, interpret=False).astype(F32))

        compile_for_chip(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                         chip((B, S, H, D), BF16), chip((B, S, kvH, D), BF16),
                         chip((B, S, kvH, D), BF16))

    @pytest.mark.parametrize("window", [2048, None])
    def test_flash_fwd_bwd_packed_documents_at_16k(self, chip, window):
        """The trinity-mini cell's two kinds of layer: 32 query heads over 4
        key heads of 128 at 16,384 with segment ids, under a static window
        (grids cut to it) and without one (dq's partials a key head at a
        time)."""
        from deepspeed_tpu.ops.transformer.pallas_flash import \
            flash_attention_kernel
        B, S, H, kvH, D = 1, 16384, 32, 4, 128

        def loss(q, k, v, seg):
            return jnp.sum(flash_attention_kernel(
                q, k, v, causal=True, segment_ids=seg, window=window,
                interpret=False).astype(F32))

        compile_for_chip(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                         chip((B, S, H, D), BF16), chip((B, S, kvH, D), BF16),
                         chip((B, S, kvH, D), BF16), chip((B, S), I32))

    def test_flash_fwd_bwd_two_widths_at_8k(self, chip):
        """The xing4-29b-a4b cell's attention core (PR 55): 32 heads whose
        queries and keys are 192 wide (one and a half lane tiles) and whose
        values are 128, at 8,192 with segment ids: the two-width launches, by
        heads, the backward's dq added to in place in a 256-lane tile."""
        from deepspeed_tpu.ops.transformer.pallas_flash import \
            flash_attention_kernel
        B, S, H, D, Dv = 1, 8192, 32, 192, 128

        def loss(q, k, v, seg):
            return jnp.sum(flash_attention_kernel(
                q, k, v, causal=True, segment_ids=seg, interpret=False).astype(F32))

        fn = jax.value_and_grad(loss, argnums=(0, 1, 2))
        args = (chip((B, S, H, D), BF16), chip((B, S, H, D), BF16),
                chip((B, S, H, Dv), BF16), chip((B, S), I32))
        compile_for_chip(fn, *args)
        text = jax.jit(fn).lower(*args).as_text()
        assert "flash_fwd_mla" in text and "flash_bwd_mla" in text

    @pytest.mark.parametrize("window", [None, 512], ids=["full", "window"])
    def test_flash_fwd_bwd_keys_of_64_values_of_128_at_16k(self, chip, window):
        """The phi4-mini-flash-reasoning cell's attention core (PR 57): ONE half
        of a layer's pairs, 20 query heads over 10 key heads of 64 (half a lane
        tile) with the pair's two value heads side by side (128), at 16,384 with
        segment ids, under the static window and without: the two-width launches
        under their own tag."""
        from deepspeed_tpu.ops.transformer.pallas_flash import \
            flash_attention_kernel
        B, S, H, kvH, D, Dv = 1, 16384, 20, 10, 64, 128

        def loss(q, k, v, seg):
            return jnp.sum(flash_attention_kernel(
                q, k, v, causal=True, segment_ids=seg, window=window, tag="diff",
                interpret=False).astype(F32))

        fn = jax.value_and_grad(loss, argnums=(0, 1, 2))
        args = (chip((B, S, H, D), BF16), chip((B, S, kvH, D), BF16),
                chip((B, S, kvH, Dv), BF16), chip((B, S), I32))
        compile_for_chip(fn, *args)
        text = jax.jit(fn).lower(*args).as_text()
        name = "diff" if window is None else "diff_window"
        assert f"flash_fwd_{name}" in text and f"flash_bwd_{name}" in text

    def test_selective_scan_fwd_bwd_at_16k(self, chip):
        """The phi4-mini-flash-reasoning cell's scan (PR 57): 16,384 rows of 5120
        channels of 16 states in chunks of 128 and tiles of 512, forward and
        backward, and no value of rows x channels x states in the program."""
        from deepspeed_tpu.ops.transformer import pallas_scan
        R, Di, N = 16384, 5120, 16

        def loss(a, dt_raw, A, B, C, D, dt_bias, first):
            return jnp.sum(pallas_scan.scan_kernel(
                a, dt_raw, A, B, C, D, dt_bias, first, interpret=False).astype(F32))

        fn = jax.value_and_grad(loss, argnums=tuple(range(7)))
        args = (chip((R, Di), BF16), chip((R, Di), BF16), chip((Di, N), F32),
                chip((R, N), BF16), chip((R, N), BF16), chip((Di,), F32),
                chip((Di,), F32), chip((R,), I32))
        compile_for_chip(fn, *args)
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text
        assert "[16384,5120,16]" not in text and "[16384,16,5120]" not in text

    def test_ssd_fwd_bwd_at_32k(self, chip):
        """The granite-4.0-h-micro cell's state-space-duality core (PR 65): 32,768
        rows of 64 heads of 64 over 128 states in one group, forward and backward,
        and neither the decay mask nor the states a token in the program."""
        from deepspeed_tpu.ops.transformer import pallas_ssd
        R, H, P, N = 32768, 64, 64, 128

        def loss(a, dt, A, B, C, D, first):
            return jnp.sum(pallas_ssd.ssd_kernel(
                a, dt, A, B, C, D, first, interpret=False).astype(F32))

        fn = jax.value_and_grad(loss, argnums=tuple(range(6)))
        args = (chip((R, H * P), BF16), chip((R, H), F32), chip((H,), F32),
                chip((R, N), BF16), chip((R, N), BF16), chip((H,), F32), chip((R,), I32))
        text = jax.jit(fn).lower(*args).compile().as_text()    # (one compile: 40 s)
        assert "tpu_custom_call" in text and "ssd_fwd" in text and "ssd_bwd" in text
        assert "[32768,64,64,128]" not in text and "[128,256,256,64]" not in text

    def test_kda_fwd_bwd_at_32k(self, chip):
        """The kimi-linear-48b-a3b cell's Kimi Delta Attention core (PR 68): 32,768
        rows of 32 heads with keys and values of 128, forward and backward (the
        backward is ``jax.vjp`` of the chunk traced into the kernel: every product in
        it must be one Mosaic takes), and no state a token in the program."""
        from deepspeed_tpu.ops.transformer import pallas_kda
        R, H, D = 32768, 32, 128

        def loss(q, k, v, g, beta, first):
            return jnp.sum(pallas_kda.kda_kernel(q, k, v, g, beta, first,
                                                 interpret=False).astype(F32))

        fn = jax.value_and_grad(loss, argnums=tuple(range(5)))
        args = (chip((R, H * D), BF16), chip((R, H * D), BF16), chip((R, H * D), BF16),
                chip((R, H * D), F32), chip((R, H), F32), chip((R,), I32))
        text = jax.jit(fn).lower(*args).compile().as_text()    # (one compile: 10 s)
        assert "tpu_custom_call" in text and "kda_fwd" in text and "kda_bwd" in text
        assert "[32768,32,128,128]" not in text

    def test_blockdiff_attention_at_8k(self, chip, monkeypatch):
        """The sdar-30b-a3b cell's attention core: 32 query heads over 4 key
        heads of 128, 16,384 rows (a clean and a noised copy of 8,192
        positions) under the block-diffusion mask with b 4 and documents:
        the flash pair over the clean keys, the own-block einsum and the
        merge, under the launches' own names."""
        from deepspeed_tpu.ops.transformer import attention
        B, L, H, kvH, D = 1, 8192, 32, 4, 128
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("DSTPU_ATTN", raising=False)

        def loss(q, k, v, doc):
            return jnp.sum(attention.blockdiff_attention(q, k, v, 4, doc).astype(F32))

        args = (chip((B, 2 * L, H, D), BF16), chip((B, 2 * L, kvH, D), BF16),
                chip((B, 2 * L, kvH, D), BF16), chip((B, L), I32))
        fn = jax.value_and_grad(loss, argnums=(0, 1, 2))
        compile_for_chip(fn, *args)
        text = jax.jit(fn).lower(*args).as_text()
        assert "flash_fwd_blockdiff" in text and "flash_bwd_blockdiff" in text

    def test_selected_attention_at_16k(self, chip, monkeypatch):
        """The keye-vl2-30b-a3b cell's attention core: 32 query heads over 4
        key heads of 128, one row of 16,384 under documents, the flash pair
        unpacking the selection's operand (bits: int8 ``[1, 2048, 16384]``), under
        the launches' own names."""
        from deepspeed_tpu.ops.transformer import attention
        B, L, H, kvH, D = 1, 16384, 32, 4, 128
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("DSTPU_ATTN", raising=False)

        def loss(q, k, v, sel, doc):
            o, lse = attention.selected_attention(q, k, v, sel, doc, 2048)
            return jnp.sum(o.astype(F32)) + jnp.sum(lse)

        args = (chip((B, L, H, D), BF16), chip((B, L, kvH, D), BF16),
                chip((B, L, kvH, D), BF16), chip((B, L // 8, L), jnp.int8), chip((B, L), I32))
        fn = jax.value_and_grad(loss, argnums=(0, 1, 2))
        compile_for_chip(fn, *args)
        text = jax.jit(fn).lower(*args).as_text()
        assert "flash_fwd_dsa" in text and "flash_bwd_dsa" in text

    def test_indexer_kl_at_16k(self, chip, monkeypatch):
        """The keye-vl2-30b-a3b cell's indexer objective: one row of 16,384, 32
        query heads over 4 key heads of 128, an indexer of 16 heads of 64, the
        selection's operand of bits: the differentiated forward launches the
        Pallas pair, value and three gradients, under the launches' own names."""
        from deepspeed_tpu.ops.transformer import attention
        B, L, H, kvH, D, J, d = 1, 16384, 32, 4, 128, 16, 64
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("DSTPU_ATTN", raising=False)
        made = attention.plan((B, L, H, D), (B, L, kvH, D), "tpu", "", selected=2048)
        assert attention.kl_launch(made, L)[0] == "kernel"

        def loss(q_idx, k_idx, w, q, k, lse, sel, doc):
            return attention.indexer_kl(q_idx, k_idx, w, q, k, lse, sel, doc, D ** -0.5)

        args = (chip((B, L, J, d), BF16), chip((B, L, d), BF16), chip((B, L, J), F32),
                chip((B, L, H, D), BF16), chip((B, L, kvH, D), BF16),
                chip((B, H, L), F32), chip((B, L // 8, L), jnp.int8), chip((B, L), I32))
        fn = jax.value_and_grad(loss, argnums=(0, 1, 2))
        compile_for_chip(fn, *args)
        text = jax.jit(fn).lower(*args).as_text()
        assert "indexer_kl_fwd" in text and "indexer_kl_bwd" in text

    def test_dsa_select_at_16k(self, chip, monkeypatch):
        """The keye-vl2-30b-a3b cell's selection: one row of 16,384 under
        documents, an indexer of 16 heads of 64, topk 2048: `dsa_select` takes
        the one launch (the scores' slots 8 MB of VMEM, the counting passes,
        the triangular product of the tie rule, the int8 block ORed a plane at
        a time), under the launch's own name."""
        from deepspeed_tpu.ops.transformer import attention
        B, L, J, d = 1, 16384, 16, 64
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("DSTPU_ATTN", raising=False)
        assert attention.select_launch(L, "tpu", "") == ("kernel", (128, 512))
        fn = lambda q_idx, k_idx, w, doc: attention.dsa_select(q_idx, k_idx, w, doc, 2048)
        args = (chip((B, L, J, d), BF16), chip((B, L, d), BF16), chip((B, L, J), F32),
                chip((B, L), I32))
        compile_for_chip(fn, *args)
        assert "dsa_select" in jax.jit(fn).lower(*args).as_text()

    def test_eva_attention_at_32k(self, chip, monkeypatch):
        """The evabyte-6.5b cell's attention: 32 heads of 128 over a row of
        32,768 under EVA's mask with a window of 2048 and chunks of 16: the
        summaries, the exact keys a window at a time, the 2,048 summaries
        under a q-block's limit and the merge, under the launches' own
        names."""
        from deepspeed_tpu.ops.transformer import attention
        B, L, H, D, W, c = 1, 32768, 32, 128, 2048, 16
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("DSTPU_ATTN", raising=False)
        assert attention.choose_route((B, L, H, D), (B, L, H, D), "tpu", "",
                                      eva=(W, c)) == "kernel"

        def loss(q, k, v, phi, mu):
            kbar, vbar = attention.eva_summaries(k, v, phi, mu, c)
            return jnp.sum(attention.eva_attention(q, k, v, kbar, vbar, W, c).astype(F32))

        args = (chip((B, L, H, D), BF16),) * 3 + (chip((H, D), BF16),) * 2
        fn = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
        compile_for_chip(fn, *args)
        text = jax.jit(fn).lower(*args).as_text()
        for name in ("flash_fwd_eva_local", "flash_bwd_eva_local",
                     "flash_fwd_eva_far", "flash_bwd_eva_far"):
            assert name in text

    @pytest.mark.parametrize("m,k,n,g", [
        (32768, 2048, 1024, 64),    # olmoe-1b-7b.train.seq4k, wi_gate / wi_up
        (32768, 1024, 2048, 64),    # its wo
        (36864, 2048, 1408, 8),     # instella-moe-16b-a3b.train.seq8k: 11 x 128
        (36864, 1408, 2048, 8),
        (49152, 2048, 768, 16),     # sdar-30b-a3b.train.bd8k: the narrowest experts
        (49152, 768, 2048, 16),
    ])
    def test_grouped_matmul_fwd_bwd(self, chip, m, k, n, g):
        """The three grouped-matmul kernels at the MoE cells' shapes, with
        the tiles and the VMEM limit ``choose_tiles`` gives them, and under
        the names the trace's reader finds a step's products by."""
        from deepspeed_tpu.ops.transformer import pallas_gmm
        assert pallas_gmm.choose_route(m, k, n, g, BF16, "tpu", 1) == "kernel"

        def loss(rows, stack, sizes, ct):
            return jnp.sum(pallas_gmm.kernel_grouped_matmul(
                rows, stack, sizes, interpret=False).astype(F32) * ct)

        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            chip((m, k), BF16), chip((g, k, n), BF16), chip((g,), I32),
            chip((m, n), F32)).compile().as_text()
        for name in ("ragged-dot-gmm-fwd", "ragged-dot-gmm-dlhs", "ragged-dot-gmm-dw"):
            assert name in text

    @pytest.mark.parametrize("scaled", [True, False], ids=["combine", "dispatch-bwd"])
    @pytest.mark.parametrize("rows,tokens,h", [
        (36864, 16384, 2048),       # instella-moe-16b-a3b.train.seq8k, 6 a token
        (49152, 16384, 2048),       # trinity-mini.train.seq16k, 8 a token
    ])
    def test_segment_sum(self, chip, rows, tokens, h, scaled):
        """A share's rows back to the tokens at the two share cells' shapes,
        with the tiles ``choose_tiles`` gives, weighted (the combine) and not
        (the dispatch's backward)."""
        from deepspeed_tpu.ops.transformer import pallas_segment_sum as S
        assert S.choose_route(rows, tokens, h, BF16, "tpu", 1) == "kernel"

        def back(x, segment, scale, filled):
            return S.kernel_segment_sum(x, segment, scale if scaled else None, filled,
                                        tokens, interpret=False)

        text = jax.jit(back).lower(chip((rows, h), BF16), chip((rows,), I32),
                                   chip((rows,), F32), chip((), I32)).compile().as_text()
        assert "segment-sum" in text and "tpu_custom_call" in text

    @pytest.mark.parametrize("phi_dtype", [BF16, F32], ids=["phi16", "phi32"])
    def test_hc_coeff(self, chip, phi_dtype):
        """The streams' coefficients at the xing4 cell's shape (8,192 rows of
        4 x 3584) with the tiles ``choose_tiles`` gives, forward and backward:
        the launch, and ``hc/coeff`` in the ``op_name`` of the launch and of
        the backward's products when the model's scope is around the call."""
        from deepspeed_tpu import models
        from deepspeed_tpu.ops.transformer import pallas_hc
        rows, K = 8192, 4 * 3584
        assert pallas_hc.choose_route(rows, K, BF16, "tpu", 1) == "kernel"
        model = models.xing4_model("xing4-tiny", dtype=BF16, remat=False, hidden_size=3584)

        def loss(X, phi, alpha, bias, ct):
            # (`_hc_coefficients` with the route the chip takes: the backend
            # here is the CPU's)
            with jax.named_scope("hc"), jax.named_scope("coeff"):
                m = pallas_hc.coeff_product(X, phi, model.config.hc_eps,
                                            "kernel", interpret=False)
            pre, post, res = model._hc_mixes({"alpha": alpha, "bias": bias}, m)
            return jnp.sum(pre) + jnp.sum(post) + jnp.sum(res * ct)

        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
            chip((1, rows, K), BF16), chip((K, 24), phi_dtype), chip((3,), F32),
            chip((24,), F32), chip((4, 4, 1, rows), F32)).compile().as_text()
        lines = text.splitlines()
        assert any("tpu_custom_call" in line and "hc_coeff_fwd" in line
                   and "jvp(hc)/coeff/" in line for line in lines)
        products = [line for line in lines if "transpose(jvp(hc))/coeff/" in line
                    and ("convolution" in line or "fusion" in line)]
        assert products, "the backward's products lost the scope"

    @pytest.mark.parametrize("mode,moments,n", [
        ("adamw", "fp32", REAL.bucket_elems),
        ("adamw", "bf16-sr", REAL.bucket_elems),
        ("adam", "bf16-sr", REAL.bucket_elems),      # coupled weight decay
        ("lamb", "bf16-sr", REAL.bucket_elems),      # trust-ratio epilogue
        ("adamw", "bf16-sr", 50257 * 5),             # padded odd bucket
        # the cells' stand-alone leaves, launched in the layout they have
        # (ISSUE 28): the grid runs over the last two dimensions
        ("adamw", "bf16-sr", (36, 1280, 5120)),      # gpt2-large fc_in
        ("adamw", "bf16-sr", (36, 5120, 1280)),      # gpt2-large fc_out
        ("adamw", "bf16-sr", (50257, 1280)),         # ragged last row block
        ("adamw", "bf16-sr", (2, 64, 2048, 1024)),   # olmoe expert stack
        ("adamw", "bf16-sr", (2048, 50304)),         # ragged column block
        ("adam", "fp32", (36, 1280, 5120)),          # widest operands
    ])
    def test_adam_bucket(self, chip, mode, moments, n):
        from deepspeed_tpu.ops.adam.pallas_adam import adam_bucket_update
        n = n if isinstance(n, tuple) else (n,)
        md, gd, pd = ((F32, F32, None) if moments == "fp32"
                      else (BF16, BF16, BF16))

        def step(g, p, m, v, t, lr, sm, sv):
            return adam_bucket_update(
                g, p, m, v, step=t, lr=lr, weight_decay=0.01, mode=mode,
                seed_m=sm, seed_v=sv, m_dtype=md, v_dtype=md,
                param_dtype=pd, interpret=False)

        compile_for_chip(step, chip(n, gd), chip(n, F32),
                         chip(n, md), chip(n, md), chip((), I32),
                         chip((), F32), chip((), jnp.uint32),
                         chip((), jnp.uint32))

    @pytest.mark.parametrize("md,n", [
        (F32, (REAL.bucket_elems,)), (BF16, (REAL.bucket_elems,)),
        (BF16, (36, 1280, 5120)),                    # native layout
    ])
    def test_lion_bucket(self, chip, md, n):
        from deepspeed_tpu.ops.lion.pallas_lion import lion_bucket_update
        pd = None if md == F32 else BF16

        def step(g, p, m, lr, sm):
            return lion_bucket_update(
                g, p, m, lr=lr, weight_decay=0.01, seed_m=sm, m_dtype=md,
                param_dtype=pd, interpret=False)

        compile_for_chip(step, chip(n, md), chip(n, F32),
                         chip(n, md), chip((), F32),
                         chip((), jnp.uint32))

    @pytest.mark.parametrize("rows,group", [
        REAL.quant_rows, (100, 256), (7, 128), (4096, 2048)])
    def test_quantize_rows_int8(self, chip, rows, group):
        # seed: "rank 1 block shapes ... multiple of the tiling size (128)"
        from deepspeed_tpu.ops.quantizer.pallas_quant import \
            quantize_rows_int8
        compile_for_chip(
            functools.partial(quantize_rows_int8, interpret=False),
            chip((rows, group), F32))

    @pytest.mark.parametrize("tokens,top_k", [
        (REAL.moe[0], 2),   # 16 rank-scan lane blocks of 512
        (REAL.moe[0], 1),
        (640, 2),           # 128-wide blocks (no wider divisor)
        (24, 2),            # one ragged block
    ])
    def test_moe_route(self, chip, tokens, top_k):
        # seed: "Unimplemented primitive in Pallas TPU lowering: cumsum"
        from deepspeed_tpu.moe.sharded_moe import capacity
        from deepspeed_tpu.ops.transformer import pallas_moe
        E = REAL.moe[3]
        compile_for_chip(
            functools.partial(pallas_moe.moe_route, top_k=top_k,
                              capacity=capacity(tokens, E, 1.25, 4),
                              interpret=False),
            chip((tokens, E), F32))

    @pytest.mark.parametrize("tokens,n_chunks", [
        (REAL.moe[0], 1),   # train-moe dims: split FFN + token-major combine
        (REAL.moe_small_tokens, 1),   # fused combine-scatter epilogue
        (REAL.moe_small_tokens, 2),   # ... under the chunked scan carry
        (24, 1),            # a serving wave: not a multiple of any tile
    ])
    def test_moe_forward(self, chip, tokens, n_chunks):
        from deepspeed_tpu.moe.sharded_moe import capacity
        from deepspeed_tpu.ops.transformer import pallas_moe
        _, H, F, E = REAL.moe
        assert pallas_moe.moe_kernel_supported(
            top_k=2, activation="silu_gated", dtype=BF16, tokens=tokens,
            num_experts=E, hidden=H)
        fwd = pallas_moe.make_moe_forward(
            top_k=2, capacity=capacity(tokens, E, 1.25, 4),
            activation="silu_gated", mask_pad=False, n_chunks=n_chunks,
            interpret=False)
        params = {"gate": chip((H, E), BF16), "wo": chip((E, F, H), BF16),
                  "wi_gate": chip((E, H, F), BF16),
                  "wi_up": chip((E, H, F), BF16)}
        compile_for_chip(fwd, params, chip((tokens, H), BF16))

    @pytest.mark.parametrize("wire", ["bf16", "bf16-masked", "int8"])
    def test_moe_dispatch_gather(self, chip, wire):
        # seed: "last two dimensions of your block shape are divisible by
        # 8 and 128 ... Block shape (1, 1024), array shape (8192, 1024)"
        from deepspeed_tpu.ops.transformer import pallas_moe
        T, H, _, _ = REAL.moe
        fn = (functools.partial(pallas_moe.moe_dispatch_gather_int8,
                                interpret=False) if wire == "int8" else
              functools.partial(pallas_moe.moe_dispatch_gather,
                                wire_dtype=BF16, interpret=False,
                                mask_pad=wire == "bf16-masked"))
        compile_for_chip(fn, chip((T, H), F32), chip((2 * T,), I32))

    def test_moe_combine(self, chip):
        # seed: block shape (1, 2) on (8192, 2) + a dynamic lane read
        from deepspeed_tpu.ops.transformer import pallas_moe
        T, H, _, E = REAL.moe
        compile_for_chip(
            functools.partial(pallas_moe.moe_combine, interpret=False),
            chip((E * 1280, H), F32), chip((T, 2), I32), chip((T, 2), F32))

    @pytest.mark.parametrize("heads", [
        REAL.wave_heads, (20, 20, 64), (32, 8, 128), (32, 4, 64)])
    def test_ragged_wave(self, chip, heads):
        from deepspeed_tpu.inference.v2.kernels.ragged_paged_attention \
            import ragged_paged_attention
        H, kvH, D = heads
        N, A, MP, P, ps = 512, 64, 64, 1024, REAL.wave_page
        compile_for_chip(
            functools.partial(ragged_paged_attention, block_q=8,
                              use_pallas=True, interpret=False),
            chip((N, H, D), BF16), chip((kvH, P, ps, D), BF16),
            chip((kvH, P, ps, D), BF16), chip((A,), I32),
            chip((A, MP), I32), chip((A + 1,), I32))


# ---------------------------------------------------------------------------
# chip_smoke's phases, tiny, on the CPU mesh
# ---------------------------------------------------------------------------

TINY = chip_smoke.Sizes(
    dtype="float32", preset="gpt2-tiny",
    model_overrides=(("vocab_size", 256), ("max_seq_len", 64)),
    micro=1, seq=32, train_steps=3,
    flash=(1, 128, 4, 2, 16), flash_train=(2, 256, 2, 2, 16),
    bucket_elems=2048, quant_rows=(64, 128),
    moe=(32, 16, 32, 4), moe_small_tokens=8, wave_heads=(4, 2, 16), wave_page=4,
    wave_seqs=((1, 9), (1, 17), (11, 5), (6, 0)),
    moe_steps=2, n_requests=3, prompt_range=(5, 20), max_new=4,
    token_budget=64, stagger_s=0.001, multichip_steps=2)


class TestSmokePhasesOnCpu:

    def test_device(self, eight_devices):
        line = chip_smoke.phase_device(len(jax.devices()))
        assert line["platform"] == "cpu" and line["accelerator"] == "cpu"
        assert set(line["native_ops"].values()) <= {"built", "fallback"}
        with pytest.raises(AssertionError, match="asked for 3"):
            chip_smoke.phase_device(3)

    def test_train(self):
        line = chip_smoke.phase_train(TINY, 0)
        assert line["losses"][-1] < line["losses"][0]
        assert len(line["losses"]) == TINY.train_steps
        # the engine's own account of set-up stands where the smoke's copy
        # of the compile counters stood
        setup = line["setup_totals"]
        assert {"init_state", "train_step"} <= set(setup["programs"])
        assert setup["programs_compiled"] == 2 and setup["compile_s"] > 0
        assert setup["compiled_after_setup"] == 0

    def test_kernels(self):
        line = chip_smoke.phase_kernels(TINY, 0)
        assert line["compiled"]["compile_s"] > 0 and line["compiled"]["trace_s"] > 0
        assert [c["kernel"].split()[0] for c in line["checks"]] == [
            "flash", "flash", "adam/lion", "quantize_rows_int8", "moe", "moe",
            "ragged"]
        assert all(c["interpret"] for c in line["checks"])  # CPU backend

    def test_train_moe(self):
        from deepspeed_tpu.models import mixtral_model
        model = mixtral_model("mixtral-tiny", dtype=BF16, remat=False,
                              max_seq_len=64, vocab_size=512)
        line = chip_smoke.phase_train_moe(TINY, 0, model=model,
                                          micro=1, seq=32)
        assert "train_step" in line["setup_totals"]["programs"]
        assert line["moe_kernel_resolution"].startswith("xla")  # CPU mesh
        assert len(line["losses"]) == TINY.moe_steps

    def test_serve(self):
        line = chip_smoke.phase_serve(TINY, 0)
        assert line["first_calls"] and all(
            c["wall_s"] > 0 for c in line["first_calls"].values())
        assert line["new_tokens"] == [TINY.max_new] * TINY.n_requests
        assert line["prefill_logits_rel_err"] <= line["tolerance"]

    def test_multichip(self, eight_devices):
        line = chip_smoke.phase_multichip(TINY, 0)
        assert line["setup_totals"]["programs_compiled"] >= 2
        assert line["dp"] == len(jax.devices()) and line["overlap_active"]
        assert line["losses"] == pytest.approx(line["one_device_losses"],
                                               rel=0.05, abs=0.05)


def test_smoke_moe_dims_are_the_bench_model():
    # the kernels phase claims the train-moe model's dims: keep it true
    c = chip_smoke.moe_train_model().config
    micro = chip_smoke.moe_train_config()["train_micro_batch_size_per_gpu"]
    assert REAL.moe == (micro * c.max_seq_len, c.hidden_size, c.ffn_size,
                        c.moe.num_experts)
    assert c.moe.top_k == 2


def test_smoke_train_shape_is_gpt2_large_as_published():
    c = chip_smoke.train_model(REAL).config
    assert (c.num_layers, c.hidden_size, c.num_heads, c.vocab_size,
            c.max_seq_len) == (36, 1280, 20, 50257, 1024)
    assert c.remat and REAL.seq == c.max_seq_len


def test_smoke_helpers():
    import numpy as np
    toks = chip_smoke.zipf_tokens(np.random.default_rng(0), 50, (4, 64))
    assert toks.min() >= 0 and toks.max() < 50 and toks.dtype == np.int32
    assert chip_smoke.max_err([1.0, 2.0], [1.0, 2.5], dict(
        rtol=0, atol=1.0)) == 0.5
    with pytest.raises(AssertionError):
        chip_smoke.max_err([1.0], [2.0], dict(rtol=0, atol=0.5))
    with pytest.raises(AssertionError, match="non-finite"):
        chip_smoke.assert_finite([1.0, float("nan")], "x")


def test_smoke_without_a_tpu_fails():
    """No accelerator: non-zero exit before any phase, no result line."""
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and '"phase"' not in r.stdout
    assert "needs a TPU" in r.stderr

