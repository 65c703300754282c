"""The tile rule and the route rule: pure Python, no kernel runs (the old
`test_pallas_flash.py`'s second section, without the static window's grids:
`test_pallas_flash_window.py`). `choose_tiles`, `supports`,
`kernel_is_default`, `choose_route`'s table of every cell's shape, and what
`_log_path_once` says."""

import pytest

import jax
import jax.numpy as jnp

from tests.unit.ops.flash_cases import _qkv


TILE_SHAPES = [
    # Sq, Sk, head_dim, itemsize
    (1024, 1024, 64, 2),     # the benchmark cell
    (1024, 1024, 128, 2),
    (4096, 4096, 64, 2),
    (4096, 4096, 128, 4),
    (2048, 8192, 128, 2),    # a query chunk against a longer key
    (512, 512, 64, 2),       # bert
    (256, 256, 64, 2),
    (384, 384, 64, 2),       # whole: no smaller 128-multiple but 128
    (640, 640, 64, 2),
    (1536, 1536, 96, 2),
]


@pytest.mark.parametrize("sq,causal,fwd,bwd", [
    (1024, True, (512, 512), (1024, 1024)),     # what the chip sweep chose
    (1024, False, (1024, 1024), (1024, 1024)),
    (4096, True, (512, 512), (1024, 1024)),
    (512, True, (512, 512), (512, 512)),
    (640, True, (640, 640), (640, 640)),        # never the step-bound 128
    (1536, True, (512, 512), (768, 768)),
])
def test_tile_rule_is_the_measured_one(sq, causal, fwd, bwd):
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    t = pf.choose_tiles(sq, sq, 64, causal=causal)
    assert (t.fwd, t.bwd) == (fwd, bwd)


@pytest.mark.parametrize("sq,sk,d,itemsize", TILE_SHAPES)
def test_chosen_tiles_are_legal(sq, sk, d, itemsize):
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    tiles = pf.choose_tiles(sq, sk, d, itemsize)
    for (bq, bk), backward in ((tiles.fwd, False), (tiles.bwd, True)):
        assert sq % bq == 0 and sk % bk == 0
        assert bq % 128 == 0 and bk % 128 == 0      # the 128-lane layout
        assert pf.tile_vmem_bytes((bq, bk), d, itemsize,
                                  backward=backward) <= pf.VMEM_BUDGET
    # inside the budget the compiler's own limit stands
    assert tiles.vmem_limit_bytes is None
    assert pf.supports((1, sq, 8, d), (1, sk, 8, d))


def test_explicit_tiles_win_and_oversize_ones_raise_the_limit():
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    t = pf.choose_tiles(1024, 1024, 64, block_q=128, block_k=256)
    assert t.fwd == t.bwd == (128, 256) and t.vmem_limit_bytes is None
    # one argument alone: the other comes from the rule
    t = pf.choose_tiles(1024, 1024, 64, block_q=256)
    assert t.fwd == (256, pf.FWD_CAUSAL_TILE_TARGET[1])
    assert t.bwd == (256, pf.TILE_TARGET[1])
    # clamped to the lengths, as the 128-default was
    assert pf.choose_tiles(128, 256, 64, block_q=512,
                           block_k=512).fwd == (128, 256)
    big = pf.choose_tiles(4096, 4096, 128, block_q=2048, block_k=2048)
    assert big.fwd == (2048, 2048)
    assert pf.VMEM_BUDGET < big.vmem_limit_bytes <= pf.VMEM_CAP


@pytest.mark.parametrize("sq,sk,kw,legal", [
    (192, 192, {}, False),                   # no 128-multiple divides
    (1000, 1000, {}, False),
    (64, 64, {}, False),                     # compiled: off the lane layout
    (64, 64, {"compiled": False}, True),     # interpret: whole
    (1024, 1024, {"block_k": 384}, False),   # explicit tile does not divide
    (64, 128, {}, False),                    # a short q against 128 keys
    (64, 128, {"compiled": False}, True),
])
def test_lengths_without_a_legal_tile(sq, sk, kw, legal):
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    assert (pf.choose_tiles(sq, sk, 64, **kw) is not None) == legal
    compiled = kw.get("compiled", True)
    assert pf.supports((1, sq, 4, 64), (1, sk, 4, 64),
                       block_k=kw.get("block_k"),
                       compiled=compiled) == legal


ROUTE_SHAPES = [
    # q shape, k shape
    ((4, 1024, 20, 64), (4, 1024, 20, 64)),     # the benchmark cell
    ((1, 4096, 32, 64), (1, 4096, 4, 64)),      # GQA long
    ((8, 512, 16, 64), (8, 512, 16, 64)),       # bert
    ((16, 256, 20, 64), (16, 256, 20, 64)),     # XLA won it 2.3x
    ((16, 256, 16, 128), (16, 256, 16, 128)),   # the kernel won it by 3 %
    ((10, 384, 32, 64), (10, 384, 4, 64)),
    ((2, 128, 8, 64), (2, 128, 2, 64)),
    ((1, 1000, 8, 64), (1, 1000, 8, 64)),       # no legal tile
    ((1, 2048, 8, 192), (1, 2048, 8, 192)),     # head dim off the lanes
]


@pytest.mark.parametrize("q_shape,k_shape", ROUTE_SHAPES)
def test_route_rule_is_shape_and_platform_only(q_shape, k_shape, monkeypatch):
    """Off the TPU nothing takes the kernel unasked; on it, exactly the
    supported shapes at or over the measured crossover do; no environment
    variable enters the rule."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    for backend in ("cpu", "gpu", "METAL"):
        assert not attn_mod.kernel_is_default(q_shape, k_shape, backend)
    min_seq = (attn_mod.FLASH_MIN_SEQ_WIDE_HEAD if q_shape[3] >= 128
               else attn_mod.FLASH_MIN_SEQ)
    want = pf.supports(q_shape, k_shape) and q_shape[1] >= min_seq
    assert attn_mod.kernel_is_default(q_shape, k_shape, "tpu") == want
    monkeypatch.setenv("DSTPU_ATTN", "xla")
    assert attn_mod.kernel_is_default(q_shape, k_shape, "tpu") == want


@pytest.mark.parametrize("q_shape,kv_heads,backend,mode,route", [
    ((4, 1024, 20, 64), 20, "tpu", "", "kernel"),     # the GPT-2 cell
    ((1, 4096, 16, 128), 16, "tpu", "", "kernel"),    # the OLMoE cell
    ((1, 2048, 32, 64), 4, "tpu", "", "kernel"),      # GQA 32q/4kv
    ((4, 256, 20, 64), 20, "tpu", "", "xla"),
    ((4, 256, 16, 128), 16, "tpu", "", "kernel"),
    ((4, 384, 20, 64), 20, "tpu", "", "kernel"),
    ((1, 2048, 8, 192), 8, "tpu", "", "xla"),         # head dim off the lanes
    ((1, 4096, 8, 192), 8, "tpu", "", "xla_chunked"),
    ((1, 4000, 16, 64), 16, "tpu", "", "xla"),        # no 128-multiple tile
    ((1, 4104, 16, 64), 16, "tpu", "", "xla_chunked"),
    ((1, 8192, 16, 128), 16, "tpu", "", "kernel"),
    # the trinity-mini cell: 32q/4kv x 128 at 16,384 with segment ids, under
    # a window of 2048 and under none (neither is an argument of the route)
    ((1, 16384, 32, 128), 4, "tpu", "", "kernel"),
    ((1, 16384, 32, 128), 4, "tpu", "xla", "xla_chunked"),
    ((1, 16384, 32, 128), 4, "cpu", "", "xla"),
    ((1, 16384, 32, 128), 4, "cpu", "pallas", "kernel"),
    # the smallthinker-21b-a3b cell: 28q/4kv x 128, a GROUP OF 7 (no power of
    # two: 28 column blocks of the "rows" layout), two rows of 16,384 with
    # segment ids, under a window of 4096 (three layers of four) and under
    # none (neither is an argument of the route); and its tiny preset
    ((2, 16384, 28, 128), 4, "tpu", "", "kernel"),
    ((2, 16384, 28, 128), 4, "tpu", "xla", "xla_chunked"),
    ((2, 16384, 28, 128), 4, "cpu", "", "xla"),
    ((2, 16384, 28, 128), 4, "cpu", "pallas", "kernel"),
    ((4, 64, 14, 16), 2, "cpu", "pallas", "kernel"),  # 14q/2kv x 16, interpret
    ((4, 64, 14, 16), 2, "tpu", "pallas", "xla"),     # tiles off the lanes
    ((2, 128, 8, 64), 2, "tpu", "", "xla"),           # under the crossover
    ((1, 8192, 16, 128), 16, "tpu", "xla", "xla_chunked"),
    ((4, 1024, 20, 64), 20, "tpu", "xla", "xla"),
    ((4, 1024, 20, 64), 20, "cpu", "", "xla"),
    ((1, 4096, 16, 128), 16, "cpu", "", "xla"),       # never chunked on the CPU
    ((1, 4096, 16, 128), 16, "cpu", "xla", "xla"),
    ((2, 128, 8, 64), 2, "cpu", "pallas", "kernel"),  # interpret
    ((2, 100, 8, 64), 2, "cpu", "pallas", "kernel"),  # one interpret tile
    ((1, 8192, 16, 128), 16, "cpu", "pallas", "kernel"),
    ((2, 128, 6, 64), 4, "cpu", "pallas", "xla"),     # heads do not divide
    ((2, 128, 8, 64), 2, "tpu", "pallas", "kernel"),  # forced under the crossover
    ((2, 100, 8, 64), 2, "tpu", "pallas", "xla"),     # no compiled tile
    ((1, 4096, 8, 192), 8, "tpu", "pallas", "xla_chunked"),
    # the sdar-30b-a3b cell: the block-diffusion mask (a block length in the
    # key heads' place: (kv heads, b)), 32q/4kv x 128, 2 x 8192 query rows (a
    # clean and a noised copy) over the clean copy's 8192 keys, segment ids
    ((1, 16384, 32, 128), (4, 4), "tpu", "", "kernel"),
    ((1, 16384, 32, 128), (4, 4), "tpu", "xla", "xla_chunked"),
    ((1, 16384, 32, 128), (4, 4), "cpu", "", "xla"),
    ((1, 16384, 32, 128), (4, 4), "cpu", "pallas", "kernel"),
    ((8, 128, 8, 16), (2, 4), "cpu", "pallas", "kernel"),   # the tiny preset, interpret
    ((8, 128, 8, 16), (2, 4), "tpu", "pallas", "xla"),      # head dim and tiles off the lanes
    ((1, 16384, 32, 128), (4, 6), "tpu", "", "xla_chunked"),  # b no power of two
    ((1, 16384, 32, 128), (4, 256), "tpu", "", "kernel"),   # a block of 256 divides the tiles
    ((1, 16384, 32, 128), (4, 2048), "tpu", "", "xla_chunked"),  # wider than a tile: never cut
    ((1, 256, 32, 128), (4, 4), "tpu", "", "kernel"),       # 2 x 128 rows: at the crossover
    ((1, 128, 32, 128), (4, 4), "tpu", "", "xla"),          # 2 x 64: under it, and no tile
    # the keye-vl2-30b-a3b cell: a learned selection's operand (("dsa", kv
    # heads, topk) in the key heads' place), 32q/4kv x 128 over 16,384 rows: a
    # full layer's tiles and route, whatever topk is
    ((1, 16384, 32, 128), ("dsa", 4, 2048), "tpu", "", "kernel"),
    ((1, 16384, 32, 128), ("dsa", 4, 2048), "tpu", "xla", "xla_chunked"),
    ((1, 16384, 32, 128), ("dsa", 4, 2048), "cpu", "", "xla"),
    ((1, 16384, 32, 128), ("dsa", 4, 2048), "cpu", "pallas", "kernel"),
    ((1, 16384, 32, 128), ("dsa", 4, 64), "tpu", "", "kernel"),
    ((4, 64, 4, 16), ("dsa", 2, 8), "cpu", "pallas", "kernel"),       # the tiny preset, interpret
    ((4, 64, 4, 16), ("dsa", 2, 8), "tpu", "pallas", "xla"),          # tiles off the lanes
    ((1, 128, 32, 128), ("dsa", 4, 2048), "tpu", "", "xla"),          # under the crossover
    ((1, 1024, 32, 128), ("dsa", 4, 2048), "tpu", "", "kernel"),      # one group of the operand's bits
    ((1, 512, 32, 128), ("dsa", 4, 2048), "tpu", "", "xla"),          # a plane under the chip's 128 lanes
    ((1, 512, 32, 128), ("dsa", 4, 2048), "cpu", "pallas", "kernel"),  # interpret mode takes it
    # the evabyte-6.5b cell: EVA's mask (("eva", window, chunk) in the key
    # heads' place: as many key heads as query heads), 32 x 128 at 32,768, and
    # a group of 4 of its heads, which is what one launch of the cell holds
    ((1, 32768, 32, 128), ("eva", 2048, 16), "tpu", "", "kernel"),
    ((1, 32768, 4, 128), ("eva", 2048, 16), "tpu", "", "kernel"),
    ((1, 32768, 32, 128), ("eva", 2048, 16), "tpu", "xla", "xla_chunked"),
    ((1, 32768, 32, 128), ("eva", 2048, 16), "cpu", "", "xla"),
    ((1, 32768, 32, 128), ("eva", 2048, 16), "cpu", "pallas", "kernel"),
    ((2, 128, 4, 16), ("eva", 32, 4), "cpu", "pallas", "kernel"),     # the tiny preset, interpret
    ((2, 128, 4, 16), ("eva", 32, 4), "tpu", "pallas", "xla"),        # tiles off the lanes
    ((1, 1024, 32, 128), ("eva", 2048, 16), "tpu", "", "kernel"),     # inside one window: causal
    ((1, 128, 32, 128), ("eva", 2048, 16), "tpu", "", "xla"),         # under the crossover
    ((1, 32768 + 2048 + 16, 32, 128), ("eva", 2048, 16), "tpu", "", "xla_chunked"),  # no whole windows
    ((1, 8192, 32, 128), ("eva", 256, 16), "tpu", "", "kernel"),      # the crossover is a window's
    ((1, 8192, 32, 64), ("eva", 256, 16), "tpu", "", "xla_chunked"),  # narrow heads: under it
    ((1, 8192, 32, 128), ("eva", 384, 16), "tpu", "", "xla_chunked"),  # 8192 is no multiple of 384
])
def test_route_table(q_shape, kv_heads, backend, mode, route, monkeypatch):
    """`choose_route` is the whole decision of `flash_attention`, of
    `blockdiff_attention` and of `eva_attention`, a pure function: the TPU's
    rows are checked here on the CPU, and an environment that asks for another
    route moves none of them."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    blockdiff = eva = selected = None
    if isinstance(kv_heads, tuple) and kv_heads[0] == "eva":
        kv_heads, eva = q_shape[2], kv_heads[1:]
    elif isinstance(kv_heads, tuple) and kv_heads[0] == "dsa":
        _, kv_heads, selected = kv_heads
    elif isinstance(kv_heads, tuple):     # the mask's rows: keys are half the queries
        kv_heads, blockdiff = kv_heads
    k_shape = (q_shape[0], q_shape[1] // (2 if blockdiff else 1), kv_heads, q_shape[3])
    monkeypatch.setenv("DSTPU_ATTN", "xla" if route == "kernel" else "pallas")
    assert attn_mod.choose_route(q_shape, k_shape, backend, mode, blockdiff, eva,
                                 selected) == route


def test_attention_reads_one_environment_variable():
    import inspect
    import re

    from deepspeed_tpu.ops.transformer import attention as attn_mod
    names = set(re.findall(r"DSTPU_[A-Z0-9_]+", inspect.getsource(attn_mod)))
    assert names == {"DSTPU_ATTN"}


def test_route_rule_takes_the_benchmark_cell_and_leaves_the_cpu(
        eight_devices, monkeypatch):
    """The cell's call (gpt2-large, micro 4 x 1024) is a kernel shape on the
    TPU; on this CPU mesh the very same call still traces the XLA path."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    monkeypatch.delenv("DSTPU_ATTN", raising=False)
    shape = (4, 1024, 20, 64)
    assert attn_mod.kernel_is_default(shape, shape, "tpu")
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = jax.jit(attn_mod.flash_attention).lower(q, q, q).as_text()
    assert "pallas" not in text and "custom_call" not in text


def test_log_names_the_path_and_the_tiles(eight_devices, monkeypatch):
    """`_log_path_once` says which route a call took and, for the kernel,
    the tiles of both kernels: a silent fallback or a silent 128-tile is
    how this path lost its speed before."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    said = []
    monkeypatch.setattr(attn_mod, "_log_path_once", said.append)
    q, k, v = _qkv(B=1, S=1024, H=2, kvH=2, seed=13)
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    attn_mod.flash_attention(q, k, v, causal=True)
    monkeypatch.delenv("DSTPU_ATTN")
    attn_mod.flash_attention(q, k, v, causal=True)       # CPU: XLA
    assert said == ["pallas_flash_inrepo, tiles (block_q x block_k) "
                    "forward 512x512 backward 1024x1024, operands by heads", "xla"]
