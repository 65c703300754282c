"""Where a launch takes its operands' heads (PR 51; ``layout`` ``"rows"`` and
``"heads"``): the old `test_pallas_flash.py`'s last section, both layouts bit
for bit over every kind of launch, the transposes left around a launch by rows,
and `launch_layout`'s rule at every cell's heads."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.pallas_flash import (
    flash_attention_kernel, flash_attention_with_lse)


@functools.lru_cache(None)
def _layout_cases():
    """name -> (q, k, v, the launch's keywords): every kind of launch at head
    dim 128, small rows and interpret mode's tiles, so that among them dq leaves
    in each of its three ways."""
    rng = np.random.default_rng(51)
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.4)
    B, S, D = 2, 256, 128
    qkv = lambda H, kvH, sq=S, sk=S: (f(B, sq, H, D), f(B, sk, kvH, D), f(B, sk, kvH, D))
    docs = jnp.asarray(np.sort(rng.integers(0, 3, (B, S)), axis=1), jnp.int32)
    picked = (rng.random((B, S, S)) < 0.3) & np.tril(np.ones((S, S), bool))
    from deepspeed_tpu.ops.transformer.attention import pack_selection
    return {
        # one tile a row: dq the kernel's own output
        "plain_causal": qkv(4, 4) + (dict(causal=True),),
        # a power of two, which q is multiplied by before the launch
        "plain_scale_pow2": qkv(4, 2) + (dict(causal=True, scale=0.125, block_q=64,
                                              block_k=64),),
        # a static window's cut grids, its partials masked and summed
        "window": qkv(4, 2) + (dict(causal=True, window=100, block_q=64, block_k=64),),
        # eight k-blocks a q-block: dq added to in place, tiles of other documents skipped
        "segment_ids": qkv(4, 2) + (dict(causal=True, segment_ids=docs, block_q=32,
                                         block_k=32),),
        "grouped_32q_4kv": tuple(a[:1] for a in qkv(32, 4)) + (
            dict(causal=True, segment_ids=docs[:1], block_q=64, block_k=64),),
        "blockdiff": qkv(4, 2, sq=2 * S) + (dict(
            causal=True, blockdiff=4, segment_ids=docs, block_q=32, block_k=32,
            q_segment_ids=jnp.concatenate([docs, docs], axis=1)),),
        "eva_local": qkv(4, 4) + (dict(causal=True, tag="eva_local"),),
        "eva_far": qkv(4, 4, sk=S // 64 * 16) + (dict(
            causal=True, summaries=(64, 16), tag="eva_far", block_q=32, block_k=16),),
        "selected": qkv(4, 2) + (dict(causal=True, segment_ids=docs, block_q=128,
                                      block_k=64, selected=pack_selection(jnp.asarray(picked))),),
    }


def _layout_pair(name, grads):
    """The launch ``name`` in both layouts: (o, lse) or (dq, dk, dv) of a loss
    through both outputs."""
    q, k, v, kw = _layout_cases()[name]
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)

    def run(layout):
        def loss(q, k, v):
            o, lse = flash_attention_with_lse(q, k, v, interpret=True, layout=layout, **kw)
            return jnp.sum(o * w) + jnp.sum(jnp.sin(lse)), (o, lse)
        # (one program a layout, as a step runs a launch: outside a jit every
        # operation around the launch is compiled alone)
        if grads:
            return jax.jit(jax.grad(lambda *a: loss(*a)[0], argnums=(0, 1, 2)))(q, k, v)
        return jax.jit(loss)(q, k, v)[1]
    return run("rows"), run("heads")


@pytest.mark.parametrize("grads", [False, True], ids=["forward", "gradients"])
@pytest.mark.parametrize("name", sorted(_layout_cases()))
def test_both_layouts_give_the_same_bits(eight_devices, name, grads):
    """``"rows"`` (``[B, S, heads x D]`` blocks through the index maps) against
    ``"heads"`` (the transposes): the same tiles in the same order, so ``o``,
    ``lse``, ``dq``, ``dk`` and ``dv`` are equal BIT FOR BIT."""
    rows, heads = _layout_pair(name, grads)
    for a, b in zip(rows, heads):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if not grads:     # (and the answer is no zero: a row sees a key)
        assert float(jnp.max(jnp.abs(rows[0]))) > 0.01



def _transposes(fn, *args):
    """The sizes of the values a ``transpose`` makes in ``fn``'s jaxpr."""
    sizes = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "transpose":
                sizes.extend(v.aval.size for v in eqn.outvars)
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return sizes


@pytest.mark.parametrize("name", sorted(_layout_cases()))
def test_a_launch_by_rows_has_no_transpose_around_it(eight_devices, name):
    """The jaxpr of the forward and the three gradients by rows holds no
    ``transpose`` of q, ``o`` or ``do``: what is left are the four of the key
    side (k, v, ``dk``, ``dv`` lead with their heads in either layout: an eighth
    of q's size under 32 query heads over 4), per-row statistics (1 / 128 of q),
    a selection's packed bits, and ONE of dq's size where dq leaves the launch
    in float32 with its heads leading (partials to sum, the array added to in
    place): the pass that sums or casts it writes a head's rows to its
    columns."""
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    q, k, v, kw = _layout_cases()[name]
    run = lambda layout: lambda q, k, v: flash_attention_with_lse(
        q, k, v, interpret=True, layout=layout, **kw)

    def loss(q, k, v):
        o, lse = run("rows")(q, k, v)
        return jnp.sum(o) + jnp.sum(lse)
    large = lambda sizes: sorted(n for n in sizes if n >= k.size)
    assert large(_transposes(run("rows"), q, k, v)) == [k.size] * 2         # k, v
    tiles = pf._prepare(q, k, v, True, kw.get("scale"), kw.get("segment_ids"),
                        kw.get("q_segment_ids"), None, kw.get("window"), None,
                        kw.get("block_q"), kw.get("block_k"), True, kw.get("blockdiff"),
                        kw.get("summaries"), kw.get("tag"), "selected" in kw)[0].tiles
    one_block = pf.dq_mode(q.shape[1], k.shape[1], tiles, pf.static_window(
        kw.get("window"), q.shape[1], k.shape[1])) == "one_block"
    assert large(_transposes(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)) == sorted(
        [k.size] * 4 + ([] if one_block else [q.size]))
    forced = _transposes(jax.grad(lambda *a: jnp.sum(run("heads")(*a)[0]),
                                  argnums=(0, 1, 2)), q, k, v)
    assert large(forced) == sorted([q.size] * 4 + [k.size] * 4)   # q, o, do, dq; k, v, dk, dv


@pytest.mark.parametrize("q_shape,kv_heads,layout", [
    ((4, 1024, 20, 64), 20, "heads"),        # the GPT-2 cell: half a lane tile a head
    ((1, 2048, 32, 64), 4, "heads"),
    ((1, 16384, 32, 128), 4, "rows"),        # the Trinity, SDAR and Keye cells' heads
    ((2, 16384, 28, 128), 4, "rows"),        # the SmallThinker cell's: a group of 7
    ((1, 2048, 14, 128), 2, "rows"),         # (its column blocks: 14 over 2 key heads)
    ((1, 2048, 8, 256), 2, "rows"),          # two lane tiles a head
    ((1, 2048, 8, 128), 4, "rows"),
    ((1, 4096, 16, 128), 16, "heads"),       # the OLMoE cell: as many key heads as query heads
    ((2, 8192, 16, 128), 16, "heads"),       # the Instella cell
    ((1, 32768, 4, 128), 4, "heads"),        # one launch of the EvaByte cell
    ((2, 128, 8, 16), 2, "heads"),           # a tiny preset
])
def test_the_layout_is_the_heads_alone(eight_devices, q_shape, kv_heads, layout):
    """`launch_layout` is the rule, `_prepare` and ``attention.plan`` ask it: by
    rows where a head is whole lane tiles AND the query heads are grouped; a
    head narrower than the lanes keeps the transposed layout (and refuses the
    other by name)."""
    from deepspeed_tpu.ops.transformer import attention as attn_mod
    from deepspeed_tpu.ops.transformer import pallas_flash as pf
    k_shape = q_shape[:2] + (kv_heads, q_shape[3])
    assert pf.launch_layout(q_shape, k_shape) == layout
    made = attn_mod.plan(q_shape, k_shape, "cpu", "pallas", 2)
    assert made.route == "kernel" and made.layout("flash") == layout
    if q_shape[1] > 2048:
        return
    q, k = (jnp.zeros(s, jnp.bfloat16) for s in (q_shape, k_shape))
    prepared = pf._prepare(q, k, k, True, None, None, None, None, None, None, None,
                           None, True)
    assert prepared[0].layout == layout
    B, S, H, D = q_shape
    assert prepared[1].shape == ((B, 1, S, H * D) if layout == "rows"
                                 else (B * kv_heads, H // kv_heads, S, D))
    assert prepared[2].shape == (B * kv_heads, S, D)
    if q_shape[3] % 128:
        assert _transposes(lambda q, k: flash_attention_kernel(q, k, k, interpret=True),
                           q, k).count(q.size) >= 2            # q in, o out
        with pytest.raises(ValueError, match="rows"):
            flash_attention_kernel(q, k, k, interpret=True, layout="rows")
