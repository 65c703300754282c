"""An attention entry point launches exactly what its plan lists: the
``attention.Plan`` the counters read (``TransformerLM.attention_records``)
against the ``FlashConfig`` the kernel is built with, under
``DSTPU_ATTN=pallas`` in interpret mode."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.transformer import attention, pallas_flash

B, H, D = 1, 2, 16
WIDE = 128      # a head of whole lane tiles: grouped query heads go to the launches by rows


def _qkv(rows, keys=None, kv_heads=H, d=D):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, rows, H, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, keys or rows, kv_heads, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, keys or rows, kv_heads, d), jnp.float32)
    return q, k, v


def _flash(d=D, **mask):
    q, k, v = _qkv(64, kv_heads=1, d=d)
    plan = attention.plan(q.shape, k.shape, "cpu", "pallas", 4, **mask)
    return plan, lambda: attention.flash_attention(q, k, v, **mask)


def _blockdiff(d=D, kv_heads=H):
    q, k, v = _qkv(128, kv_heads=kv_heads, d=d)
    plan = attention.plan(q.shape, (B, 64, kv_heads, d), "cpu", "pallas", 4, blockdiff=4)
    return plan, lambda: attention.blockdiff_attention(q, k, v, 4)


def _eva(rows, d=D):
    q, k, v = _qkv(rows, d=d)
    kbar, vbar = attention.eva_summaries(k, v, jnp.ones((H, d)), jnp.zeros((H, d)), 4)
    plan = attention.plan(q.shape, k.shape, "cpu", "pallas", 4, eva=(32, 4))
    return plan, lambda: attention.eva_attention(q, k, v, kbar, vbar, 32, 4)


def _selected(d=D):
    q, k, v = _qkv(64, kv_heads=1, d=d)
    bits = attention.pack_selection(jnp.tril(jnp.ones((B, 64, 64), bool)))
    plan = attention.plan(q.shape, k.shape, "cpu", "pallas", 4, selected=8)
    return plan, lambda: attention.selected_attention(
        q, k, v, bits, jnp.zeros((B, 64), jnp.int32), 8)


# the call -> (its plan and the call itself, the launches' tags, their layout)
CALLS = {
    "flash": (lambda: _flash(), ["flash"], "heads"),
    "flash_static_window": (lambda: _flash(window=16), ["flash"], "heads"),
    "flash_bidirectional": (lambda: _flash(causal=False), ["flash"], "heads"),
    "blockdiff": (_blockdiff, ["blockdiff"], "heads"),
    "eva_one_window": (lambda: _eva(32), ["eva_local"], "heads"),
    "eva_four_windows": (lambda: _eva(128), ["eva_local", "eva_far"], "heads"),
    "selected": (_selected, ["dsa"], "heads"),
    "flash_wide_head": (lambda: _flash(WIDE), ["flash"], "rows"),
    "flash_static_window_wide_head": (lambda: _flash(WIDE, window=16), ["flash"], "rows"),
    "blockdiff_wide_head": (lambda: _blockdiff(WIDE, 1), ["blockdiff"], "rows"),
    "blockdiff_wide_head_ungrouped": (lambda: _blockdiff(WIDE), ["blockdiff"], "heads"),
    "eva_four_windows_wide_head": (lambda: _eva(128, WIDE), ["eva_local", "eva_far"], "heads"),
    "selected_wide_head": (lambda: _selected(WIDE), ["dsa"], "rows"),
}


@pytest.mark.parametrize("call", sorted(CALLS))
def test_an_entry_point_launches_what_its_plan_lists(monkeypatch, call):
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    build, tags, layout = CALLS[call]
    plan, run = build()
    assert plan.route == "kernel" and [at.tag for at in plan.launches] == tags
    assert [at.layout for at in plan.launches] == [layout] * len(tags)
    launched = []
    kernel = pallas_flash._flash

    def recording(cfg, q, k, *rest):
        # (by rows q is [B, 1, S, heads x D], else [B x kv heads, G, S, D])
        launched.append((cfg, q.shape[2], k.shape[1]))
        assert q.shape[1] == 1 or cfg.layout == "heads"
        return kernel(cfg, q, k, *rest)

    monkeypatch.setattr(pallas_flash, "_flash", recording)
    run()
    assert len(launched) == len(plan.launches)
    for at, (cfg, sq, sk) in zip(plan.launches, launched):
        assert (at.sq, at.sk, at.tiles, at.window) == (sq, sk, cfg.tiles, cfg.window)
        assert cfg.layout == at.layout == plan.layout(at.tag)
        assert cfg.tag == (at.tag if at.tag in pallas_flash.TAGS else None)
        assert (cfg.blockdiff is not None) == (at.tag == "blockdiff")
        assert (cfg.summaries is not None) == (at.tag == "eva_far")
        assert plan.dq(at.tag) == pallas_flash.dq_mode(sq, sk, cfg.tiles, cfg.window)
    if call.startswith("flash_static_window"):
        assert plan.launches[0].window == 16


@pytest.mark.parametrize("call", sorted(CALLS))
def test_off_the_kernel_route_a_plan_lists_no_launch(monkeypatch, call):
    """The CPU's own route is XLA's: no launch, no dq mode, and no kernel is
    built."""
    monkeypatch.setenv("DSTPU_ATTN", "")
    monkeypatch.setattr(pallas_flash, "_flash", lambda *a: pytest.fail("a kernel launch"))
    _, run = CALLS[call][0]()
    run()
    for plan in (attention.plan((B, 64, H, D), (B, 64, 1, D), "cpu", ""),
                 attention.plan((B, 128, H, D), (B, 64, H, D), "cpu", "", blockdiff=4),
                 attention.plan((B, 128, H, D), (B, 128, H, D), "cpu", "", eva=(32, 4))):
        assert plan.route == "xla" and plan.launches == () and plan.dq("flash") is None
        assert plan.layout("flash") is None


@pytest.mark.parametrize("backend,mode,length,want", [
    ("tpu", "", 16384, ("kernel", (128, 512))), ("tpu", "", 1024, ("kernel", (128, 512))),
    ("tpu", "pallas", 2048, ("kernel", (128, 512))),
    ("tpu", "", 3072, ("kernel", (128, 512))),
    ("tpu", "xla", 16384, ("bisection", None)),     # `attn_mode` asks for XLA
    ("tpu", "", 512, ("bisection", None)),          # a short row's plane is 64 queries
    ("tpu", "", 1536, ("bisection", None)),         # not whole groups of 1,024
    ("tpu", "", 1000, ("bisection", None)),
    ("cpu", "", 16384, ("bisection", None)),        # off the chip the XLA loop, always:
    ("cpu", "pallas", 16384, ("bisection", None)),  # a model's interpreted runs too
    ("cpu", "pallas", 64, ("bisection", None)),
])
def test_the_selections_form_follows_the_back_end_and_the_row(backend, mode, length, want):
    """`attention.select_launch`: the one launch of ``pallas_select`` only on a
    TPU, at whole groups of 1,024 queries in planes of the chip's 128 lanes."""
    assert attention.select_launch(length, backend, mode) == want
    assert attention.SELECT_THRESHOLD == "bisection"


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_dsa_select_takes_the_form_its_rule_names(monkeypatch, backend):
    """`dsa_select` on a row of 1,024: the rule's launch, under scope
    ``select`` and by the launch's name, where the back end is the chip; the
    XLA loop's two scopes elsewhere."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setenv("DSTPU_ATTN", "")
    monkeypatch.setattr(pallas_flash, "_auto_interpret", lambda: True)
    L, J, d = 1024, 2, 8
    draw = lambda i, shape: jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)
    args = draw(0, (B, L, J, d)), draw(1, (B, L, d)), draw(2, (B, L, J))
    docs = (jnp.arange(L) >= 300).astype(jnp.int32)[None]
    fn = jax.jit(lambda *a: attention.dsa_select(*a, docs, 64))
    text, jaxpr = fn.lower(*args).as_text(debug_info=True), str(jax.make_jaxpr(fn)(*args))
    assert ("pallas_call" in jaxpr and "name=dsa_select" in jaxpr) == (backend == "tpu")
    assert "select/" in text and ("attn/indexer" in text) == (backend == "cpu")
    want = attention.dsa_select_xla(*args, docs, 64)
    got = fn(*args)
    assert got.shape == want.shape and got.dtype == jnp.int8
    # (the heads' sum in another order: a pair at a row's threshold may differ)
    differ = attention.unpack_selection(got, L) != attention.unpack_selection(want, L)
    assert int(jnp.sum(differ)) <= 2 * 4
