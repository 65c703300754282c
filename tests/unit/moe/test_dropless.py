"""The no-drop expert path (``moe/layer.py::MoE.dropless_forward``: sorted
assignments, grouped matmuls) against a dense all-experts float32
computation written here from the equations: every expert on every token
under the 0/1 mask of the tokens that chose it. Tolerance 1e-5 of an
array's largest element (1e-5 absolute where that is under 1): float32
rounding over a few hundred-term sums; a bf16 run of the same layer misses
it by two orders of magnitude (the last test holds that)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.moe.sharded_moe import softmax_topk_router

TOL = 1e-5
H, F, B, S = 32, 16, 2, 24


def close(got, want):
    want = np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=TOL * max(1.0, float(np.abs(want).max())))


def layer(num_experts, top_k, **kw):
    kw.setdefault("balance_loss", "topk_share")
    return MoE(hidden_size=H, intermediate_size=F, num_experts=num_experts,
               top_k=top_k, capacity_factor=None, **kw)


def skewed(moe, seed=0):
    """Parameters and tokens with an uneven routing: every token carries a
    shared direction, expert 0's router column points along it (more than
    half of the tokens choose it: the most one expert can get is every
    token once) and the last quarter of the experts point against it (no
    token chooses them)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = jax.tree.map(lambda a: a * 10.0, moe.init(k1))      # outputs of order 0.1-1
    base = jax.random.normal(k2, (H,)) / np.sqrt(H)
    x = base * 3.0 + jax.random.normal(k3, (B, S, H))
    gate = params["gate"]
    dead = moe.num_experts // 4
    gate = gate.at[:, 0].set(base * 3.0).at[:, -dead:].set(-base[:, None] * 6.0)
    return dict(params, gate=gate), x


def dense(moe, params, x):
    """All experts on all tokens under the mask; float32, the obvious way."""
    t = x.reshape(-1, H).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(t @ params["gate"].astype(jnp.float32), axis=-1)
        top, idx = jax.lax.top_k(probs, moe.top_k)
        if moe.normalize_weights:
            top = top / top.sum(-1, keepdims=True)
        w = jnp.einsum("tk,tke->te", top, jax.nn.one_hot(idx, moe.num_experts))
        mid = (jax.nn.silu(jnp.einsum("th,ehf->etf", t, params["wi_gate"]))
               * jnp.einsum("th,ehf->etf", t, params["wi_up"]))
        y = jnp.einsum("etf,efh->eth", mid, params["wo"])
        return jnp.einsum("eth,te->th", y, w).reshape(x.shape)


def probe(fn, moe, params, x):
    """A scalar of the layer's output with a fixed random cotangent, so
    that the gradient tests every element of it."""
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    return jnp.sum(fn(moe, params, x) * ct)


def run_layer(moe, params, x):
    with jax.default_matmul_precision("highest"):
        return moe(params, x)[0]


CASES = [pytest.param(64, 8, id="8-of-64"), pytest.param(8, 3, id="3-of-8")]


@pytest.mark.parametrize("normalize", [False, True], ids=["as-is", "renormalised"])
@pytest.mark.parametrize("num_experts,top_k", CASES)
def test_output_and_gradients_match_dense(num_experts, top_k, normalize):
    moe = layer(num_experts, top_k, normalize_weights=normalize)
    params, x = skewed(moe)
    _, _, _, rows = route_of(moe, params, x)
    assert rows.sum() == B * S * top_k                 # nothing dropped
    assert rows[0] > B * S // 2 and (rows == 0).sum() >= num_experts // 4
    close(jax.jit(lambda p, v: run_layer(moe, p, v))(params, x),
          jax.jit(lambda p, v: dense(moe, p, v))(params, x))
    got = jax.jit(jax.grad(lambda p, v: probe(run_layer, moe, p, v), (0, 1)))(params, x)
    want = jax.jit(jax.grad(lambda p, v: probe(dense, moe, p, v), (0, 1)))(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(g, w)


def route_of(moe, params, x):
    t = x.reshape(-1, H)
    with jax.default_matmul_precision("highest"):
        return softmax_topk_router(t @ params["gate"], moe.top_k,
                                   normalize=moe.normalize_weights,
                                   balance_loss=moe.balance_loss)


@pytest.mark.parametrize("num_experts,top_k", CASES)
def test_gradients_under_checkpoint(num_experts, top_k):
    """Rematerialised whole (an explicit ``"nothing_saveable"``, or a
    budget with no room, recomputes the layer): the custom backward of the
    two row movements replays the same numbers."""
    moe = layer(num_experts, top_k, normalize_weights=False)
    params, x = skewed(moe, seed=1)
    plain = jax.grad(lambda p, v: probe(run_layer, moe, p, v), (0, 1))(params, x)
    remat = jax.grad(jax.checkpoint(lambda p, v: probe(run_layer, moe, p, v)),
                     (0, 1))(params, x)
    want = jax.grad(lambda p, v: probe(dense, moe, p, v), (0, 1))(params, x)
    for a, b, w in zip(*map(jax.tree.leaves, (plain, remat, want))):
        np.testing.assert_array_equal(a, b)
        close(b, w)


@pytest.mark.parametrize("num_experts,top_k", CASES)
def test_router_losses_are_the_papers(num_experts, top_k):
    """E x sum_e f_e P_e over the T x k assignments, and mean logsumexp^2,
    by hand from the logits; both carry a gradient to the router."""
    moe = layer(num_experts, top_k, normalize_weights=False)
    params, x = skewed(moe, seed=2)
    _, losses, rows = moe.dropless_forward(params, x)
    logits = np.asarray(x.reshape(-1, H), np.float64) @ np.asarray(params["gate"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    chosen = np.argsort(-probs, axis=-1, kind="stable")[:, :top_k]
    f = np.bincount(chosen.reshape(-1), minlength=num_experts) / chosen.size
    np.testing.assert_array_equal(np.asarray(rows), f * chosen.size)
    assert float(losses[0]) == pytest.approx(num_experts * (f * probs.mean(0)).sum(), rel=1e-5)
    lse = np.log(np.exp(logits).sum(-1))
    assert float(losses[1]) == pytest.approx((lse ** 2).mean(), rel=1e-5)
    g = jax.grad(lambda p: moe.dropless_forward(p, x)[1].sum())(params)["gate"]
    assert float(jnp.abs(g).max()) > 0


def test_equals_the_capacity_path_when_capacity_cannot_bind():
    """Capacity = tokens and renormalised weights: the two paths compute
    the same layer, and with ``gshard_top1`` the same balance loss."""
    kw = dict(hidden_size=H, intermediate_size=F, num_experts=8, top_k=2)
    bucketed = MoE(capacity_factor=8.0, **kw)
    dropless = MoE(capacity_factor=None, normalize_weights=True,
                   balance_loss="gshard_top1", **kw)
    params, x = skewed(bucketed, seed=3)
    with jax.default_matmul_precision("highest"):
        a, aux_a = bucketed(params, x)
        b, aux_b = dropless(params, x)
    close(a, b)
    assert aux_b.shape == (2,) and float(aux_a) == pytest.approx(float(aux_b[0]), rel=1e-6)


def test_capacity_path_takes_no_description_of_the_other():
    with pytest.raises(ValueError, match="capacity_factor=None"):
        MoE(hidden_size=H, intermediate_size=F, normalize_weights=False)
    with pytest.raises(ValueError, match="capacity_factor=None"):
        MoE(hidden_size=H, intermediate_size=F, balance_loss="topk_share")
    with pytest.raises(ValueError, match="balance_loss"):
        layer(8, 2, balance_loss="nope").dropless_forward(*skewed(layer(8, 2)))


def test_a_live_expert_axis_is_refused(eight_devices):
    from deepspeed_tpu.runtime import topology as topo_mod
    from deepspeed_tpu.runtime.topology import TopologyConfig
    moe = layer(8, 3)
    params, x = skewed(moe)
    topo_mod.reset()
    try:
        topo = topo_mod.initialize(TopologyConfig(expert=2, data=-1), force=True)
        with topo.mesh, pytest.raises(NotImplementedError, match="expert"):
            moe(params, x)
    finally:
        topo_mod.reset()


def test_the_pallas_capacity_kernels_stay_out_of_the_way():
    """OLMoE's shape resolves to no kernel, cleanly (top-k 8, hidden 2048)."""
    from deepspeed_tpu.ops.transformer import pallas_moe as pm
    shape = dict(top_k=8, activation="silu_gated", dtype=jnp.bfloat16,
                 tokens=4096, num_experts=64, hidden=2048)
    assert not pm.moe_kernel_supported(**shape)
    assert pm.moe_kernel_resolution(**shape, kernel=None).startswith("xla")


def test_bf16_fails_the_float32_tolerance():
    """The tolerance tells float32 from bfloat16: the same layer on bf16
    parameters and tokens is off by more than 10x the tolerance."""
    moe = layer(8, 3, normalize_weights=False)
    params, x = skewed(moe)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    got = moe(low, x.astype(jnp.bfloat16))[0].astype(jnp.float32)
    assert float(jnp.abs(got - dense(moe, params, x)).max()) > 10 * TOL


def both_routes(monkeypatch, make, *args):
    """``make()(*args)`` traced with ``grouped_matmul`` on its ``ragged_dot``
    route (what the CPU takes) and again with the kernel forced through the
    route function itself (interpret mode here). ``make`` builds the function
    anew: a scan keeps the jaxpr of a body it has traced."""
    from deepspeed_tpu.ops.transformer import pallas_gmm
    want = make()(*args)
    assert "pallas_call" not in str(jax.make_jaxpr(make())(*args))
    monkeypatch.setattr(pallas_gmm, "choose_route", lambda *a: "kernel")
    text = str(jax.make_jaxpr(make())(*args))
    assert "pallas_call" in text and "ragged_dot" not in text
    return make()(*args), want


def under(remat, moe_loss):
    """``moe_loss(params, x)`` as one block of a scan, plain or rematerialised
    under the models' default policy (``KEEP_PRODUCTS``: the products' names
    are what the backward keeps)."""
    from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
        KEEP_PRODUCTS, checkpointed)

    def block(carry, layer):
        acc, x = carry
        return (acc + moe_loss(layer, x), x), None
    if remat:
        block = checkpointed(block, KEEP_PRODUCTS, 1)

    def loss(params, x):
        stacked = jax.tree.map(lambda a: a[None], params)
        return jax.lax.scan(block, (jnp.zeros(()), x), stacked)[0][0]
    return jax.value_and_grad(loss, (0, 1))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "keep-products"])
def test_all_rows_through_the_kernel_is_the_ragged_dot_program(remat, monkeypatch):
    """``_all_rows`` with its three products through the Pallas grouped
    matmul against the same layer through ``ragged_dot``: loss and every
    gradient, at the file's float32 tolerance."""
    moe = layer(8, 3, normalize_weights=False)
    params, x = skewed(moe, seed=2)
    make = lambda: under(remat, lambda p, v: probe(run_layer, moe, p, v))
    (got, g), (want, w) = both_routes(monkeypatch, make, params, x)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
        close(a, b)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "keep-products"])
def test_held_rows_through_the_kernel_is_the_ragged_dot_program(remat, monkeypatch):
    """``_held_rows`` (4 of 16 experts held, the buffer's unfilled rows in
    the last group) the same way."""
    moe = MoE(H, F, num_experts=16, top_k=3, capacity_factor=None,
              balance_loss="topk_share", router="sigmoid_bias", routed_scale=2.5,
              experts_held=(4, 8))
    params = jax.tree.map(lambda a: a * 10.0, moe.init(jax.random.PRNGKey(3)))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 512, H))
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def moe_loss(p, v):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(moe.dropless_forward(p, v)[0] * ct)
    make = lambda: jax.jit(under(remat, lambda p, v: moe_loss(p, v)))
    (got, g), (want, w) = both_routes(monkeypatch, make, params, x)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
        close(a, b)


def test_grouped_products_are_the_shapes_the_layer_multiplies(monkeypatch):
    """What the engine counts (``MoE.grouped_products``) is what
    ``dropless_forward`` hands ``grouped_matmul``, with all experts held and
    with a share."""
    from deepspeed_tpu.ops.transformer import pallas_gmm
    seen = []

    def spy(rows, stack, sizes, devices=1):
        seen.append((rows.shape[0], rows.shape[1], stack.shape[2], stack.shape[0]))
        assert devices == 1
        return jax.lax.ragged_dot(rows, stack, sizes)
    monkeypatch.setattr(pallas_gmm, "grouped_matmul", spy)
    for held in (None, (4, 8)):
        moe = MoE(H, F, num_experts=16, top_k=3, capacity_factor=None,
                  balance_loss="topk_share", experts_held=held)
        x = jnp.ones((2, 512, H))
        del seen[:]
        jax.eval_shape(moe.dropless_forward, moe.init(jax.random.PRNGKey(0)), x)
        names, shapes = zip(*((p[0], p[1:]) for p in moe.grouped_products(2 * 512)))
        assert names == ("wi_gate", "wi_up", "wo") and list(shapes) == seen
    assert seen[0][0] < 2 * 512 * 3 and seen[0][3] == 4       # a share's buffer


# -- a share's rows back to the tokens (PR 38) --------------------------------
# ``_held_rows`` sums each token's rows of the held experts' buffer over the
# buffer's rows alone (``_token_order``, one gather, ``pallas_segment_sum``).
# The reference here does it the plain way: every held expert on every token,
# then one ``[tokens, h]`` slab a slot of the k.

SHARE_T, SHARE_E = 1024, 32


def share_layer(top_k, held):
    return MoE(H, F, num_experts=SHARE_E, top_k=top_k, capacity_factor=None,
               balance_loss="topk_share", normalize_weights=False, experts_held=held)


def steered(moe, fill, seed=0):
    """Parameters and tokens whose routing is set by hand: the gate copies a
    token's first E features, which hold its logits. ``fill``: how many
    assignments the held experts draw against the buffer's rows: ``below``
    it, exactly ``at`` it, ``beyond`` it (so that ``_overflow_rows`` runs)."""
    from deepspeed_tpu.moe.layer import held_capacity
    lo, hi = moe.held
    k, nh = moe.top_k, hi - lo
    cap = held_capacity(SHARE_T * k, nh, SHARE_E)
    want = {"below": cap // 2, "at": cap, "beyond": cap + cap // 4}[fill]
    rng = np.random.default_rng(seed)
    logits = rng.normal(0.0, 0.3, (SHARE_T, SHARE_E))
    logits[:, lo:hi] -= 8.0                       # nobody comes by chance
    each = min(k, nh)                             # held rows a steered token gives
    for t in rng.permutation(SHARE_T)[:-(-want // each)]:
        n = min(each, want)
        logits[t, lo + rng.permutation(nh)[:n]] += 16.0
        want -= n
    assert want == 0
    x = rng.normal(0.0, 1.0, (1, SHARE_T, H))
    x[0, :, :SHARE_E] = logits
    params = jax.tree.map(lambda a: a * 10.0, moe.init(jax.random.PRNGKey(seed)))
    gate = jnp.zeros((H, SHARE_E)).at[:SHARE_E].set(jnp.eye(SHARE_E))
    return dict(params, gate=gate), jnp.asarray(x, jnp.float32), cap


def slab_by_slab(moe, params, x):
    """The held experts' part of the layer: float32, a slab a slot."""
    lo, hi = moe.held
    t = x.reshape(-1, H).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        top, idx = jax.lax.top_k(jax.nn.softmax(t @ params["gate"], axis=-1), moe.top_k)
        mid = (jax.nn.silu(jnp.einsum("th,ehf->etf", t, params["wi_gate"]))
               * jnp.einsum("th,ehf->etf", t, params["wi_up"]))
        y = jnp.einsum("etf,efh->eth", mid, params["wo"])           # [held, T, h]
    out = jnp.zeros_like(t)
    for j in range(moe.top_k):
        e = idx[:, j] - lo
        slab = y[jnp.clip(e, 0, hi - lo - 1), jnp.arange(t.shape[0])]
        out = out + jnp.where(((e >= 0) & (e < hi - lo))[:, None],
                              top[:, j, None] * slab, 0.0)
    return out.reshape(x.shape)


@pytest.mark.parametrize("fill", ["below", "at", "beyond"])
@pytest.mark.parametrize("held", [(8, 16), (4, 8)], ids=["a-quarter", "an-eighth"])
@pytest.mark.parametrize("top_k", [2, 6, 8])
def test_a_shares_rows_come_back_to_their_tokens(top_k, held, fill):
    """Value and every gradient (through the rows: the experts' stacks and
    the tokens; through the routing weights: the gate) of a share against
    the slab-by-slab reference, with the buffer half full, exactly full and
    over full."""
    moe = share_layer(top_k, held)
    params, x, cap = steered(moe, fill)
    rows = np.asarray(route_of(moe, params, x)[3])[held[0]:held[1]].sum()
    assert rows == {"below": cap // 2, "at": cap, "beyond": cap + cap // 4}[fill]
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)      # `probe`'s cotangent

    def both(fn):
        """`probe`, the layer's output and the gradients: one jitted program."""
        def scalar(p, v):
            out = fn(moe, p, v)
            return jnp.sum(out * ct), out
        return jax.jit(jax.value_and_grad(scalar, (0, 1), has_aux=True))(params, x)
    ((got, out), g), ((want, ref_out), w) = both(run_layer), both(slab_by_slab)
    assert float(got) == pytest.approx(float(want), rel=1e-4, abs=1e-3)   # a sum of 32 k terms
    close(out, ref_out)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
        close(a, b)


def buffer_of(top_k, held, fill, dtype, seed=0):
    """A step's sorted buffer as ``_held_rows`` hands it to the two
    movements: (rows [cap, h], weight [T, k], order, inv, perm, by_token,
    filled), the rows past ``filled`` no number."""
    from deepspeed_tpu.moe.layer import _token_order
    moe = share_layer(top_k, held)
    params, x, cap = steered(moe, fill, seed)
    eidx, weight, _, _ = route_of(moe, params, x)
    local = eidx.reshape(-1) - held[0]
    nh = held[1] - held[0]
    order = jnp.argsort(jnp.where((local >= 0) & (local < nh), local, nh),
                        stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.size, dtype=jnp.int32))
    filled = jnp.minimum(jnp.sum((local >= 0) & (local < nh)), cap).astype(jnp.int32)
    order = order[:cap]
    rows = jax.random.normal(jax.random.PRNGKey(seed), (cap, 128), jnp.float32)
    rows = jnp.where((jnp.arange(cap) < filled)[:, None], rows, jnp.nan).astype(dtype)
    return (rows, weight, order, inv, *_token_order(order, filled), filled)


def combine_slabs(rows, weight, inv, filled):
    """The combine one slab a slot, in float64 on the host."""
    rows, weight = np.asarray(rows, np.float64), np.asarray(weight, np.float64)
    inv = np.asarray(inv).reshape(weight.shape)
    out = np.zeros((weight.shape[0], rows.shape[1]))
    for j in range(weight.shape[1]):
        mine = inv[:, j] < int(filled)
        out[mine] += weight[mine, j, None] * rows[inv[mine, j]]
    return out


def forced_route(monkeypatch, route):
    """``segment_sum`` on ``route`` whatever the backend (the kernel in
    interpret mode here); -> the list its kernel calls are noted in."""
    from deepspeed_tpu.ops.transformer import pallas_segment_sum as S
    calls, kernel = [], S.kernel_segment_sum
    monkeypatch.setattr(S, "choose_route", lambda *a: route)
    monkeypatch.setattr(S, "kernel_segment_sum",
                        lambda *a, **kw: calls.append(a[0].shape) or kernel(*a, **kw))
    return calls


@pytest.mark.parametrize("route", ["xla", "kernel"])
@pytest.mark.parametrize("fill", ["below", "at"])
def test_the_buffers_tail_is_selected_away(fill, route, monkeypatch):
    """The rows past ``filled`` hold NaN (a grouped matmul leaves them as it
    found them): both movements give finite results equal to the slabs', on
    either route (the kernel in interpret mode here)."""
    from deepspeed_tpu.moe.layer import _combine_held_rows, _dispatch_held_rows
    calls = forced_route(monkeypatch, route)
    rows, weight, order, inv, perm, by_token, filled = buffer_of(6, (4, 8), fill, jnp.bfloat16)
    assert (fill == "at") == (int(filled) == rows.shape[0])
    out = _combine_held_rows(rows, weight, order, inv, perm, by_token, filled)
    assert calls == [rows.shape] * (route == "kernel")
    assert np.isfinite(np.asarray(out)).all()
    close(out, combine_slabs(rows, weight, inv, filled))
    # the dispatch's backward is the same sum without the weight
    tokens = jnp.zeros((weight.shape[0], rows.shape[1]), jnp.bfloat16)
    d = jax.vjp(lambda t: _dispatch_held_rows(t, order, perm, by_token, filled,
                                              weight.shape), tokens)[1](rows)[0]
    assert d.dtype == jnp.bfloat16 and np.isfinite(np.asarray(d, np.float32)).all()
    want = combine_slabs(rows, np.ones(weight.shape), inv, filled)
    np.testing.assert_allclose(np.asarray(d, np.float32), want, rtol=2 ** -8,
                               atol=2 ** -8 * np.abs(want).max())


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_a_routing_weight_keeps_its_float32(route, monkeypatch):
    """Weights bfloat16 cannot hold (1/3, 0.1234567, ...) over bfloat16 rows
    of ones: each token's sum is its weights' to 1e-6, so no operand of the
    sum was rounded to bfloat16 on the way."""
    from deepspeed_tpu.moe.layer import _combine_held_rows
    forced_route(monkeypatch, route)
    rows, weight, order, inv, perm, by_token, filled = buffer_of(8, (8, 16), "below", jnp.bfloat16)
    rows = jnp.where(jnp.isnan(rows), rows, jnp.ones_like(rows))
    odd = jnp.asarray([1 / 3, 0.1234567, 0.7071068, 1e-3 / 7], jnp.float32)
    weight = odd[jnp.arange(weight.size) % 4].reshape(weight.shape)
    out = np.asarray(_combine_held_rows(rows, weight, order, inv, perm, by_token, filled))
    want = combine_slabs(rows, weight, inv, filled)
    assert want.max() > 1.0
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=0)
    rounded = combine_slabs(rows, weight.astype(jnp.bfloat16), inv, filled)
    assert np.abs(rounded - want).max() > 1e-4 * want.max()       # bf16 would show


@pytest.mark.parametrize("m,segments,h,dtype,backend,devices,route", [
    (36864, 16384, 2048, jnp.bfloat16, "tpu", 1, "kernel"),    # the Instella cell
    (49152, 16384, 2048, jnp.bfloat16, "tpu", 1, "kernel"),    # the Trinity cell
    (36864, 16384, 2048, jnp.float16, "tpu", 1, "kernel"),
    (36864, 16384, 2048, jnp.bfloat16, "cpu", 1, "xla"),       # the tests' program is XLA's
    (36864, 16384, 2048, jnp.bfloat16, "gpu", 1, "xla"),
    (36864, 16384, 2048, jnp.bfloat16, "tpu", 4, "xla"),       # GSPMD does not partition it
    (36864, 16384, 2048, jnp.float32, "tpu", 1, "xla"),        # the MXU would round a row
    (36864, 16384, 2048, jnp.int8, "tpu", 1, "xla"),
    (36864, 16384, 2000, jnp.bfloat16, "tpu", 1, "xla"),       # a width off the lanes
    (36864 + 8, 16384, 2048, jnp.bfloat16, "tpu", 1, "xla"),   # no 128-multiple row tile
    (36864, 16384 + 8, 2048, jnp.bfloat16, "tpu", 1, "xla"),
    (36864, 16384, 1 << 16, jnp.bfloat16, "tpu", 1, "xla"),    # blocks over the budget
    (512, 128, 128, jnp.bfloat16, "tpu", 1, "kernel"),
])
def test_segment_sum_route_table(m, segments, h, dtype, backend, devices, route):
    from deepspeed_tpu.ops.transformer import pallas_segment_sum as S
    assert S.choose_route(m, segments, h, dtype, backend, devices) == route
    if route == "kernel":
        ts, tr, limit = S.choose_tiles(m, segments, h, jnp.dtype(dtype).itemsize)
        assert segments % ts == 0 and m % tr == 0 and ts % 128 == 0 and tr % 128 == 0
        assert S.vmem_bytes(ts, tr, h, 2) <= min(S.VMEM_BUDGET, limit) and limit <= S.VMEM_CAP


def test_a_steps_work_does_not_follow_the_rows():
    """The kernel's grid, blocks and loop bounds come from ``(rows, tokens,
    h)`` alone: the traced program is the same text whatever ``filled``."""
    from deepspeed_tpu.ops.transformer import pallas_segment_sum as S
    rows = jnp.ones((512, 128), jnp.bfloat16)
    seg = jnp.sort(jnp.arange(512) % 256).astype(jnp.int32)
    texts = {str(jax.make_jaxpr(lambda f: S.kernel_segment_sum(
        rows, seg, jnp.ones((512,)), f, 256))(jnp.int32(filled))) for filled in (0, 100, 512)}
    assert len(texts) == 1 and "grid=(" + str(512 // 256 + 256 // 128) in texts.pop().replace(" ", "")


# -- the router's bookkeeping without a scatter or a gather of scalars (PR 54) --
# The formulas the routers and the two paths had until PR 53, kept here as the
# plain reference: the experts' counts by a scatter-add, the inverse
# permutation by a scatter, the chosen scores as ``top_k``'s values (whose
# gradient is a scatter-add). What replaced them gives the same integers and
# the same bits, forward and in the gradient.

def softmax_router_as_it_was(logits, top_k, *, normalize, balance_loss="topk_share"):
    tokens, num_experts = logits.shape
    logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    weight, expert_idx = jax.lax.top_k(gates, top_k)
    if normalize:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    rows = jnp.zeros((num_experts,), jnp.int32).at[expert_idx.reshape(-1)].add(1)
    if balance_loss == "topk_share":
        share = rows.astype(jnp.float32) / (tokens * top_k)
    else:
        share = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], num_experts,
                                        dtype=jnp.float32), axis=0)
    balance = num_experts * jnp.sum(share * jnp.mean(gates, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return (expert_idx.astype(jnp.int32), weight, jnp.stack([balance, z]), rows)


def sorted_as_it_was(key):
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.size, dtype=jnp.int32), unique_indices=True)
    return order, inv


def tied_logits(tokens, experts, seed):
    """Logits in steps of a half, so that most rows hold ties among their
    largest; the last expert far below (it draws nothing)."""
    rng = np.random.default_rng(seed)
    logits = np.round(rng.normal(0.0, 1.0, (tokens, experts)) * 2) / 2
    logits[:, -1] = -30.0
    return jnp.asarray(logits, jnp.float32)


def same_bits(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def routed(router, logits, ct, **kw):
    """What a router returns, and the gradients of its weights (under the
    cotangent ``ct``) and of its losses by the logits."""
    out = router(logits, **kw)
    d_weight = jax.grad(lambda l: jnp.sum(router(l, **kw)[1] * ct))(logits)
    d_losses = jax.grad(lambda l: jnp.sum(router(l, **kw)[2] * jnp.asarray([1.0, 0.37])))(logits)
    return out, d_weight, d_losses


@pytest.mark.parametrize("balance_loss", ["topk_share", "gshard_top1"])
@pytest.mark.parametrize("normalize", [False, True], ids=["as-is", "renormalised"])
@pytest.mark.parametrize("top_k", [1, 6, 8])
def test_the_softmax_router_is_the_integers_and_the_bits_it_was(top_k, normalize, balance_loss):
    logits = tied_logits(96, 16, seed=top_k)
    ct = jax.random.normal(jax.random.PRNGKey(3), (96, top_k))
    kw = dict(top_k=top_k, normalize=normalize, balance_loss=balance_loss)
    got = jax.jit(lambda l, c: routed(softmax_topk_router, l, c, **kw))(logits, ct)
    want = jax.jit(lambda l, c: routed(softmax_router_as_it_was, l, c, **kw))(logits, ct)
    same_bits(got, want)
    rows = np.asarray(got[0][3])
    assert rows[-1] == 0 and rows.sum() == 96 * top_k
    assert float(jnp.abs(got[1]).max()) > 0 and float(jnp.abs(got[2]).max()) > 0


@pytest.mark.parametrize("held", [(0, 16), (4, 8)], ids=["every-expert", "a-quarter"])
@pytest.mark.parametrize("top_k", [1, 6, 8])
def test_the_sort_and_its_inverse_are_the_permutations_they_were(top_k, held):
    from deepspeed_tpu.moe.layer import _sorted_by
    eidx = jax.lax.top_k(tied_logits(96, 16, seed=top_k), top_k)[1].reshape(-1)
    local, nh = eidx - held[0], held[1] - held[0]
    key = jnp.where((local >= 0) & (local < nh), local, nh)
    order, inv = jax.jit(_sorted_by)(key)
    same_bits((order, inv), sorted_as_it_was(key))
    np.testing.assert_array_equal(np.asarray(order)[np.asarray(inv)], np.arange(96 * top_k))


def as_it_was(monkeypatch):
    """The layer on PR 53's formulas."""
    from deepspeed_tpu.moe import layer as L
    monkeypatch.setattr(L, "softmax_topk_router", softmax_router_as_it_was)
    monkeypatch.setattr(L, "_sorted_by", sorted_as_it_was)


def layer_and_gradients(moe, params, x):
    def loss(p, v):
        out, losses, rows = moe.dropless_forward(p, v)
        return jnp.sum(jnp.sin(out)) + jnp.sum(losses), (out, losses, rows)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(params, x)


@pytest.mark.parametrize("top_k,held,fill", [
    (1, (8, 16), "below"), (6, None, "below"), (8, (8, 16), "beyond")])
def test_the_layer_is_the_program_it_was(top_k, held, fill, monkeypatch):
    """Both paths over two sequences a batch, with the held experts drawing
    fewer rows than the buffer has and more (``_overflow_rows`` runs):
    output, losses and rows to the bit. The gradients to float32's rounding:
    op for op they are the same bits too (run eagerly they are), but the CPU
    compiler contracts multiply-adds as its fusions fall, and those fall
    differently around a scatter-add and a select."""
    moe = share_layer(top_k, held)
    params, x, cap = steered(share_layer(top_k, (8, 16)), fill)
    if held is None:
        whole = jax.tree.map(lambda a: a * 10.0, moe.init(jax.random.PRNGKey(0)))
        params = dict(whole, gate=params["gate"])
    x = x.reshape(2, SHARE_T // 2, H)
    (got, got_aux), got_g = layer_and_gradients(moe, params, x)
    as_it_was(monkeypatch)
    (want, want_aux), want_g = layer_and_gradients(moe, params, x)
    same_bits((got, got_aux), (want, want_aux))
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        close(a, b)
    drawn = int(np.asarray(got_aux[2])[8:16].sum())
    assert (drawn > cap) is (fill == "beyond")


def under_scope(jaxpr, scope, outer=""):
    """(name stack, primitive) of every equation under the name scope,
    through every jaxpr an equation carries (``jit``, a custom gradient, a
    loop, a rematerialised block)."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        if scope in stack:
            yield stack, eqn.primitive.name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from under_scope(sub, scope, stack)


@pytest.mark.parametrize("held", [None, (4, 8)], ids=["every-expert", "a-quarter"])
@pytest.mark.parametrize("router", ["softmax", "sigmoid_bias"])
def test_the_route_lowers_to_no_scatter_and_no_gather(router, held):
    """No chip needed: the forward, and the gradient of a block made again
    (forward, made again and backward), as jaxprs. The TPU runs a scatter,
    a scatter-add and a gather of scalars one element at a time; a sort, a
    compare and a select it does not."""
    moe = MoE(H, F, num_experts=16, top_k=3, capacity_factor=None, router=router,
              balance_loss="topk_share", experts_held=held)
    params, x = moe.init(jax.random.PRNGKey(0)), jnp.zeros((2, 64, H))

    def loss(p, v):
        out, losses, _ = moe.dropless_forward(p, v)
        return jnp.sum(out) + jnp.sum(losses)

    made_again = ("/jvp(moe/route", "/transpose(jvp(jvp()))/rematted_computation/moe/route",
                  "/transpose(jvp(jvp()))/moe/route")
    for fn, passes in ((moe.dropless_forward, ("/moe/route",)),
                       (jax.grad(jax.checkpoint(loss), (0, 1)), made_again)):
        jaxpr = jax.make_jaxpr(fn)(params, x).jaxpr
        found = list(under_scope(jaxpr, "moe/route"))
        assert all(any(s.startswith(p) for s, _ in found) for p in passes)
        assert {"top_k", "sort", "dot_general"} <= {name for _, name in found}
        assert not [f for f in found if "scatter" in f[1] or "gather" in f[1]]
        # and the walk does see one where there is one
        assert any("gather" in name for _, name in under_scope(jaxpr, "moe/dispatch"))
