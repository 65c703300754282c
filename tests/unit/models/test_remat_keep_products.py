"""What ``remat=True`` keeps by default (``checkpointing.KEEP_PRODUCTS``):
the block's backward saves what a matmul or a kernel produced, inside a
byte budget, and recomputes norms, rope, activations and residual adds.

A saved value is the value the recomputation would have produced, so
gradients must equal those of an explicit ``"nothing_saveable"`` bit for
bit; what changes is the program, which the jaxpr checks read."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt2_model, llama_model, olmoe_model
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    KEEP_PRODUCTS, SAVE_ORDER, STACK_COST, Budget,
    Kind, choose_saved, live_bytes, named_bytes, saved_budget, step_candidates,
    step_costs)

SEQ = 128   # the interpreted flash kernel's smallest tile


def _model(family: str, policy: str, dtype=jnp.float32):
    kw = dict(max_seq_len=SEQ, vocab_size=256, remat=True, remat_policy=policy,
              dtype=dtype)
    if family == "dense":        # learned positions, LayerNorm, GELU, biases
        return gpt2_model("gpt2-tiny", **kw)
    if family == "rope_gated":   # rope, RMSNorm, gated SiLU, GQA
        return llama_model("llama2-tiny", **kw)
    return olmoe_model("olmoe-tiny", **kw)   # no-drop MoE, QK-norm


def _batch(rows: int = 2):
    ids = np.random.default_rng(0).integers(0, 256, size=(rows, SEQ))
    return {"input_ids": jnp.asarray(ids, jnp.int32)}


def _grads(model, room=None, dtype=jnp.float32):
    """((loss, gradients), the decision's totals) under an engine's
    reading ``room`` of the device; None saves everything named, as a
    model without an engine does."""
    params = model.init(jax.random.PRNGKey(0), dtype)
    budget = Budget(room)
    fn = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, _batch(), remat_budget=budget)))
    return fn(params), budget.totals


def _room_for(model, saved_bytes: int, working_bytes: int) -> int:
    """The engine's reading under which ``saved_budget`` comes to
    ``saved_bytes``: the inverse of that function at the model's shapes,
    ``working_bytes`` as a trace of the model reckoned it."""
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.float32))
    carry = jax.eval_shape(lambda p: model.embed(p, _batch()["input_ids"], None)
                           + (model._aux_zero(),), params)
    layers = model.config.num_layers
    return (layers * checkpointing._bytes(carry) + working_bytes
            + int(np.ceil(saved_bytes * STACK_COST)))


def _assert_same_bits(a, b):
    (loss_a, grads_a), (loss_b, grads_b) = a, b
    assert float(loss_a) == float(loss_b)
    for x, y in zip(jax.tree.leaves(grads_a), jax.tree.leaves(grads_b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- (a) the gradients are the recomputing program's, bit for bit -----------

@pytest.mark.parametrize("family,attn", [
    ("dense", "xla"), ("dense", "pallas"),
    ("rope_gated", "xla"), ("rope_gated", "pallas"),
    ("moe_qk_norm", "xla"), ("moe_qk_norm", "pallas"),
])
def test_gradients_equal_nothing_saveable(monkeypatch, family, attn):
    monkeypatch.setenv("DSTPU_ATTN", attn)   # pallas: the kernel, interpreted
    kept, _ = _grads(_model(family, KEEP_PRODUCTS))
    recomputed, _ = _grads(_model(family, "nothing_saveable"))
    _assert_same_bits(kept, recomputed)


@pytest.mark.parametrize("family", ["dense", "rope_gated", "moe_qk_norm"])
def test_gradients_equal_under_a_partial_budget(monkeypatch, family):
    """A budget that admits some names and not others changes what is
    saved and not one bit of the gradients."""
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    model = _model(family, KEEP_PRODUCTS)
    _, everything = _grads(model)
    half = everything["candidate_bytes"] // 2
    got, kept = _grads(model, _room_for(model, half, everything["working_bytes"]))
    assert 0 < len(kept["saved"]) < len(everything["saved"])
    assert kept["saved"] == everything["saved"][:len(kept["saved"])]
    assert 0 < kept["saved_bytes"] <= half
    assert kept["saved_cost_bytes"] <= kept["budget_bytes"] == int(np.ceil(half * STACK_COST))
    recomputed, _ = _grads(_model(family, "nothing_saveable"))
    _assert_same_bits(got, recomputed)


@pytest.mark.parametrize("family", ["dense", "rope_gated", "moe_qk_norm"])
def test_no_room_is_the_recomputing_program(monkeypatch, family):
    """A device with no room saves nothing: the program of an explicit
    ``"nothing_saveable"``, jaxpr for jaxpr."""
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    _, kept = _grads(_model(family, KEEP_PRODUCTS), 0)
    assert kept["saved"] == () and kept["saved_bytes"] == 0 == kept["budget_bytes"]
    assert kept["candidate_bytes"] > 0
    assert _recomputed(_model(family, KEEP_PRODUCTS), Budget(0)) \
        == _recomputed(_model(family, "nothing_saveable"))


@pytest.mark.parametrize("family", ["dense", "rope_gated", "moe_qk_norm"])
def test_bfloat16_gradients_within_a_rounding(monkeypatch, family):
    """In bfloat16 a kept value is rounded to bfloat16 where it is named,
    where the recomputing program's fusions may carry float32 across the
    same point (XLA's excess precision; on the chip the first step's
    compared numbers move in the fourth digit, PERF.md PR 30). The
    difference is a rounding, not another gradient: every leaf within 2 %
    of its own norm, the loss within 2e-3."""
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    bf16 = jnp.bfloat16
    (loss_a, grads_a), _ = _grads(_model(family, KEEP_PRODUCTS, bf16), dtype=bf16)
    (loss_b, grads_b), _ = _grads(_model(family, "nothing_saveable", bf16), dtype=bf16)
    assert abs(float(loss_a) - float(loss_b)) <= 2e-3
    for x, y in zip(jax.tree.leaves(grads_a), jax.tree.leaves(grads_b)):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        assert np.linalg.norm(x - y) <= 0.02 * np.linalg.norm(y) + 1e-6


# -- (b) the decision, two pure functions ------------------------------------

MB = 1 << 20
#: name -> bytes over all layers: a kernel's output and its row statistics,
#: four matmul outputs, and an XLA route's [B, H, S, S] scores (named
#: "attn_big", which SAVE_ORDER does not list)
CANDIDATES = {
    "q_proj": 10 * MB, "k_proj": 10 * MB, "v_proj": 10 * MB, "fc_in": 40 * MB,
    "attn_o": 10 * MB, "attn_lse": MB // 4, "wo": 80 * MB, "attn_big": 2 * MB,
}
RANKED = ("attn_lse", "attn_o", "wo", "fc_in", "q_proj", "k_proj", "v_proj")
KERNEL = 10 * MB + MB // 4


#: a 32,768-row EVA step's one candidate (PR 43): nothing inside a head group
#: or an MLP slice is named, the grouped branch's output is, once a layer:
#: four ``bf16[1, 32768, 4096]``
LONG_EVA_ROW = {"o_proj": 4 * 32768 * 4096 * 2}


@pytest.mark.parametrize("candidates,budget,saved", [
    (CANDIDATES, None, RANKED),                # no budget: every name listed
    (CANDIDATES, 0, ()),                       # no room: the parent's program
    (CANDIDATES, 10 * MB, ()),                 # the kernel's two or neither
    (CANDIDATES, KERNEL, RANKED[:2]),
    # wo does not fit and fc_in, cheaper per byte, may not jump the queue
    (CANDIDATES, KERNEL + 50 * MB, RANKED[:2]),
    (CANDIDATES, KERNEL + 80 * MB, RANKED[:3]),
    (CANDIDATES, KERNEL + 120 * MB - 1, RANKED[:3]),
    (CANDIDATES, KERNEL + 120 * MB, RANKED[:4]),
    # q and k without v spare nothing (one merged matmul): all three or none
    (CANDIDATES, KERNEL + 140 * MB, RANKED[:4]),
    (CANDIDATES, KERNEL + 150 * MB, RANKED),
    (CANDIDATES, 1 << 40, RANKED),             # and never the scores
    # the EvaByte cell's budget as traced at its shape with 5.9 GB of room
    # admits the four outputs with 37 % to spare; a byte short keeps none
    (LONG_EVA_ROW, 1_693_311_258, ("o_proj",)),
    (LONG_EVA_ROW, 1_073_741_823, ()),
])
def test_choose_saved_table(candidates, budget, saved):
    assert choose_saved(candidates, budget) == saved


def test_choose_saved_takes_the_measured_order():
    names = tuple(n for group in SAVE_ORDER for n in group)
    every = {n: MB for n in names}
    assert choose_saved(every, None) == names
    # (a learned selection's values lead: the KL's gradients, the operand)
    assert choose_saved(every, 3 * MB) == ("indexer_kl_dq", "indexer_kl_dk", "indexer_kl_dw")
    assert choose_saved(every, 8 * MB)[-2:] == ("attn_lse", "attn_o")
    assert choose_saved({}, None) == () == choose_saved({}, 0)
    # the projections in front of the kernel go first when room runs out
    # (latent attention's compressed vector with them, its up-projection,
    # which contracts over the rank alone, after them)
    assert SAVE_ORDER[-2:] == (("q_proj", "k_proj", "v_proj", "kv_latent", "q_latent",
                                "q_b_proj", "indexer_q", "indexer_k"), ("kv_up",))
    # (the two-width launch's pair stands right after the plain pair: PR 55)
    assert SAVE_ORDER[3:5] == (("attn_lse", "attn_o"), ("attn_lse_mla", "attn_o_mla"))
    assert len(set(names)) == len(names)


#: the step's own needs at 36 blocks of 10 MB and a working set of 900 MB,
#: charged whole
NEEDS = 36 * 10 * MB + 900 * MB


@pytest.mark.parametrize("room,want", [
    (None, None),                                        # no reading: no budget
    (0, 0),
    (NEEDS, 0),                                          # the step's own needs
    (NEEDS + 18 * MB, 18 * MB),
    (1 << 40, (1 << 40) - NEEDS),
])
def test_saved_budget_table(room, want):
    """Room less every block's input and the working set, whole: what the
    kept values may cost the step's peak."""
    assert saved_budget(room, blocks=36, carry_bytes=10 * MB,
                        working_bytes=900 * MB) == want


# -- (b') the working set the budget is charged: a walk over a jaxpr ----------

KB = 1 << 10
X = jax.ShapeDtypeStruct((256, 256), jnp.float32)      # 256 KB


def _live(fn, *args) -> int:
    return live_bytes(jax.make_jaxpr(fn)(*args).jaxpr)


def test_live_bytes_holds_the_products_and_not_the_links():
    """``dot -> convert -> multiply -> tanh -> dot``: the first product is
    written (float32) and read by the second's fusion, which makes the three
    links inside itself; the second product (bfloat16) leaves the jaxpr."""
    w = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16)

    def chain(x, w):
        h = x @ x
        return jnp.tanh(h.astype(jnp.bfloat16) * w) @ w
    assert _live(chain, X, w) == 256 * KB + 128 * KB


def test_live_bytes_counts_what_is_held_together():
    def chain(x):                  # one fusion: only what leaves it is written
        return jnp.tanh(jnp.cos(jnp.sin(x)))
    assert _live(chain, X) == 256 * KB

    def held(x):                   # three products live while the fourth is made
        a, b, c = x @ x, x @ x, x @ x
        return (jnp.sin(a) * jnp.cos(b) + c) @ x
    assert _live(held, X) == 4 * 256 * KB

    def read_through_a_chain(x):   # a is read by the LAST line's fusion, through two links
        a = x @ x
        link = jnp.cos(jnp.sin(a))
        b = x @ x
        return b @ x + link
    # a outlives b: three at once (a, b and b's product; then a, the product, the result)
    assert _live(read_through_a_chain, X) == 3 * 256 * KB


def test_live_bytes_leaves_out_what_died_before_the_peak():
    def early_and_late(x):
        a = jnp.sin(x) @ jnp.cos(x)              # one product, then one scalar
        early = jnp.sum(a)
        b, c = x @ x, x @ x
        return early + jnp.sum(b * jnp.exp(c))   # two products, fused into the sum
    # the first half's product is gone when the second half's two live
    assert _live(early_and_late, X) == 2 * 256 * KB + 4 + 4


def test_live_bytes_reads_through_a_name_and_skips_broadcasts():
    from jax.ad_checkpoint import checkpoint_name

    def named(x, scale):
        a = checkpoint_name(jnp.sin(x), "a")          # a name is no copy
        return a * scale[None, :]                     # nor is a broadcast written
    assert _live(named, X, jax.ShapeDtypeStruct((256,), jnp.float32)) == 2 * 256 * KB


def test_live_bytes_counts_a_kernels_declared_results():
    """A ``pallas_call``'s results count as declared, whatever its body
    holds: the float32 dq partials of the flash backward are results."""
    from jax.experimental import pallas as pl

    def body(x_ref, o_ref, partial_ref):
        wide = jnp.concatenate([x_ref[...]] * 8, axis=1)    # 8x inside the body
        o_ref[...] = wide[:, :256]
        partial_ref[...] = jnp.stack([x_ref[...]] * 4)

    def launch(x):
        o, partials = pl.pallas_call(
            body, out_shape=[jax.ShapeDtypeStruct((256, 256), jnp.float32),
                             jax.ShapeDtypeStruct((4, 256, 256), jnp.float32)],
            interpret=True, name="toy")(x)
        return o + jnp.sum(partials, axis=0)
    # the two results (1 + 4) and the sum; nothing of the body's 8x value
    assert _live(launch, X) == (1 + 4 + 1) * 256 * KB


def test_live_bytes_looks_inside_a_jit_and_a_loop():
    """A ``jit`` is read in its caller's place (a chain fuses across its
    edge), a loop's body as a jaxpr of its own. PR 35's two cases, whose
    values were elementwise chains into a sum, are one fusion each now (the
    scalar alone is written); with products in the links' place they hold
    what they held."""
    inner = jax.jit(lambda x: jnp.sum(jnp.sin(x) * jnp.cos(x)))
    assert _live(lambda x: inner(x) + 1.0, X) == 4 + 4
    inner = jax.jit(lambda x: jnp.sum((x @ x) * jnp.cos(x @ x) * (x @ x)))
    assert _live(lambda x: inner(x) + 1.0, X) == 3 * 256 * KB + 4

    def loop(x):
        step = lambda c, _: (c + jnp.sum(jnp.tanh(x) * jnp.exp(x)), None)
        return jax.lax.scan(step, 0.0, None, length=3)[0]
    assert _live(loop, X) == 4 + 4

    def loop(x):
        step = lambda c, _: (c + jnp.sum((x @ x) * jnp.cos(x @ x) * (x @ x)), None)
        return jax.lax.scan(step, 0.0, None, length=3)[0]
    assert _live(loop, X) == 3 * 256 * KB + 4


def test_a_chain_fuses_across_a_jits_edge():
    """``jax.nn.silu`` and ``jnp.where`` are jitted functions: their bodies
    are links of the caller's chain, not written results."""
    def gated(x):
        return (jax.nn.silu(x @ x) * jnp.where(x > 0, x, 0.0)) @ x
    assert _live(gated, X) == 2 * 256 * KB


def test_named_bytes_counts_what_a_backward_would_hold():
    """A name twice in a block counts twice; inside a scan once a trip (the
    kept values are stacked); a cond's dearest branch; inside a while
    nothing, since no backward reads it."""
    from jax.ad_checkpoint import checkpoint_name as name

    def block(x):
        a = name(jnp.sin(x), "pair") + name(jnp.cos(x), "pair")
        a, _ = jax.lax.scan(lambda c, _: (c + name(jnp.tanh(c), "trip"), None),
                            a, None, length=3)
        a = jax.lax.while_loop(lambda c: jnp.sum(c) < 0, lambda c: name(c * 2, "never"), a)
        return jax.lax.cond(jnp.sum(a) > 0, lambda c: name(c, "branch"),
                            lambda c: name(c.astype(jnp.bfloat16), "branch").astype(c.dtype), a)
    assert named_bytes(jax.make_jaxpr(block)(X).jaxpr) == {
        "pair": 2 * 256 * KB, "trip": 3 * 256 * KB, "branch": 256 * KB}


def _toy_block(width: int):
    from jax.ad_checkpoint import checkpoint_name

    def block(carry, layer):
        h = checkpoint_name(carry @ layer, "fc_in")              # [64, width]
        return jnp.tanh(h) @ layer.T, None
    return block, jax.ShapeDtypeStruct((64, 64), jnp.float32), \
        jax.ShapeDtypeStruct((64, width), jnp.float32)


def test_the_largest_kind_of_block_counts():
    """Two kinds of block of one step: each is reckoned before either
    decides, and both are held to the larger one's bytes."""
    budget = Budget(room_bytes=1 << 40)
    kinds = [(checkpointing.checkpointed(fn, KEEP_PRODUCTS, 2, budget, label), carry, layer)
             for label, (fn, carry, layer) in (("narrow", _toy_block(128)),
                                               ("wide", _toy_block(1024)))]
    seen = []
    for block, carry, layer in kinds:
        block.reckon(carry, layer)
        seen.append(budget.block_bytes)
    assert 0 < seen[0] < seen[1]                 # the wider block raised it
    kinds[0][0].reckon(*kinds[0][1:])            # and a smaller one does not lower it
    assert budget.block_bytes == seen[1]
    x, w = jnp.ones((64, 64)), jnp.ones((64, 128))
    jax.make_jaxpr(jax.grad(lambda x: jnp.sum(kinds[0][0](x, w)[0])))(x)
    assert budget.totals["block_bytes"] == seen[1] == budget.totals["working_bytes"]
    # a room that would hold the narrow block's product beside its own bytes
    # holds nothing beside the wide block's
    narrow = Budget(room_bytes=None)
    checkpointing.checkpointed(_toy_block(128)[0], KEEP_PRODUCTS, 2, narrow).reckon(
        *kinds[0][1:])
    product = 2 * 64 * 128 * 4
    room = 2 * 64 * 64 * 4 + narrow.block_bytes \
        + int(np.ceil(product * STACK_COST)) + 8
    for budget, saved in ((Budget(room), ("fc_in",)), (Budget(room, block_bytes=seen[1]), ())):
        block = checkpointing.checkpointed(_toy_block(128)[0], KEEP_PRODUCTS, 2, budget)
        jax.make_jaxpr(jax.grad(lambda x: jnp.sum(block(x, w)[0])))(x)
        assert budget.totals["saved"] == saved


def _two_kind_step(room, handed=0):
    """A step of 1 wide block (its product is named ``wo``) and 3 narrow ones
    (``fc_in``), every kind reckoned before the first is applied, as
    ``TransformerLM._trunk`` does -> the budget after the step is traced.
    ``handed``: the bytes a layer hands on to later ones, as a model says."""
    from jax.ad_checkpoint import checkpoint_name

    def kind_of(width, name):
        def block(carry, layer):
            h = checkpoint_name(carry @ layer, name)
            return jnp.tanh(h) @ layer.T, None
        return block, jax.ShapeDtypeStruct((64, width), jnp.float32)
    budget = Budget(room, handed_bytes=handed)
    carry = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    blocks = [(checkpointing.checkpointed(fn, KEEP_PRODUCTS, layers, budget, label), layer)
              for (fn, layer), layers, label in (
                  (kind_of(1024, "wo"), 1, "wide"),
                  (kind_of(128, "fc_in"), 3, "narrow"))]
    for block, layer in blocks:
        block.reckon(carry, layer)

    def step(x):
        for (block, layer), layers in zip(blocks, (1, 3)):
            for _ in range(layers):
                x, _ = block(x, jnp.ones(layer.shape))
        return jnp.sum(x)
    jax.make_jaxpr(jax.grad(step))(jnp.ones((64, 64)))
    return budget


WIDE, NARROW = 64 * 1024 * 4, 64 * 128 * 4     # one layer's named product


def test_a_kinds_names_count_over_that_kinds_layers():
    """1 + 3 layers: ``wo`` is in ONE layer and ``fc_in`` in THREE. A room
    that holds both by those counts keeps both; charged as if each were in
    all four layers (the rule before PR 60) the dearer name alone, 4 x its
    bytes, is more than the budget."""
    free = _two_kind_step(None)
    assert step_candidates(free.kinds) == {"wo": WIDE, "fc_in": 3 * NARROW}
    assert free.totals["candidate_bytes"] == WIDE + 3 * NARROW
    assert free.totals["carries_bytes"] == 4 * 64 * 64 * 4    # once a block, whatever its kind
    needs = free.totals["carries_bytes"] + free.working_bytes
    room = needs + int(np.ceil((WIDE + 3 * NARROW) * STACK_COST)) + 8
    kept = _two_kind_step(room).totals
    assert kept["saved"] == ("wo", "fc_in") and kept["saved_bytes"] == WIDE + 3 * NARROW
    assert kept["budget_bytes"] < 4 * WIDE


def test_one_choice_a_step_and_its_report_by_kind():
    """The step takes ONE prefix of ``SAVE_ORDER`` over every kind's bytes:
    with room for ``wo`` alone the narrow kind keeps nothing, though its own
    ``fc_in`` would fit a budget of its own; ``saved_by_kind`` says what each
    kind kept, and the step's totals are the sums."""
    free = _two_kind_step(None).totals
    assert free["saved"] == ("wo", "fc_in")
    assert free["saved_by_kind"]["wide"]["saved"] == ("wo",)
    assert free["saved_by_kind"]["narrow"] == {
        "layers": 3,
        "block_bytes": free["saved_by_kind"]["narrow"]["block_bytes"],
        "saved": ("fc_in",), "saved_bytes": 3 * NARROW}
    needs = free["carries_bytes"] + free["working_bytes"]
    only_wo = _two_kind_step(needs + int(np.ceil(WIDE * STACK_COST)) + 8).totals
    assert only_wo["saved"] == ("wo",) and only_wo["saved_bytes"] == WIDE
    assert only_wo["saved_by_kind"]["narrow"]["saved"] == ()
    assert only_wo["saved_by_kind"]["wide"]["saved_bytes"] == WIDE
    # a prefix: room for fc_in and not for wo, which stands before it, keeps nothing
    short = _two_kind_step(needs + int(np.ceil(3 * NARROW * STACK_COST)) + 8).totals
    assert short["saved"] == () and short["candidate_bytes"] == WIDE + 3 * NARROW


def test_every_kept_byte_costs_the_stack_cost():
    """A kept byte costs ``STACK_COST`` in whatever layer it is kept, under a
    scan or by itself (one constant: what it over-charges a layer that runs
    by itself is cover the walk has no other of): a room that holds ``wo`` at
    1.0 and not at 1.2 does not keep it."""
    assert step_costs({"a": Kind(3, 0, 0, {"x": 1000}), "b": Kind(1, 0, 0, {"x": 500})}) \
        == {"x": int(np.ceil(STACK_COST * 3500))}
    free = _two_kind_step(None).totals
    needs = free["carries_bytes"] + free["working_bytes"]
    assert _two_kind_step(needs + WIDE + 8).totals["saved"] == ()
    kept = _two_kind_step(needs + int(np.ceil(WIDE * STACK_COST))).totals
    assert kept["saved"] == ("wo",) and kept["saved_bytes"] == WIDE
    assert kept["saved_cost_bytes"] == int(np.ceil(WIDE * STACK_COST)) == kept["budget_bytes"]


def test_what_is_handed_on_stands_beside_the_working_set():
    """What a boundary layer hands to later layers outlives the blocks
    between with the gradients live: it is charged beside the largest block
    (and beside the head, where that is more), never under their maximum, and
    of a block's results the carry alone counts among its bytes."""
    free = _two_kind_step(None).totals
    handed = 3 * WIDE
    with_handed = _two_kind_step(None, handed).totals
    assert with_handed["handed_bytes"] == handed and free["handed_bytes"] == 0
    assert with_handed["block_bytes"] == free["block_bytes"]
    assert with_handed["working_bytes"] == free["working_bytes"] + handed
    # a room that keeps both names keeps neither once the handed bytes are charged
    room = free["carries_bytes"] + free["working_bytes"] \
        + int(np.ceil((WIDE + 3 * NARROW) * STACK_COST)) + 8
    assert _two_kind_step(room).totals["saved"] == ("wo", "fc_in")
    assert _two_kind_step(room, handed).totals["saved"] == ()
    assert Budget(None, outside_bytes=900, block_bytes=500, grads_bytes=700,
                  handed_bytes=50).working_bytes == 550
    # what a block returns beside its carry (a scan's rows, a handed value) is
    # not the block's to count
    from jax.ad_checkpoint import checkpoint_name

    def hands(carry, layer):
        h = checkpoint_name(carry @ layer, "fc_in")
        return jnp.tanh(h) @ layer.T, h
    plain, _, _ = _toy_block(128)
    sizes = []
    for fn in (plain, hands):
        budget = Budget(None)
        checkpointing.checkpointed(fn, KEEP_PRODUCTS, 1, budget).reckon(
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
            jax.ShapeDtypeStruct((64, 128), jnp.float32))
        sizes.append(budget.block_bytes)
    assert sizes[0] == sizes[1]


def test_a_label_is_one_shape():
    """A kind's label stands for one shape with that kind's layers: the same
    block traced again under one budget (a pipeline stage's retrace) changes
    nothing, a second shape under the label is refused, not counted twice."""
    fn, carry, layer = _toy_block(128)
    budget = Budget(None)
    for _ in range(2):
        checkpointing.checkpointed(fn, KEEP_PRODUCTS, 2, budget, "stage").reckon(carry, layer)
    assert list(budget.kinds) == ["stage"] and budget.kinds["stage"].layers == 2
    with pytest.raises(ValueError, match="second shape"):
        checkpointing.checkpointed(fn, KEEP_PRODUCTS, 2, budget, "stage").reckon(
            carry, jax.ShapeDtypeStruct((64, 256), jnp.float32))


def test_a_name_two_launches_share_counts_twice():
    """Two values of one layer under one name (a differential layer's two
    launches): both are kept, so both are charged."""
    from jax.ad_checkpoint import checkpoint_name

    def block(carry, layer):
        first = checkpoint_name(carry @ layer, "attn_o_diff")
        second = checkpoint_name((2 * carry) @ layer, "attn_o_diff")
        return (first - second) @ layer.T, None
    budget = Budget(None)
    fn = checkpointing.checkpointed(block, KEEP_PRODUCTS, 4, budget, "diff")
    fn.reckon(jax.ShapeDtypeStruct((64, 64), jnp.float32),
              jax.ShapeDtypeStruct((64, 128), jnp.float32))
    assert budget.kinds["diff"] == Kind(
        4, 64 * 64 * 4, budget.kinds["diff"].block_bytes, {"attn_o_diff": 2 * NARROW})
    assert step_candidates(budget.kinds) == {"attn_o_diff": 4 * 2 * NARROW}


def test_the_gradients_room_holds_the_head():
    """Outside the blocks no gradient is live yet: what lives there counts
    only past the gradients' bytes, a block's always."""
    assert Budget(None, outside_bytes=900, block_bytes=500).working_bytes == 900
    assert Budget(None, outside_bytes=900, block_bytes=500, grads_bytes=300).working_bytes == 600
    assert Budget(None, outside_bytes=900, block_bytes=500, grads_bytes=700).working_bytes == 500


@pytest.mark.parametrize("preset,want", [
    ("phi4flash-tiny", {"ssm.full": 2, "attn.window8": 2, "ssm.full.hands_memory": 1,
                        "attn.full.hands_kv": 1, "gmu.full": 1, "cross.full": 1}),
    ("afmoe-tiny", {"dense.window16": 2, "window16": 3, "full": 1}),
    ("instella-tiny", {"dense.full": 1, "full": 2, "mtp": 1}),
    ("gpt2-tiny", {"full": 2}),
])
def test_a_models_kinds_and_their_layers(preset, want):
    """``_trunk`` hands each kind of block its own count of layers: the
    leading dense layers apart (another tree of parameters), a mixed stack's
    kinds as its run plan spells them, the prediction module last (a traced
    step reports them so: `test_the_plan_tool_prints_a_tiny_cells_plan`)."""
    from deepspeed_tpu import models
    from deepspeed_tpu.models.transformer import _kind_label
    build = {"phi4flash-tiny": models.phi4flash_model, "afmoe-tiny": models.afmoe_model,
             "instella-tiny": models.instella_moe_model, "gpt2-tiny": gpt2_model}[preset]
    model = build(preset, dtype=jnp.float32)
    counts = {_kind_label(key): n for key, n in model._layers_of_kind(with_mtp=True).items()}
    assert counts == want
    assert sum(counts.values()) == model.config.num_layers + model.config.mtp_layers
    assert sum(model._layers_of_kind(with_mtp=False).values()) == model.config.num_layers


def test_a_block_is_traced_once_a_shape():
    calls = []
    fn, carry, layer = _toy_block(128)

    def counted(c, l):
        calls.append(1)
        return fn(c, l)
    block = checkpointing.checkpointed(counted, KEEP_PRODUCTS, 2, Budget(None))
    block.reckon(carry, layer)
    jax.make_jaxpr(jax.grad(lambda x: jnp.sum(
        block(block(x, jnp.ones((64, 128)))[0], jnp.ones((64, 128)))[0])))(jnp.ones((64, 64)))
    assert len(calls) == 1


@pytest.mark.parametrize("family,heads", [("dense", 1), ("moe_qk_norm", 1),
                                          ("two_heads", 2)])
def test_the_heads_bytes_are_added_once(family, heads):
    """Outside the blocks a step holds one head's float32 logits and their
    gradient; a prediction module's two heads run one after the other, so
    one counts, and each hands its backward the stream's gradient and its
    parameters' in float32 (`TransformerLM.fused_head_loss`)."""
    if family == "two_heads":
        from deepspeed_tpu.models import instella_moe_model
        model = instella_moe_model("instella-tiny", dtype=jnp.float32)
        assert model.config.mtp_layers == heads - 1
    else:
        model = _model(family, KEEP_PRODUCTS)
    ids = jnp.zeros((2, SEQ), jnp.int32) % model.config.vocab_size
    budget = Budget(None)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.float32))
    jax.make_jaxpr(jax.grad(lambda p: model.loss(
        p, {"input_ids": ids}, remat_budget=budget)))(params)
    c = model.config
    handed = 2 * SEQ * c.hidden_size * 4 + 4 * c.hidden_size * (c.vocab_size + 1)
    assert budget.totals["head_form"] == ("fused" if heads == 2 else "whole")
    assert budget.outside_bytes == (2 * (2 * SEQ * c.vocab_size * 4)
                                    + (2 * handed if heads == 2 else 0))
    assert budget.totals["outside_bytes"] == budget.outside_bytes
    assert budget.totals["working_bytes"] == max(budget.outside_bytes, budget.block_bytes)
    assert budget.block_bytes > 0


#: The nine benchmark cells as the chip traced them (PERF.md, PR 60: the
#: engine's ``remat room`` / ``remat keeps`` lines and ``remat_totals`` of a
#: process a cell under the benchmark's own set-up, the reference's signs
#: resident): the room the engine read (free less gradients), the gradients,
#: what lives outside the blocks, one block's input, the step's own figure
#: for its temporaries with the plan's choice kept
#: (``memory_totals["step_extra_bytes"]``), the last name the choice reaches,
#: and every kind of block: (layers, the block's bytes as the chip's trace
#: walked them, name -> bytes in ONE layer); ``handed``: what a boundary layer
#: hands on with its cotangent (the decoder-hybrid-decoder cell alone).
#: (PR 63: ``outside`` of the four cells whose head takes its gradient in the
#: forward counts what each head hands its backward too, `_charge_head`'s
#: arithmetic at the cell's shapes.)
# <CELLS>
CELLS = {
    "gpt2-large.train.seq1k": dict(
        room=6_845_896_192, grads=1_548_060_160, outside=1_646_821_376, carry=10_489_860,
        step_bytes=6_183_190_528, reaches='v_proj', kinds={
            "full": (36, 244_261_396, {"q_proj": 10_485_760, "k_proj": 10_485_760, "v_proj": 10_485_760, "attn_o": 10_485_760, "attn_lse": 327_680, "o_proj": 10_485_760, "fc_in": 41_943_040}),
        }),
    "olmoe-1b-7b.train.seq4k": dict(
        room=3_321_693_184, grads=2_090_373_120, outside=1_648_361_472, carry=16_793_608,
        step_bytes=3_781_132_288, reaches='v_proj', kinds={
            "full": (2, 1_897_994_314, {"q_proj": 16_777_216, "k_proj": 16_777_216, "v_proj": 16_777_216, "attn_o": 16_777_216, "attn_lse": 262_144, "o_proj": 16_777_216, "moe_logits": 1_048_576, "wi_gate": 67_108_864, "wi_up": 67_108_864, "wo": 134_217_728}),
        }),
    "instella-moe-16b-a3b.train.seq8k": dict(
        room=6_895_945_984, grads=1_540_443_392, outside=2_510_045_184, carry=134_250_504,
        step_bytes=6_734_659_584, reaches='wo', kinds={
            "dense.full": (1, 2_358_912_552, {"q_proj": 67_108_864, "kv_latent": 17_825_792, "kv_up": 117_440_512, "attn_o": 67_108_864, "attn_lse": 1_048_576, "attn_gate": 67_108_864, "o_proj": 67_108_864, "gate_proj": 358_612_992, "up_proj": 358_612_992}),
            "full": (5, 2_201_536_882, {"q_proj": 67_108_864, "kv_latent": 17_825_792, "kv_up": 117_440_512, "attn_o": 67_108_864, "attn_lse": 1_048_576, "attn_gate": 67_108_864, "o_proj": 67_108_864, "moe_logits": 4_194_304, "wi_gate": 103_809_024, "wi_up": 103_809_024, "wo": 150_994_944, "gate_proj": 92_274_688, "up_proj": 92_274_688}),
            "mtp": (1, 2_201_536_882, {"q_proj": 67_108_864, "kv_latent": 17_825_792, "kv_up": 117_440_512, "attn_o": 67_108_864, "attn_lse": 1_048_576, "attn_gate": 67_108_864, "o_proj": 67_108_864, "moe_logits": 4_194_304, "wi_gate": 103_809_024, "wi_up": 103_809_024, "wo": 150_994_944, "gate_proj": 92_274_688, "up_proj": 92_274_688}),
        }),
    "trinity-mini.train.seq16k": dict(
        room=6_892_640_768, grads=1_540_988_928, outside=3_279_945_728, carry=67_174_408,
        step_bytes=5_452_087_296, reaches='wo', kinds={
            "dense.window2048": (2, 1_898_595_106, {"q_proj": 134_217_728, "k_proj": 16_777_216, "v_proj": 16_777_216, "attn_o": 134_217_728, "attn_lse": 2_097_152, "attn_gate": 134_217_728, "o_proj": 67_108_864, "gate_proj": 201_326_592, "up_proj": 201_326_592}),
            "window2048": (3, 2_818_797_578, {"q_proj": 134_217_728, "k_proj": 16_777_216, "v_proj": 16_777_216, "attn_o": 134_217_728, "attn_lse": 2_097_152, "attn_gate": 134_217_728, "o_proj": 67_108_864, "moe_logits": 8_388_608, "wi_gate": 100_663_296, "wi_up": 100_663_296, "wo": 201_326_592, "gate_proj": 33_554_432, "up_proj": 33_554_432}),
            "full": (1, 2_818_797_578, {"q_proj": 134_217_728, "k_proj": 16_777_216, "v_proj": 16_777_216, "attn_o": 134_217_728, "attn_lse": 2_097_152, "attn_gate": 134_217_728, "o_proj": 67_108_864, "moe_logits": 8_388_608, "wi_gate": 100_663_296, "wi_up": 100_663_296, "wo": 201_326_592, "gate_proj": 33_554_432, "up_proj": 33_554_432}),
        }),
    "sdar-30b-a3b.train.bd8k": dict(
        room=6_055_234_560, grads=1_669_804_032, outside=1_244_659_712, carry=67_174_408,
        step_bytes=7_337_951_232, reaches='wi_up', kinds={
            "full": (8, 2_328_317_642, {"q_proj": 134_217_728, "k_proj": 16_777_216, "v_proj": 16_777_216, "attn_o": 134_217_728, "attn_lse": 2_097_152, "o_proj": 67_108_864, "moe_logits": 8_388_608, "wi_gate": 75_497_472, "wi_up": 75_497_472, "wo": 201_326_592}),
        }),
    # (re-read at PR 64: q, k, v outlive the head groups and are named, the
    # block's walk 2,938.8 -> 3,391.8 MB, the step 6,787.8 -> 7,355.8)
    "evabyte-6.5b.train.seq32k": dict(
        room=6_231_251_968, grads=1_642_733_568, outside=671_088_640, carry=268_566_532,
        step_bytes=7_355_826_176, reaches='o_proj', kinds={
            "full": (4, 3_391_799_306, {"q_proj": 268_435_456, "k_proj": 268_435_456, "v_proj": 268_435_456, "o_proj": 268_435_456, "attn_o_eva_local": 268_435_456, "attn_lse_eva_local": 4_194_304, "attn_o_eva_far": 268_435_456, "attn_lse_eva_far": 4_194_304}),
        }),
    "keye-vl2-30b-a3b.train.dsa16k": dict(
        room=5_820_218_368, grads=1_705_977_856, outside=2_489_319_424, carry=67_174_412,
        step_bytes=5_672_501_248, reaches='moe_logits', kinds={
            "full": (8, 2_229_161_998, {"q_proj": 134_217_728, "k_proj": 16_777_216, "v_proj": 16_777_216, "indexer_q": 33_554_432, "indexer_k": 2_097_152, "dsa_mask": 33_554_432, "attn_o_dsa": 134_217_728, "attn_lse_dsa": 2_097_152, "indexer_kl_dq": 33_554_432, "indexer_kl_dk": 2_097_152, "indexer_kl_dw": 1_048_576, "o_proj": 67_108_864, "moe_logits": 8_388_608, "wi_gate": 75_497_472, "wi_up": 75_497_472, "wo": 201_326_592}),
        }),
    "xing4-29b-a4b.train.mhc": dict(
        room=3_367_239_820, grads=2_083_341_172, outside=1_660_973_056, carry=234_913_804,
        step_bytes=5_142_315_008, reaches=None, kinds={
            "dense.full": (2, 2_618_114_866, {"q_latent": 12_582_912, "q_b_proj": 100_663_296, "kv_latent": 9_437_184, "kv_up": 134_217_728, "attn_o_mla": 67_108_864, "attn_lse_mla": 1_048_576, "o_proj": 58_720_256, "gate_proj": 150_994_944, "up_proj": 150_994_944}),
            "full": (4, 2_912_181_306, {"q_latent": 12_582_912, "q_b_proj": 100_663_296, "kv_latent": 9_437_184, "kv_up": 134_217_728, "attn_o_mla": 67_108_864, "attn_lse_mla": 1_048_576, "o_proj": 58_720_256, "moe_logits": 2_097_152, "wi_gate": 25_165_824, "wi_up": 25_165_824, "wo": 88_080_384, "gate_proj": 16_777_216, "up_proj": 16_777_216}),
            "mtp": (1, 2_912_181_306, {"q_latent": 12_582_912, "q_b_proj": 100_663_296, "kv_latent": 9_437_184, "kv_up": 134_217_728, "attn_o_mla": 67_108_864, "attn_lse_mla": 1_048_576, "o_proj": 58_720_256, "moe_logits": 2_097_152, "wi_gate": 25_165_824, "wi_up": 25_165_824, "wo": 88_080_384, "gate_proj": 16_777_216, "up_proj": 16_777_216}),
        }),
    "phi4-mini-flash-reasoning.train.sambay": dict(
        room=5_009_300_480, grads=1_830_623_232, outside=1_159_440_384, handed=754_974_720,
        carry=83_951_620,
        step_bytes=5_173_051_392, reaches='attn_o_diff', kinds={
            "ssm.full": (2, 2_490_558_486, {"ssm_in": 335_544_320, "ssm_x": 6_291_456, "ssm_dt": 167_772_160, "ssm_m": 167_772_160, "ssm_state": 41_943_040, "gate_proj": 335_544_320, "up_proj": 335_544_320}),
            "attn.window512": (2, 2_277_282_600, {"q_proj": 83_886_080, "kv_proj": 83_886_080, "attn_o_diff": 167_772_160, "attn_lse_diff": 2_621_440, "o_proj": 83_886_080, "gate_proj": 335_544_320, "up_proj": 335_544_320}),
            "ssm.full.hands_memory": (1, 2_490_558_486, {"ssm_in": 335_544_320, "ssm_x": 6_291_456, "ssm_dt": 167_772_160, "ssm_m": 167_772_160, "ssm_state": 41_943_040, "gate_proj": 335_544_320, "up_proj": 335_544_320}),
            "attn.full.hands_kv": (1, 2_277_282_088, {"q_proj": 83_886_080, "kv_proj": 83_886_080, "attn_o_diff": 167_772_160, "attn_lse_diff": 2_621_440, "o_proj": 83_886_080, "gate_proj": 335_544_320, "up_proj": 335_544_320}),
            "gmu.full": (1, 2_076_725_266, {"gmu_in": 167_772_160, "gate_proj": 335_544_320, "up_proj": 335_544_320}),
            "cross.full": (1, 2_348_055_848, {"q_proj": 83_886_080, "attn_o_diff": 167_772_160, "attn_lse_diff": 2_621_440, "o_proj": 83_886_080, "gate_proj": 335_544_320, "up_proj": 335_544_320}),
        }),
    # (PR 62; the room, the blocks' and the step's bytes as the chip's log line and
    # `memory_totals` print them, in MB to one decimal; the names' bytes exact)
    "smallthinker-21b-a3b.train.win16k": dict(
        room=8_374_100_000, grads=1_313_059_840, outside=1_801_398_272, carry=167_837_704,
        step_bytes=7_066_222_592, reaches='moe_logits', kinds={
            "full": (1, 5_098_500_000, {"moe_logits": 8_388_608, "q_proj": 234_881_024, "k_proj": 33_554_432, "v_proj": 33_554_432, "attn_o": 234_881_024, "attn_lse": 3_670_016, "o_proj": 167_772_160, "wi_gate": 226_492_416, "wi_up": 226_492_416, "wo": 754_974_720}),
            "window4096": (3, 5_333_400_000, {"moe_logits": 8_388_608, "q_proj": 234_881_024, "k_proj": 33_554_432, "v_proj": 33_554_432, "attn_o": 234_881_024, "attn_lse": 3_670_016, "o_proj": 167_772_160, "wi_gate": 226_492_416, "wi_up": 226_492_416, "wo": 754_974_720}),
        }),
    # (PR 65; the room, the dearest block's and the step's bytes as the chip's log
    # line and `memory_totals` print them, the attention block's as
    # tools/remat_plan.py walks it; the names' bytes exact. The budget, 1,520.8 MB,
    # ends between the flash pair's 166.1 and the SSD core's result, 2,899.1 over
    # nine layers: the backward runs `ssd_fwd` again)
    "granite-4.0-h-micro.train.ssd32k": dict(
        room=6_536_500_000, grads=1_595_701_120, outside=1_161_830_400, carry=134_348_804,
        step_bytes=6_597_361_664, reaches='attn_o', kinds={
            "ssd.full": (9, 3_672_200_000, {"ssm_in": 285_212_672, "ssm_z": 268_435_456, "ssd_m": 268_435_456, "ssd_state": 268_435_456, "gate_proj": 536_870_912, "up_proj": 536_870_912}),
            "mha.full": (1, 2_877_825_558, {"q_proj": 134_217_728, "k_proj": 33_554_432, "v_proj": 33_554_432, "attn_o": 134_217_728, "attn_lse": 4_194_304, "o_proj": 134_217_728, "gate_proj": 536_870_912, "up_proj": 536_870_912}),
        }),
}
# </CELLS>


def _cell_budget(cell: str, share: float = 1.0) -> Budget:
    """The cell's ``Budget`` as the chip's trace filled it, at ``share`` of
    the room it read."""
    c = CELLS[cell]
    budget = Budget(int(share * c["room"]), outside_bytes=c["outside"],
                    grads_bytes=c["grads"], handed_bytes=c.get("handed", 0),
                    block_bytes=max(block for _, block, _ in c["kinds"].values()))
    budget.kinds.update({label: Kind(layers, c["carry"], block, named)
                         for label, (layers, block, named) in c["kinds"].items()})
    return budget


#: the two cells whose 1,024- and 4,096-token rows left PR 35's old rule
#: (128 more layer inputs before anything is kept) with something to keep
SHORT = ("gpt2-large.train.seq1k", "olmoe-1b-7b.train.seq4k")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_keep_what_the_chip_has_room_for(cell):
    """The decision the chip printed, from the pure functions alone: the two
    short cells keep every name, as before PR 60, and the EVA cell the branch
    output alone (its one candidate until PR 64; since then q, k, v are named
    too, 1,288.5 MB each of a budget of 1,765.2 that ``o_proj`` takes 1,288.5
    of: the last group of ``SAVE_ORDER`` is never reached); the Trinity cell reaches ``wo`` by its layers' own counts, the
    Phi-4 cell the differential launches' pairs where it kept nothing; the
    SDAR, Instella and Keye cells what they kept, and the hyper-connected
    cell nothing: its room ends under its blocks' inputs and one block. No
    decision sits within a twentieth of the room of a group's edge, in any of
    the ten (the SmallThinker cell, PR 62, keeps the flash pair's two and the
    routing's logits, which its router makes BEFORE the mixer): the same names at 95 % and at 105 % of the reading, so what a
    run keeps does not hang on a few MB; and the step as reckoned (gradients
    + inputs + working set + what the kept values cost, no margin) is never
    under the step the chip measured."""
    c = CELLS[cell]
    budget = _cell_budget(cell)
    saved = budget.saved()
    assert (saved[-1] if saved else None) == c["reaches"]
    candidates = step_candidates(budget.kinds)
    listed = [n for group in SAVE_ORDER for n in group if n in candidates]
    assert list(saved) == listed[:len(saved)]
    if cell in SHORT:
        assert list(saved) == listed
    for share in (0.95, 1.05):
        assert _cell_budget(cell, share).saved() == saved
    totals = budget.totals
    assert totals["saved_cost_bytes"] <= totals["budget_bytes"]
    reckoned = (c["grads"] + totals["carries_bytes"] + totals["working_bytes"]
                + totals["saved_cost_bytes"])
    assert reckoned >= c["step_bytes"]
    by_kind = totals["saved_by_kind"]
    assert sum(kind["saved_bytes"] for kind in by_kind.values()) == totals["saved_bytes"]
    blocks = sum(kind["layers"] for kind in by_kind.values())
    assert blocks * c["carry"] == totals["carries_bytes"]
    # the rule PR 35 replaced: 128 more layer inputs before anything is kept
    old = max(0, int((c["room"] - (blocks + 128) * c["carry"]) / STACK_COST))
    assert (choose_saved(candidates, old) == ()) == (cell not in SHORT)


def test_the_trinity_cell_reaches_wo_by_its_layers_own_counts():
    """PR 60's first fault: every kind was charged as if all six layers were
    of its kind. ``wo`` is in FOUR layers (805 MB); charged six (1,208 MB)
    the five names in front of it and it come to more than they take."""
    c = CELLS["trinity-mini.train.seq16k"]
    kinds = _cell_budget("trinity-mini.train.seq16k").kinds
    real = step_candidates(kinds)
    assert real["wo"] == 4 * 201_326_592 and real["attn_o"] == 6 * 134_217_728
    as_if_all = step_candidates({label: Kind(6, k.carry_bytes, k.block_bytes, k.named)
                                 for label, k in kinds.items()})
    assert as_if_all["wo"] == 2 * 6 * 201_326_592     # two kinds name it, each charged six
    assert as_if_all["wo"] > real["wo"] * 2.9


def test_the_plan_tool_prints_a_tiny_cells_plan():
    """``tools/remat_plan.py``: a cell's plan from shapes alone (the tiny
    decoder-hybrid-decoder preset through its adapter: six kinds in eight
    layers), with a room that holds the MLPs' first products and no more."""
    import os
    from tests.benchmark.helpers import REPO
    from tools import remat_plan
    manifest = os.path.join(REPO, "tests", "benchmark", "data", "BENCHMARK.phi4flash-tiny.json")
    free = remat_plan.plan(manifest, "phi4flash-tiny.train")
    assert {label: kind["layers"] for label, kind in free["kinds"].items()} == {
        "ssm.full": 2, "attn.window8": 2, "ssm.full.hands_memory": 1,
        "attn.full.hands_kv": 1, "gmu.full": 1, "cross.full": 1}
    assert free["budget_bytes"] is None and free["saved"] == [n for n, *_ in free["candidates"]]
    assert "stacked" not in free["kinds"]["ssm.full"] and free["handed_bytes"] > 0
    running = {name: total for name, _, _, total in free["candidates"]}
    one = free["kinds"]["gmu.full"]["named"]["up_proj"]
    # gate_proj and up_proj in all eight layers, every byte at STACK_COST
    assert running["up_proj"] == 2 * int(np.ceil(STACK_COST * 8 * one))
    needs = free["carries_bytes"] + free["working_bytes"]
    assert free["working_bytes"] == free["block_bytes"] + free["handed_bytes"]
    held = remat_plan.plan(manifest, "phi4flash-tiny.train",
                           room_bytes=needs + running["up_proj"] + 8)
    assert held["saved"] == ["gate_proj", "up_proj"] and held["saved_bytes"] == 2 * 8 * one
    assert held["saved_cost_bytes"] == running["up_proj"]
    assert all(kind["saved"] == ["gate_proj", "up_proj"] for kind in held["saved_by_kind"].values())
    # the number that stands beside the chip's train_step_temp_gb
    assert held["reckoned_step_bytes"] == (
        held["grads_bytes"] + held["carries_bytes"] + held["working_bytes"]
        + held["saved_cost_bytes"])


@pytest.fixture(scope="module")
def keye_cell_candidates():
    """name -> bytes over the eight layers of what the
    ``keye-vl2-30b-a3b.train.dsa16k`` cell's block names, read by
    ``named_bytes`` off the block's own jaxpr at the cell's shapes (one row of
    16,384 at the published widths, bf16; shapes alone, no chip): the step's
    candidates on the kernel route (`step_candidates`)."""
    import json
    import os
    from benchmark.adapters import keye_vl2 as adapter
    from tests.benchmark.helpers import REPO
    with open(os.path.join(REPO, "benchmark", "configs", "keye-vl2-30b-a3b.json")) as f:
        model = adapter.model(json.load(f), remat=True, dtype="bfloat16")
    budget = Budget(None)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        patch.delenv("DSTPU_ATTN", raising=False)
        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.bfloat16))
        jax.make_jaxpr(jax.grad(lambda p, ids: model.loss(
            p, {"input_ids": ids}, remat_budget=budget)))(
                params, jax.ShapeDtypeStruct((1, 16384), jnp.int32))
    listed = {n for group in SAVE_ORDER for n in group}
    return {n: size for n, size in step_candidates(budget.kinds).items() if n in listed}


def test_the_selections_operand_is_a_bit_a_pair_at_the_keye_cell(keye_cell_candidates):
    """``dsa_mask`` is ``L x L / 8`` bytes a row and layer (PR 50; a byte a
    pair it was 2,147.5 MB over the eight layers, more than the whole budget),
    beside the selected launch's pair and the KL's three gradients."""
    L, layers = 16384, 8
    named = keye_cell_candidates
    assert named["dsa_mask"] == layers * L * L // 8 == 268_435_456
    assert named["attn_o_dsa"] == layers * L * 32 * 128 * 2
    assert named["attn_lse_dsa"] == layers * 32 * L * 4
    assert sum(named[n] for n in SAVE_ORDER[0]) == 293_601_280


@pytest.mark.parametrize("budget_mb,groups,last", [
    (1927.5, 3, "moe_logits"),      # the parent's budget on the chip (PERF.md, PR 49)
    (1652.6, 3, "attn_o_dsa"),      # the three leading groups alone: 293.6 + 268.4 + 1,090.5
    (1600.0, 2, "dsa_mask"),        # the operand fits, the selected pair's results do not
    (500.0, 1, "indexer_kl_dw"),
    (2122.4, 3, "moe_logits"),      # what PR 49 took for the experts' first products' edge
    (2927.6, 3, "moe_logits"),      # their TRUE edge: the held buffer's rows, 604 MB each,
    (2927.8, 3, "wi_up"),           # not the overflow loop's copy's 201 (PR 60)
])
def test_the_keye_cell_keeps_the_three_leading_groups(keye_cell_candidates, budget_mb,
                                                      groups, last):
    """Under the cell's budget `choose_saved` takes the KL's gradients, the
    operand and the selected launch's pair (1,652.5 MB) by the rule it has,
    `SAVE_ORDER` as it was; the router's float32 logits (67.1 MB, the next
    group the cell's names hold) ride along where they fit, and the experts'
    first products (1,208 MB over the eight layers) do not."""
    saved = choose_saved(keye_cell_candidates, int(budget_mb * 1e6))
    leading = [n for group in SAVE_ORDER[:groups] for n in group]
    assert list(saved[:len(leading)]) == leading and saved[-1] == last
    assert set(saved) - set(leading) <= {"moe_logits", "wi_gate", "wi_up"}
    if groups < 3:
        assert len(saved) == len(leading)


@pytest.mark.parametrize("family", ["dense", "rope_gated", "moe_qk_norm"])
def test_scores_are_never_a_candidate(monkeypatch, family):
    """The XLA routes' [B, H, S, S] tensors stay recomputed: with no budget
    at all the saved names are the projections' and the experts' alone."""
    monkeypatch.setenv("DSTPU_ATTN", "xla")
    _, kept = _grads(_model(family, KEEP_PRODUCTS))
    assert "attn_big" not in kept["saved"]
    assert not {"attn_o", "attn_lse"} & set(kept["saved"])   # the kernel's
    rows, heads = 2, _model(family, KEEP_PRODUCTS).config.num_heads
    assert kept["candidate_bytes"] < rows * heads * SEQ * SEQ * 4 * 2 * 8


def test_every_name_a_producer_gives_is_in_the_order(monkeypatch):
    """A value named in the block and missing from SAVE_ORDER would never
    be kept, silently: the three families' names are all listed (the
    scores apart), the kernel's two among them."""
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    for family in ("dense", "rope_gated", "moe_qk_norm"):
        _, kept = _grads(_model(family, KEEP_PRODUCTS))
        assert {"attn_o", "attn_lse", "q_proj", "o_proj"} <= set(kept["saved"])
        assert set(kept["saved"]) <= {n for group in SAVE_ORDER for n in group}
    assert {"wi_gate", "wi_up", "wo", "moe_logits"} <= set(kept["saved"])


# -- (c) what the program is: the backward's recomputation -------------------

def _recomputed(model, budget=None):
    """Counts of the primitives (and Pallas kernel names) inside the
    differentiated ``remat2`` equations of the loss's gradient: what the
    backward of a block runs, recomputation and gradient products."""
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: model.loss(p, _batch(), remat_budget=budget)))(params)
    counts: dict = {}

    def walk(jx, inside):
        for eqn in jx.eqns:
            here = inside or (eqn.primitive.name == "remat2"
                              and eqn.params.get("differentiated"))
            if inside:
                key = eqn.primitive.name
                if key == "pallas_call":
                    key = eqn.params["name"]
                counts[key] = counts.get(key, 0) + 1
            if eqn.primitive.name == "pallas_call":
                continue    # a kernel's own body is not the block's program
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, here)
    walk(jaxpr.jaxpr, False)
    return counts


@pytest.mark.parametrize("family,matmuls,named", [
    ("dense", 6, 5),         # q k v o fc_in fc_out; fc_out's result feeds an add
    ("rope_gated", 7, 6),    # q k v o gate up down
])
def test_backward_recomputes_no_matmul_and_no_kernel(monkeypatch, family,
                                                     matmuls, named):
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    kept = _recomputed(_model(family, KEEP_PRODUCTS))
    full = _recomputed(_model(family, "nothing_saveable"))
    # two gradient products a matmul, and with everything saved no more
    assert kept["dot_general"] == 2 * matmuls
    assert full["dot_general"] == 2 * matmuls + named
    assert kept.get("flash_fwd", 0) == 0 and full["flash_fwd"] == 1
    assert kept["flash_bwd"] == full["flash_bwd"] == 1
    # what stays recomputed is elementwise or a reduction over a row
    assert kept["rsqrt"] == full["rsqrt"] > 0


def test_backward_recomputes_no_grouped_matmul(monkeypatch):
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    kept = _recomputed(_model("moe_qk_norm", KEEP_PRODUCTS))
    full = _recomputed(_model("moe_qk_norm", "nothing_saveable"))
    grouped = lambda c: sum(v for k, v in c.items() if k.startswith("ragged_dot"))
    assert grouped(kept) == 6          # two gradient products for each of three
    assert grouped(full) == 9          # and the three forward products again
    assert kept.get("flash_fwd", 0) == 0 and full["flash_fwd"] == 1
    # q k v o and the router: their products and nothing recomputed
    assert kept["dot_general"] == 2 * 5
    assert full["dot_general"] == 2 * 5 + 5
    # the router's top-k and the sort are recomputed under both
    assert kept["top_k"] == full["top_k"] == 1
    assert kept["sort"] == full["sort"] >= 1


@pytest.mark.parametrize("policy", ["nothing_saveable", "full", "dots_saveable"])
def test_explicit_policies_record_nothing(policy):
    """The knob keeps its meanings: under an explicit policy no value is
    chosen by budget, whatever the tags (the head's form is written
    whatever the policy: PR 63)."""
    _, kept = _grads(_model("dense", policy), 0)
    assert kept == {"head_form": "whole", "head_passes": 3}


@pytest.mark.parametrize("policy", [KEEP_PRODUCTS, "nothing_saveable"])
def test_pipeline_stage_builds_the_same_policy(policy):
    """One builder: a pipeline stage's layer slice under each policy gives
    the gradients of the slice without remat, and under the default one the
    budget's report names the stage's own layer count."""
    from deepspeed_tpu.models import gpt2_config
    from deepspeed_tpu.runtime.pipe.module import PipelineModule

    def stage_grads(remat):
        cfg = gpt2_config("gpt2-tiny", num_layers=4, max_seq_len=32,
                          vocab_size=256, remat=remat, remat_policy=policy)
        pipe = PipelineModule(cfg, num_stages=2)
        blocks = jax.tree.map(lambda a: a[0], pipe.init(jax.random.PRNGKey(0))["blocks"])
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 128))
        budget = Budget(None)
        loss = lambda b, v: jnp.sum(pipe._stage_fn(
            b, v, jnp.arange(32)[None], passes=3, remat_budget=budget)[0] ** 2)
        return jax.jit(jax.grad(loss, (0, 1)))(blocks, x), budget.totals

    (want, _), (got, kept) = stage_grads(False), stage_grads(True)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    if policy == KEEP_PRODUCTS:
        # 2 of the 4 layers, kept once for each of the schedule's 3 passes
        # through the stage, x [2, 32] x 1024 wide x float32
        assert kept["saved_bytes"] == 2 * 3 * 2 * 32 * 1024 * 4
    else:
        assert kept == {}


# -- (d) the engine's counter -------------------------------------------------

def test_engine_remat_totals(eight_devices):
    model = gpt2_model("gpt2-tiny", max_seq_len=32, vocab_size=256)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "zero_optimization": {"stage": 1},
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    assert engine.remat_totals["policy"] is None      # nothing traced yet
    batch = {"input_ids": np.random.default_rng(0).integers(0, 256, size=(8, 32))}
    engine.train_batch(batch)
    totals = engine.remat_totals
    assert totals["policy"] == KEEP_PRODUCTS
    assert totals["saved"] == ("fc_in", "o_proj", "q_proj", "k_proj", "v_proj")
    assert totals["budget_bytes"] is None             # the CPU reports no memory
    # [8, 32] tokens x (4 x 128 + 512) wide x float32 x 2 layers
    assert totals["saved_bytes"] == totals["candidate_bytes"] == 8 * 32 * 1024 * 4 * 2
    engine.train_batch(batch)
    assert engine.remat_totals is totals              # set once


def test_engine_remat_totals_explicit_policy(eight_devices):
    model = gpt2_model("gpt2-tiny", max_seq_len=32, vocab_size=256,
                       remat_policy="nothing_saveable")
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    engine.train_batch({"input_ids": np.zeros((8, 32), np.int32)})
    assert engine.remat_totals["policy"] is None and engine.remat_totals["saved"] == ()


def test_engine_reads_its_room_from_the_device(eight_devices):
    """limit less in use less gradients, times the ways the mesh splits the
    batch; read once, and the step is then traced under the budget that
    ``saved_budget`` makes of it."""
    import types
    model = gpt2_model("gpt2-tiny", max_seq_len=32, vocab_size=256)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "zero_optimization": {"stage": 1},
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    grads = sum(p.size * 4 for p in jax.tree.leaves(engine.state["params"]))
    ids = np.zeros((8, 32), np.int32)
    carry = checkpointing._bytes(jax.eval_shape(
        lambda p: model.embed(p, ids, None) + (model._aux_zero(),),
        engine.state["params"]))
    wide = lambda width: 2 * 8 * 32 * width * 4      # 2 layers x [8, 32] x float32
    # the working set, as a trace without an engine reckons it: a block's
    # bytes, which are more than the two float32 tables of [8, 32] x 256
    # logits outside the blocks (less the eight devices' gradients, nothing)
    working = Budget(None)
    jax.make_jaxpr(jax.grad(lambda p: model.loss(
        p, {"input_ids": ids}, remat_budget=working)))(engine.state["params"])
    assert working.outside_bytes == 2 * 8 * 32 * 256 * 4 < working.block_bytes
    # room for fc_in and o_proj, and not for the three projections (with a
    # quarter of a MB to spare: the engine's own trace of the block holds one
    # [8, 32, 128] value more than this one)
    room = 2 * carry + working.block_bytes + (256 << 10) \
        + int(np.ceil((wide(512 + 128) + 8) * STACK_COST))
    room += -room % 8
    free = grads + room // 8

    class Device:
        process_index = jax.process_index()

        def __init__(self, in_use):
            self.in_use = in_use

        def memory_stats(self):
            return {"bytes_limit": 10 * free, "bytes_in_use": self.in_use}

    real = engine.mesh
    # the fullest device decides
    engine.mesh = types.SimpleNamespace(
        devices=np.array([Device(9 * free), Device(8 * free)], dtype=object),
        shape=real.shape)
    assert engine._remat_room_bytes == room // 8
    engine.mesh = real
    engine.train_batch({"input_ids": ids})
    totals = engine.remat_totals
    assert totals["working_bytes"] == totals["block_bytes"] \
        <= working.block_bytes + (256 << 10)
    assert totals["grads_bytes"] == 8 * grads and totals["carries_bytes"] == 2 * carry
    assert totals["budget_bytes"] == saved_budget(room, 2, carry, totals["working_bytes"])
    assert totals["saved_by_kind"]["full"]["saved"] == totals["saved"]
    assert totals["saved"] == ("fc_in", "o_proj")
    assert totals["saved_bytes"] == wide(640) <= totals["budget_bytes"] < wide(1024)
    assert totals["candidate_bytes"] == wide(1024)
