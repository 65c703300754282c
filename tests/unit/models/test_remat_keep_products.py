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
    KEEP_PRODUCTS, SAVE_ORDER, STACK_COST, WORKING_SHARE, Budget,
    choose_saved, live_bytes, saved_budget)

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
    return (layers * checkpointing._bytes(carry)
            + int(np.ceil(WORKING_SHARE * working_bytes))
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
    assert 0 < kept["saved_bytes"] <= half == kept["budget_bytes"]
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


#: the step's own needs at 36 layers of 10 MB and a working set of 900 MB
NEEDS = 36 * 10 * MB + int(WORKING_SHARE * 900 * MB)


@pytest.mark.parametrize("room,want", [
    (None, None),                                        # no reading: no budget
    (0, 0),
    (NEEDS, 0),                                          # the step's own needs
    (NEEDS + 18 * MB, int(18 * MB / STACK_COST)),
    (1 << 40, int(((1 << 40) - NEEDS) / STACK_COST)),
])
def test_saved_budget_table(room, want):
    """Room less every layer's input and the working set, over what a
    saved byte costs the step's peak."""
    assert saved_budget(room, layers=36, carry_bytes=10 * MB,
                        working_bytes=900 * MB) == want


# -- (b') the working set the budget is charged: a walk over a jaxpr ----------

KB = 1 << 10
X = jax.ShapeDtypeStruct((256, 256), jnp.float32)      # 256 KB


def _live(fn, *args) -> int:
    return live_bytes(jax.make_jaxpr(fn)(*args).jaxpr)


def test_live_bytes_counts_what_is_held_together():
    def chain(x):                  # each link dies as the next is made
        return jnp.tanh(jnp.cos(jnp.sin(x)))
    assert _live(chain, X) == 2 * 256 * KB

    def held(x):                   # a stays for the last line: three at once
        a = jnp.sin(x)
        b = jnp.cos(a)
        return jnp.tanh(b) + a
    assert _live(held, X) == 3 * 256 * KB


def test_live_bytes_leaves_out_what_died_before_the_peak():
    def early_and_late(x):
        a = jnp.sin(x) @ jnp.cos(x)              # three values, then one scalar
        early = jnp.sum(a)
        b = jnp.tanh(x)
        return early + jnp.sum(b * jnp.exp(b))   # b, exp(b), their product
    # the first half's three values are gone when the second half's three live
    assert _live(early_and_late, X) == 3 * 256 * KB + 4


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
    inner = jax.jit(lambda x: jnp.sum(jnp.sin(x) * jnp.cos(x)))
    assert _live(lambda x: inner(x) + 1.0, X) == 3 * 256 * KB

    def loop(x):
        step = lambda c, _: (c + jnp.sum(jnp.tanh(x) * jnp.exp(x)), None)
        return jax.lax.scan(step, 0.0, None, length=3)[0]
    assert _live(loop, X) == 3 * 256 * KB


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
    kinds = [(checkpointing.checkpointed(fn, KEEP_PRODUCTS, 2, budget), carry, layer)
             for fn, carry, layer in (_toy_block(128), _toy_block(1024))]
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
    room = 2 * 64 * 64 * 4 + int(WORKING_SHARE * narrow.block_bytes) \
        + int(np.ceil(product * STACK_COST)) + 8
    for budget, saved in ((Budget(room), ("fc_in",)), (Budget(room, block_bytes=seen[1]), ())):
        block = checkpointing.checkpointed(_toy_block(128)[0], KEEP_PRODUCTS, 2, budget)
        jax.make_jaxpr(jax.grad(lambda x: jnp.sum(block(x, w)[0])))(x)
        assert budget.totals["saved"] == saved


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
    gradient; a prediction module's second head is run again in the
    backward like the first, so one counts."""
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
    assert budget.outside_bytes == 2 * (2 * SEQ * model.config.vocab_size * 4)
    assert budget.totals["outside_bytes"] == budget.outside_bytes
    assert budget.totals["working_bytes"] == budget.outside_bytes + budget.block_bytes
    assert budget.block_bytes > 0


#: Five benchmark cells as the chip traced them (PERF.md, PR 35, and PR 43 for
#: the last: the engine's log lines and ``remat_totals``): layers, one layer's input, the
#: room the engine read (free less gradients, with the reference's signs
#: resident), the working set as reckoned (a block's + outside the blocks),
#: name -> bytes over all layers; and the group the decision must reach.
CELLS = {
    "gpt2-large.train.seq1k": (36, 10_489_860, 6_850_000_000, 587_672_606 + 1_646_821_376, {
        "attn_lse": 11_796_480, "attn_o": 377_487_360, "fc_in": 1_509_949_440,
        "o_proj": 377_487_360, "q_proj": 377_487_360, "k_proj": 377_487_360,
        "v_proj": 377_487_360}, "v_proj"),
    "olmoe-1b-7b.train.seq4k": (2, 16_793_608, 3_320_000_000, 2_587_466_536 + 1_648_361_472, {
        "attn_lse": 524_288, "attn_o": 33_554_432, "moe_logits": 2_097_152,
        "wi_gate": 134_217_728, "wi_up": 134_217_728, "wo": 268_435_456,
        "o_proj": 33_554_432, "q_proj": 33_554_432, "k_proj": 33_554_432,
        "v_proj": 33_554_432}, "v_proj"),
    "instella-moe-16b-a3b.train.seq8k": (7, 134_250_504, 6_900_000_000, 5_241_973_156 + 2_111_832_064, {
        "attn_lse": 7_340_032, "attn_o": 469_762_048, "moe_logits": 29_360_128,
        "wi_gate": 322_961_408, "wi_up": 322_961_408, "wo": 1_056_964_608,
        "gate_proj": 645_922_816, "up_proj": 645_922_816, "o_proj": 469_762_048,
        "attn_gate": 469_762_048, "q_proj": 469_762_048, "kv_latent": 124_780_544,
        "kv_up": 822_083_584}, "wo"),
    "trinity-mini.train.seq16k": (6, 67_174_408, 6_890_000_000, 6_650_287_908 + 3_279_945_728, {
        "attn_lse": 12_582_912, "attn_o": 805_306_368, "moe_logits": 50_331_648,
        "wi_gate": 201_326_592, "wi_up": 201_326_592, "wo": 1_207_959_552,
        "gate_proj": 201_326_592, "up_proj": 201_326_592, "o_proj": 402_653_184,
        "attn_gate": 805_306_368, "q_proj": 805_306_368, "k_proj": 100_663_296,
        "v_proj": 100_663_296}, "wi_up"),
    # PR 43: a 32,768-row EVA step's one candidate, the grouped branch's output
    # (its flash launches' residuals are inside the groups and unlisted)
    "evabyte-6.5b.train.seq32k": (4, 268_566_532, 6_231_251_968, 5_908_512_778 + 671_088_640, {
        "o_proj": 1_073_741_824}, "o_proj"),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_keep_what_the_chip_has_room_for(cell):
    """The two short cells keep every name, as before PR 35; the two
    long-sequence cells, which 128 layer inputs left with nothing, keep the
    kernel's pair and the experts' first products (the Instella cell their
    rows after the combine as well); the 32,768-row EVA cell keeps its one
    candidate, the grouped branches' outputs (PR 43). No decision sits within
    a twentieth of the room of a group's edge: the same names at 95 % and at
    105 % of the reading, so what a run keeps does not hang on a few MB."""
    layers, carry, room, working, candidates, reaches = CELLS[cell]
    saved = choose_saved(candidates, saved_budget(room, layers, carry, working))
    assert {"attn_lse", "attn_o"} & set(candidates) <= set(saved) and saved[-1] == reaches
    if reaches in ("v_proj", "o_proj"):
        assert set(saved) == set(candidates)
    for share in (0.95, 1.05):
        assert choose_saved(candidates, saved_budget(
            int(share * room), layers, carry, working)) == saved
    # the rule PR 35 replaced: 128 more layer inputs before anything is kept
    old = max(0, int((room - (layers + 128) * carry) / STACK_COST))
    assert (choose_saved(candidates, old) == ()) == any(
        long in cell for long in ("seq8k", "seq16k", "seq32k"))


@pytest.fixture(scope="module")
def keye_cell_candidates():
    """name -> bytes over the eight layers of what the
    ``keye-vl2-30b-a3b.train.dsa16k`` cell's block names, read by
    ``named_bytes`` off the block's own jaxpr at the cell's shapes (one row of
    16,384 at the published widths, bf16; shapes alone, no chip): what
    ``choose_saved`` is handed on the kernel route."""
    import json
    import os
    from benchmark.adapters import keye_vl2 as adapter
    from tests.benchmark.helpers import REPO
    with open(os.path.join(REPO, "benchmark", "configs", "keye-vl2-30b-a3b.json")) as f:
        model = adapter.model(json.load(f), remat=True, dtype="bfloat16")
    seen = []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        patch.delenv("DSTPU_ATTN", raising=False)
        patch.setattr(checkpointing, "choose_saved", lambda candidates, budget: (
            seen.append(dict(candidates)), choose_saved(candidates, budget))[1])
        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.bfloat16))
        jax.make_jaxpr(jax.grad(lambda p, ids: model.loss(
            p, {"input_ids": ids}, remat_budget=Budget(None))))(
                params, jax.ShapeDtypeStruct((1, 16384), jnp.int32))
    (candidates,) = seen
    return candidates


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
    (2122.4, 3, "wi_up"),           # the experts' first products: 116 MB past the chip's ~2,006
])
def test_the_keye_cell_keeps_the_three_leading_groups(keye_cell_candidates, budget_mb,
                                                      groups, last):
    """Under the cell's budget `choose_saved` takes the KL's gradients, the
    operand and the selected launch's pair (1,652.5 MB) by the rule it has,
    `SAVE_ORDER`, `STACK_COST` and `WORKING_SHARE` as they were; the router's
    float32 logits (67.1 MB, the next group the cell's names hold) ride along
    where they fit, and the experts' first products (402.6 MB) do not."""
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
    chosen by budget, whatever the tags."""
    _, kept = _grads(_model("dense", policy), 0)
    assert kept == {}


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
    # the working set, as a trace without an engine reckons it: the two
    # float32 tables of [8, 32] x 256 logits outside the blocks, and a block
    working = Budget(None)
    jax.make_jaxpr(jax.grad(lambda p: model.loss(
        p, {"input_ids": ids}, remat_budget=working)))(engine.state["params"])
    assert working.outside_bytes == 2 * 8 * 32 * 256 * 4 < working.working_bytes
    # room for fc_in and o_proj, and not for the three projections
    room = 2 * carry + int(np.ceil(WORKING_SHARE * working.working_bytes)) \
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
    assert totals["working_bytes"] == working.working_bytes \
        == totals["block_bytes"] + totals["outside_bytes"]
    assert totals["budget_bytes"] == saved_budget(room, 2, carry, totals["working_bytes"])
    assert totals["saved"] == ("fc_in", "o_proj")
    assert totals["saved_bytes"] == wide(640) <= totals["budget_bytes"] < wide(1024)
    assert totals["candidate_bytes"] == wide(1024)
