"""A head whose float32 logits are not kept takes each row slice's gradient
where its logits are (`TransformerLM.fused_head_loss`): its value and its
gradients against autodiff of the whole-logits loss, the products over the
vocabulary a program holds (counted in its jaxpr), the float16 exception, a
prediction module's pair against the `jax.checkpoint` form it replaced, and
what the budget is told."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt2_model, instella_moe_model, transformer
from deepspeed_tpu.models.transformer import masked_cross_entropy
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import Budget

F32 = jnp.float32
VOCAB, ROWS, SEQ = 320, 2, 16          # (no other dimension of a head is 320)


def close(a, b, rel=1e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-12)


@pytest.fixture(scope="module", params=["tied", "untied"])
def head(request):
    """(model, the head's parameters, the last stream, labels)."""
    model = gpt2_model("gpt2-tiny", max_seq_len=SEQ, vocab_size=VOCAB, dtype=F32,
                       tie_embeddings=request.param == "tied")
    params = model.init(jax.random.PRNGKey(0))
    matrix = "wte" if request.param == "tied" else "lm_head"
    ids = jax.random.randint(jax.random.PRNGKey(1), (ROWS, SEQ), 0, VOCAB)
    x = jax.random.normal(jax.random.PRNGKey(2), (ROWS, SEQ, model.config.hidden_size), F32)
    return model, {k: params[k] for k in ("ln_f", matrix)}, x, model.derive_labels({"input_ids": ids})


def masking(kind, labels):
    """(labels, loss_mask) of a case."""
    if kind == "loss_mask":
        return labels, (jnp.arange(SEQ)[None, :] % 3 != 0).astype(F32) * jnp.ones((ROWS, 1))
    if kind == "ignored":
        return jnp.where(jnp.arange(SEQ)[None, :] % 4 == 1, -100, labels), None
    return labels, None


@pytest.mark.parametrize("cotangent", [1.0, 0.3])
@pytest.mark.parametrize("kind", ["plain", "loss_mask", "ignored"])
@pytest.mark.parametrize("slices", [1, 2, 4])
def test_value_and_gradients_are_the_whole_heads(head, slices, kind, cotangent):
    """The loss, and its gradients by the stream, the matrix and the final
    norm under an incoming cotangent, against autodiff over whole logits."""
    model, params, x, labels = head
    labels, mask = masking(kind, labels)

    @jax.jit
    def both(params, x):
        out = []
        for loss in (lambda p, x: masked_cross_entropy(model.head(p, x), labels, mask),
                     lambda p, x: model.fused_head_loss(p, x, labels, mask, slices)):
            value, pull = jax.vjp(loss, params, x)
            out.append((value, pull(jnp.asarray(cotangent, F32))))
        return out
    (want, want_g), (got, got_g) = both(params, x)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert a.dtype == b.dtype and close(a, b)


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(getattr(inner, "jaxpr", inner))


def vocabulary_products(jaxpr, vocab: int = VOCAB) -> int:
    """The ``dot_general`` equations with the vocabulary among an operand's
    or the result's dimensions."""
    return sum(eqn.primitive.name == "dot_general"
               and any(vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars))
               for eqn in equations(jaxpr))


def primitives(jaxpr) -> set:
    return {eqn.primitive.name for eqn in equations(jaxpr)}


@pytest.mark.parametrize("slices", [1, 4])
def test_an_evaluation_makes_one_product_and_no_gradient(head, slices):
    model, params, x, labels = head
    loss = lambda p, x: model.fused_head_loss(p, x, labels, None, slices)
    want = jax.jit(lambda p, x: masked_cross_entropy(model.head(p, x), labels))(params, x)
    assert float(jax.jit(loss)(params, x)) == pytest.approx(float(want), rel=1e-6)
    assert vocabulary_products(jax.make_jaxpr(loss)(params, x).jaxpr) == 1


@pytest.mark.parametrize("slices", [1, 4])
def test_a_differentiated_head_makes_three_products_where_the_rerun_makes_four(head, slices):
    """The mechanism's own assertion: logits, the rows' gradient and the
    matrix's, once each; a `jax.checkpoint` of the slice (what the sliced head
    was, and float16 still is) makes the logits a second time."""
    model, params, x, labels = head
    grad = lambda model, params, x: jax.make_jaxpr(jax.grad(
        lambda p, x: model.fused_head_loss(p, x, labels, None, slices), argnums=(0, 1)))(
            params, x).jaxpr
    fused = grad(model, params, x)
    assert vocabulary_products(fused) == 3
    assert "remat2" not in primitives(fused)
    half = transformer.TransformerLM(dataclasses.replace(model.config, dtype=jnp.float16))
    to_half = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float16), tree)
    rerun = grad(half, to_half(params), to_half(x))
    assert vocabulary_products(rerun) == 4
    assert "remat2" in primitives(rerun)
    assert half.head_form(slices=4) == "rerun" and model.head_form(slices=4) == "fused"
    assert model.head_form(slices=1) == "whole"


def test_float16_keeps_autodiffs_order_under_a_loss_scale(head):
    """Under float16 a loss scale reaches the logits' gradient before it is
    rounded: the scaled gradients over slices are the whole head's."""
    model, params, x, labels = head
    half = transformer.TransformerLM(dataclasses.replace(model.config, dtype=jnp.float16))
    params, x = jax.tree.map(lambda a: a.astype(jnp.float16), (params, x))
    scale = 2.0 ** 12
    grads = lambda loss: jax.jit(jax.grad(lambda p, x: scale * loss(p, x), argnums=(0, 1)))(params, x)
    want = grads(lambda p, x: masked_cross_entropy(half.head(p, x), labels))
    got = grads(lambda p, x: half.fused_head_loss(p, x, labels, None, 4))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == jnp.float16 and np.isfinite(np.asarray(a, np.float32)).all()
        assert close(a, b, rel=2e-2)
    assert float(jnp.abs(got[1]).max()) > 0


# -- a prediction module's pair ------------------------------------------------

@pytest.fixture(scope="module")
def pair():
    model = instella_moe_model("instella-tiny", dtype=F32)
    assert model.config.mtp_layers == 1 and model.config.remat
    params = model.init(jax.random.PRNGKey(3))
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, model.config.vocab_size)
    return model, params, {"input_ids": ids}


def checkpointed_pair(model, params, batch, mask=None):
    """The objective as `loss_and_stats` had it before PR 63: each head's
    whole-logits loss under `jax.checkpoint`."""
    c = model.config
    labels = model.derive_labels(batch)
    later = lambda a, fill: jnp.pad(a[:, 1:], ((0, 0), (0, 1)), constant_values=fill)
    x, aux, _, mtp_x = model._trunk(params, batch["input_ids"], None, None, None, None,
                                    with_mtp=True)
    head_loss = jax.checkpoint(lambda x, labels, mask, ln_f=None: masked_cross_entropy(
        model.head(params, x, ln_f=ln_f), labels, extra_mask=mask))
    loss = head_loss(x, labels, mask) + c.mtp_loss_coef * head_loss(
        mtp_x, later(labels, -100), None if mask is None else later(mask, 0),
        params["mtp"]["ln_f"])
    return model.combine_aux(loss, aux)


@pytest.mark.parametrize("room", [None, "four_slices"])
@pytest.mark.parametrize("masked", [False, True])
def test_the_prediction_modules_pair_is_the_checkpointed_pair(pair, masked, room):
    model, params, batch = pair
    ids = batch["input_ids"]
    if masked:
        batch = {**batch, "loss_mask": (jnp.arange(ids.shape[1])[None, :] % 5 != 2)
                 .astype(F32) * jnp.ones((ids.shape[0], 1))}
    budget = None
    if room:
        budget = Budget(2 * 4 * ids.size * model.config.vocab_size * 2 - 1)
        assert transformer.head_slices(model.config, budget, ids) == 4
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p: checkpointed_pair(model, p, batch, batch.get("loss_mask"))))(params)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, batch, remat_budget=budget)))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_g), jax.tree.leaves(want_g)):
        assert close(a, b, rel=2e-5), jax.tree_util.keystr(path)
    if room:
        assert budget.totals["head_form"] == "fused" and budget.totals["head_passes"] == 3
        assert budget.totals["head_row_slices"] == 4


def test_a_step_with_a_prediction_module_makes_three_products_a_head(pair):
    model, params, batch = pair
    vocab = model.config.vocab_size
    assert vocab != VOCAB
    count = lambda loss: vocabulary_products(jax.make_jaxpr(jax.grad(loss))(params).jaxpr, vocab)
    assert count(lambda p: checkpointed_pair(model, p, batch)) == 8
    assert count(lambda p: model.loss(p, batch)) == 6
    # an evaluation: a product a head
    assert vocabulary_products(jax.make_jaxpr(lambda p: model.loss(p, batch))(params).jaxpr,
                               vocab) == 2


def test_a_prediction_modules_heads_take_slices_of_the_room(pair):
    """`head_slices` for a configuration with ``mtp_layers``: the count of any
    head's shape under the room (it was 1 whatever the room), 1 without a
    budget; and the budget is told what the pair holds."""
    model, _, batch = pair
    ids, vocab = batch["input_ids"], model.config.vocab_size
    whole = 2 * 4 * ids.size * vocab
    slices = lambda room: transformer.head_slices(model.config, Budget(room), ids)
    assert transformer.head_slices(model.config, None, ids) == 1
    assert slices(None) == 1 == slices(2 * whole)
    assert slices(2 * whole - 1) == 4 and slices(whole // 4) == 16
    for room, n in ((None, 1), (2 * whole - 1, 4)):
        budget = Budget(room)
        model._charge_head(budget, ids)
        handed = ids.size * model.config.hidden_size * 4 + 4 * model.config.hidden_size * (vocab + 1)
        assert budget.outside_bytes == whole // n + 2 * handed
        assert budget.totals["head_form"] == "fused"


@pytest.mark.parametrize("room,form,slices", [(None, "whole", None), ("small", "fused", 4)])
def test_the_budget_is_told_the_heads_form(room, form, slices):
    """`remat_totals`' ``head_form`` / ``head_passes`` beside
    ``head_row_slices``, written while a step is traced."""
    model = gpt2_model("gpt2-tiny", max_seq_len=SEQ, vocab_size=VOCAB, dtype=F32, remat=True)
    ids = jnp.zeros((ROWS, SEQ), jnp.int32)
    whole = 2 * 4 * ids.size * VOCAB
    budget = Budget(2 * whole - 1 if room else None)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.loss(
        p, {"input_ids": ids}, remat_budget=budget)))(params).jaxpr
    assert budget.totals["head_form"] == form and budget.totals["head_passes"] == 3
    assert budget.totals.get("head_row_slices") == slices
    assert vocabulary_products(jaxpr) == 3
    handed = ids.size * model.config.hidden_size * 4 + 4 * model.config.hidden_size * (VOCAB + 1)
    assert budget.outside_bytes == (whole // slices + handed if slices else whole)
