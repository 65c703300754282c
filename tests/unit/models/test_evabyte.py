"""EvaByte's block (``model_type`` ``evabyte``) at the tiny preset, on the CPU:
loss and every gradient against the plain reference
(benchmark/reference/evabyte.py) on both attention routes; ``eva_visible``
against a loop; the merge of the two partial softmaxes against one softmax
over the concatenated keys; a row no longer than a window is plain causal
attention; the eight-shift loss against eight loops; the head groups and the
MLP's row slices against the whole; what each stated float32 is worth; the
first step through ``initialize`` -> ``train_batch``; the published stack; and
the paths that refuse the kind by name."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from deepspeed_tpu.models import evabyte_model, mixers, transformer
from deepspeed_tpu.models.registry import get_architecture
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
from deepspeed_tpu.ops.transformer import attention, pallas_flash
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import Budget
from tests.benchmark.helpers import DATA

MANIFEST = os.path.join(DATA, "BENCHMARK.evabyte-tiny.json")
F32 = jnp.float32
W, C = 32, 4        # the tiny preset's window and chunk


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(MANIFEST, "evabyte-tiny.train")


@pytest.fixture(scope="module")
def parts(cell):
    """(reference module, adapter module, configuration, weights, ids): two
    rows of 128 byte ids = four windows of 32, chunks of 4."""
    ref = cell.load_module("reference", cell.config["reference"])
    adapter = cell.load_module("adapters", cell.config["adapter"])
    w = ref.make_weights(ref.key_of(7), cell.config, F32)
    ids = np.random.default_rng(0).integers(0, cell.config["vocab_size"], (2, 128))
    return ref, adapter, cell.config, w, jnp.asarray(ids, jnp.int32)


@pytest.fixture(scope="module")
def want(parts):
    """The reference's loss and gradient on ``parts`` (one jitted program)."""
    ref, _, cfg, w, ids = parts
    return jax.jit(jax.value_and_grad(lambda p: ref.next_token_loss(p, ids, cfg)))(w)


@pytest.fixture(scope="module")
def layer_at_a_time(parts):
    """The reference a layer at a time (its own ``loss_and_gradient``: what
    runs at 32,768 rows), one jitted program for both routes."""
    ref, _, cfg, w, ids = parts
    return jax.jit(lambda p: ref.loss_and_gradient(p, ids, cfg))(w)


def worst(a, b):
    """The largest difference, as a share of ``b``'s largest element."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-12)


def program_loss_and_grad(parts, budget=None, **model_kw):
    """``budget``: the engine's reading of the device (``checkpointing.
    Budget``); None keeps every named value, as a model without an engine."""
    _, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, dtype="float32", **{"remat": True, **model_kw})
    with jax.default_matmul_precision("highest"):
        loss, g = jax.value_and_grad(lambda p: model.loss(
            p, {"input_ids": ids}, remat_budget=budget))(adapter.to_program(w))
    return float(loss), adapter.from_program(g)


@pytest.mark.parametrize("route", ["xla", "pallas"])
def test_loss_and_gradient_match_the_reference(parts, want, layer_at_a_time, route,
                                               monkeypatch):
    """float32 against float32 at ``highest``, the XLA form of
    ``eva_visible`` and the two launches (interpret mode) with the merge: the
    loss to 1e-6 (one reduction order apart), every gradient to 2e-5 of its
    largest element (measured 1e-6; the online softmax of the kernels and the
    merge through the LSE reorder float32 sums), the logits to 1e-5."""
    ref, adapter, cfg, w, ids = parts
    monkeypatch.setenv("DSTPU_ATTN", route)
    got, flat = program_loss_and_grad(parts)
    assert got == pytest.approx(float(want[0]), rel=1e-6)
    assert set(flat) == set(w)
    for name, g in want[1].items():
        assert worst(flat[name], g) < 2e-5, name
    model = adapter.model(cfg, remat=False, dtype="float32")
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(adapter.to_program(w), ids)
    assert logits.shape == (2, 128, 8, 320) and logits.dtype == F32
    assert worst(logits, ref.forward_heads(w, ids, cfg)) < 1e-5
    np.testing.assert_array_equal(np.asarray(ref.forward(w, ids, cfg)),
                                  np.asarray(ref.forward_heads(w, ids, cfg)[:, :, 0]))
    # the reference a layer at a time (what runs at 32,768 rows) is the reference
    loss, gnorm, signs = layer_at_a_time
    assert float(loss) == pytest.approx(float(want[0]), rel=1e-6)
    assert float(gnorm) == pytest.approx(float(jnp.sqrt(sum(
        jnp.sum(jnp.square(g)) for g in want[1].values()))), rel=1e-5)
    for name, g in want[1].items():
        assert signs[name].shape == g.shape
        assert np.mean(np.asarray(signs[name]) == np.sign(np.asarray(g))) > 0.9999, name


def test_the_reference_scores_queries_in_blocks(parts, want, monkeypatch):
    """At the cell's size the reference scores 256 queries at a time against
    their own window's keys and the row's summaries, and takes the MLP and
    the head's loss a block of rows at a time: the same numbers (here blocks
    of 16 queries and 64 rows)."""
    ref, _, cfg, w, ids = parts
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "TOKEN_BLOCK", 64)
    blocked, blocked_g = jax.jit(jax.value_and_grad(
        lambda p: ref.next_token_loss(p, ids, cfg, checkpoint=True)))(w)
    assert float(blocked) == pytest.approx(float(want[0]), rel=1e-6)
    assert all(worst(blocked_g[k], want[1][k]) < 1e-5 for k in w)


def test_eva_visible_against_a_loop():
    """THE definition, key by key: an exact key in the query's own window up
    to the query; the summary of every chunk of every window before it."""
    L = 3 * W + 5
    q, k, g = np.arange(L), np.arange(L), np.arange(L // C)
    exact = np.asarray(attention.eva_visible(q[:, None], k[None, :], False, W, C))
    far = np.asarray(attention.eva_visible(q[:, None], g[None, :], True, W, C))
    for i in range(L):
        for j in range(L):
            assert exact[i, j] == (j // W == i // W and j <= i), (i, j)
        for c in range(L // C):
            assert far[i, c] == ((c * C) // W < i // W), (i, c)
    # a query of window w sees w x W / C summaries and its offset + 1 keys
    assert far.sum(axis=1).tolist() == [i // W * (W // C) for i in range(L)]
    assert exact.sum(axis=1).tolist() == [i % W + 1 for i in range(L)]


def qkv(L, heads=2, d=16, seed=0, dtype=F32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(ks[i], (2, L, heads, d), dtype) for i in range(3))
    phi, mu = (jax.random.normal(ks[3 + i], (heads, d), dtype) for i in range(2))
    return q, k, v, phi, mu


def one_softmax(q, k, v, kbar, vbar):
    """EVA by the book: every query's visible keys gathered, ONE softmax."""
    q, k, v, kbar, vbar = (np.asarray(a, np.float64) for a in (q, k, v, kbar, vbar))
    B, L, H, D = q.shape
    out = np.zeros_like(q)
    for b in range(B):
        for h in range(H):
            for i in range(L):
                keys = [k[b, j, h] for j in range(i // W * W, i + 1)]
                vals = [v[b, j, h] for j in range(i // W * W, i + 1)]
                n = i // W * (W // C)
                keys += list(kbar[b, :n, h])
                vals += list(vbar[b, :n, h])
                s = np.array(keys) @ q[b, i, h] / np.sqrt(D)
                p = np.exp(s - s.max())
                out[b, i, h] = (p / p.sum()) @ np.array(vals)
    return out


@pytest.mark.parametrize("route", ["xla", "pallas"])
def test_two_partial_softmaxes_are_one(route, monkeypatch):
    """The exact keys' partial softmax and the summaries', merged through
    their LSEs, against one softmax over the concatenated keys (float64, a
    loop), forward; and the summaries against their definition."""
    monkeypatch.setenv("DSTPU_ATTN", route)
    q, k, v, phi, mu = qkv(4 * W)
    kbar, vbar = attention.eva_summaries(k, v, phi, mu, C)
    kc = np.asarray(k, np.float64).reshape(2, -1, C, 2, 16)
    w = np.exp(np.einsum("bgchd,hd->bgch", kc, np.asarray(phi, np.float64)))[..., None]
    w = w / w.sum(axis=2, keepdims=True)
    assert worst(kbar, (w * kc).sum(axis=2) + np.asarray(mu)) < 1e-5
    got = attention.eva_attention(q, k, v, kbar, vbar, W, C)
    assert worst(got, one_softmax(q, k, v, kbar, vbar)) < 1e-5


@pytest.mark.parametrize("route", ["xla", "pallas"])
def test_a_row_inside_one_window_is_causal_attention(route, monkeypatch):
    """No summary is seen by a row no longer than a window: EVA is then
    plain causal attention, whatever phi and mu hold."""
    monkeypatch.setenv("DSTPU_ATTN", route)
    q, k, v, phi, mu = qkv(W)
    kbar, vbar = attention.eva_summaries(k, v, 100.0 * phi, 100.0 * mu, C)
    got = attention.eva_attention(q, k, v, kbar, vbar, W, C)
    assert worst(got, attention._xla_attention(q, k, v, True, None, None)) < 1e-6


def test_eight_shifts_against_eight_loops():
    """Head m at position i is scored against token i + 1 + m; a head's loss
    is the mean over the positions that have such a token, the loss the mean
    over the heads (each head weighs the same, whatever it has to score)."""
    rng = np.random.default_rng(1)
    B, S, P, V = 2, 11, 8, 7
    logits = rng.normal(size=(B, S, P, V)).astype(np.float32)
    ids = rng.integers(0, V, (B, S))
    labels = np.concatenate([ids[:, 1:], np.full((B, 1), -100)], axis=1)
    got = transformer.pred_heads_cross_entropy(jnp.asarray(logits), jnp.asarray(labels))
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    heads = []
    for m in range(P):
        nll = [-logp[b, i, m, ids[b, i + 1 + m]]
               for b in range(B) for i in range(S) if i + 1 + m < S]
        heads.append(np.mean(nll))
    assert float(got) == pytest.approx(np.mean(heads), rel=1e-5)
    # a head left out, or scored one token early, is another number
    assert abs(np.mean(heads[:-1]) - np.mean(heads)) > 1e-3


def two_head_groups(monkeypatch):
    """The tiny preset's four heads in two groups of two, as a 32,768-row
    step takes its 32 in eight groups of four."""
    monkeypatch.setattr(mixers, "EVA_GROUP_ELEMENTS", 2 * 128 * 2 * 16)
    assert mixers.eva_head_groups(256, 4, 16) == 2


@pytest.mark.parametrize("what", ["head_groups", "head_groups_no_room", "mlp_slices"])
def test_slices_are_the_whole(parts, want, what, monkeypatch):
    """A long row's heads in groups and its MLP over slices of the rows (what
    a 32,768-row step takes so that it fits) are the same arithmetic: the MLP
    slices' forward bit for bit (a row's MLP reads no other row), loss and
    gradients to the tolerance of the whole's (a weight's gradient is summed
    over the slices in another order). The grouped branch's output kept for
    the block's backward (``head_groups``: no reading of the device keeps
    every name) or made again (``head_groups_no_room``) is one value: both
    equal the whole's gradients, and so each other's."""
    _, adapter, cfg, w, ids = parts
    budget = None
    if what.startswith("head_groups"):
        two_head_groups(monkeypatch)
        budget = Budget(0 if what == "head_groups_no_room" else None)
    else:
        monkeypatch.setattr(transformer, "MLP_WHOLE_ELEMENTS", 64 * 96)
        monkeypatch.setattr(transformer, "MLP_SLICE_ELEMENTS", 64 * 96)
        assert transformer.mlp_row_slices(256, 96) == 4
        model = adapter.model(cfg, remat=False, dtype="float32")
        params = adapter.to_program(w)
        block = jax.tree.map(lambda a: a[0], params["blocks"])
        h = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 64), F32)
        sliced = model._gated_mlp(block, h)
        monkeypatch.setattr(transformer, "MLP_WHOLE_ELEMENTS", 2 ** 28)
        np.testing.assert_array_equal(np.asarray(sliced),
                                      np.asarray(model._gated_mlp(block, h)))
        monkeypatch.setattr(transformer, "MLP_WHOLE_ELEMENTS", 64 * 96)
    got, flat = program_loss_and_grad(parts, budget)
    assert got == pytest.approx(float(want[0]), rel=1e-6)
    for name, g in want[1].items():
        assert worst(flat[name], g) < 2e-5, name
    if budget is not None:
        assert ("o_proj" in budget.totals["saved"]) == (budget.room_bytes is None)


def eva_branch(parts, dtype="float32", backward=False):
    """``() -> `` the jaxpr of the tiny preset's EVA branch over ``[2, 128,
    64]``, or of its gradient (traced anew at every call: `two_head_groups`
    changes it)."""
    _, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype=dtype)
    block = jax.tree.map(lambda a: a[0], adapter.to_program(w)["blocks"])
    h = jax.ShapeDtypeStruct((2, 128, 64), jnp.dtype(dtype))
    positions = jnp.broadcast_to(jnp.arange(128), (2, 128))

    def trace():        # (a function of its own a call: ``make_jaxpr`` keeps what it traced)
        branch = lambda b, x: model._mixer(b, x, positions)[0]
        if backward:
            branch = jax.grad(lambda b, x: model._mixer(b, x, positions)[0].astype(F32).sum(),
                              argnums=(0, 1))
        return jax.make_jaxpr(branch)(block, h).jaxpr
    return trace


def forward_eqns(jaxpr):
    """The branch's equations as its forward runs them: the top level's,
    and in a custom-derivative call's place its forward's own."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "custom_vjp_call":
            out += forward_eqns(eqn.params["call_jaxpr"].jaxpr)
        else:
            out.append(eqn)
    return out


def scans(jaxpr):
    return [e for e in forward_eqns(jaxpr) if e.primitive.name == "scan"]


def inside(jaxpr, found=None):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            inside(sub, found)
    return found


def test_a_grouped_branch_names_its_output_once(parts, monkeypatch):
    """What outlives a long row's inner checkpoints is named for the block's
    policy, and nothing inside one is: of the names ``SAVE_ORDER`` lists the
    grouped branch's jaxpr holds FOUR, ``q_proj``, ``k_proj``, ``v_proj`` and
    ``o_proj`` (the names the ungrouped path gives the same values), each
    once, each at rows x width x itemsize, each made outside the group scan.
    A listed name inside a group would be kept for every group, stacked: the
    summaries' ``eva_kbar`` / ``eva_vbar`` are named on the ungrouped path
    alone (the scores' ``attn_big`` is no candidate on either path)."""
    branch = eva_branch(parts)
    listed = {n for group in checkpointing.SAVE_ORDER for n in group}
    candidates = lambda jaxpr: {n: b for n, b in checkpointing.named_bytes(
        jaxpr).items() if n in listed}
    whole = candidates(branch())
    assert whole["o_proj"] == 2 * 128 * 64 * 4
    assert set(whole) == {"q_proj", "k_proj", "v_proj", "eva_kbar", "eva_vbar", "o_proj"}
    two_head_groups(monkeypatch)
    grouped = branch()
    projections = ["q_proj", "k_proj", "v_proj", "o_proj"]
    assert candidates(grouped) == dict.fromkeys(projections, 2 * 128 * 64 * 4)
    named = [e for e in grouped.eqns if e.primitive.name == "name"]
    assert [e.params["name"] for e in named] == projections      # not in the scan
    assert all(e.outvars[0].aval.shape == (2, 128, 64) for e in named)
    (scan,) = scans(grouped)
    assert not [e.params["name"] for e in inside(scan.params["jaxpr"].jaxpr)
                if e.primitive.name == "name" and e.params["name"] in listed]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_grouped_branch_projects_whole_and_carries_nothing(parts, dtype, monkeypatch):
    """The group scan covers what a group is for: the grouped branch's four
    products over ``[B, S, hidden]`` run ONCE a layer at the whole width,
    outside the scan, whose body holds no product of the stream and which
    carries nothing: it reads a group's columns of q, k, v (and of ``phi``,
    ``mu``) and yields the group's ``[B, S, heads x head]`` in the stream's
    dtype. (Until PR 64 the body projected a group's 2 x 16 columns and added
    ``out_g @ wo_g`` into a float32 ``[B, S, hidden]`` carry.)"""
    two_head_groups(monkeypatch)
    jaxpr = eva_branch(parts, dtype)()
    stream = jnp.dtype(dtype)
    products = [e for e in forward_eqns(jaxpr) if e.primitive.name == "dot_general"]
    assert [(e.invars[0].aval.shape, e.invars[1].aval.shape, e.outvars[0].aval.shape)
            for e in products] == 4 * [((2, 128, 64), (64, 64), (2, 128, 64))]
    (scan,) = scans(jaxpr)
    assert scan.params["length"] == 2 and scan.params["num_carry"] == 0
    # what goes in by group: q, k, v [groups, B, S, 2 x 16], phi and mu
    # [groups, 2, 16]; no column of a weight, no [B, S, hidden] to add into
    by_group = [v.aval for v in scan.invars[scan.params["num_consts"]:]]
    assert sorted((a.shape, a.dtype) for a in by_group) == (
        2 * [((2, 2, 16), jnp.dtype("float32"))] + 3 * [((2, 2, 128, 32), stream)])
    assert [(v.aval.shape, v.aval.dtype) for v in scan.outvars] == [((2, 2, 128, 32), stream)]
    # nothing inside a group is as wide as the stream
    assert not [v.aval for e in inside(scan.params["jaxpr"].jaxpr) for v in e.outvars
                if v.aval.shape == (2, 128, 64)]
    # the backward's scan over the groups carries nothing either: a trip
    # reads the branch's cotangent and yields its columns' and ``wo``'s rows'
    trips = [e for e in inside(eva_branch(parts, dtype, backward=True)())
             if e.primitive.name == "scan" and e.params["length"] == 2]
    assert len(trips) == 2 and all(e.params["num_carry"] == 0 for e in trips)
    assert sorted(v.aval.shape for v in trips[1].outvars) == (
        2 * [(2, 2, 16)] + 3 * [(2, 2, 128, 32)] + [(2, 32, 64)])


def launches(jaxpr, counts=None):
    """Pallas kernel name -> launches in ``jaxpr`` and every jaxpr inside it
    (a scan's body once: launches a layer, or a group)."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] = counts.get(eqn.params["name"], 0) + 1
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            launches(sub, counts)
    return counts


@pytest.mark.parametrize("room,forwards", [(None, 2), (0, 3)])
def test_a_kept_branch_output_drops_the_groups_from_the_blocks_recompute(
        parts, room, forwards, monkeypatch):
    """A group's attention forward in the differentiated step: once forward,
    once in the group's own recompute for its backward, and once more in the
    BLOCK's recompute only where the branch's output (the MLP's input) is not
    kept. With it kept the block's recompute has no use for the group scan,
    and JAX's dead-code pass drops it: the chip's 96 launches a step of each
    forward kind become 64 (4 layers x 8 groups x 2)."""
    _, adapter, cfg, w, ids = parts
    monkeypatch.setenv("DSTPU_ATTN", "pallas")
    two_head_groups(monkeypatch)
    model = adapter.model(cfg, remat=True, dtype="float32")
    budget = Budget(room)
    found = launches(jax.make_jaxpr(jax.grad(lambda p: model.loss(
        p, {"input_ids": ids}, remat_budget=budget)))(adapter.to_program(w)).jaxpr)
    assert found["flash_fwd_eva_local"] == found["flash_fwd_eva_far"] == forwards
    assert found["flash_bwd_eva_local"] == found["flash_bwd_eva_far"] == 1
    assert ("o_proj" in budget.totals["saved"]) == (room is None)


def test_the_slicing_rules():
    """The cell's shape is sliced, no shape the benchmark had before is."""
    assert transformer.mlp_row_slices(32768, 11008) == 8
    assert mixers.eva_head_groups(32768, 32, 128) == 8
    for rows, width in ((16384, 10944), (16384, 6144), (4096, 5120), (128, 96)):
        assert transformer.mlp_row_slices(rows, width) == 1
    assert mixers.eva_head_groups(128, 4, 16) == 1
    assert mixers.eva_head_groups(2 ** 30, 3, 128) == 3     # never past one head


def leave_out(name):
    """``parts`` -> the program's loss and gradient with one piece of the
    mathematics taken out of the program."""
    def run(parts, monkeypatch):
        if name == "summaries":          # no query sees a summary
            real = attention.eva_visible
            monkeypatch.setattr(attention, "eva_visible", lambda q, k, s, w, c:
                                real(q, k, s, w, c) & ~jnp.asarray(s))
        elif name == "first_chunks":     # a window's first chunk has no summary
            real = attention.eva_visible
            monkeypatch.setattr(attention, "eva_visible", lambda q, k, s, w, c:
                                real(q, k, s, w, c) & ~(jnp.asarray(s) & ((k * c) % w == 0)))
        elif name == "mu":
            real = attention.eva_summaries
            monkeypatch.setattr(attention, "eva_summaries", lambda k, v, phi, mu, c:
                                real(k, v, phi, 0.0 * mu, c))
        elif name == "a_head":           # the eighth head is not scored
            real = transformer.pred_heads_cross_entropy
            monkeypatch.setattr(transformer, "pred_heads_cross_entropy",
                                lambda logits, labels, mask=None:
                                real(logits[:, :, :-1], labels, mask))
        return program_loss_and_grad(parts)
    return run


@pytest.mark.parametrize("name", ["summaries", "first_chunks", "mu", "a_head"])
def test_leaving_a_piece_out_fails_the_comparison(parts, want, name, monkeypatch):
    """The tolerances above are tight enough that each piece of the
    mathematics is held: without it the loss or some gradient is off by more
    than a hundred times the tolerance."""
    monkeypatch.setenv("DSTPU_ATTN", "xla")
    got, flat = leave_out(name)(parts, monkeypatch)
    off = max([abs(got - float(want[0])) / float(want[0]) / 1e-6]
              + [worst(flat[k], g) / 2e-5 for k, g in want[1].items()])
    assert off > 100, off


@pytest.mark.parametrize("where", ["summary_softmax", "attention_softmax", "merge",
                                   "norm_statistics", "residual", "logits"])
def test_bfloat16_where_float32_is_stated_fails(parts, want, where, monkeypatch):
    """Each float32 the configuration states, computed in bfloat16 instead
    while all else stays float32: the loss or some gradient leaves the
    tolerance of ``test_loss_and_gradient_match_the_reference``."""
    low = lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
    monkeypatch.setenv("DSTPU_ATTN", "pallas" if where == "merge" else "xla")
    if where == "summary_softmax":
        real = jax.nn.softmax
        monkeypatch.setattr(attention.jax.nn, "softmax", lambda x, axis=-1:
                            low(real(low(x), axis=axis)) if x.ndim == 4 else real(x, axis=axis))
    elif where == "attention_softmax":
        real = jax.nn.softmax
        monkeypatch.setattr(attention.jax.nn, "softmax", lambda x, axis=-1:
                            low(real(low(x), axis=axis)) if x.ndim == 5 else real(x, axis=axis))
    elif where == "merge":
        real = pallas_flash.merge_partials
        monkeypatch.setattr(pallas_flash, "merge_partials", lambda oa, la, ob, lb:
                            real(oa, low(la), ob, low(lb)))
    elif where == "norm_statistics":
        from deepspeed_tpu.nn import layers
        monkeypatch.setattr(layers.RMSNorm, "__call__", lambda self, p, x: (
            x * low(jax.lax.rsqrt(low(jnp.mean(low(x * x), axis=-1, keepdims=True)) + self.eps))
            * (1.0 + p["scale"])))
    elif where == "residual":
        monkeypatch.setattr(TransformerLM, "_add_fp32", staticmethod(
            lambda x, y: low(low(x) + low(y))))
    elif where == "logits":
        real = TransformerLM.head
        monkeypatch.setattr(TransformerLM, "head", lambda self, params, x, ln_f=None:
                            low(real(self, params, x, ln_f)))
    got, flat = program_loss_and_grad(parts)
    off = max([abs(got - float(want[0])) / float(want[0]) / 1e-6]
              + [worst(flat[k], g) / 2e-5 for k, g in want[1].items()])
    assert off > 1, (where, off)


def test_first_step_through_initialize(parts, want):
    """``initialize`` -> ``train_batch`` in float32: the step's loss and
    gradient norm are the reference's, every weight moves against the
    reference's gradient, and the counters say what ran."""
    import deepspeed_tpu
    _, adapter, cfg, w, ids = parts
    model = adapter.model(cfg, remat=True, dtype="float32")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=adapter.to_program(w), config={
            "train_micro_batch_size_per_gpu": 1,
            "zero_optimization": {"stage": 1},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3, "weight_decay": 0.0}}})
    assert engine.attn_totals["eva"] == {"window": 32, "chunk": 4, "summaries_a_row": None,
                                         "pred_heads": 8, "head_groups": None,
                                         "projected": "whole", "route": None,
                                         "dq_local": None, "dq_far": None, "layout": None}
    # the CPU mesh's eight devices take a row each: the two rows four times
    # over have the two rows' loss and gradient
    loss = float(engine.train_batch({"input_ids": np.tile(np.asarray(ids), (4, 1))}))
    assert loss == pytest.approx(float(want[0]), rel=1e-5)
    gnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in want[1].values())))
    assert float(engine.get_global_grad_norm()) == pytest.approx(gnorm, rel=1e-4)
    assert engine.attn_totals["eva"] == {"window": 32, "chunk": 4, "summaries_a_row": 32,
                                         "pred_heads": 8, "head_groups": 1,
                                         "projected": "whole", "route": "xla",
                                         "dq_local": None, "dq_far": None, "layout": None}
    # the summaries are values the backward may keep, and on the CPU it does
    assert {"eva_kbar", "eva_vbar"} <= set(engine.remat_totals["saved"])
    after = adapter.from_program(engine.state["opt"]["master"])
    for name, g in want[1].items():
        moved = np.sign(np.asarray(after[name]) - np.asarray(w[name]))
        sure = np.abs(np.asarray(g)) > 1e-3 * np.abs(np.asarray(g)).max()
        assert np.mean((moved == -np.sign(np.asarray(g)))[sure]) > 0.999, name
    # the counters ride in the profiler's record of a step
    from deepspeed_tpu.telemetry import setup_spans
    flat = setup_spans.flat_totals(attn=engine.attn_totals)
    assert flat["attn.eva.window"] == 32 and flat["attn.eva.route"] == "xla"


def test_the_published_stack():
    """The published configuration builds at its widths (shapes alone: no
    weight is made), 6.5 B parameters, and the cell's shape takes the kernel
    route on a TPU."""
    model = evabyte_model("evabyte-6.5b")
    c = model.config
    assert (c.num_layers, c.hidden_size, c.num_heads, c.head_dim, c.ffn_size) == (
        32, 4096, 32, 128, 11008)
    assert (c.eva_window, c.eva_chunk, c.pred_heads, c.vocab_size, c.max_seq_len) == (
        2048, 16, 8, 320, 32768)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.bfloat16))
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert total == c.num_parameters() + (2 * 32 + 1) * 4096     # the norms' gains
    assert 6.4e9 < total < 6.6e9
    assert shapes["lm_head"]["kernel"].shape == (4096, 8 * 320)
    assert shapes["blocks"]["eva_phi"]["value"].shape == (32, 32, 128)
    shape = (1, 32768, 32, 128)
    assert attention.choose_route(shape, shape, "tpu", "", eva=(2048, 16)) == "kernel"


def test_what_is_refused_by_name(parts):
    """The configuration keys this program does not compute, and the paths
    that do not carry the kind."""
    _, adapter, cfg, w, ids = parts
    config_fn = get_architecture("evabyte").config_fn
    for key, value in (("num_chunks", 64), ("num_key_value_heads", 2),
                       ("attention_class", "mha"), ("tie_word_embeddings", True)):
        with pytest.raises(NotImplementedError, match=key):
            config_fn({**cfg, key: value})
    kw = config_fn(cfg)
    for bad in (dict(eva_chunk=5), dict(document_separator=319),
                dict(attn_windows=16), dict(num_kv_heads=2)):
        with pytest.raises(ValueError, match="EVA attention"):
            TransformerLM(TransformerConfig(**{**kw, **bad}))
    with pytest.raises(ValueError, match="pred_heads"):
        TransformerLM(TransformerConfig(**{**kw, "tie_embeddings": True}))
    model = adapter.model(cfg, remat=False, dtype="float32")
    block = jax.tree.map(lambda a: a[0], adapter.to_program(w)["blocks"])
    with pytest.raises(NotImplementedError, match="attention='eva'"):
        model.block_apply(block, jnp.zeros((1, 32, 64)), jnp.arange(32)[None])
    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    with pytest.raises(NotImplementedError, match="attention='eva'"):
        PipelineModule(model.config, num_stages=2)
    from deepspeed_tpu.inference.v2.model import RaggedInferenceModel
    with pytest.raises(NotImplementedError, match="attention='eva'"):
        RaggedInferenceModel(model, 16, 4)
