"""A layer's token mixer as a value (``deepspeed_tpu/models/mixers.py``): over
the benchmark cells' tiny presets and the dense families, a model's
mixers' layers are exactly a block's leaves beside the norms and the MLP, their
counts add up to ``num_parameters`` and to what ``init`` makes, each kind's
record has the keys docs/OBSERVABILITY.md lists for it, and the two remat
policies that went with PR 59 are refused by name."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu import models
from deepspeed_tpu.models import mixers
from deepspeed_tpu.models.transformer import TransformerLM
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing

F32 = jnp.float32
# preset -> (its factory, the kinds of mixer its layers have)
PRESETS = {
    "gpt2-tiny": (models.gpt2_model, ["mha"]),
    "olmoe-tiny": (models.olmoe_model, ["mha"]),
    "instella-tiny": (models.instella_moe_model, ["latent"]),
    "afmoe-tiny": (models.afmoe_model, ["mha"]),
    "sdar-tiny": (models.sdar_moe_model, ["mha"]),
    "evabyte-tiny": (models.evabyte_model, ["eva"]),
    "keye-vl2-tiny": (models.keye_vl2_model, ["selected"]),
    "xing4-tiny": (models.xing4_model, ["latent"]),
    "phi4flash-tiny": (models.phi4flash_model, ["ssm", "attn", "gmu", "cross"]),
    "granite-hybrid-tiny": (models.granite_hybrid_model, ["ssd", "mha"]),
    "kimi-linear-tiny": (models.kimi_linear_model, ["kda", "latent"]),
    "llama2-tiny": (models.llama_model, ["mha"]),
    "bert-tiny": (models.bert_model, ["mha"]),
    "falcon-tiny": (models.falcon_model, ["mha"]),
    "gpt-neo-tiny": (models.gpt_neo_model, ["mha"]),
}
#: what every block has beside its mixer's layers: the norms, the streams'
#: coefficients, the MLP's (dense or the experts')
OTHERS = {"ln_1", "ln_2", "post_ln_1", "post_ln_2", "hc_attn", "hc_mlp", "moe",
          "fc_in", "fc_out", "gate_proj", "up_proj", "down_proj"}


@pytest.fixture(scope="module", params=sorted(PRESETS))
def built(request):
    factory, kinds = PRESETS[request.param]
    model = factory(request.param, dtype=F32)
    return model, kinds, jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))


def elements(tree, only=lambda path: True) -> int:
    return sum(leaf.size for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
               if only(jax.tree_util.keystr(path)))


def blocks_of(model, params):
    """(kind of mixer, one stacked block's tree) for every stack of the model."""
    if not model.config.mixed:
        return [(next(iter(model._mixers)), params["blocks"])]
    return [(kind[2], params["runs"][str(i)][str(j)])
            for i, (unit, _) in enumerate(model.run_plan) for j, kind in enumerate(unit)]


def test_a_models_mixers_layers_are_its_blocks_leaves(built):
    """One mixer a kind, picked by ``TransformerConfig.mixer_of``; a block's
    leaves are its mixer's `layers` and what every block has, and nothing
    else: a layer a mixer forgot to list is a leaf too many here."""
    model, kinds, params = built
    c = model.config
    assert list(model._mixers) == kinds
    assert [name for name, _ in model._mixer_kinds] == [c.mixer_of(l)[0]
                                                        for l in range(c.num_layers)]
    assert all(isinstance(model._mixers[k], mixers.KINDS[k]) for k in kinds)
    assert (model._mixer is None) == c.mixed
    for kind, block in blocks_of(model, params):
        own = set(model._mixers[kind].layers())
        assert own and not own & OTHERS
        assert set(block) - OTHERS == own
        # (an expert layer's ``moe``, a leading dense layer's MLP in a listed stack)
        other = ({"moe"} | set(model._dense_mlp_layers)) & set(block)
        assert set(block) - own == (set(model._block_layers) | other) - own


def test_the_counts_add_up(built):
    """A mixer's `parameters` is every element of its `layers` (a plain
    stack's projections' biases aside, which ``num_parameters`` never
    counted); over the layers, with the embeddings, the head and the MLPs,
    they are ``num_parameters``, which for a dense stack is what ``init``
    makes less the norms' gains and the biases."""
    model, kinds, params = built
    c = model.config
    for kind, block in blocks_of(model, params):
        mixer = model._mixers[kind]
        own = {name: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                                  block[name]) for name in mixer.layers()}
        biases = 0 if c.mixed else elements(own, lambda path: path.endswith("['bias']")
                                            and "norm" not in path)
        assert mixer.parameters() == elements(own) - biases, kind
    total = elements(params)
    if c.mixed:
        assert total == c.num_parameters()
    elif c.moe is None and not c.mlm_head:
        mixing = {name for m in model._mixers.values() for name in m.layers()}
        uncounted = elements(params, lambda path: (
            path.endswith("['bias']") or path.endswith("['scale']"))
            and not any(f"['{n}']['scale']" in path for n in mixing))
        assert total - c.num_parameters() == uncounted


# the keys docs/OBSERVABILITY.md lists for each kind's entry of ``attn_totals``
# (``diffusion``: ``diffusion_totals``)
RECORD_KEYS = {
    "eva": {"window", "chunk", "summaries_a_row", "pred_heads", "head_groups",
            "projected", "route", "dq_local", "dq_far", "layout"},
    "dsa": {"topk", "indexer_heads", "indexer_head_dim", "route", "select",
            "select_tiles", "select_rows", "dq", "layout", "kl", "kl_tiles", "operand",
            "operand_bytes"},
    "mla": {"qk_dim", "v_dim", "q_rank", "kv_rank", "route", "dq", "layout"},
    "ssm": {"kind", "heads", "head_dim", "groups", "layers", "memory_units", "d_inner",
            "d_state", "conv", "dt_rank", "route", "chunk", "tile"},
    "diff": {"qk_dim", "v_dim", "launches_a_layer", "shared_readers"},
    "kda": {"heads", "key_dim", "value_dim", "conv", "gate_rank", "layers", "route", "chunk",
            "tile"},
    "diffusion": {"block_length", "rows_per_token", "route", "dq", "layout"},
}
RECORDS = {"gpt2-tiny": [], "instella-tiny": [], "evabyte-tiny": ["eva"],
           "keye-vl2-tiny": ["dsa"], "xing4-tiny": ["mla"], "sdar-tiny": ["diffusion"],
           "phi4flash-tiny": ["ssm", "diff"], "granite-hybrid-tiny": ["ssm"],
           "kimi-linear-tiny": ["kda", "mla"]}


@pytest.mark.parametrize("preset", sorted(RECORDS))
def test_each_kinds_record_has_the_documented_keys(preset):
    """Declared from the configuration (every traced key None) and filled
    from a shape, by the same function: the same keys both times."""
    model = PRESETS[preset][0](preset, dtype=F32)
    declared, traced = {}, {}
    for mixer in model._mixers.values():
        declared.update(mixer.record())
        traced.update(mixer.record(2, 128))
    assert sorted(declared) == sorted(traced) == sorted(RECORDS[preset])
    for key in RECORDS[preset]:
        assert set(declared[key]) == set(traced[key]) == RECORD_KEYS[key]
        assert declared[key]["route"] is None if "route" in declared[key] else True
        assert all(traced[key][k] is not None for k in ("route",) if k in traced[key])
    attn, diffusion = model.attention_records(2, 128)
    assert {k for k in RECORD_KEYS if k in attn} | ({"diffusion"} if diffusion else set()) \
        == set(RECORDS[preset])


@pytest.mark.parametrize("policy", ["alternating", "attention_only"])
def test_a_policy_that_went_is_refused_by_name(policy):
    """By ``resolve_policy``, with the policies there are, when the model is
    built and not at its first trace; a policy that stays is built."""
    listed = ".*".join(["matmul_and_kernel_outputs", "full", "nothing_saveable",
                        "dots_saveable"])
    with pytest.raises(ValueError, match=f"{policy!r} is none of .*{listed}"):
        checkpointing.resolve_policy(policy)
    with pytest.raises(ValueError, match=f"{policy!r} is none of"):
        models.gpt2_model("gpt2-tiny", remat_policy=policy)
    assert policy not in checkpointing.POLICIES
    TransformerLM(models.gpt2_config("gpt2-tiny", remat_policy="dots_saveable"))
    assert models.gpt2_model("gpt2-tiny", remat=False, remat_policy=policy).config.remat is False
