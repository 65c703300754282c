"""BENCHMARK.json and the files it names, held to the contract's letter."""

import importlib.util
import json
import os
import re

import pytest

from tests.benchmark.helpers import EXPERT_COUNT, REPO, data_manifests

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


#: the real manifest and every one under the tests' data, found and not listed
MANIFESTS = {"real": os.path.join(REPO, "BENCHMARK.json"), **data_manifests()}

# "No width is ever cut" (the model-configs guide, section 4). No check
# compares a configuration file with its source, so this is the only guard,
# and it fails closed: what ``reduced`` may name is a function of the key AND
# of the file.
#
# - Depth (``DEPTH``) and the dropouts, in any file.
# - The number of routed experts (``EXPERT_COUNT``, a closed set of names) and
#   ``vocab_size``, ONLY in a file that states the deployment this chip is a
#   share of, in one block:
#
#       "share": {"chips_sharing_a_layer": 8,
#                 "published": {"n_routed_experts": 64, "vocab_size": 128896,
#                               "num_hidden_layers": 27},
#                 "held": "experts 0-7 of every expert layer, vocabulary rows 0-16111 (rank 0 of 8)"}
#
#   ``published`` names the key, the file's value is the published one
#   divided by ``chips_sharing_a_layer`` (rounded up), and the guide's floors
#   hold: at least 8 experts held, at least an eighth of the vocabulary and at
#   least four layers after the leading dense ones (``share_faults`` says
#   which of these a file breaks). ``published`` may name an expert count,
#   the vocabulary and the depth and nothing else. An expert count cut with
#   no deployment stated is what the guide forbids, so without a sound block
#   it is refused.
# - The two floors are two (the guide, section 4: "at least 8 routed experts
#   in each layer that has them, and at least an eighth of the vocabulary"),
#   so the divisions are two (PR 67): the experts are divided over every chip
#   that shares a layer, the vocabulary's rows over eight of them at the most
#   (``ROWS_OVER``: over ``min(chips, 8)``). Up to eight chips that is the one
#   division it was. Beyond eight, as in the guide's own "with 256 experts
#   over 32 chips, 8 experts live here", the chips come in whole groups of
#   eight, each slice of the rows lies on ``chips / 8`` of them, a copy each
#   (vocabulary parallelism inside a subgroup of an expert-parallel group),
#   and ``published`` names an expert count, so that the number of chips
#   still reproduces a number of the file. ``held`` says the layout in words:
#
#       "share": {"chips_sharing_a_layer": 16,
#                 "published": {"num_experts": 256, "vocab_size": 163840,
#                               "num_hidden_layers": 27},
#                 "held": "experts 0-15 of every expert layer, vocabulary rows 0-20479
#                          (rank 0 of 16; the rows over 8, two chips a slice)"}
#
#   No key says over how many chips the rows lie: no file wants them over
#   fewer than the floor allows, and the first that does argues the key in a
#   ``benchmark`` PR. Whom the rule is for: 44 of the catalog's 88 rows count
#   more than 128 routed experts (scanned for PR 67 under the five names
#   below: 160 to 896, 23 rows of 256), and under one division each could
#   slice its vocabulary only while holding an eighth of its experts, 20 to
#   112 a layer.
# - The depth floor holds in EVERY file that states a share, whatever its
#   family calls its leading dense layers: ``first_k_dense_replace`` or
#   ``num_dense_layers`` (``DENSE_FIRST``) where the file has either, none
#   where it has neither (every layer is then of the kinds that repeat). A
#   share file with both of those keys, with more than one depth key or with
#   none cannot say how many layers follow, and that is a fault. One reading
#   is left as it was: a ``cpu_test_preset`` that names no leading dense
#   layers may be shallower. Two toys of 2 layers stand in the tests' data, a
#   toy has nothing published, and the real manifest may name no preset
#   (``test_real_manifest_names_are_the_issues``).
# - Nothing else, with or without a block: a hidden, intermediate, latent,
#   state or projection size, a head size or count, a window, the positions,
#   the experts per token, a shared-expert count, a zero-compute expert count
#   or a group count is refused without being listed; a cut under another
#   name has to be argued in a ``benchmark`` PR that adds it here.
#
# Where ``EXPERT_COUNT`` is from: the top-level keys of the 88 rows of the
# guide's catalog (``architectures.jsonl``), scanned for PR 61. The rows count
# their routed experts under five names: ``n_routed_experts`` (45 rows),
# ``num_experts`` (23), ``num_local_experts`` (6, one of them beside
# ``num_experts``), ``moe_num_experts`` (3) and ``moe_num_primary_experts``
# (1); the last two were missing until PR 61, so four rows could not state
# their share. One more row counts 4 "dynamic" experts of the dense width
# under a name of its own and needs none here: fewer than 8 cannot be
# shared. Every row has one of the three depth names and ``vocab_size``. The
# set stays closed: a name that merely holds "expert" is a per-token count
# (``moe_num_active_primary_experts``, ``num_experts_per_token``,
# ``experts_top_k``), a shared, null or zero-compute count, a group count or
# a width (``expert_ffn_hidden_size``, ``moe_ffn_hidden_size``) as often as
# it is a routed count, so no pattern is matched.
#
# What the share asks of the PROGRAM (an expert layer told which experts it
# holds, a sliced embedding and head) and of the traffic (ids drawn from the
# slice: ``jobs/train.py`` draws them from the file's ``vocab_size``) is the
# ``model_config`` PR's that adds such a file (PERF.md, section 3).
DEPTH = {"num_hidden_layers", "n_layer", "num_layers"}
DENSE_FIRST = {"first_k_dense_replace", "num_dense_layers"}
VOCABULARY = "vocab_size"
#: of however many chips share a layer, the vocabulary's rows lie over no more
#: than this many: its floor, an eighth
ROWS_OVER = 8
SHARED = EXPERT_COUNT | {VOCABULARY}


def depth_fault(cfg):
    """Why a share file does not keep four layers after its leading dense
    ones, or None (the rule is the comment above)."""
    depth = [cfg[k] for k in DEPTH if k in cfg]
    dense = [cfg[k] for k in DENSE_FIRST if k in cfg]
    if len(depth) != 1 or len(dense) > 1:
        return "one depth key and at most one key for the leading dense layers"
    if type(depth[0]) is not int or any(type(d) is not int or d < 0 for d in dense):
        return "the depth and the leading dense layers are whole numbers"
    if depth[0] - sum(dense) < 4 and (dense or not cfg.get("cpu_test_preset")):
        return "fewer than four layers after the leading dense ones"
    return None


def share_faults(cfg):
    """Why a configuration file's ``share`` block does not carry a cut of
    the experts or the vocabulary: a list of faults, empty for a sound
    block (the rule is the comment above)."""
    share = cfg.get("share")
    if not isinstance(share, dict):
        return ["the file states no share"]
    if set(share) != {"chips_sharing_a_layer", "published", "held"}:
        return [f"share has the keys {sorted(share)}"]
    chips, published, held = share["chips_sharing_a_layer"], share["published"], share["held"]
    if type(chips) is not int or chips < 2 or not isinstance(published, dict):
        return ["chips_sharing_a_layer is a whole number from 2, published a table"]
    if chips > ROWS_OVER and chips % ROWS_OVER:
        return [f"{chips} chips: more than {ROWS_OVER} share a layer in whole groups of "
                f"{ROWS_OVER}"]
    if chips > ROWS_OVER and not EXPERT_COUNT & set(published):
        return [f"{chips} chips: more than {ROWS_OVER} divide experts alone, and published "
                "names no expert count"]
    faults = [] if isinstance(held, str) and held.strip() else ["held says nothing"]
    for key, full in published.items():
        here = cfg.get(key)
        over = min(chips, ROWS_OVER) if key == VOCABULARY else chips
        if key not in SHARED | DEPTH:
            faults.append(f"{key}: published may name an expert count, the vocabulary and depth")
        elif type(full) is not int or type(here) is not int or not 0 < here <= full:
            faults.append(f"{key}: the file and published give whole numbers, 0 < file <= published")
        elif here != full and key not in cfg.get("reduced", []):
            faults.append(f"{key}: differs from the published value and is not in reduced")
        elif key == VOCABULARY and ROWS_OVER * here < full:   # said first: it is the cause
            faults.append(f"{key}: less than an eighth of the vocabulary")
        elif key in SHARED and here != -(-full // over):
            faults.append(f"{key}: {here} is not {full} over {over} chips, rounded up")
        elif key in EXPERT_COUNT and here < 8:
            faults.append(f"{key}: fewer than 8 experts held")
    fault = depth_fault(cfg)
    return faults + ([fault] if fault else [])


def may_be_reduced(key, cfg):
    """Whether the configuration file ``cfg`` may list ``key`` in ``reduced``."""
    if key in DEPTH or key.endswith("_pdrop") or "dropout" in key:
        return True
    return (key in SHARED and not share_faults(cfg)
            and key in cfg["share"]["published"])


@pytest.fixture(params=sorted(MANIFESTS))
def manifest(request):
    path = MANIFESTS[request.param]
    return os.path.dirname(path), load(path)


def metrics_of(m):
    return m["end_to_end"] + m["per_layer"]


def cells_reporting(m, metric):
    return metric.get("workloads") or [w["name"] for w in m["workloads"]]


def test_keys_are_exactly_the_contracts(manifest):
    _, m = manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}


def test_names_units_and_lines(manifest):
    root, m = manifest
    names = [x["name"] for x in metrics_of(m)]
    assert len(names) == len(set(names))
    for kind in ("workloads", "configs"):
        ns = [x["name"] for x in m[kind]]
        assert len(ns) == len(set(ns))
    for x in metrics_of(m):
        assert NAME.match(x["name"]), x["name"]
        assert UNIT.match(x["unit"]), x["unit"]
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in m["configs"]:
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        cfg = load(os.path.join(root, c["file"]))
        assert not [k for k in c["reduced"] if not may_be_reduced(k, cfg)], share_faults(cfg)
        # a share that is stated is sound, whatever ``reduced`` names
        assert "share" not in cfg or not share_faults(cfg), share_faults(cfg)


#: the cut ISSUE 31 sized (a ``deepseek_v3``-style file: one leading dense
#: layer, 64 routed experts, vocabulary 128,896; eight chips share a layer)
SHARE_CUT = {"n_routed_experts": 8, "vocab_size": 16112, "num_hidden_layers": 6,
             "first_k_dense_replace": 1,
             "reduced": ["n_routed_experts", "num_hidden_layers", "vocab_size"],
             "share": {"chips_sharing_a_layer": 8,
                       "published": {"n_routed_experts": 64, "vocab_size": 128896,
                                     "num_hidden_layers": 27},
                       "held": "experts 0-7 of every expert layer, vocabulary rows "
                               "0-16111 (rank 0 of 8)"}}
#: the cut ISSUE 61 sized, as far as the guard reads the file: 64 routed
#: experts counted under ``moe_num_primary_experts``, every layer an expert
#: layer (no key for leading dense ones), a period of 4 layers of 52,
#: vocabulary 151,936; the four chips of one host share a layer
PRIMARY_CUT = {"moe_num_primary_experts": 16, "moe_num_active_primary_experts": 6,
               "vocab_size": 37984, "num_hidden_layers": 4,
               "reduced": ["moe_num_primary_experts", "num_hidden_layers", "vocab_size"],
               "share": {"chips_sharing_a_layer": 4,
                         "published": {"moe_num_primary_experts": 64, "vocab_size": 151936,
                                       "num_hidden_layers": 52},
                         "held": "experts 0-15 of every layer, vocabulary rows 0-37983 "
                                 "(rank 0 of 4)"}}
#: the cut ISSUE 67 sized (a file of 256 routed experts under ``num_experts``,
#: 8 a token, one leading dense layer, a period of 4 layers of 27, vocabulary
#: 163,840): sixteen chips share each layer's experts and the vocabulary's
#: rows lie over eight of them, so each floor is met by its own division
OVER_CUT = {"num_experts": 16, "num_experts_per_token": 8, "first_k_dense_replace": 1,
            "num_hidden_layers": 5, "vocab_size": 20480,
            "reduced": ["num_experts", "num_hidden_layers", "vocab_size"],
            "share": {"chips_sharing_a_layer": 16,
                      "published": {"num_experts": 256, "vocab_size": 163840,
                                    "num_hidden_layers": 27},
                      "held": "experts 0-15 of every expert layer, vocabulary rows 0-20479 "
                              "(rank 0 of 16; the rows over 8, two chips a slice)"}}
#: the same deployment as the guide's own example has it, "256 experts over
#: 32 chips, 8 experts live here": both floors met exactly
FLOORS_CUT = dict(OVER_CUT, num_experts=8, share=dict(
    OVER_CUT["share"], chips_sharing_a_layer=32,
    held="experts 0-7 of every expert layer, vocabulary rows 0-20479 "
         "(rank 0 of 32; the rows over 8, four chips a slice)"))
#: the sized cuts, by how many chips share a layer
CUTS = {"eight-share-a-layer": SHARE_CUT, "four-share-a-layer": PRIMARY_CUT,
        "sixteen-share-a-layer": OVER_CUT, "thirty-two-share-a-layer": FLOORS_CUT}
#: the third cut's name, which most of its spoiled variants start from
OVER = "sixteen-share-a-layer"


def a_file(key, share):
    """The least file that lists ``key`` in ``reduced``; with ``share``, one
    that states an eighth of a deployment and names ``key`` as published
    (four layers deep, the least a share file may be); with ``share`` "over",
    sixteen chips share a layer's experts (under ``key`` where it counts
    them) and the vocabulary's rows lie over eight."""
    held = 16112 if key == VOCABULARY else 8
    cfg = {key: held, "reduced": [key]}
    if share:
        cfg["share"] = {"chips_sharing_a_layer": 8, "published": {key: 8 * held},
                        "held": "rank 0 of 8"}
        if key not in DEPTH:
            cfg["num_hidden_layers"] = 4
    if share == "over":
        count = key if key in EXPERT_COUNT else "n_routed_experts"
        cfg["share"].update(chips_sharing_a_layer=16, held="rank 0 of 16, the rows over 8")
        cfg["share"]["published"].update({count: 128, VOCABULARY: 8 * 16112})
        cfg.update({count: 8, VOCABULARY: 16112, "reduced": sorted({count, VOCABULARY})})
    return cfg


#: what no block buys (each case runs without a share and with one that names
#: the key as published): the per-token counts, the shared, zero-compute and
#: group counts, and the widths and the window under the names of the rows
#: that PR 61 made room for
NEVER = ("moe_num_active_primary_experts", "num_experts_per_token", "experts_top_k",
         "num_shared_experts", "zero_expert_num", "num_expert_groups", "moe_ffn_hidden_size",
         "sliding_window_size", "expert_ffn_hidden_size")


@pytest.mark.parametrize("key,share,allowed", [
    ("hidden_size", False, False), ("n_embd", False, False), ("intermediate_size", False, False),
    ("moe_intermediate_size", False, False), ("head_dim", False, False),
    ("qk_rope_head_dim", False, False), ("kv_lora_rank", False, False),
    ("num_experts_per_tok", False, False), ("sliding_window", False, False),
    ("num_hidden_layers", False, True), ("n_layer", False, True),
    ("attn_pdrop", False, True), ("resid_pdrop", False, True),
    # what a list of widths would have let through
    ("ffn_hidden_size", False, False), ("mlp_hidden_size", False, False), ("d_ff", False, False),
    ("d_kv", False, False), ("n_head", False, False), ("num_attention_heads", False, False),
    ("num_key_value_heads", False, False), ("max_position_embeddings", False, False),
    ("num_layers", False, True), ("attention_dropout", False, True),
    # a chip's share of the experts and of the vocabulary: refused where the
    # file states no deployment, allowed where it states a sound one
    ("num_experts", False, False), ("num_experts", True, True),
    ("num_local_experts", False, False), ("num_local_experts", True, True),
    ("n_routed_experts", False, False), ("n_routed_experts", True, True),
    ("moe_num_primary_experts", False, False), ("moe_num_primary_experts", True, True),
    ("moe_num_experts", False, False), ("moe_num_experts", True, True),
    ("vocab_size", False, False), ("vocab_size", True, True),
    # no block buys a width: naming one as published spoils the block
    ("hidden_size", True, False), ("moe_intermediate_size", True, False),
    ("num_experts_per_tok", True, False), ("head_dim", True, False),
    ("kv_lora_rank", True, False), ("num_attention_heads", True, False),
    ("sliding_window", True, False), ("max_position_embeddings", True, False),
    ("n_shared_experts", True, False),
    *[(key, share, False) for key in NEVER for share in (False, True)],
    # depth and dropouts as ever, beside a block too
    ("num_hidden_layers", True, True), ("attn_pdrop", True, True),
    # sixteen chips share a layer, the vocabulary's rows over eight of them:
    # the block buys the expert count, under each of its names, and the slice
    *[(key, "over", True) for key in sorted(EXPERT_COUNT)], ("vocab_size", "over", True)])
def test_reduced_may_name_depth_and_never_a_width(key, share, allowed):
    assert may_be_reduced(key, a_file(key, share)) is allowed


def spoiled(cut="eight-share-a-layer", **changes):
    """A copy of one of ``CUTS`` with top-level keys replaced; ``chips`` and
    ``published`` reach into the block (None takes a key out of it)."""
    cfg = json.loads(json.dumps(CUTS[cut]))
    cfg["share"]["chips_sharing_a_layer"] = changes.pop(
        "chips", cfg["share"]["chips_sharing_a_layer"])
    cfg["share"]["published"].update(changes.pop("published", {}))
    cfg["share"]["published"] = {k: v for k, v in cfg["share"]["published"].items()
                                 if v is not None}
    cfg.update(changes)
    return cfg


#: the spoiled variants of the cuts, each with the fault that refuses it
SPOILED = {
    "a-sixteenth-of-the-vocabulary": (
        dict(chips=16, vocab_size=8056, published={"n_routed_experts": 128}), "an eighth"),
    "four-experts-held": (
        dict(chips=16, n_routed_experts=4, vocab_size=128896, published={"vocab_size": None},
             reduced=["n_routed_experts", "num_hidden_layers"]), "fewer than 8 experts"),
    "three-expert-layers": (dict(num_hidden_layers=4), "fewer than four layers"),
    "a-division-that-does-not-give-it": (dict(published={"n_routed_experts": 72}),
                                         "8 is not 72 over 8 chips"),
    "a-width-smuggled-into-published": (
        dict(moe_intermediate_size=704, published={"moe_intermediate_size": 1408},
             reduced=SHARE_CUT["reduced"] + ["moe_intermediate_size"]),
        "moe_intermediate_size: published may name"),
    # the second cut's: what eight chips would hold where four are stated,
    # sixteen chips' four experts, one layer short of the floor where no key
    # names leading dense layers, the per-token count named as published
    "an-eighth-held-of-four-chips": (
        dict(cut="four-share-a-layer", moe_num_primary_experts=8), "8 is not 64 over 4 chips"),
    "four-primary-experts-held": (
        dict(cut="four-share-a-layer", chips=16, moe_num_primary_experts=4, vocab_size=151936,
             published={"vocab_size": None},
             reduced=["moe_num_primary_experts", "num_hidden_layers"]), "fewer than 8 experts"),
    "three-layers-and-no-dense-key": (
        dict(cut="four-share-a-layer", num_hidden_layers=3), "fewer than four layers"),
    "the-experts-per-token-smuggled-into-published": (
        dict(cut="four-share-a-layer", moe_num_active_primary_experts=3,
             published={"moe_num_active_primary_experts": 6},
             reduced=PRIMARY_CUT["reduced"] + ["moe_num_active_primary_experts"]),
        "moe_num_active_primary_experts: published may name"),
    # the third and fourth cuts' (sixteen and thirty-two chips, the
    # vocabulary's rows over eight of them): the rows divided by the chips as
    # the experts are, a slice that is not an eighth, the experts divided by
    # eight as the rows are, the experts' floor, chips that are no whole
    # groups of eight, and chips that reproduce no number of the file
    "a-sixteenth-of-the-rows-over-sixteen": (dict(cut=OVER, vocab_size=10240), "an eighth"),
    "a-thirty-second-of-the-rows-over-thirty-two": (
        dict(cut="thirty-two-share-a-layer", vocab_size=5120), "an eighth"),
    "a-quarter-of-the-rows-over-sixteen": (
        dict(cut=OVER, vocab_size=40960), "40960 is not 163840 over 8 chips"),
    "an-eighth-of-the-experts-over-sixteen": (
        dict(cut=OVER, num_experts=32), "32 is not 256 over 16 chips"),
    "a-sixteenth-of-the-experts-over-thirty-two": (
        dict(cut="thirty-two-share-a-layer", num_experts=16), "16 is not 256 over 32 chips"),
    "four-of-the-experts-over-sixty-four": (
        dict(cut=OVER, chips=64, num_experts=4), "fewer than 8 experts"),
    "twelve-share-a-layer": (
        dict(cut=OVER, chips=12, num_experts=22), "12 chips: more than 8 share a layer in whole"),
    "sixteen-share-a-layer-and-no-experts-published": (
        dict(cut=OVER, num_experts=256, published={"num_experts": None},
             reduced=["num_hidden_layers", "vocab_size"]), "16 chips: more than 8 divide experts"),
}


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_the_sized_cut_is_a_sound_share(cut):
    cfg = CUTS[cut]
    assert share_faults(cfg) == []
    assert all(may_be_reduced(k, cfg) for k in cfg["reduced"])
    # (the second cut's file has the per-token count, at its published 6)
    assert not [k for k in NEVER if may_be_reduced(k, dict(cfg, reduced=cfg["reduced"] + [k]))]


def deep(layers, preset=False, **dense):
    """``PRIMARY_CUT`` at another depth, with keys for its leading dense
    layers; ``preset`` makes it a CPU preset."""
    return spoiled(cut="four-share-a-layer", num_hidden_layers=layers, cpu_test_preset=preset,
                   **dense)


@pytest.mark.parametrize("cfg,fault", [
    # no key for leading dense layers: four layers, the new reading
    (deep(4), None), (deep(3), "fewer than four layers"), (deep(1), "fewer than four layers"),
    # an ``afmoe``-shaped file: two leading dense layers under its own key
    (deep(6, num_dense_layers=2), None),
    (deep(5, num_dense_layers=2), "fewer than four layers"),
    # the reading that was there
    (deep(5, first_k_dense_replace=1), None),
    (deep(4, first_k_dense_replace=1), "fewer than four layers"),
    # a file that cannot say how many layers follow
    (deep(8, first_k_dense_replace=1, num_dense_layers=1), "at most one key"),
    (deep(8, n_layer=8), "one depth key"),
    ({k: v for k, v in deep(8).items() if k != "num_hidden_layers"}, "one depth key"),
    (deep(8, num_dense_layers="2"), "whole numbers"),
    (deep(8, first_k_dense_replace=-4), "whole numbers"),
    # a CPU preset: a toy of two layers stands where it names no leading
    # dense layers, and is held as any file where it names them
    (deep(2, preset=True), None),
    (deep(5, preset=True, num_dense_layers=2), "fewer than four layers"),
    (deep(4, preset=True, first_k_dense_replace=1), "fewer than four layers"),
    (deep(2, preset=True, n_layer=2), "one depth key")])
def test_a_share_file_keeps_four_layers_after_its_leading_dense_ones(cfg, fault):
    faults = share_faults(cfg)
    if fault is None:
        assert faults == [] and all(may_be_reduced(k, cfg) for k in cfg["reduced"])
    else:
        assert any(fault in f for f in faults), faults
        assert not [k for k in SHARED if may_be_reduced(k, cfg)]
        assert may_be_reduced("num_hidden_layers", cfg)


@pytest.mark.parametrize("name", sorted(SPOILED))
def test_a_spoiled_share_is_refused_for_its_own_fault(name):
    """One fault each, and that fault alone: the floor, the division or the
    smuggled width is what ``share_faults`` names, and with it every key the
    share carried is refused; depth stays allowed."""
    changes, fault = SPOILED[name]
    cfg = spoiled(**changes)
    faults = share_faults(cfg)
    assert len(faults) == 1 and fault in faults[0], faults
    assert not [k for k in SHARED if may_be_reduced(k, cfg)]
    assert may_be_reduced("num_hidden_layers", cfg)


@pytest.mark.parametrize("share,fault", [
    (None, "no share"), ("eight chips", "no share"),
    ({"chips_sharing_a_layer": 8, "published": {}}, "has the keys"),
    ({"chips_sharing_a_layer": 8, "published": {}, "held": "x", "why": "y"}, "has the keys"),
    ({"chips_sharing_a_layer": 1, "published": {"n_routed_experts": 8}, "held": "all"},
     "whole number from 2"),
    ({"chips_sharing_a_layer": 8.0, "published": {"n_routed_experts": 64}, "held": "x"},
     "whole number from 2"),
    ({"chips_sharing_a_layer": 8, "published": {"n_routed_experts": 64}, "held": " "},
     "held says nothing"),
    ({"chips_sharing_a_layer": 8, "published": {"n_routed_experts": 64.0}, "held": "x"},
     "whole numbers"),
    ({"chips_sharing_a_layer": 8, "published": {"num_local_experts": 64}, "held": "x"},
     "whole numbers"),                     # published names a key the file lacks
    ({"chips_sharing_a_layer": 8, "published": {"num_hidden_layers": 5}, "held": "x"},
     "whole numbers"),                     # deeper than published
    # no key says over how many chips the rows lie: a fourth key is refused
    ({"chips_sharing_a_layer": 16, "vocabulary_over": 8,
      "published": {"n_routed_experts": 128, "vocab_size": 128896}, "held": "x"}, "has the keys"),
    # beyond eight chips: whole groups of eight, and an expert count to divide
    ({"chips_sharing_a_layer": 20, "published": {"n_routed_experts": 160}, "held": "x"},
     "20 chips: more than 8 share a layer in whole groups of 8"),
    ({"chips_sharing_a_layer": 4096, "published": {"vocab_size": 128896}, "held": "x"},
     "4096 chips: more than 8 divide experts alone"),
    ({"chips_sharing_a_layer": 16, "published": {"num_hidden_layers": 27}, "held": "x"},
     "16 chips: more than 8 divide experts alone"),
])
def test_a_share_block_is_held_to_its_form(share, fault):
    cfg = dict(SHARE_CUT, share=share)
    if share is None:
        del cfg["share"]
    assert any(fault in f for f in share_faults(cfg)), share_faults(cfg)
    assert not may_be_reduced("n_routed_experts", cfg) and not may_be_reduced("vocab_size", cfg)


def test_what_differs_from_published_is_listed_in_reduced():
    cfg = dict(SHARE_CUT, reduced=["n_routed_experts", "num_hidden_layers"])
    assert any("vocab_size" in f and "not in reduced" in f for f in share_faults(cfg))
    # held whole, the vocabulary needs no listing and no division
    whole = spoiled(vocab_size=128896, published={"vocab_size": None},
                    reduced=["n_routed_experts", "num_hidden_layers"])
    assert share_faults(whole) == [] and not may_be_reduced("vocab_size", whole)


def test_bounds_and_setup(manifest):
    _, m = manifest
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_what_it_must(manifest):
    _, m = manifest
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for w in m["workloads"]:
        mine = [e for e in m["end_to_end"] if w["name"] in cells_reporting(m, e)]
        assert len(mine) >= 2, w["name"]
        assert any(w["name"] in cells_reporting(m, p) for p in m["per_layer"])
    for p in m["per_layer"]:
        assert p["moves"] in e2e, p
        moved = set(cells_reporting(m, e2e[p["moves"]]))
        assert set(cells_reporting(m, p)) <= moved, p["name"]
    layers = {}
    for p in m["per_layer"]:
        layers.setdefault(p["layer"].lower(), set()).add(p["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_at_most_one_four_chip_cell(manifest):
    _, m = manifest
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)


def test_every_named_file_is_there(manifest):
    root, m = manifest
    paths = [os.path.join(root, p) for p in m["paths"]]
    assert all(os.path.isdir(p) for p in paths)
    search = paths + [os.path.join(REPO, "benchmark")]

    def find(kind, filename):
        hits = [os.path.join(s, kind, filename) for s in search
                if os.path.exists(os.path.join(s, kind, filename))]
        assert hits, (kind, filename)
        return hits[0]

    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    for c in m["configs"]:
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        cfg = load(os.path.join(root, c["file"]))
        assert cfg["source"] == c["source"] or cfg.get("cpu_test_preset")
        assert cfg["reduced"] == c["reduced"]
        assert {"reference", "adapter", "engine", "limits", "assumed", "deployment",
                "stated_precision"} <= set(cfg)
        find("reference", cfg["reference"] + ".py")
        find("adapters", cfg["adapter"] + ".py")
    for w in m["workloads"]:
        assert w["config"] in {c["name"] for c in m["configs"]}
        mix = load(find("traffic", w["traffic"] + ".json"))
        find("jobs", mix["job"] + ".py")
    for p in m["per_layer"]:
        path = find("layer_metrics", p["name"] + ".py")
        spec = importlib.util.spec_from_file_location("reader", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(mod.read) and mod.__doc__


#: the manifest contract, as a later PR's manifest meets it
CONTRACT = (test_keys_are_exactly_the_contracts, test_names_units_and_lines,
            test_bounds_and_setup, test_every_cell_reports_what_it_must,
            test_at_most_one_four_chip_cell, test_every_named_file_is_there)


def hold_to_the_contract(path):
    for check in CONTRACT:
        check((os.path.dirname(path), load(path)))


def write_share_cell(root, cfg):
    """A manifest with one cell on a configuration file that holds ``cfg``'s
    keys and no other of a model's, as files only: stub reference and adapter
    of their own, the real benchmark's traffic file, metrics and readers."""
    source = "https://huggingface.co/some-org/some-moe/blob/main/config.json"
    chips = cfg.get("share", {}).get("chips_sharing_a_layer", "some")
    cfg = dict(cfg, name="moe-share", source=source,
               reference="moe_share_stub", adapter="moe_share_stub", engine={}, limits={},
               assumed={}, deployment=f"one of {chips} chips that share each layer",
               stated_precision="bfloat16")
    bench = os.path.join(root, "benchmark")
    for kind, name, text in (
            ("configs", "moe-share.json", json.dumps(cfg)),
            ("reference", "moe_share_stub.py", '"""A stub: the contract looks for the file."""\n'),
            ("adapters", "moe_share_stub.py", '"""A stub: the contract looks for the file."""\n')):
        os.makedirs(os.path.join(bench, kind), exist_ok=True)
        with open(os.path.join(bench, kind, name), "w") as f:
            f.write(text)
    m = load(MANIFESTS["real"])
    m["paths"] = ["benchmark"]
    m["configs"] = [{"name": "moe-share", "source": source, "reduced": cfg["reduced"],
                     "file": "benchmark/configs/moe-share.json",
                     "why": "routed experts of which a chip holds its share"}]
    m["workloads"] = [{"name": "moe-share.train.seq4k", "config": "moe-share",
                       "traffic": "train.seq4k", "chips": 1,
                       "why": f"one chip's share of a layer divided over {chips}"}]
    for metric in metrics_of(m):
        if "workloads" in metric:
            metric["workloads"] = ["moe-share.train.seq4k"]
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(m, f)
    return path


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_a_chips_share_is_files_only(tmp_path, cut):
    """What the next ``model_config`` PR brings for a model no chip holds
    whole: a configuration file with the keys of a sized cut and a manifest
    that lists them in ``reduced``. ISSUE 31's (8 of 64 routed experts,
    16,112 of 128,896 vocabulary rows, 6 of 27 layers, one of them dense,
    eight chips) and ISSUE 61's (16 of 64 under ``moe_num_primary_experts``,
    37,984 of 151,936 rows, 4 of 52 layers and none dense, four chips) and
    ISSUE 67's (16 of 256 under ``num_experts`` over sixteen chips or 8 over
    thirty-two, 20,480 of 163,840 rows over eight of either, 5 of 27 layers,
    one of them dense): the contract passes on each unedited, and the file is
    written as the cut has it, with no key put in for it."""
    path = write_share_cell(str(tmp_path), CUTS[cut])
    hold_to_the_contract(path)
    written = load(os.path.join(str(tmp_path), "benchmark/configs/moe-share.json"))
    assert {k: written[k] for k in CUTS[cut]} == CUTS[cut]
    assert DENSE_FIRST & set(written) == DENSE_FIRST & set(CUTS[cut])


@pytest.mark.parametrize(
    "name", sorted(SPOILED) + [f"no-share-stated-{cut}" for cut in sorted(CUTS)])
def test_a_spoiled_share_fails_the_contract(tmp_path, name):
    """The same files with the share spoiled, or with the experts and the
    vocabulary cut and no deployment stated: the contract refuses them."""
    if name in SPOILED:
        changes, fault = SPOILED[name]
        cfg = spoiled(**changes)
    else:
        cut = CUTS[name[len("no-share-stated-"):]]
        cfg, fault = {k: v for k, v in cut.items() if k != "share"}, "states no share"
    with pytest.raises(AssertionError, match=fault):
        hold_to_the_contract(write_share_cell(str(tmp_path), cfg))


def test_real_manifest_names_are_the_issues():
    m = load(MANIFESTS["real"])
    assert m["command"] == ["python3", "benchmark/run.py"]
    assert m["paths"] == ["benchmark", "tests/benchmark"]
    assert [w["name"] for w in m["workloads"]][:1] == ["gpt2-large.train.seq1k"]
    assert {"train_tokens_per_s", "setup_s"} <= {e["name"] for e in m["end_to_end"]}
    # what holds of any configuration a cell may use, whatever its
    # architecture (that its adapter and reference are found:
    # test_every_named_file_is_there)
    for c in m["configs"]:
        cfg = load(os.path.join(REPO, c["file"]))
        assert cfg["source"] == c["source"] and c["source"].startswith("https://")
        assert not cfg.get("cpu_test_preset")
        assert all(len(v["why"]) > 40 for v in cfg["limits"].values())
    for p in m["per_layer"]:
        if p["name"].endswith("_roofline") or "mfu" in p["name"]:
            assert p["unit"] == "%"
