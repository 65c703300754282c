"""The yardstick's arithmetic against numbers worked out by hand: the trace
reduction on a small recorded fixture (events cut from PR 23's chip traces
of the training cell, names and times as recorded), the FLOPs per
token, the bytes of a kernel launch and the traffic."""

import json
import os

import numpy as np
import pytest

from benchmark import flops, harness, peaks, traffic
from benchmark.trace import reduce

FIXTURE = os.path.join(os.path.dirname(reduce.__file__), "fixture.json")


@pytest.fixture(scope="module")
def trace():
    return reduce.load(FIXTURE)


def test_fixture_is_a_recorded_trace(trace):
    assert sorted(trace["devices"]) == ["/device:TPU:0"]
    assert all(len(e) == 4 and e[3].startswith("%" + e[0])
               for ev in trace["devices"].values() for e in ev)


# worked out by hand in benchmark/trace/fixture.md
HAND = json.load(open(os.path.join(os.path.dirname(reduce.__file__), "fixture_hand.json")))
SPANS = {"train_batch", "wait_loss"}


def adam_roofline(trace):
    from benchmark import harness as h
    cell = h.Cell(os.path.join(os.path.dirname(os.path.dirname(reduce.__file__)), "..",
                               "BENCHMARK.json"), "gpt2-large.train.seq1k")
    return cell.load_module("layer_metrics", "adam_roofline").read(
        {"trace": trace, "chips": 1, "device_kind": "TPU v5 lite"})


READERS = {
    "busy_s": reduce.busy_s, "window_s": reduce.window_s,
    "idle_share": reduce.idle_share,
    "adam_kernel_s": lambda t: sum(e[2] for e in reduce.matching(
        t, 'custom_call_target="tpu_custom_call"')) / 1e9,
    "top_op_s": lambda t: reduce.top_ops(t, 1)[0][1],
    "idle_gap_train_batch_s": lambda t: dict(reduce.idle_gaps(t, SPANS)).get("train_batch", 0.0),
    "idle_gap_wait_loss_s": lambda t: dict(reduce.idle_gaps(t, SPANS))["wait_loss"],
    "adam_roofline": adam_roofline,
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reduction_against_hand_numbers(trace, name):
    assert READERS[name](trace) == pytest.approx(HAND[name], rel=1e-9, abs=1e-15)


def test_containers_are_not_work(trace):
    names = [e[0] for e in reduce.leaf_events(trace["devices"]["/device:TPU:0"])]
    assert len(names) == 12 and not [n for n in names if n.startswith("while")]
    assert reduce.top_ops(trace, 1)[0][0] == HAND["top_op"]
    summary = reduce.summary(trace, [("wait_loss", 0, 1)])
    assert summary["busy_s"] == HAND["busy_s"] and summary["window_s"] > summary["busy_s"]
    (name, secs), = summary["breakdown"]["idle_gaps"]
    assert name == "wait_loss" and secs == pytest.approx(HAND["idle_gap_wait_loss_s"])


def test_union_and_short_names():
    assert reduce.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert reduce.total([(0, 4), (5, 10)]) == 9
    assert reduce.short_name("%fusion.7 = bf16[4]{0} fusion(bf16[4]{0} %p), kind=kLoop") == "fusion.7"


def test_flops_per_token_by_hand():
    large = {"n_embd": 1280, "n_layer": 36, "vocab_size": 50257, "n_positions": 1024}
    # 36 x 12 x 1280^2 + 50257 x 1280
    assert flops.gpt2_matmul_params(large) == 707_788_800 + 64_328_960 == 772_117_760
    # 6 x 772,117,760 + 6 x 36 x 1280 x 1024
    assert flops.train_flops_per_token(large, 1024) == 4_632_706_560 + 283_115_520
    assert flops.train_flops_per_token(large, 1024) / 1e9 == pytest.approx(4.916, abs=1e-3)
    xl = {"n_embd": 1600, "n_layer": 48, "vocab_size": 50257, "n_positions": 1024}
    # 48 x 12 x 1600^2 + 50257 x 1600 = 1,474,560,000 + 80,411,200
    assert flops.train_flops_per_token(xl, 1024) == 6 * 1_554_971_200 + 6 * 48 * 1600 * 1024


def test_kernel_bytes_from_a_launch_by_hand():
    hlo = ('%branch_0_fun.21 = (f32[2048,128]{1,0:T(8,128)S(1)}, bf16[2048,128]{1,0}, '
           'bf16[2048,128]{1,0}, bf16[2048,128]{1,0}) custom-call(bf16[2048,128]{1,0} %g, '
           'f32[2048,128]{1,0} %p, bf16[2048,128]{1,0} %m, bf16[2048,128]{1,0} %v, '
           'f32[4]{0} %hyper, u32[2]{0} %seed), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={bf16[2048,128]{1,0}}')
    # 262,144 elements: results 4+2+2+2 and operands 2+4+2+2 bytes each
    assert flops.custom_call_io_bytes(hlo) == 262_144 * 20
    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")


def test_traffic_is_the_mixes_sizes_and_the_seeds_tokens():
    spec = {"dist": "lognormal", "median": 192, "sigma": 0.7, "min": 16, "max": 512}
    lens = traffic.lengths(spec, 16)
    assert lens.min() >= 16 and lens.max() <= 512 and 150 < np.median(lens) < 240
    # quantile i of 16 of lognormal(median 192, sigma 0.7): i=0 -> exp(ln192 - 0.7*1.8627)
    assert lens[0] == round(192 * np.exp(-0.7 * 1.8627318674))
    assert traffic.lengths({"dist": "fixed", "value": 7}, 3).tolist() == [7, 7, 7]
    uni = traffic.tokens(traffic.rng_of(3_000_000_000, 2), {"dist": "uniform"}, 50257, 4096)
    assert uni.min() >= 0 and uni.max() < 50257 and len(set(uni.tolist())) > 3000
    tmix = {"seq_len": 64, "separator": 50256, "docs_per_cycle": 8,
            "doc_len": {"dist": "lognormal", "median": 40, "sigma": 1.0, "min": 2, "max": 400},
            "token_dist": {"dist": "zipf", "a": 1.2}}
    s1, s2 = traffic.train_batches(tmix, 5, 50257, 3), traffic.train_batches(tmix, 5, 50257, 3)
    b1, b2 = next(s1), next(s2)
    assert b1["input_ids"].shape == (3, 64) and (b1["input_ids"] == b2["input_ids"]).all()
    assert (next(s1)["input_ids"] != b1["input_ids"]).any()
    assert (b1["input_ids"] == 50256).sum() >= 1 and b1["input_ids"].max() <= 50256
    # another seed (a large one): other tokens, the same multiset of document lengths
    b3 = next(traffic.train_batches(tmix, 3_000_000_000, 50257, 3))
    assert (b3["input_ids"] != b1["input_ids"]).any()
