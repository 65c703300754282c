"""The batch generator hands out VIEWS of a cycle's buffer (PR 47) and still
yields, byte for byte, what the generator before it yielded: that one is kept
here verbatim as the plain reference. It copied what was left of the cycle
into a fresh array every batch, which on the chip gave one cell two speeds
by the shape of the host's heap. And the reader that shows the harness's own
share of a lap, ``train_input_ms``, on spans made by hand."""

import itertools
import json
import os
import tracemalloc

import numpy as np
import pytest

from benchmark import harness, traffic
from tests.benchmark.helpers import REPO

MANIFEST = os.path.join(REPO, "BENCHMARK.json")
SEEDS = [5, 3_000_000_000]
ZIPF = {"dist": "zipf", "a": 1.2}
#: name -> (mix, vocabulary, rows a batch)
MIXES = {
    # test_yardstick.py's: a cycle of 8 documents is about three batches
    "tiny": ({"seq_len": 64, "separator": 50256, "docs_per_cycle": 8, "token_dist": ZIPF,
              "doc_len": {"dist": "lognormal", "median": 40, "sigma": 1.0, "min": 2, "max": 400}},
             50257, 3),
    # every document as long: a cycle is 12 x 31 tokens, a batch 128
    "fixed": ({"seq_len": 32, "separator": 99, "docs_per_cycle": 12,
               "token_dist": {"dist": "uniform"}, "doc_len": {"dist": "fixed", "value": 30}},
              100, 4),
    # train.seq32k at a 64th: one row a batch, documents up to a row long,
    # a cycle of 16 some nine batches, so 40 batches cross four refills
    "seq32k_scaled": ({"seq_len": 512, "separator": 319, "docs_per_cycle": 16, "token_dist": ZIPF,
                       "doc_len": {"dist": "lognormal", "median": 256, "sigma": 1.3,
                                   "min": 1, "max": 512}},
                      320, 1),
    # a batch larger than a cycle: every batch is a refill of several cycles
    "batch_over_a_cycle": ({"seq_len": 64, "separator": 7, "docs_per_cycle": 4, "token_dist": ZIPF,
                            "doc_len": {"dist": "fixed", "value": 9}}, 50, 8),
}


def _plain_batches(mix, seed, vocab, rows):
    """``traffic.train_batches`` as it stood before PR 47, body verbatim."""
    seq = int(mix["seq_len"])
    sep = int(mix["separator"]) % vocab
    doc_lens = traffic.lengths(mix["doc_len"], int(mix["docs_per_cycle"]))
    rng = traffic.rng_of(seed, 1)
    need = rows * seq
    buf = np.empty(0, np.int32)
    while True:
        parts = [buf]
        have = len(buf)
        while have < need:
            for n in rng.permutation(doc_lens):
                doc = traffic.tokens(rng, mix["token_dist"], vocab - 1, int(n))
                parts += [doc, np.asarray([sep], np.int32)]
                have += int(n) + 1
        flat = np.concatenate(parts)
        yield {"input_ids": flat[:need].reshape(rows, seq)}
        buf = flat[need:]


def take(stream, n):
    return [b["input_ids"] for b in itertools.islice(stream, n)]


def cell_mix(cell):
    """(traffic mix, vocabulary) of a cell of the manifest."""
    c = harness.Cell(MANIFEST, cell)
    return c.traffic, c.config["vocab_size"]


def owner(array):
    """The array that owns a view's memory."""
    while array.base is not None:
        array = array.base
    return array


def refills(batches):
    """How often the owner changed from one batch to the next."""
    owners = [owner(b) for b in batches]
    return sum(a is not b for a, b in zip(owners, owners[1:]))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(MIXES))
def test_forty_batches_equal_the_plain_generators(name, seed):
    mix, vocab, rows = MIXES[name]
    got = take(traffic.train_batches(mix, seed, vocab, rows), 40)
    for k, (batch, want) in enumerate(zip(got, take(_plain_batches(mix, seed, vocab, rows), 40))):
        assert batch.dtype == want.dtype == np.int32 and batch.shape == want.shape == (
            rows, mix["seq_len"])
        assert np.array_equal(batch, want), f"batch {k}"
    assert refills(got) >= 3


@pytest.mark.parametrize("name", sorted(set(MIXES) - {"batch_over_a_cycle"}))
def test_a_batch_is_a_view_and_a_refill_leaves_it_alone(name):
    """Inside a cycle consecutive batches share one owner and do not overlap;
    a batch handed out before a refill reads the same after it (the engine
    may still be copying a lap's pending batches to the device)."""
    mix, vocab, rows = MIXES[name]
    kept, copies = [], []
    for batch in itertools.islice(traffic.train_batches(mix, 5, vocab, rows), 40):
        kept.append(batch["input_ids"])
        copies.append(kept[-1].copy())    # taken before the next batch is made
    shared = 0
    for a, b in zip(kept, kept[1:]):
        if owner(a) is owner(b):
            shared += 1
            assert np.shares_memory(a, owner(b)) and not np.shares_memory(a, b)
            assert b.ctypes.data - a.ctypes.data == a.nbytes     # the next slice
    assert shared >= 20 and refills(kept) >= 3
    assert all(np.array_equal(a, c) for a, c in zip(kept, copies))


@pytest.mark.parametrize("name,generator", [
    ("seq32k_scaled", traffic.train_batches),
    ("fixed", traffic.train_batches),
    # the control: the plain generator copies its remainder every batch
    ("seq32k_scaled", _plain_batches),
    ("fixed", _plain_batches)])
def test_between_two_refills_nothing_the_size_of_a_batch_is_allocated(name, generator):
    """``tracemalloc`` sees numpy's buffers: a batch that is no refill may
    allocate its dict and its views, never ``rows * seq_len * 4`` bytes."""
    mix, vocab, rows = MIXES[name]
    batch_bytes = rows * mix["seq_len"] * 4
    stream = generator(mix, 3_000_000_000, vocab, rows)
    held, large = [next(stream)["input_ids"]], []
    tracemalloc.start()
    try:
        for _ in range(39):
            tracemalloc.reset_peak()
            before = tracemalloc.get_traced_memory()[0]
            held.append(next(stream)["input_ids"])
            large.append(tracemalloc.get_traced_memory()[1] - before >= batch_bytes)
    finally:
        tracemalloc.stop()
    refilled = [owner(a) is not owner(b) for a, b in zip(held, held[1:])]
    if generator is _plain_batches:
        assert sum(large) >= 20     # every batch whose remainder is a batch or more
    else:
        assert not any(l and not r for l, r in zip(large, refilled))
        assert 3 <= sum(refilled) <= 13


def test_a_cycle_of_the_evabyte_mix_is_some_270_batches():
    """What ISSUE 47 counts on: the cell's 45 s window (25 steps after 2 of
    set-up and 3 traced ones) lies inside the first cycle, so the window
    holds no allocation of the generator's at all."""
    mix, vocab = cell_mix("evabyte-6.5b.train.seq32k")
    cycle = int(traffic.lengths(mix["doc_len"], mix["docs_per_cycle"]).sum()) + mix["docs_per_cycle"]
    assert 250 <= cycle // mix["seq_len"] <= 290
    first = take(traffic.train_batches(mix, 5, vocab, 1), 60)
    assert refills(first) == 0 and 33 << 20 < owner(first[0]).nbytes < 37 << 20


def separators(batches, sep):
    return [np.flatnonzero(b.reshape(-1) == sep).tolist() for b in batches]


def test_a_mix_that_fixes_its_order_puts_every_seeds_documents_in_the_same_places():
    """``order_seed``: the documents' order is the mix's and --seed draws the
    ids alone, so two seeds' batches end their documents at the same
    positions, across refills, and hold other ids; another ``order_seed`` is
    another order, and without the key the order follows --seed as before."""
    mix, vocab, rows = MIXES["seq32k_scaled"]
    fixed = dict(mix, order_seed=7)
    forty = lambda m, seed: take(traffic.train_batches(m, seed, vocab, rows), 40)
    a, b = forty(fixed, 5), forty(fixed, 3_000_000_000)
    assert refills(a) >= 3
    assert separators(a, 319) == separators(b, 319)
    assert np.mean(np.stack(a) != np.stack(b)) > 0.3
    assert separators(forty(dict(mix, order_seed=8), 5), 319) != separators(a, 319)
    assert separators(forty(mix, 5), 319) != separators(forty(mix, 3_000_000_000), 319)
    # the same seed gives the same batches
    assert all(np.array_equal(x, y) for x, y in zip(a, forty(fixed, 5)))


def test_the_block_diffusion_mix_fixes_its_order_and_a_mix_without_the_key_follows_the_seed():
    """``train.bd8k`` carries an ``order_seed`` (PR 47: a seed's order moved
    that cell's rate by up to 1.25 %), so two seeds' windows end their
    documents at the same places. Which other mixes carry one is theirs to
    say (a later cell's mix brings its own file); a mix WITHOUT the key, here
    ``train.seq4k``, still gives what ``_plain_batches`` gives, which reads no
    such key, and its documents' places follow --seed."""
    mix, vocab = cell_mix("sdar-30b-a3b.train.bd8k")
    assert "order_seed" in mix
    window = lambda seed: take(traffic.train_batches(mix, seed, vocab, 1), 72)
    assert separators(window(1), 18991) == separators(window(3_000_000_000), 18991)
    plain, vocab = cell_mix("olmoe-1b-7b.train.seq4k")
    assert "order_seed" not in plain
    got = {seed: take(traffic.train_batches(plain, seed, vocab, 1), 12) for seed in SEEDS}
    for seed in SEEDS:
        want = take(_plain_batches(plain, seed, vocab, 1), 12)
        assert all(np.array_equal(a, b) for a, b in zip(got[seed], want))
    sep = plain["separator"] % vocab
    assert separators(got[SEEDS[0]], sep) != separators(got[SEEDS[1]], sep)


def test_the_trinity_mix_fixes_its_order_and_a_window_lies_inside_one_cycle():
    """``train.seq16k`` carries an ``order_seed`` since PR 67 (the check read
    the cell's rate 0.56 % apart over six seeds' own orders, under a bound of
    1 %): two seeds' rows end their documents at the same places and hold
    other ids, and the 90 rows of set-up, trace and a window of 45 s are under
    a cycle's, so no window sees a second permutation."""
    mix, vocab = cell_mix("trinity-mini.train.seq16k")
    assert mix["order_seed"] == 188
    cycle = int(traffic.lengths(mix["doc_len"], mix["docs_per_cycle"]).sum()) + mix["docs_per_cycle"]
    assert cycle // mix["seq_len"] > 2 * 90
    a, b = (take(traffic.train_batches(mix, seed, vocab, 1), 6) for seed in SEEDS)
    sep = mix["separator"] % vocab
    assert separators(a, sep) == separators(b, sep)
    assert np.mean(np.stack(a) != np.stack(b)) > 0.3


# ---------------------------------------------------------------------------
# train_input_ms
# ---------------------------------------------------------------------------

def spans_of(*records):
    spans = harness.Spans()
    spans.records = list(records)
    return spans


def reader():
    return harness.Cell(MANIFEST, "evabyte-6.5b.train.seq32k").load_module(
        "layer_metrics", "train_input_ms")


def lap(t, first_ms, others_ms=(0.5, 0.5), step_ms=2.0, wait_ms=100.0):
    """One lap's records from ``t`` on: a batch and its dispatch a step,
    then the fetch; returns (records, the time after them)."""
    out = []
    for ms in (first_ms, *others_ms):
        out.append(("generate_input", t, t + ms / 1e3))
        t += ms / 1e3
        out.append(("train_batch", t, t + step_ms / 1e3))
        t += step_ms / 1e3
    out.append(("wait_loss", t, t + wait_ms / 1e3))
    return out, t + wait_ms / 1e3


def test_the_reader_takes_the_first_batch_of_each_lap():
    """Set-up's second step, then a window of three laps whose first batches
    take 3, 41 and 4 ms and whose other batches take 0.5: the median is of the
    three first batches alone (4 ms), not of the nine (0.5)."""
    setup, t0 = lap(10.0, 60.0, others_ms=())
    records, t = list(setup), t0
    for first in (3.0, 41.0, 4.0):
        more, t = lap(t, first)
        records += more
    ctx = {"spans": spans_of(*records), "window": (t0, t)}
    assert reader().read(ctx) == pytest.approx(4.0)
    # set-up's own first batch (60 ms) lies before the window and is not read
    assert reader().read({"spans": spans_of(*setup), "window": (t0, t)}) is None


def test_the_reader_finds_nothing_without_a_fetch_before_a_batch():
    """A window whose batches never follow a ``wait_loss`` (or no span at
    all): None, so the metric is left out of the line; never 0."""
    records = [("generate_input", 1.0, 1.001), ("train_batch", 1.001, 1.002),
               ("generate_input", 1.002, 1.003), ("train_batch", 1.003, 1.004)]
    assert reader().read({"spans": spans_of(*records), "window": (0.5, 2.0)}) is None
    assert reader().read({"spans": spans_of(), "window": (0.0, 1.0)}) is None
    # a fetch that another span follows is no first batch either
    records = [("wait_loss", 1.0, 1.1), ("train_batch", 1.1, 1.2), ("generate_input", 1.2, 1.3)]
    assert reader().read({"spans": spans_of(*records), "window": (0.5, 2.0)}) is None


def test_the_manifest_lists_the_reader_for_every_training_cell():
    with open(MANIFEST) as f:
        m = json.load(f)
    entry = {p["name"]: p for p in m["per_layer"]}["train_input_ms"]
    training = next(e for e in m["end_to_end"] if e["name"] == "train_tokens_per_s")
    assert entry == {"name": "train_input_ms", "unit": "ms", "better": "lower",
                     "source": "host_clock", "layer": "harness",
                     "moves": "train_tokens_per_s", "workloads": training["workloads"]}
    assert entry["workloads"] == [w["name"] for w in m["workloads"]]
