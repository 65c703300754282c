"""The one general traffic generator. A traffic mix is a data file under
``benchmark/traffic/<name>.json``; this module turns it and ``--seed`` into
inputs. The program under test never sees the seed, only the inputs.

Sizes (document lengths) come from the mix alone: the quantiles of its
stated distribution, the same multiset for every seed. ``--seed`` draws
their order and the token ids (and the weights), so a run's work does not
depend on the seed it was given; where it still would (a window shorter than
a cycle, under attention that stops at a document's end), the mix draws the
order itself: ``"order_seed": n``.

Length distribution: ``{"dist": "lognormal", "median": m, "sigma": s,
"min": a, "max": b}`` or ``{"dist": "fixed", "value": v}``.
Token distribution: ``{"dist": "zipf", "a": 1.2}`` (heavy-tailed unigrams,
as ``chip_smoke.py::zipf_tokens``) or ``{"dist": "uniform"}``.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterator

import numpy as np


def rng_of(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any non-negative seed."""
    return np.random.default_rng([int(seed), int(stream)])


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of ``spec``: the same multiset
    for every seed."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = statistics.NormalDist()
    mu = math.log(spec["median"])
    out = [math.exp(mu + spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
           for i in range(n)]
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def tokens(rng: np.random.Generator, spec: dict, vocab: int, n: int) -> np.ndarray:
    if spec["dist"] == "zipf":
        return (np.minimum(rng.zipf(spec["a"], size=n), vocab) - 1).astype(np.int32)
    if spec["dist"] == "uniform":
        return rng.integers(0, vocab, size=n, dtype=np.int32)
    raise ValueError(f"unknown token distribution {spec['dist']!r}")


def train_batches(mix: dict, seed: int, vocab: int, rows: int) -> Iterator[Dict[str, np.ndarray]]:
    """Endless stream of ``{"input_ids": [rows, seq_len]}``: documents of
    heavy-tailed length, each ended by the separator token, packed back to
    back into rows of ``seq_len`` (a document may straddle two rows, as in
    GPT-2's own training data).

    A batch is a VIEW of the cycle's buffer: between two refills the
    generator allocates nothing, so what a batch costs does not hang on the
    shape of the host's heap (PERF.md, PR 47: a copy of the 35 MiB that was
    left, every batch, gave one cell two speeds). A refill makes a new
    array, the remainder and one more cycle of documents, and never writes
    to the old one: whoever still holds a batch of it keeps its bytes."""
    seq = int(mix["seq_len"])
    sep = int(mix["separator"]) % vocab
    doc_lens = lengths(mix["doc_len"], int(mix["docs_per_cycle"]))
    rng = rng_of(seed, 1)
    # Where a step's cost follows the documents' places in the rows (attention
    # cut at a separator), a mix fixes their order with "order_seed": --seed
    # then draws the ids alone, and every seed's window does the same work.
    order = rng_of(mix["order_seed"], 2) if "order_seed" in mix else rng
    need = rows * seq
    buf, at = np.empty(0, np.int32), 0
    while True:
        have = len(buf) - at
        if have < need:
            parts = [buf[at:]]
            while have < need:
                for n in order.permutation(doc_lens):
                    doc = tokens(rng, mix["token_dist"], vocab - 1, int(n))
                    parts += [doc, np.asarray([sep], np.int32)]
                    have += int(n) + 1
            buf, at = np.concatenate(parts), 0
        yield {"input_ids": buf[at:at + need].reshape(rows, seq)}
        at += need
