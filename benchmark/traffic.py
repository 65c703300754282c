"""The one general traffic generator. A traffic mix is a data file under
``benchmark/traffic/<name>.json``; this module turns it and ``--seed`` into
inputs. The program under test never sees the seed, only the inputs.

Sizes (document lengths) come from the mix alone: the quantiles of its
stated distribution, the same multiset for every seed. ``--seed`` draws
their order and the token ids (and the weights), so a run's work does not
depend on the seed it was given.

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
    GPT-2's own training data)."""
    seq = int(mix["seq_len"])
    sep = int(mix["separator"]) % vocab
    doc_lens = lengths(mix["doc_len"], int(mix["docs_per_cycle"]))
    rng = rng_of(seed, 1)
    need = rows * seq
    buf = np.empty(0, np.int32)
    while True:
        parts = [buf]
        have = len(buf)
        while have < need:
            for n in rng.permutation(doc_lens):
                doc = tokens(rng, mix["token_dist"], vocab - 1, int(n))
                parts += [doc, np.asarray([sep], np.int32)]
                have += int(n) + 1
        flat = np.concatenate(parts)
        yield {"input_ids": flat[:need].reshape(rows, seq)}
        buf = flat[need:]
