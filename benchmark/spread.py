#!/usr/bin/env python3
"""The spread a bound is set from (PERF.md section 2): for each metric of a
file of result lines, per set, the interquartile range as a share of the
median, ``statistics.quantiles(values, n=4)`` as the contract says.

    python3 benchmark/spread.py runs.jsonl

Each line of the file is ``{"set": 1|2, "seed": n, "line": <result line>}``.
For ``setup_s`` a set's first run is left out (it may compile).
"""

import json
import statistics
import sys


def main() -> int:
    rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
    names = sorted({k for r in rows for k in r["line"].get("metrics", {})})
    print("runs", len(rows), "correct", sum(bool(r["line"].get("correct")) for r in rows))
    for name in names:
        for s in sorted({r["set"] for r in rows}):
            v = [r["line"]["metrics"][name]["value"] for r in rows
                 if r["set"] == s and name in r["line"].get("metrics", {})]
            use = v[1:] if name == "setup_s" else v
            if len(use) < 2:
                continue
            q = statistics.quantiles(use, n=4)
            med = statistics.median(use)
            print(f"{name:26s} set {s}: median {med:.6g}  spread {100 * (q[2] - q[0]) / med:.3f} %")
    return 0


if __name__ == "__main__":
    sys.exit(main())
