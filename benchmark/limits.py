#!/usr/bin/env python3
"""Read the two numbers a limit of ``correct`` is set from (PERF.md, "How
correct is decided"): what sound runs of the program give over many seeds,
and what the control gives, all in one process and with no timed window.

    python3 benchmark/limits.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --control fp8

Prints one JSON line per seed and a last line with the largest sound value
and the smallest control value of every compared number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default=None)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    args = ap.parse_args()
    from benchmark import harness, program
    cell = harness.Cell(args.manifest, args.workload)
    harness.place_compile_cache()
    device = harness.require_device(cell)
    job = cell.load_module("jobs", cell.traffic["job"])
    ints = lambda s: [int(x) for x in s.split(",") if x]
    sound, control = {}, {}
    for seeds, ctl, into in ((ints(args.seeds), None, sound),
                             (ints(args.control_seeds), args.control, control)):
        for seed in seeds:
            numbers = job.numbers(cell, seed, ctl)
            program.release()
            print(json.dumps({"seed": seed, "control": ctl, **numbers}), flush=True)
            for k, v in numbers.items():
                into.setdefault(k, []).append(v)
    print(json.dumps({"largest_sound": {k: max(v) for k, v in sound.items()},
                      "smallest_control": {k: min(v) for k, v in control.items()},
                      "device": {"platform": device["platform"],
                                 "kind": device["kind"], "count": device["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
