#!/usr/bin/env python3
"""Run ONE cell of the benchmark once and print one JSON line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix, its job and its per-layer
readers are all found by name from ``BENCHMARK.json`` (or ``--manifest``):
adding one needs new files only. Without a TPU holding the chips the cell
asks for the command fails and prints no result. ``--control <precision>``
is the harness's own switch for the run that ``correct`` must reject (see
PERF.md): it checks and exits without measuring.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here (less reaching the chip)

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--control", default=None,
                    help="lower precision for the run that must come out "
                         "not correct (train: the fp8 reference in the "
                         "program's place)")
    args = ap.parse_args()

    from benchmark import harness
    cell = harness.Cell(args.manifest, args.workload)
    if args.seconds is None:
        args.seconds = float(cell.manifest["run_seconds"])

    harness.place_compile_cache()
    imports_s = time.perf_counter() - T_START
    device = harness.require_device(cell)
    # The TPU runtime's own start (``jax.devices()``) runs no code of this
    # repository and drifted from 6.3 to 8.3 s over sixteen process starts
    # on one machine (PERF.md): it is reported, and left out of ``setup_s``.
    reach_chip_s = time.perf_counter() - T_START - imports_s
    device.update(imports_s=imports_s, reach_chip_s=reach_chip_s)
    stats = harness.CompileStats()
    spans = harness.Spans()
    job = cell.load_module("jobs", cell.traffic["job"])
    result = job.run(cell, args, device, stats, spans, T_START + reach_chip_s)

    if result.get("control_only"):
        # the control's verdict alone: no window was measured
        line = {"correct": bool(result["correct"]), "attempted": 1, "failed": 0,
                "metrics": {}, "control": args.control,
                "device": {k: device[k] for k in ("platform", "kind", "count")}}
    else:
        if args.trace and device["on_chip"]:
            from benchmark.trace import reduce as trace_reduce
            ctx = result["ctx"]
            ctx["trace"] = trace_reduce.load(ctx["trace_out"]["trace_file"])
            summary = trace_reduce.summary(ctx["trace"], spans.records)
            result.update(busy_s=summary["busy_s"],
                          trace_window_s=summary["window_s"],
                          breakdown=summary["breakdown"],
                          per_layer=harness.read_per_layer(cell, ctx))
        line = harness.result_line(cell, device, bool(args.trace), result)
    line["checks"] = result["checks"]
    if "setup_phases" in result:
        line["setup_phases"] = result["setup_phases"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
