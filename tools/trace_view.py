#!/usr/bin/env python
"""Summarize a dstpu-telemetry trace JSONL for bench runs.

Usage:
    python tools/trace_view.py <trace.rank0.jsonl> [--top N] [--phase P]

Reads the JSONL export (``Telemetry.export()``; one record per line — see
deepspeed_tpu/telemetry/trace.py for the schema) and prints:

- top spans by total time (count, total/mean/p50/p95 ms) grouped by name,
- per-phase time breakdown,
- the span tree: spans grouped by their path of parents (``id`` /
  ``parent``, since PR 24) with total and self time, and for serving the
  number of distinct requests (``req``) under each path; a JSONL from before
  those fields prints no tree,
- comm overlap: overlapped/exposed traced bytes and the overlap fraction
  (the ``record_collective`` schedule-class split, docs/ZERO_OVERLAP.md),
- the last flushed derived metrics (MFU, goodput, tokens/sec, step
  percentiles) from the metric records.

Pure stdlib — runs anywhere the JSONL lands, no jax required.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict


def _pct(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1,
            max(0, int(round(p / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[i]


def _fmt_bytes(n):
    for unit, div in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if n >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n:.0f} B"


def load(path):
    records = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                print(f"warning: skipping malformed line {lineno}",
                      file=sys.stderr)
    return records


def span_tree(spans, top=15):
    """Lines of the span tree: spans grouped by the names along their
    chain of parents. Self time is a group's total less its children's."""
    by_id = {s["id"]: s for s in spans if "id" in s}
    if not by_id:
        return []

    def path(s):
        names = [s["name"]]
        while s.get("parent") in by_id:
            s = by_id[s["parent"]]
            names.append(s["name"])
        return tuple(reversed(names))

    total = defaultdict(float)
    count = defaultdict(int)
    reqs = defaultdict(set)
    for s in by_id.values():
        p = path(s)
        total[p] += s["dur"]
        count[p] += 1
        reqs[p].update(s.get("req") or ())
    child_total = defaultdict(float)
    for p, t in total.items():
        if len(p) > 1:
            child_total[p[:-1]] += t
    roots = sorted((p for p in total if len(p) == 1),
                   key=lambda p: -total[p])[:top]
    lines = [f"{'span tree':<36}{'count':>7}{'total ms':>12}{'self ms':>11}"
             f"{'requests':>10}"]

    def emit(p):
        label = "  " * (len(p) - 1) + p[-1]
        lines.append(f"{label:<36}{count[p]:>7}{total[p] * 1e3:>12.2f}"
                     f"{(total[p] - child_total[p]) * 1e3:>11.2f}"
                     f"{len(reqs[p]) or '':>10}")
        for c in sorted((q for q in total if q[:-1] == p),
                        key=lambda q: -total[q]):
            emit(c)

    for r in roots:
        emit(r)
    return lines + [""]


def summarize(records, top=15, phase=None):
    spans = [r for r in records if r.get("kind") == "span"]
    if phase:
        spans = [s for s in spans if s.get("phase") == phase]
    by_name = defaultdict(list)
    by_phase = defaultdict(float)
    phase_of = {s["id"]: s.get("phase") for s in spans if "id" in s}
    for s in spans:
        by_name[s["name"]].append(s["dur"])
        # a child in its parent's phase is time the parent already counts
        if phase_of.get(s.get("parent")) != s.get("phase", "other"):
            by_phase[s.get("phase", "other")] += s["dur"]

    lines = []
    if by_name:
        lines.append(f"{'span':<28}{'count':>7}{'total ms':>12}"
                     f"{'mean ms':>10}{'p50 ms':>10}{'p95 ms':>10}")
        ranked = sorted(by_name.items(), key=lambda kv: -sum(kv[1]))[:top]
        for name, durs in ranked:
            sd = sorted(durs)
            lines.append(f"{name:<28}{len(durs):>7}{sum(durs) * 1e3:>12.2f}"
                         f"{sum(durs) / len(durs) * 1e3:>10.2f}"
                         f"{_pct(sd, 50) * 1e3:>10.2f}"
                         f"{_pct(sd, 95) * 1e3:>10.2f}")
        lines.append("")
        lines += span_tree(spans, top)
        total = sum(by_phase.values())
        lines.append("phase breakdown:")
        for ph, t in sorted(by_phase.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {ph:<14}{t * 1e3:>12.2f} ms"
                         f"  ({100 * t / max(total, 1e-12):.1f}%)")
        lines.append("")
        # optimizer wall-fraction (ISSUE 10 observability): the apply/
        # optimizer dispatch's share of training wall — the number the
        # fused bucket kernels exist to shrink. Only the SPLIT step path
        # (DSTPU_FUSED_STEP=0 / gas>1) records an 'optimizer' span; its
        # wall is the sum of the sequential per-step phases (data/fwd/
        # bwd/optimizer host intervals). The fused gas==1 dispatch is
        # one program — its optimizer slice is device-internal and
        # belongs to the XLA profiler, so no line is printed there.
        opt_t = by_phase.get("optimizer", 0.0)
        wall_t = sum(t for ph, t in by_phase.items() if ph != "step")
        if phase is None and opt_t > 0 and wall_t > 0:
            lines.append(f"optimizer wall-fraction: {opt_t / wall_t:.3f} "
                         f"of step ({opt_t * 1e3:.2f} / {wall_t * 1e3:.2f} ms"
                         f" — fused opt kernels target this slice, "
                         f"docs/KERNELS.md)")
            lines.append("")

        # offload stall decomposition (ISSUE 15): the four pipeline phases
        # of the out-of-core optimizer boundary — everything except
        # bucket_compute is time the pipeline exists to hide
        # (docs/OBSERVABILITY.md "Offload stall decomposition")
        off = {name[len("offload/"):]: sum(durs)
               for name, durs in by_name.items()
               if name.startswith("offload/")}
        if phase is None and off:
            total_off = sum(off.values())
            blocked = total_off - off.get("bucket_compute", 0.0)
            parts = "  ".join(
                f"{k} {v * 1e3:.2f} ms"
                for k, v in sorted(off.items(), key=lambda kv: -kv[1]))
            lines.append(f"offload stall decomposition: {parts}")
            lines.append(
                f"  blocked fraction "
                f"{blocked / max(total_off, 1e-12):.3f} "
                f"(everything but bucket_compute; the double-buffered "
                f"pipeline drives this toward 0, docs/OFFLOAD.md)")
            lines.append("")

    ov = ex = 0
    for r in records:
        if r.get("kind") != "comm":
            continue
        b = r["bytes"] * r.get("count", 1)
        if r.get("overlapped") is True:
            ov += b
        elif r.get("overlapped") is False:
            ex += b
    if ov or ex:
        lines.append(f"comm traced bytes: overlapped {_fmt_bytes(ov)} / "
                     f"exposed {_fmt_bytes(ex)} "
                     f"(overlap fraction {ov / max(ov + ex, 1):.2f})")
        lines.append("")

    # newest value per metric tag
    metrics = {}
    for r in records:
        if r.get("kind") == "metric":
            metrics[r["name"]] = r["value"]
    if metrics:
        lines.append("derived metrics (last flush):")
        for name in sorted(metrics):
            lines.append(f"  {name:<40}{metrics[name]:>14.6g}")
    if not lines:
        lines.append("no span/comm/metric records found "
                     "(is this a telemetry JSONL export?)")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="summarize a dstpu-telemetry trace JSONL")
    parser.add_argument("path", help="trace.rank*.jsonl from Telemetry.export()")
    parser.add_argument("--top", type=int, default=15,
                        help="how many span groups to print (default 15)")
    parser.add_argument("--phase", default=None,
                        help="restrict the span table to one phase")
    args = parser.parse_args(argv)
    records = load(args.path)
    print(summarize(records, top=args.top, phase=args.phase))
    return 0


if __name__ == "__main__":
    sys.exit(main())
