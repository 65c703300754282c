"""Median host time, in ms, of the harness's own ``generate_input`` span for
a lap's FIRST batch inside the timed window: the one that directly follows a
``wait_loss`` span. The device has run dry at the sync, so it idles for as
long as that batch takes; every other batch of a lap is made while the
device works. It is the benchmark's own share of a step, not the program's
(PERF.md, PR 47: 41-44 ms here gave one cell two speeds)."""

from benchmark.harness import median


def read(ctx):
    t0 = ctx["window"][0]
    records = ctx["spans"].records
    firsts = [e - s for (before, _, _), (name, s, e) in zip(records, records[1:])
              if before == "wait_loss" and name == "generate_input" and s >= t0]
    return 1e3 * median(firsts) if firsts else None
