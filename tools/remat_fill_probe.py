#!/usr/bin/env python3
"""A training step's TRUE peak on the chip, which no allocator counter
shows (PERF.md section 7, row 14): build a benchmark cell's engine as
``benchmark/jobs/train.py`` does, run two steps, then fill the device with
128 MiB arrays, one more before each further step, until a step fails.

Since PR 53 the program keeps an account of its own, ``engine.memory_totals``
(two readings of the allocator, on every traced run's line as
``train_step_peak_gb`` / ``train_step_temp_gb``): this probe is that
account's CHECK, at its grain of one filling, and no longer the only source.
It prints the account beside its own truth, and every candidate there is for
``step_extra_bytes`` from inside the program less the truth
(``account_minus_probe_bytes``): the compiled step's fields, which the tool
reads itself and the program does not keep, and the allocator's counters.

    chiprun -- python tools/remat_fill_probe.py --workload <cell> [--seed n]
        [--room BYTES] [--rows r] [--seq s]

``--room`` puts a number in the place of the engine's reading of the device
(0: the blocks recomputed whole), ``--rows`` / ``--seq`` another batch than
the cell's. One JSON line: what ``engine.remat_totals`` decided, the bytes
in use at rest, ``true_peak_bytes`` = ``bytes_limit`` less the largest
filling under which a step still ran, ``memory_totals`` and the candidates'
differences. This is how ``checkpointing.STACK_COST`` was measured (PERF.md,
PRs 30 and 35); since PR 60 the budget is checked against the account's own
figure in nine cells and has no other fitted number. A fresh process a cell
and seed (row 22(h)).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHUNK = 128 << 20


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--room", type=int, default=None)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    args = ap.parse_args()

    from benchmark import harness, program, traffic
    from benchmark.jobs import train
    cell = harness.Cell(args.manifest, args.workload)
    harness.place_compile_cache()
    device = harness.require_device(cell)["devices"][0]
    import jax
    import jax.numpy as jnp

    cfg, mix = cell.config, dict(cell.traffic)
    settings = cfg["engine"]["train"]
    if args.seq is not None:
        mix["seq_len"] = args.seq
    rows = args.rows or int(settings["ds_config"]["train_micro_batch_size_per_gpu"])
    ds_config = dict(settings["ds_config"], train_micro_batch_size_per_gpu=rows)
    ref = cell.load_module("reference", cfg["reference"])
    adapter = cell.load_module("adapters", cfg["adapter"])
    model = adapter.model(cfg, remat=settings["remat"], dtype=settings["param_dtype"])
    weights = train.make_weights(ref, cfg, args.seed, settings["param_dtype"])
    # what the job keeps on the device while the first step is traced: the
    # reference's sign of every gradient element, one byte each
    signs = jax.jit(lambda w: {k: jnp.zeros(v.shape, jnp.int8)
                               for k, v in w.items()})(weights)
    engine = program.train_engine(model, ds_config, adapter.to_program(weights),
                                  args.seed)
    del weights
    if args.room is not None:
        engine.__dict__["_remat_room_bytes"] = args.room
    stream = traffic.train_batches(mix, args.seed, cfg["vocab_size"], rows)
    in_use = lambda: int(device.memory_stats()["bytes_in_use"])
    at_trace = in_use()
    step_avals = spy_on_the_step(engine)
    float(engine.train_batch(next(stream)))
    del signs
    float(engine.train_batch(next(stream)))
    jax.block_until_ready(engine.state)
    limit, at_rest = int(device.memory_stats()["bytes_limit"]), in_use()
    account = account_of(engine, at_rest, step_avals)
    filling, held, laps, failure = [], 0, [], None
    while failure is None:
        try:
            filling.append(jax.block_until_ready(
                jax.device_put(jnp.zeros((CHUNK,), jnp.uint8), device)))
            t0 = time.perf_counter()
            float(engine.train_batch(next(stream)))
            laps.append(1e3 * (time.perf_counter() - t0))
            held = CHUNK * len(filling)
        except Exception as e:   # RESOURCE_EXHAUSTED, from the chunk or the step
            failure = str(e).splitlines()[0][:200]
    line = {
        "workload": cell.name, "rows": rows, "seq": int(mix["seq_len"]),
        "room_given": args.room,
        "remat_totals": {k: list(v) if isinstance(v, tuple) else v
                         for k, v in engine.remat_totals.items()},
        "bytes_limit": limit, "in_use_at_trace": at_trace, "in_use_at_rest": at_rest,
        "filled_bytes": held, "true_peak_bytes": limit - held,
        "step_temporaries_bytes": limit - held - at_rest, "failure": failure,
        "synced_step_ms": statistics.median(laps) if laps else None,
        **account,
    }
    truth = line["step_temporaries_bytes"]
    line["account_minus_probe_bytes"] = {
        name: None if got is None else got - truth
        for name, got in line.pop("candidates").items()}
    print(json.dumps(line), flush=True)
    out = os.path.join(harness.CHECKOUT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "remat_fill_probe.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")
    # the failed step may have taken the donated state with it: leave now
    os._exit(0)


def spy_on_the_step(engine) -> list:
    """The fused step's arguments at its next call, as ``ShapeDtypeStruct``s
    that find the call's lowering again (a committed array keeps its
    sharding, an uncommitted scalar has none, as the call saw them): taken
    before the call donates its state. The list is filled by that call."""
    import jax
    engine._build_fused_jit()
    jitted, seen = engine._jit_train_step, []

    def aval(x):
        if not isinstance(x, jax.Array):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)

    def step(*args):
        seen.extend(jax.tree.map(aval, args))
        engine._jit_train_step = jitted
        return jitted(*args)

    engine._jit_train_step = step
    return seen


def account_of(engine, at_rest: int, step_avals: list) -> dict:
    """The program's account beside the probe's truth: ``engine.memory_totals``
    with its residents renewed to this moment's (the signs are gone, as in a
    traced window), and every candidate for ``step_extra_bytes`` there is
    from inside the program: the compiled step's own fields (the tool asks
    the executable again: ``lower(...).compile()`` on the call's own avals
    finds it, and where it does not the tool pays a compile), and the
    allocator's counters. PR 53 found the allocator's reservation the true
    one in seven cells of seven (PERF.md section 7, row 14)."""
    from deepspeed_tpu.telemetry import memory
    device = memory.device_memory(engine.mesh.devices.flat)
    out = {"memory_totals": memory.with_residents(dict(engine.memory_totals), device),
           "device": device}
    with engine.mesh:
        mem = engine._jit_train_step.lower(*step_avals).compile().memory_analysis()
    temp, peak = mem.temp_size_in_bytes, getattr(mem, "peak_memory_in_bytes", 0)
    # the donated state is resident before the step and its new value is
    # written over it: what an output adds is what is NOT aliased
    fresh = mem.output_size_in_bytes - mem.alias_size_in_bytes
    out["compiled"] = {
        "argument_bytes": mem.argument_size_in_bytes, "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes, "temp_bytes": temp,
        "code_bytes": mem.generated_code_size_in_bytes, "compiler_peak_bytes": peak or None,
        "buffer_assignment_bytes": len(getattr(
            mem, "serialized_buffer_assignment_proto", b"") or b"")}
    out["candidates"] = {
        "temp_output_less_alias": temp + fresh,
        "temp_output_code_less_alias": temp + fresh + mem.generated_code_size_in_bytes,
        "peak_less_argument": peak - mem.argument_size_in_bytes if peak else None,
        "peak_less_alias": peak - mem.alias_size_in_bytes if peak else None,
        "allocator_peak_less_rest": device["peak_bytes_in_use"] - at_rest,
        "reserved": memory.reserved_bytes(device)}
    return out


if __name__ == "__main__":
    main()
