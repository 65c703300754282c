"""The training job: ``deepspeed_tpu.initialize`` -> ``engine.train_batch``
on a fresh batch every step, as the configuration file's engine settings
say, on one chip. Set-up makes the weights, computes the plain reference
on the first batch, builds the engine and holds its first step to the
reference; the window then trains for ``--seconds`` and is timed over
whole steps.
"""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Dict, Optional

import numpy as np

from benchmark import harness, program, traffic


def make_weights(ref, config: dict, seed: int, dtype: str):
    """The benchmark's weights: one jitted call on the device, in the
    dtype they are trained from."""
    import jax
    import jax.numpy as jnp
    make = functools.partial(ref.make_weights, config=config, dtype=jnp.dtype(dtype))
    return jax.jit(make)(ref.key_of(int(seed)))


def reference_numbers(ref, config: dict, weights, ids: np.ndarray,
                      control: Optional[str] = None) -> Dict[str, Any]:
    """Loss, gradient norm and the sign of every gradient element of the
    plain float32 reference on ``ids`` (the signs stay on the device)."""
    import jax
    fn = jax.jit(functools.partial(ref.loss_and_gradient,
                                   nh=int(config["n_head"]), control=control))
    loss, gnorm, signs = fn(weights, ids)
    return {"loss": float(loss), "grad_norm": float(gnorm), "signs": signs}


def _uphill_share(moved: Dict[str, Any], gradient: Dict[str, Any]) -> float:
    """Of the weights the reference's gradient gives a direction (it is
    not exactly 0: a position embedding never used has none), the share
    whose step (``moved``: sign of new - old) is not the reference's way
    down (minus the sign of its ``gradient``)."""
    import jax
    import jax.numpy as jnp

    def count(m, g):
        f32 = jnp.float32
        wrong = sum(jnp.sum((m[k] + g[k] != 0) & (g[k] != 0), dtype=f32) for k in m)
        return wrong / sum(jnp.sum(g[k] != 0, dtype=f32) for k in m)
    return float(jax.jit(count)(moved, gradient))


def _signs_of_step(new: Dict[str, Any], old: Dict[str, Any]) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    return jax.jit(lambda n, o: {
        k: jnp.sign(n[k].astype(f32) - o[k].astype(f32)).astype(jnp.int8)
        for k in n})(new, old)


def first_step_check(cell, seed: int, control: Optional[str],
                     checks: list) -> Dict[str, Any]:
    """Weights, reference, engine and the engine's FIRST step against the
    reference: its loss, its gradient norm, and which way each master
    weight moved. With ``control`` the reference computed in that lower
    precision stands in the program's place (and no engine is built)."""
    import jax
    if cell.chips != 1:
        raise SystemExit("jobs/train.py drives one chip; a cell across chips "
                         "brings a job file of its own")
    cfg, mix = cell.config, cell.traffic
    settings = cfg["engine"]["train"]
    ref = cell.load_module("reference", cfg["reference"])
    rows = int(settings["ds_config"]["train_micro_batch_size_per_gpu"])
    stream = traffic.train_batches(mix, seed, cfg["vocab_size"], rows)
    batch0 = next(stream)
    clock = [time.perf_counter()]

    def lap():
        clock.append(time.perf_counter())
        return clock[-1] - clock[-2]

    weights = make_weights(ref, cfg, seed, settings["param_dtype"])
    want = reference_numbers(ref, cfg, weights, batch0["input_ids"])
    phases = {"weights_and_reference_s": lap()}
    engine = None
    if control is None:
        model = program.transformer_lm(cfg, remat=settings["remat"],
                                       dtype=settings["param_dtype"])
        engine = program.train_engine(model, settings["ds_config"], weights, seed)
        del weights
        phases["engine_build_s"] = lap()
        loss = engine.train_batch(batch0)
        got = {"loss": float(loss), "grad_norm": float(engine.get_global_grad_norm())}
        phases["first_step_s"] = lap()
        # the same seed gives the same weights again: cheaper than keeping
        # a copy on the device through the step
        old = make_weights(ref, cfg, seed, settings["param_dtype"])
        moved = _signs_of_step(program.flat_weights(program.master_weights(engine)), old)
        del old
    else:
        got = reference_numbers(ref, cfg, weights, batch0["input_ids"], control=control)
        del weights
        # the control's step: straight down its own gradient
        moved = jax.jit(lambda g: {k: -v for k, v in g.items()})(got.pop("signs"))
    limits = cfg["limits"]["train"]
    numbers = {
        "first_loss_abs_err": abs(got["loss"] - want["loss"]),
        "first_grad_norm_rel_err": abs(got["grad_norm"] - want["grad_norm"])
        / want["grad_norm"],
        "first_step_uphill_share": _uphill_share(moved, want.pop("signs")),
    }
    del moved
    phases["compare_s"] = lap()
    ok = all([harness.check(k, v, limits[k], checks) for k, v in numbers.items()])
    return {"ok": ok, "numbers": numbers, "engine": engine, "stream": stream,
            "rows": rows, "got": got, "want": want, "phases": phases}


def numbers(cell, seed: int, control: Optional[str]) -> Dict[str, float]:
    """The compared numbers alone (benchmark/limits.py reads them)."""
    return first_step_check(cell, seed, control, [])["numbers"]


def run(cell, args, device: dict, stats, spans, t_start: float) -> Dict[str, Any]:
    import jax
    mix = cell.traffic
    checks: list = []
    first = first_step_check(cell, args.seed, args.control, checks)
    if args.control is not None:
        return {"correct": first["ok"], "attempted": 1, "failed": 0,
                "control_only": True, "checks": checks}
    engine, stream, rows = first["engine"], first["stream"], first["rows"]
    seq = int(mix["seq_len"])
    sync_every = int(mix["sync_every"])

    def step():
        with spans("generate_input"):
            batch = next(stream)
        with spans("train_batch"):
            return engine.train_batch(batch)

    def fetch(pending):
        with spans("wait_loss"):
            return [float(x) for x in pending]

    # one more step after the checked one: every shape is now compiled
    t_second = time.perf_counter()
    losses = [first["got"]["loss"]] + fetch([step()])
    jax.block_until_ready(engine.state)
    setup_compile = stats.take()

    trace_out: dict = {}
    if args.trace:
        # a few steps under the profiler, BEFORE the window: writing the
        # trace out takes seconds and must not count as training time
        with harness.trace_window(True, cell.name, trace_out):
            fetch([step() for _ in range(int(mix["trace_steps"]))])
            jax.block_until_ready(engine.state)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    phases = dict(first["phases"], imports_s=device["imports_s"],
                  reach_chip_s=device["reach_chip_s"],
                  second_step_s=None if args.trace else t0 - t_second,
                  **{k: setup_compile[k] for k in ("compile_s", "cache_hits", "cache_misses")})
    harness.info(**phases)
    steps, window_losses, pending = 0, [], []
    with harness.log_compiles():        # names whatever compiles in here
        while True:
            pending.append(step())
            steps += 1
            if steps % sync_every == 0:
                window_losses += fetch(pending)
                pending = []
                if time.perf_counter() - t0 >= args.seconds:
                    break
        jax.block_until_ready(engine.state)
        t1 = time.perf_counter()
    window_compile = stats.take()

    losses += window_losses
    failed = sum(1 for v in window_losses if not math.isfinite(v))
    fell = harness.check("last_loss_minus_first", losses[-1] - losses[0], 0.0, checks)
    tokens_per_s_chip = steps * rows * seq / (t1 - t0)
    return {
        "correct": first["ok"] and fell and failed == 0,
        "attempted": steps, "failed": failed, "checks": checks,
        "setup_phases": phases,
        "end_to_end": {"train_tokens_per_s": tokens_per_s_chip, "setup_s": setup_s},
        "ctx": {"cell": cell, "spans": spans, "window": (t0, t1),
                "steps": steps, "rows": rows, "seq": seq,
                "train_tokens_per_s": tokens_per_s_chip,
                "setup_compile": setup_compile, "window_compile": window_compile,
                "device_kind": device["kind"], "chips": cell.chips,
                "trace_out": trace_out},
    }
