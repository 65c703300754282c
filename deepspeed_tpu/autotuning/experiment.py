"""One autotuning experiment, run as its own PROCESS.

The reference autotuner launches every experiment as a separate job through
the launcher and parses its output (``autotuning/autotuner.py:404``,
``scheduler.py`` run_job); an in-process loop cannot try configs that OOM
or crash without killing the search. This runner is the experiment body:
build the model from a declarative spec, construct the engine with the
candidate config, time a few steps, write ``result.json``.

This launched form remains the isolation hatch for candidates that might
take the process down. The primary search path is now ``dstpu tune``
(``search.run_search`` + ``trial.TrialRunner`` — see docs/AUTOTUNING.md):
the Layer-E oracle rejects the OOM candidates *statically*, which is what
makes in-process measurement safe enough to be the default.

Usage: ``python -m deepspeed_tpu.autotuning.experiment <exp_dir>`` where
``exp_dir/exp.json`` holds::

    {"model": {"family": "gpt2", "preset": "gpt2-tiny", "kwargs": {...}},
     "config": {...engine config...},
     "seq_len": 16, "warmup_steps": 1, "measure_steps": 3}
"""

from __future__ import annotations

import json
import os
import sys
import time

MODEL_FAMILIES = ("gpt2", "llama", "mixtral")


def build_model_from_spec(spec):
    family = spec["family"]
    if family not in MODEL_FAMILIES:
        raise ValueError(f"unknown model family {family!r} "
                         f"(known: {MODEL_FAMILIES})")
    from .. import models
    fn = getattr(models, f"{family}_model")
    preset = spec.get("preset")
    kwargs = spec.get("kwargs", {})
    return fn(preset, **kwargs) if preset else fn(**kwargs)


def synthetic_batch(model, micro_batch: int, dp: int, seq_len: int) -> dict:
    """The one batch builder both experiment modes measure with — the two
    paths must stay comparable."""
    import numpy as np
    rng = np.random.default_rng(0)
    return {"input_ids": rng.integers(0, model.config.vocab_size,
                                      size=(micro_batch * max(dp, 1), seq_len))}


def run_experiment_dir(exp_dir: str) -> dict:
    import jax

    # DSTPU_ACCELERATOR=cpu pins the JAX platform too (the config API
    # wins while no backend is initialized)
    if os.environ.get("DSTPU_ACCELERATOR") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    import deepspeed_tpu

    with open(os.path.join(exp_dir, "exp.json")) as f:
        exp = json.load(f)
    result = {"status": "ok"}
    try:
        model = build_model_from_spec(exp["model"])
        engine, _, _, _ = deepspeed_tpu.initialize(model=model,
                                                   config=exp["config"])
        dp = engine.topology.data_parallel_size
        micro = exp["config"].get("train_micro_batch_size_per_gpu", 1)
        batch = synthetic_batch(model, micro, dp, exp.get("seq_len", 16))
        for _ in range(exp.get("warmup_steps", 1)):
            jax.block_until_ready(engine.train_batch(batch))
        t0 = time.perf_counter()
        loss = None
        steps = exp.get("measure_steps", 3)
        for _ in range(steps):
            loss = engine.train_batch(batch)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        samples = micro * max(dp, 1) * steps * engine.gradient_accumulation_steps
        result.update({"samples_per_sec": samples / dt, "loss": float(loss),
                       "measure_time_s": dt})
    except Exception as e:  # any failure is a data point, not a crash
        result = {"status": f"error: {type(e).__name__}: {e}",
                  "samples_per_sec": 0.0}
    # atomic: a kill mid-write must not leave a torn result.json that the
    # parent's resume logic would treat as a finished experiment
    tmp = os.path.join(exp_dir, ".result.json.tmp")
    with open(tmp, "w") as f:
        json.dump(result, f, indent=2)
    os.replace(tmp, os.path.join(exp_dir, "result.json"))
    return result


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    run_experiment_dir(argv[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
