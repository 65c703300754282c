#!/usr/bin/env python
"""A/B microbenchmark of a head and its loss on the attached chip (PR 63), at
the shapes of the four cells whose float32 logits are not kept for the
backward: rows x hidden x vocabulary and the slices `head_row_slices` gave each
on the chip,

- ``smallthinker-21b-a3b.train.win16k``: 32,768 x 2,560 x 37,984 in 8;
- ``phi4-mini-flash-reasoning.train.sambay``: 16,384 x 2,560 x 25,008, tied, in 4;
- ``instella-moe-16b-a3b.train.seq8k``: 16,384 x 2,048 x 16,112, whole;
- ``xing4-29b-a4b.train.mhc``: 8,192 x 3,584 x 16,384, whole

(the last two run a second such head for their prediction module; one is
timed). Each cell's model is built through its adapter at the configuration
file's own size, and the head alone is run: the final norm, the matrix and the
cross-entropy over a random bfloat16 stream, forward + backward (the loss and
its gradients by the stream and the head's parameters).

Two forms of the same arithmetic:

- ``rerun``: what the model had until PR 63. In slices, a `lax.scan` whose body
  is a `jax.checkpoint` of one slice; whole, a `jax.checkpoint` of the head and
  its loss. Either makes the logits (and their softmax) again in the backward:
  four products over the vocabulary.
- ``fused``: `TransformerLM.fused_head_loss`, each slice's gradient taken where
  its logits are: three products, one softmax.

One JSON line a reading on stdout and in ``chiprun_out/head_loss_ab.jsonl``:
``ms`` a pass (the best of ``--windows`` windows of ``--calls`` calls, host
clock around ``block_until_ready``; the forms alternate within a window),
``pass_ms_at_peak`` (one product's 2 x rows x hidden x vocabulary operations
over the chip's 197 TFLOP/s), ``temp_gb`` / ``peak_gb`` (the compiled
program's temporaries, and those with its arguments and results, from
``memory_analysis()``), and ``err``: the fused form's largest distance from
the rerun form's on this chip over its largest element, for the loss and each
gradient. ``--tiny`` rehearses the script at the cells' tiny presets (the
CPU). No cell runs this file.
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402
from deepspeed_tpu.models.transformer import masked_cross_entropy, _token_nll  # noqa: E402

F32 = jnp.float32
PEAK_FLOPS = 197e12          # one v5e chip, bfloat16 (Google Cloud documentation, "TPU v5e")
#: cell -> (rows, the slices the chip's room gave its head: PERF.md, PRs 57 and 62)
CELLS = {"smallthinker-21b-a3b.train.win16k": (32768, 8),
         "phi4-mini-flash-reasoning.train.sambay": (16384, 4),
         "instella-moe-16b-a3b.train.seq8k": (16384, 1),
         "xing4-29b-a4b.train.mhc": (8192, 1)}
TINY = {"smallthinker-tiny.train": (64, 4), "phi4flash-tiny.train": (64, 4),
        "instella-tiny.train": (64, 1), "xing4-tiny.train": (64, 1)}


def rerun(model, slices):
    """The head's loss as `TransformerLM.head_loss` (in slices) and
    `loss_and_stats` (a prediction module's pair, whole) had it until PR 63."""
    if slices == 1:
        return jax.checkpoint(lambda head, x, labels: masked_cross_entropy(
            model.head(head, x), labels))

    def loss(head, x, labels):
        B, S, _ = x.shape
        wide = jax.tree.map(lambda a: a.astype(F32), head)
        valid = labels >= 0
        mask = valid.astype(F32)
        by_slice = lambda a: a.reshape((slices, B * S // slices) + a.shape[2:])

        def one(total, xs):
            xb, targets, weights = xs
            narrow = jax.tree.map(lambda a, like: a.astype(like.dtype), wide, head)
            logits = model.head(narrow, xb[None])[0]
            with jax.named_scope("loss"):
                return total + jnp.sum(_token_nll(logits, targets) * weights), None

        with jax.named_scope("head"):
            total, _ = jax.lax.scan(
                jax.checkpoint(one), jnp.zeros((), F32),
                (by_slice(x), by_slice(jnp.where(valid, labels, 0)), by_slice(mask)))
        return total / jnp.maximum(jnp.sum(mask), 1.0)
    return loss


def fused(model, slices):
    return lambda head, x, labels: model.fused_head_loss(head, x, labels, None, slices)


def build(manifest, workload, rows, seed):
    """(the cell's model, its head's parameters, a stream, labels), random."""
    cell = harness.Cell(manifest, workload)
    cfg = cell.config
    settings = cfg["engine"]["train"]
    adapter = cell.load_module("adapters", cfg["adapter"])
    model = adapter.model(cfg, remat=settings["remat"], dtype=settings["param_dtype"])
    c, dtype = model.config, jnp.dtype(settings["param_dtype"])
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), dtype))
    key = jax.random.PRNGKey(seed)
    leaves, tree = jax.tree.flatten(
        {k: shapes[k] for k in ("ln_f", "wte" if c.tie_embeddings else "lm_head")})
    fresh = [jax.random.normal(jax.random.fold_in(key, 3 + i), leaf.shape, F32)
             for i, leaf in enumerate(leaves)]
    # a norm's scale about 1, a matrix's elements about hidden ** -0.5
    head = jax.tree.unflatten(tree, [
        (1.0 + 0.1 * a if leaf.ndim == 1 else a * c.hidden_size ** -0.5).astype(leaf.dtype)
        for a, leaf in zip(fresh, leaves)])
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, rows, c.hidden_size), F32).astype(c.dtype)
    labels = jax.random.randint(jax.random.fold_in(key, 2), (1, rows), 0, c.vocab_size)
    return model, head, x, labels.at[0, -1].set(-100)


def distance(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--cells", default="", help="comma-separated cell names (default: all four)")
    ap.add_argument("--windows", type=int, default=5)
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    manifests = ({name: f"tests/benchmark/data/BENCHMARK.{name.split('.')[0]}.json" for name in TINY}
                 if args.tiny else dict.fromkeys(CELLS, "BENCHMARK.json"))
    table = TINY if args.tiny else CELLS
    wanted = [c for c in args.cells.split(",") if c] or list(table)
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/head_loss_ab.jsonl", "a")
    device = jax.devices()[0]
    for workload in wanted:
        rows, slices = table[workload]
        model, head, x, labels = build(manifests[workload], workload, rows, args.seed)
        c = model.config
        programs, results, memory = {}, {}, {}
        for form, loss in (("rerun", rerun(model, slices)), ("fused", fused(model, slices))):
            step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
            compiled = step.lower(head, x, labels).compile()
            m = compiled.memory_analysis()
            memory[form] = (m.temp_size_in_bytes,
                            m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes)
            programs[form] = compiled
            results[form] = jax.block_until_ready(compiled(head, x, labels))
        best = dict.fromkeys(programs, float("inf"))
        for _ in range(args.windows):
            for form, program in programs.items():
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    last = program(head, x, labels)
                jax.block_until_ready(last)
                best[form] = min(best[form], (time.perf_counter() - t0) / args.calls)
        want, got = results["rerun"], results["fused"]
        err = {"loss": distance(got[0], want[0]),
               **{jax.tree_util.keystr(path): distance(a, b) for (path, a), b in zip(
                   jax.tree_util.tree_leaves_with_path(got[1]), jax.tree.leaves(want[1]))}}
        for form in programs:
            line = {"cell": workload, "form": form, "rows": rows, "hidden": c.hidden_size,
                    "vocab": c.vocab_size, "tied": c.tie_embeddings, "slices": slices,
                    "ms": round(best[form] * 1e3, 3),
                    "pass_ms_at_peak": round(2 * rows * c.hidden_size * c.vocab_size
                                             / PEAK_FLOPS * 1e3, 2),
                    "temp_gb": round(memory[form][0] / 1e9, 3),
                    "peak_gb": round(memory[form][1] / 1e9, 3),
                    "loss": float(results[form][0]),
                    "device": f"{device.platform}:{device.device_kind}"}
            if form == "fused":
                line["err"] = err
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
        del programs, results, head, x
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
