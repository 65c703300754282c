"""The expert layer's grouped matmuls against the MXU's peak, in percent:
the FLOPs the launches under ``moe/experts`` execute in a step, counted from
the rows that exist (``tokens x experts per token``, never a padded tile;
each of the three products forward, recomputed forward and the two
backward products: benchmark/trace/moe.py::expert_matmul_flops_a_step),
over those launches' summed device time (the activation's passes
included: they are part of what the layer costs) and the chip's published
bf16 peak (benchmark/peaks.py). Compute-bound: 2 x 2048 x 1024 FLOPs for
6 KB of row. The sizes are the configuration file's, under Hugging Face's
names for a sparse mixture of experts. None where the program names no such
scope or the configuration has no experts."""

from benchmark.peaks import peaks_of
from benchmark.trace import moe


def read(ctx):
    sums = moe.of_run(ctx)
    cfg = ctx["cell"].config
    if sums is None or not sums["experts"] or "num_experts_per_tok" not in cfg:
        return None
    flops = moe.expert_matmul_flops_a_step(
        tokens=ctx["rows"] * ctx["seq"],
        experts_per_token=int(cfg["num_experts_per_tok"]),
        hidden=int(cfg["hidden_size"]), width=int(cfg["intermediate_size"]),
        layers=int(cfg["num_hidden_layers"]),
        remat=bool(cfg["engine"]["train"]["remat"]))
    peak = peaks_of(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops * sums["steps"] / sums["experts"] / peak
