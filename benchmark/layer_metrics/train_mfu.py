"""Model FLOP/s utilisation: FLOPs one token requires (benchmark/flops.py,
recomputation not counted) x tokens/s/chip over the chip's published bf16
peak (benchmark/peaks.py), in percent."""

from benchmark.flops import train_flops_per_token
from benchmark.peaks import peaks_of


def read(ctx):
    if ctx.get("train_tokens_per_s") is None:
        return None
    need = train_flops_per_token(ctx["cell"].config, ctx["seq"])
    peak = peaks_of(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * need * ctx["train_tokens_per_s"] / peak
