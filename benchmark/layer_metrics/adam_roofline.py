"""The fused AdamW bucket kernel (``ops/adam/pallas_adam.py``) against the
HBM roofline, in percent: the bytes its launches must move (every operand
read once, every result written once, from the shapes in each launch's
own HLO text: benchmark/flops.py) over the chip's published bandwidth,
divided by the launches' summed device time. Memory-bound by nature: 20
bytes and a dozen FLOPs per parameter.

The trace does not name the kernel (its events are ``branch_0_fun.N``);
it is recognised by what only it has in a training step: a Mosaic custom
call whose results are (float32 master, bf16 weight, two moments) rows of
128. A ``name=`` on the ``pallas_call`` is on PERF.md's list for the
tracing issue. Nothing to read without a trace or off one chip (the
multi-chip step takes the XLA update)."""

from benchmark.flops import custom_call_io_bytes
from benchmark.peaks import peaks_of
from benchmark.trace import reduce

ADAM_BUCKET = (r'= \(f32\[\d+,128\][^()]*(\([^()]*\)[^()]*)*\) custom-call\('
               r'.*custom_call_target="tpu_custom_call"')


def read(ctx):
    if ctx.get("trace") is None or ctx["chips"] != 1:
        return None
    launches = reduce.matching(ctx["trace"], ADAM_BUCKET)
    seconds = sum(e[2] for e in launches) / 1e9
    if not seconds:
        return None
    need = sum(custom_call_io_bytes(e[3]) for e in launches)
    return 100.0 * need / peaks_of(ctx["device_kind"])["hbm_bytes_per_s"] / seconds
