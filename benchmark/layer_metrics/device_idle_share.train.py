"""1 - (union of device-operation intervals / traced window), averaged
over the chips, in percent."""

from benchmark.trace import reduce


def read(ctx):
    if ctx.get("trace") is None:
        return None
    return 100.0 * reduce.idle_share(ctx["trace"])
