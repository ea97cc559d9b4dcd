"""select_mfu: the selection passes' algorithmic operations (over real
rows, ``harness/flops.py``) as a share of the chip's bf16 peak over the
passes' device time."""
from bench.harness.layers import pass_device, pass_flops


def read(ctx):
    total, n = pass_device(ctx.trace)
    flops = pass_flops(ctx)
    if not n or not flops:
        return None
    return 100.0 * flops / (ctx.peaks["bf16_flops_per_s"] * total)
