"""select_device_ms: mean device time of one selection pass (the jitted
``_pass`` module in the device trace)."""
from bench.harness.layers import pass_device


def read(ctx):
    total, n = pass_device(ctx.trace)
    return 1e3 * total / n if n else None
