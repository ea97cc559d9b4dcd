"""select_host_ms: host time of a bucket's selection, the mean select span
(``Orchestrator._select``) less the device time of its pass(es)."""
from bench.harness.layers import pass_device


def read(ctx):
    spans = ctx.spans.select
    total, n = pass_device(ctx.trace)
    if not spans or not n:
        return None
    host = sum(b - a for a, b, _ in spans) - total
    return 1e3 * host / len(spans)
