"""loop_lag_p95_ms: 95th percentile of how late the load generator sent
each request after its due time.  The generator shares the asyncio loop
with admission, ticket callbacks and streamed-chunk forwarding, so this is
how long that loop was held up; a stall of the loop shows here first."""
from bench.harness.e2e import percentile


def read(ctx):
    lags = [r.sent - r.due for r in ctx.records]
    return 1e3 * percentile(lags, 95) if lags else None
