"""admit_wait_ms: mean time a request waits in admission, ``selected -
admitted`` on its ticket less its bucket's select span (the benchmark's
span around ``Orchestrator._select``)."""


def read(ctx):
    waits = []
    for r in ctx.records:
        a, s = r.event("admitted"), r.event("selected")
        span = r.span()
        if a is None or s is None or span is None:
            continue
        waits.append((s - a) - (span[1] - span[0]))
    return 1e3 * sum(waits) / len(waits) if waits else None
