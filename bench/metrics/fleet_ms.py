"""fleet_ms: mean time from a request's fleet dispatch to its settled
response (``completed - dispatched`` on the ticket): the fleet queue plus
the host emulation of the path, without the modelled model latency.  It
runs in fleet threads under the same interpreter lock as admission and
select, so it moves the decision latency."""


def read(ctx):
    xs = []
    for r in ctx.records:
        d, c = r.event("dispatched"), r.event("completed")
        if d is not None and c is not None:
            xs.append(c - d)
    return 1e3 * sum(xs) / len(xs) if xs else None
