"""settle_hop_ms: mean time from the end of a request's
``eco.fleet.respond`` on the fleet worker to the start of its
``eco.settle`` on the event loop, the hop back onto the loop (program
spans, ``bench/harness/spans.py``)."""
from bench.harness.spans import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.mean_ms("settle_hop")
