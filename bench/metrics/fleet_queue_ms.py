"""fleet_queue_ms: mean time from a request's ``dispatched`` mark to the
start of its winning ``eco.fleet.exec`` span, its wait in the fleet's
replica queues (program spans, ``bench/harness/spans.py``)."""
from bench.harness.spans import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.mean_ms("fleet_queue")
