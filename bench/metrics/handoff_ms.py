"""handoff_ms: mean time per request in the hops between the event loop and
the selection executor: from the end of ``eco.bucket`` to the start of
``eco.select``, plus from its end to the ticket's ``selected`` mark
(program spans, ``bench/harness/spans.py``)."""
from bench.harness.spans import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.mean_ms("handoff")
