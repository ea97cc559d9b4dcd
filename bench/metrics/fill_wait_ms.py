"""fill_wait_ms: mean time from a request's take to its bucket's close (the
start of ``eco.bucket``): the coalescing window a taken request waits out
(program spans, ``bench/harness/spans.py``)."""
from bench.harness.spans import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.mean_ms("fill")
