"""queue_wait_ms: mean time a request waits in the admission queue before
the admission loop takes it for a bucket (``taken - admitted`` on its
ticket; program spans, ``bench/harness/spans.py``).  Under DRR the take
happens at the bucket's close, so this holds the coalescing window too."""
from bench.harness.spans import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.mean_ms("queue")
