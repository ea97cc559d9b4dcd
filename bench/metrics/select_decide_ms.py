"""select_decide_ms: mean ``eco.select.decide`` time per bucket, the host
OOD fallback and ``Decision`` construction after the passes (program
spans, ``bench/harness/spans.py``)."""
from bench.harness.spans import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.span_ms(w.decide, per_bucket=True)
