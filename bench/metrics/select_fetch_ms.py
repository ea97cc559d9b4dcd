"""select_fetch_ms: mean ``eco.select.fetch`` span per selection pass, the
host's wait for the pass's outputs and their copy to numpy; less
``select_device_ms`` it is the sync and transfer cost of a pass (program
spans, ``bench/harness/spans.py``)."""
from bench.harness.spans import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.span_ms(w.fetch, per_bucket=False)
