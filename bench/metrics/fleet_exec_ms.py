"""fleet_exec_ms: mean winning ``eco.fleet.exec`` span per request, the
host emulation of its path on a fleet worker (program spans,
``bench/harness/spans.py``)."""
from bench.harness.spans import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.mean_ms("fleet_exec")
