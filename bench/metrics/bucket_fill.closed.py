"""bucket_fill.closed: ``bucket_fill`` in the closed-loop cells, where it
moves the served rate."""
from bench.harness import spec

read = spec.metric_reader("bucket_fill")
