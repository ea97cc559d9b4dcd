"""handoff_ms.closed: ``handoff_ms`` in the closed-loop cells, where it
moves the served rate."""
from bench.harness import spec

read = spec.metric_reader("handoff_ms")
