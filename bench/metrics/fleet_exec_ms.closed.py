"""fleet_exec_ms.closed: ``fleet_exec_ms`` in the closed-loop cells, where
it moves the served rate."""
from bench.harness import spec

read = spec.metric_reader("fleet_exec_ms")
