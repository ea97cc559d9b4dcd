"""device_idle_pct.closed: ``device_idle_pct`` in the closed-loop cells,
where it moves the served rate."""
from bench.harness import spec

read = spec.metric_reader("device_idle_pct")
