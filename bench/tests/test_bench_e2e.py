"""End-to-end arithmetic: tails over all requests with shed ones counted as
infinite, rates over the whole window."""
import math
from types import SimpleNamespace

import pytest

import _tiny  # noqa: F401
from bench.harness import e2e


def rec(due, **marks):
    return SimpleNamespace(due=due, event=lambda name: marks.get(name))


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert e2e.percentile(xs, 50) == 50
    assert e2e.percentile(xs, 95) == 95
    assert e2e.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        e2e.percentile([], 50)


def test_shed_and_failed_count_as_infinite():
    recs = [rec(0.0, selected=0.001 * i, completed=0.002 * i)
            for i in range(1, 91)]
    recs += [rec(0.0, shed=0.5) for _ in range(9)]
    recs += [rec(0.0, selected=0.1, failed=0.2)]
    dec = e2e.latencies(recs, "selected")
    assert sum(math.isinf(x) for x in dec) == 10
    out = e2e.end_to_end({"decide_p50_ms", "decide_p95_ms",
                          "respond_p95_ms"}, recs, 0.0, 1.0)
    assert out["decide_p50_ms"] == (pytest.approx(50.0), "ms")
    assert out["decide_p95_ms"] == (e2e.INF_MS, "ms")
    assert out["respond_p95_ms"] == (e2e.INF_MS, "ms")


def test_latency_is_from_the_due_time():
    recs = [rec(10.0, selected=10.004, completed=10.010)]
    out = e2e.end_to_end({"decide_p50_ms", "respond_p95_ms"}, recs, 10, 11)
    assert out["decide_p50_ms"][0] == pytest.approx(4.0)
    assert out["respond_p95_ms"][0] == pytest.approx(10.0)


def test_served_rate_counts_completions_inside_the_window():
    recs = [rec(0.0, completed=t) for t in (0.5, 1.0, 1.5, 2.0, 2.5, 3.5)]
    out = e2e.end_to_end({"served_qps"}, recs, 1.0, 3.0)
    assert out == {"served_qps": (2.0, "req/s")}
