"""End-to-end arithmetic: every tail is over all requests of the window,
and every rate over the whole window.

A request that was shed, failed or never settled has an infinite latency;
a percentile that falls on one prints as ``INF_MS``.  Percentiles are
nearest-rank: the smallest value with at least q% of the requests at or
below it.
"""
from __future__ import annotations

import math

INF_MS = 1e9  # what an infinite percentile prints as (strict JSON has no inf)


def percentile(values: list[float], q: float) -> float:
    if not values:
        raise ValueError("no requests in the window")
    xs = sorted(values)
    return xs[max(math.ceil(q / 100.0 * len(xs)) - 1, 0)]


def ms(x: float) -> float:
    return INF_MS if math.isinf(x) else x * 1e3


def latencies(records, mark: str) -> list[float]:
    """Seconds from each request's due time to its ``mark`` on the ticket's
    timeline (``selected``: decided; ``completed``: settled), infinite
    where the request never got there (shed, failed, unsettled)."""
    out = []
    for r in records:
        t = r.event(mark)
        out.append(math.inf if t is None or r.event("failed")
                   is not None else t - r.due)
    return out


def served_qps(records, t0: float, t1: float) -> float:
    """Responses settled inside [t0, t1], per second of the window."""
    n = 0
    for r in records:
        t = r.event("completed")
        if t is not None and t0 <= t <= t1:
            n += 1
    return n / (t1 - t0)


def end_to_end(names: set[str], records, t0: float, t1: float) -> dict:
    """The end-to-end metrics of a cell that this module computes."""
    out = {}
    if names & {"decide_p50_ms", "decide_p95_ms"}:
        dec = latencies(records, "selected")
        out["decide_p50_ms"] = (ms(percentile(dec, 50)), "ms")
        out["decide_p95_ms"] = (ms(percentile(dec, 95)), "ms")
    if "respond_p95_ms" in names:
        out["respond_p95_ms"] = (
            ms(percentile(latencies(records, "completed"), 95)), "ms")
    if "served_qps" in names:
        out["served_qps"] = (served_qps(records, t0, t1), "req/s")
    return {k: v for k, v in out.items() if k in names}
