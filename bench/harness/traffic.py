"""The one traffic generator: reads a mix's data file
(``bench/traffic/<mix>.json``) and makes the requests of one run from the
run seed.

A mix file holds:

* ``loop``: ``"open"`` (arrivals on a schedule, whatever the system does)
  or ``"closed"`` (``outstanding`` clients, each sending its next request
  when the last one settles);
* ``arrivals`` (open loop): ``{"cycle_s": c, "phases": [{"s": d,
  "rate_qps": r}, ...]}``, the phases repeated every ``c`` seconds over the
  window; one phase as long as the cycle is a plain Poisson process;
* ``slo_mix``: ``[{"weight": w, "max_latency_s": l, "max_cost_usd": c}]``,
  a missing limit meaning none;
* ``tenants`` (optional): ``{"n": n, "zipf": a, "slo_class": name}``:
  ``n`` tenants with Zipf(``a``) shares, tenant ``i`` on domain
  ``i mod D``;
* ``request``: the request form, which the configuration's deployment
  kind reads and refuses where it does not serve it (``"qid"``: the query
  arrives already embedded).

Arrivals are a Poisson process drawn from the run seed: each phase of
each cycle holds a Poisson number of arrivals (mean rate x length) at
uniform times, so bursts and quiet spells vary in size as real ones do.
The SLO classes and tenants come in exact shares of the arrivals, shuffled
by the run seed.  Query ids are drawn uniformly from each domain's
held-out pool by the run seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

INF = float("inf")


@dataclass(frozen=True)
class Arrival:
    due_s: float          # offset from the start of the window
    qid: int
    domain: str
    max_latency_s: float
    max_cost_usd: float
    tenant: str | None


def zipf_shares(n: int, alpha: float = 1.1) -> np.ndarray:
    """Zipf popularity profile: share of rank i is proportional to
    1/(i+1)^alpha."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** alpha
    return w / w.sum()


def exact_counts(shares, n: int) -> np.ndarray:
    """Split ``n`` into integer counts in proportion to ``shares``
    (largest remainder, ties to the lower index)."""
    shares = np.asarray(shares, np.float64) / np.sum(shares)
    raw = shares * n
    counts = np.floor(raw).astype(np.int64)
    rest = n - int(counts.sum())
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:rest]] += 1
    return counts


def shuffled_labels(shares, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` labels in exact ``shares``, in an order drawn from ``rng``."""
    labels = np.repeat(np.arange(len(shares)), exact_counts(shares, n))
    return rng.permutation(labels)


def segment_times(start: float, length: float, rate: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets of one phase: a Poisson process of ``rate`` over
    ``length`` seconds."""
    n = int(rng.poisson(rate * length)) if length > 0 else 0
    return start + np.sort(rng.uniform(0.0, length, n))


def arrival_times(arrivals: dict, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    cycle = float(arrivals["cycle_s"])
    phases = arrivals["phases"]
    if not math.isclose(sum(p["s"] for p in phases), cycle):
        raise ValueError("phases must fill the cycle")
    times = []
    for c in range(int(math.ceil(seconds / cycle))):
        start = c * cycle
        for ph in phases:
            length = min(ph["s"], max(seconds - start, 0.0))
            times.append(segment_times(start, length, ph["rate_qps"], rng))
            start += ph["s"]
    return np.concatenate(times) if times else np.empty(0)


def _slos(mix: dict) -> list[tuple[float, float]]:
    return [(float(s.get("max_latency_s", INF)),
             float(s.get("max_cost_usd", INF))) for s in mix["slo_mix"]]


def requests(mix: dict, pools: dict[str, np.ndarray], n: int,
             rng: np.random.Generator):
    """``n`` (qid, domain, max_latency_s, max_cost_usd, tenant) tuples."""
    domains = list(pools)
    slos = _slos(mix)
    slo_of = shuffled_labels([s["weight"] for s in mix["slo_mix"]], n, rng)
    ten = mix.get("tenants")
    if ten:
        tenant_of = shuffled_labels(zipf_shares(ten["n"], ten["zipf"]), n,
                                    rng)
        dom_of = tenant_of % len(domains)
    else:
        tenant_of = None
        dom_of = np.zeros(n, np.int64)
    out = []
    for i in range(n):
        dom = domains[int(dom_of[i])]
        pool = pools[dom]
        qid = int(pool[rng.integers(len(pool))])
        lat, cost = slos[int(slo_of[i])]
        tenant = None if tenant_of is None else f"tenant{int(tenant_of[i])}"
        out.append((qid, dom, lat, cost, tenant))
    return out


def open_loop(mix: dict, pools: dict[str, np.ndarray], seconds: float,
              seed: int) -> list[Arrival]:
    """The whole schedule of an open-loop window, in due order."""
    rng = np.random.default_rng(seed)
    times = np.sort(arrival_times(mix["arrivals"], seconds, rng))
    reqs = requests(mix, pools, len(times), rng)
    return [Arrival(float(t), *r) for t, r in zip(times, reqs)]


def closed_loop(mix: dict, pools: dict[str, np.ndarray], n_max: int,
                seed: int) -> list[Arrival]:
    """The request sequence closed-loop clients take from, in order (due
    times are set when each is sent)."""
    rng = np.random.default_rng(seed)
    return [Arrival(0.0, *r) for r in requests(mix, pools, n_max, rng)]
