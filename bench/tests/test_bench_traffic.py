"""The traffic generator: the same seed gives the same requests; arrivals
are a Poisson process per seed, the SLO and tenant mixes exact shares."""
import numpy as np
import pytest

import _tiny  # noqa: F401
from bench.harness import spec, traffic

POOLS = {"a": np.arange(100, 164), "b": np.arange(500, 532)}


def test_same_seed_same_requests():
    mix = spec.traffic("poisson")
    one = traffic.open_loop(mix, {"a": POOLS["a"]}, 2.0, 2**40 + 3)
    two = traffic.open_loop(mix, {"a": POOLS["a"]}, 2.0, 2**40 + 3)
    assert one == two


@pytest.mark.parametrize("mix_name", ["poisson", "burst"])
def test_arrivals_are_poisson_per_seed(mix_name):
    """Counts per cycle vary with the seed about rate x length; the SLO
    classes keep their exact shares."""
    mix = spec.traffic(mix_name)
    pools = POOLS if mix.get("tenants") else {"a": POOLS["a"]}
    cycle = mix["arrivals"]["cycle_s"]
    mean = sum(p["s"] * p["rate_qps"] for p in mix["arrivals"]["phases"])
    counts = []
    for seed in (1, 2**33, 2**40 + 5):
        a = traffic.open_loop(mix, pools, 40 * cycle, seed)
        per = np.histogram([x.due_s for x in a], bins=40,
                           range=(0, 40 * cycle))[0]
        counts.append(per)
        assert abs(per.mean() - mean) < 4 * np.sqrt(mean / 40)
        assert per.var() == pytest.approx(mean, rel=0.6)
        shares = traffic.exact_counts([s["weight"] for s in mix["slo_mix"]],
                                      len(a))
        slos = [(s.get("max_latency_s", traffic.INF),
                 s.get("max_cost_usd", traffic.INF))
                for s in mix["slo_mix"]]
        got = [sum((x.max_latency_s, x.max_cost_usd) == s for x in a)
               for s in slos]
        assert got == shares.tolist()
    assert not np.array_equal(counts[0], counts[1])


def test_burst_phases_and_tenants():
    mix = spec.traffic("burst")
    arr = traffic.open_loop(mix, POOLS, 20.0, 7)
    ph = mix["arrivals"]["phases"]
    in_burst = sum((x.due_s % 1.0) < ph[0]["s"] for x in arr)
    mean = 20 * ph[0]["s"] * ph[0]["rate_qps"]
    assert abs(in_burst - mean) < 4 * np.sqrt(mean)
    assert in_burst > 2 * (len(arr) - in_burst) * ph[0]["s"] / ph[1]["s"]
    for x in arr:
        i = int(x.tenant.removeprefix("tenant"))
        assert x.domain == list(POOLS)[i % len(POOLS)]
        assert x.qid in POOLS[x.domain]


def test_exact_shares():
    counts = traffic.exact_counts([0.4, 0.3, 0.25, 0.05], 1000)
    assert counts.tolist() == [400, 300, 250, 50]
    assert traffic.exact_counts([1, 1, 1], 10).sum() == 10
    z = traffic.zipf_shares(4, 1.1)
    assert z.sum() == pytest.approx(1.0) and np.all(np.diff(z) < 0)
    assert z[0] / z[1] == pytest.approx(2 ** 1.1)


def test_closed_loop_sequence_is_seeded():
    mix = spec.traffic("closed")
    a = traffic.closed_loop(mix, {"a": POOLS["a"]}, 200, 5)
    assert a == traffic.closed_loop(mix, {"a": POOLS["a"]}, 200, 5)
    assert sum(x.max_latency_s == 1e-06 for x in a) == 10
